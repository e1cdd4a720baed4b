"""The sim program on the device: device time of the `sim_targets`
program (ops/preempt.py; XLA Modules line of the trace), mean per
launch of the traced cycles."""


def reduce(trace, spans, counters):
    if trace is None:
        return None
    names = [k for k in trace["module_s"] if "sim_targets" in k]
    n = sum(trace["module_n"][k] for k in names)
    if not n:
        return None
    return sum(trace["module_s"][k] for k in names) * 1e3 / n
