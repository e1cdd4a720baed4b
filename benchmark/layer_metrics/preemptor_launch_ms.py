"""The fused classical preemptor (ops/preempt.py) inside the cycle
program: device time of the slowest `_cycle_core` launch of the traced
window. The program takes the preemptor's branch only in a cycle where
some head finds no room; such a launch is the victim search over
(ClusterQueue slots x the cohort's running workloads) and little else,
and the launches that skip it are an order of magnitude shorter. No
scope names the preemptor's operations in the trace (PERF.md, for the
tracing issue), so the whole launch is what can be read."""


def reduce(trace, spans, counters):
    if trace is None:
        return None
    worst = [v for k, v in trace["module_max_s"].items()
             if "_cycle_core" in k]
    return max(worst) * 1e3 if worst else None
