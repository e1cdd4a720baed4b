"""rowcache encode + speculative encode (tensor/rowcache.py,
_encode_cycle): the bridge's `encode` clock — and `spec_encode`, where
the cycle was served by a speculation — less the executor call that
served the cycle (the harness's span around executor.cycle_step): the
cycle's own first call, or the previous cycle's last where a
speculation was used. Mean per cycle of the window."""

from _common import mean


def reduce(trace, spans, counters):
    vals, prev = [], None
    for c in spans["cycles"]:
        ph, calls = c["phases"], c["executor_calls_s"]
        if "encode" in ph:
            if "spec_encode" in ph:
                served = prev[-1] if prev else 0.0
            else:
                served = calls[0] if calls else 0.0
            vals.append((ph["encode"] + ph.get("spec_encode", 0.0)
                         - served) * 1e3)
        prev = calls
    return mean(vals)
