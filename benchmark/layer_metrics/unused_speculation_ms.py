"""The double-buffered cycle loop (_maybe_speculate): what
schedule_once() spends outside the bridge's phase clocks, mean per
cycle of the window. In a loop whose client speaks between cycles every
speculation is thrown away, and this is its encode + dispatch + readback
(with the young-generation sweep and the cycle listeners, which are
small beside it)."""

from _common import mean


def reduce(trace, spans, counters):
    vals = []
    for c in spans["cycles"]:
        ph = c["phases"]
        if "encode" not in ph:
            continue
        inside = sum(ph.get(k, 0.0) for k in
                     ("encode", "device", "apply", "finalize"))
        vals.append(max(c["schedule_s"] - inside, 0.0) * 1e3)
    return mean(vals)
