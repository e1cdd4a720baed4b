"""client -> Engine.finish / Engine.submit: the harness's clock around
its own calls, mean per cycle of the window."""

from _common import mean


def reduce(trace, spans, counters):
    return mean((c["finish_s"] + c["submit_s"]) * 1e3
                for c in spans["cycles"])
