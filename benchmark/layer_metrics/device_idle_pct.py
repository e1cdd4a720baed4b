"""The device's idle share of the traced window: 1 - the union of the
intervals in which an operation ran, over the window."""


def reduce(trace, spans, counters):
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
