"""(head, flavor, resource) cells simulated a cycle: the window's
`n_sim_rows` (obs/span.py COUNT_KEYS, from the `sim_nomination` span's
attr `rows`) over the window's cycles."""

from _counts import window_count


def reduce(trace, spans, counters):
    rows = window_count(spans, "n_sim_rows")
    if rows is None:
        return None
    return rows / len(spans["cycles"])
