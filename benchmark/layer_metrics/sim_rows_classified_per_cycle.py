"""Rows the sim program classified a cycle: the window's
`n_sim_rows_classified` (obs/span.py COUNT_KEYS, from the `sim_launch`
span's attr `rows_classified`: each launch's live rows in whole chunks)
over the window's cycles. None where the program counts no such rows."""

from _counts import window_count


def reduce(trace, spans, counters):
    rows = window_count(spans, "n_sim_rows_classified")
    if rows is None:
        return None
    return rows / len(spans["cycles"])
