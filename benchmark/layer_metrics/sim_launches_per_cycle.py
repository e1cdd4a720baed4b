"""Launches of the sim program a cycle: the window's `n_sim_launches`
(obs/span.py COUNT_KEYS, from the `sim_nomination` span's attr
`launches`: a cycle's rows over the program's one block of rows,
rounded up) over the window's cycles."""

from _counts import window_count


def reduce(trace, spans, counters):
    launches = window_count(spans, "n_sim_launches")
    if launches is None:
        return None
    return launches / len(spans["cycles"])
