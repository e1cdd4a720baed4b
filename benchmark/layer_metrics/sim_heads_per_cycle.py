"""Heads a cycle whose flavor choice went through the sim-augmented
nomination: the window's `n_sim_heads` (obs/span.py COUNT_KEYS, from
the `sim_nomination` span's attr `heads`) over the window's cycles."""

from _counts import window_count


def reduce(trace, spans, counters):
    heads = window_count(spans, "n_sim_heads")
    if heads is None:
        return None
    return heads / len(spans["cycles"])
