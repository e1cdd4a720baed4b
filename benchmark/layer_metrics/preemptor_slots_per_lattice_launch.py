"""Slots the fused preemptor is run for, a launch that takes its
lattice: `n_preempt_slots` / `n_lattice_launches` over the window
(obs/span.py COUNT_KEYS: the `preempt_slots` attr of the `cycle` span,
the cycle program's own count of the heads it hands its preemptor, and
the launches in which that count is not 0). The lattice is laid out for
every ClusterQueue; this is how many of its rows do any work. A program
without the count gives no cycle the key, and nothing is reported; so
does a window with no lattice launch."""

from _counts import window_count


def reduce(trace, spans, counters):
    slots = window_count(spans, "n_preempt_slots")
    launches = window_count(spans, "n_lattice_launches")
    if slots is None or not launches:
        return None
    return slots / launches
