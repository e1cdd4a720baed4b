"""Victims taken from another ClusterQueue than their preemptor's, a
cycle of the window: `n_reclaim_victims` (obs/span.py COUNT_KEYS) over
the window's cycles, from the `reclaim_victims` attr of the
`verdict_decode` span (oracle/engine_bridge.py _commit_cycle: the
committed victims whose candidate variant is not within-ClusterQueue).
What `reclaimWithinCohort` decides on the device; 0 where every victim
is the head's own queue's. A program whose span has no such attr gives
no cycle the key, and nothing is reported."""

from _counts import window_count


def reduce(trace, spans, counters):
    victims = window_count(spans, "n_reclaim_victims")
    if victims is None:
        return None
    return victims / len(spans["cycles"])
