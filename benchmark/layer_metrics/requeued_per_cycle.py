"""Workloads a cohort's requeue moved from parked back into their queue
(a finish's, or the evictions' at a cycle's end): the window's
`n_requeued` (obs/span.py WINDOW_KEYS, attr `requeued` summed over the
`intake` tree and the cycle's own) over the window's cycles. None where
the program has no such key."""

from _counts import window_count


def reduce(trace, spans, counters):
    requeued = window_count(spans, "n_requeued")
    if requeued is None:
        return None
    return requeued / len(spans["cycles"])
