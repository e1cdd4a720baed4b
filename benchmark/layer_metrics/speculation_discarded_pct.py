"""Speculations thrown away, of those whose fate a cycle of the window
learned: `n_spec_discarded` / (`n_spec_used` + `n_spec_discarded`),
counted per schedule_once() from the `outcome` attr of its
`take_speculation` span (oracle/engine_bridge.py _take_speculation)."""

from _counts import window_count


def reduce(trace, spans, counters):
    discarded = window_count(spans, "n_spec_discarded")
    used = window_count(spans, "n_spec_used")
    if discarded is None or used is None or not discarded + used:
        return None
    return 100.0 * discarded / (discarded + used)
