"""Launches of the cycle program made for a speculation that was thrown
away, of all the window's launches: `n_spec_discarded` / `n_launches`,
both counted per schedule_once() from its spans' attrs (the `outcome`
of `take_speculation`, the `lattice` of the `cycle` or `speculate` span
that launched). 0 where launches were counted and none was discarded —
what the speculation gate (oracle/engine_bridge.py _maybe_speculate)
leaves of a served loop — and nothing only where no launch was
counted."""

from _counts import window_count


def reduce(trace, spans, counters):
    launches = window_count(spans, "n_launches")
    if not launches:
        return None
    return 100.0 * (window_count(spans, "n_spec_discarded") or 0) / launches
