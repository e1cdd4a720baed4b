"""(head, flavor, resource) cells a cycle that a head's flavor mask left
out of its walk — not flagged for a simulation, no row built, never the
fold's answer: the window's `n_masked_flavor_cells` (obs/span.py
COUNT_KEYS, from the `sim_nomination` span's attr `masked_flavor_cells`:
the cells of ops/assign.flavor_grid whose flavor is not `in_walk`) over
the window's cycles. A program without the count gives no cycle the key,
and nothing is reported."""

from _counts import window_count


def reduce(trace, spans, counters):
    cells = window_count(spans, "n_masked_flavor_cells")
    if cells is None:
        return None
    return cells / len(spans["cycles"])
