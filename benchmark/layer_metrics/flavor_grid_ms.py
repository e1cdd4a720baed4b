"""ops/assign.flavor_grid, launch and readback: the pre-simulation
classification of every (head, flavor, resource) cell, the program's
`flavor_grid` span under `sim_nomination`. Mean per cycle of the
window."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "flavor_grid")
