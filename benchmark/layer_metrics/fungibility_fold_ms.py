"""The fungibility fold (findFlavorForPodSets over every head at once,
array code on the host): the program's `fungibility_fold` span under
`sim_nomination`. Mean per cycle of the window."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "fungibility_fold")
