"""_encode_cycle up to the device cycle (rowcache refresh, head
selection, root partitioning, TAS and sim nomination): the program's
`host_encode` span, recorded in oracle/engine_bridge.py and summed over
everything that ran in the schedule_once() — the cycle's own encode and
a speculation's alike. Mean per cycle of the window."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "host_encode")
