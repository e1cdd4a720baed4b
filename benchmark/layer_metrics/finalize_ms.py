"""_commit_cycle finalize (status, metrics, journal): the bridge's
`finalize` clock."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "finalize")
