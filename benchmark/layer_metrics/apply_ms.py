"""_apply / controllers/colapply.py: the bridge's `apply` clock."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "apply")
