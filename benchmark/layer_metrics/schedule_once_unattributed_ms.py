"""What no leaf span of the schedule_once() tree covers: the self time
of its three containers (schedule_once, cycle, speculate), so that the
leaf keys + this = the `schedule_once` key by construction
(obs/span.py). Mean per cycle of the window."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "unattributed")
