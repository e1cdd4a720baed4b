"""client -> Engine.finish / submit / restore_workload / tick, as the
engine spent them: its `intake` key (obs/span.py WINDOW_KEYS, the sum of
the tallies of the `intake` tree that closes when a schedule_once()
opens), mean per cycle of the window. None where the program has no
such key."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "intake")
