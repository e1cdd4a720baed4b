"""The engine's host time with no device program of its own running:
its `host_bound` key, intake + schedule_once - device_launched
(obs/span.py WINDOW_KEYS; `device_launched` sums the launch windows,
each from the dispatch of a launch's first program to its outputs being
ready), mean per cycle of the window. None where the program has no such
key."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "host_bound")
