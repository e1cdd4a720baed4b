"""Shared by the readers of counts: the window's own total of a count
key of Engine.last_cycle_phases (obs/span.py COUNT_KEYS: counts of one
schedule_once(), taken from its spans' attrs), so that nothing from
before the window, the warm-up cycles least of all, is in a ratio."""


def window_count(spans, key):
    """Sum over the window's cycles; None if no cycle had the key."""
    vals = [c["phases"][key] for c in spans["cycles"] if key in c["phases"]]
    return sum(vals) if vals else None
