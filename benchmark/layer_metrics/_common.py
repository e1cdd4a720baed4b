"""Shared by the readers: means over the window's cycles."""


def mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def phase_ms(spans, *keys):
    """Mean over the window's cycles of the bridge's phase clocks
    (Engine.last_cycle_phases), in ms; None if no cycle had them."""
    vals = [sum(c["phases"].get(k, 0.0) for k in keys) * 1e3
            for c in spans["cycles"]
            if any(k in c["phases"] for k in keys)]
    return mean(vals)
