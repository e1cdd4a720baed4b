"""Heads the device decided per committed device cycle of the window:
`n_device_heads` / `n_device_cycles`, counted per schedule_once() from
its `verdict_decode` span and that span's `device_heads` attr
(oracle/engine_bridge.py _commit_cycle)."""

from _counts import window_count


def reduce(trace, spans, counters):
    cycles = window_count(spans, "n_device_cycles")
    heads = window_count(spans, "n_device_heads")
    if not cycles or heads is None:
        return None
    return heads / cycles
