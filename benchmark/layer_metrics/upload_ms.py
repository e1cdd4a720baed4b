"""Host arrays handed to the device for a launch: the program's `upload`
spans — the bridge's jnp.asarray block of the per-cycle tensors
(oracle/engine_bridge.py) and what the executor still had to convert
(oracle/service.py) — summed over every launch of the schedule_once().
Mean per cycle of the window."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "upload")
