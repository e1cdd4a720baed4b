"""The host blocked on the cycle program: the program's `device_wait`
span around jax.block_until_ready in oracle/service.py, summed over
every launch of the schedule_once() (1.5 launches a cycle where every
speculation is thrown away). Mean per cycle of the window; divide by the
launches per cycle to set it beside cycle_program_ms."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "device_wait")
