"""The verdict tensors copied to the host: the program's `readback` span
around np.asarray of the 14 outputs in oracle/service.py, summed over
every launch of the schedule_once(). Mean per cycle of the window."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "readback")
