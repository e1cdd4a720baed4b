"""The double-buffered cycle loop's speculation, whatever becomes of it:
the wall of the program's `speculate` span (_maybe_speculate: the next
cycle's encode, upload, launch, wait and readback), measured where it
runs and not by subtraction. Mean per cycle of the window."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "speculate")
