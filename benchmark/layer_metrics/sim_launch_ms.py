"""The sim program's launches as the host waits for them (upload of the
row block, the device, readback of four vectors): the program's
`sim_launch` span under `sim_nomination`. Mean per cycle of the window;
`sim_program_ms` is the device's share of it, a launch."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "sim_launch")
