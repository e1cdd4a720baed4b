"""The sim-augmented nomination of the heads whose flavor choice needs
preemption simulations (multi-flavor groups on preempting
ClusterQueues): the program's `sim_nomination` span, the container of
`flavor_grid`, `sim_rows`, `sim_launch`, `fungibility_fold` and
`sim_targets`, recorded in oracle/engine_bridge.py beside `host_encode`.
Mean per cycle of the window; nothing where no cycle had the span."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "sim_nomination")
