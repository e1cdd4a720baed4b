"""_commit_cycle before apply: the bridge's `device` clock. On the local
path the solve itself has already been waited for inside the executor
call, so what this clock holds is the host unpacking the verdict tensors
and scanning the lattice's victim mask (bool [C, A]) for preempting
slots."""

from _common import phase_ms


def reduce(trace, spans, counters):
    return phase_ms(spans, "device")
