"""Launches of the cycle program that took the fused preemptor's branch,
of all the window's launches, speculative or not: `n_lattice_launches` /
`n_launches`, counted per schedule_once() from the `lattice` attr that
the bridge stamps on the `cycle` or `speculate` span whose launch it was.

The attr is read off each launch's verdicts (oracle/engine_bridge.py
_lattice_ran), which tell the branch only where every ClusterQueue that
could drive it is BestEffortFIFO and the world has one resource group.
Elsewhere the program leaves the count out of that schedule_once(), and
this reader then reports nothing for the window: a share over some of
the launches would read low."""

from _counts import window_count


def reduce(trace, spans, counters):
    launches = window_count(spans, "n_launches")
    if not launches:
        return None
    if any("n_launches" in c["phases"]
           and "n_lattice_launches" not in c["phases"]
           for c in spans["cycles"]):
        return None
    return 100.0 * window_count(spans, "n_lattice_launches") / launches
