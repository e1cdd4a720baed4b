"""The plain reference of the returning-cohort kind
(benchmark/worlds/reclaim-any-1x1000-returning.json names it under
`modules`): plain.py's Plain, which states `reclaimWithinCohort` for
flat cohorts already — the candidates of other ClusterQueues that run
over their nominal quota, ordered before the head's own, taken until the
head fits and given back from the other end; only for a head whose queue
stays within nominal — and is not copied here. Added: the count the
world file's `compared_at_least` asks for. Nothing of the program is
imported.
"""

from __future__ import annotations

import sys

from plain import Plain

__all__ = ["Plain", "count_evictions_from_another_queue"]


def count_evictions_from_another_queue(world: dict, verdicts: list) -> int:
    """Victims that ran in another ClusterQueue than their preemptor's,
    over the reference's verdicts. A workload's queue is where the world
    put it or where a verdict admitted it; a preemptor the run never
    admits anywhere (an arrival still waiting at the end) is left out,
    so the count errs low."""
    index = {cq["name"]: i for i, cq in enumerate(world["cluster_queues"])}
    home = {name: ci for name, ci, _k, _at
            in world["running"] + world["pending"]}
    for v in verdicts:
        for name, cq, _flavor, _used in v["admitted"]:
            home[name] = index[cq]
    other = own = 0
    for v in verdicts:
        for head, victims in v["preempting"]:
            if head not in home:
                continue
            for name in victims:
                if home.get(name, home[head]) != home[head]:
                    other += 1
                else:
                    own += 1
    print(f"evictions compared: from another queue = {other}, from the "
          f"preemptor's own = {own}", file=sys.stderr, flush=True)
    return other
