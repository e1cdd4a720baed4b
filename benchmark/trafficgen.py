"""One generator for every traffic mix: it reads the numbers of
benchmark/traffic/<mix>.json and the seed. Nothing of the program is
imported here.

A mix is a closed loop on a full cluster. Before cycle k one running
workload finishes in each of ``turnover_share`` of the ClusterQueues (the
same number of queues every cycle, drawn anew each time; the workload is
drawn among that queue's running set) and the user it belonged to sends
the next one to the same queue. The arrivals' classes are the world's
class mix, exactly: they are dealt from shuffled blocks that each hold
the classes in the ratio of their `count` (7 : 2 : 1 for 350 : 100 :
50). A queue with nothing running has no finish and no arrival, so
running + waiting stays what the world made it. Every draw comes from
the world's `scenario` number; the seed relabels, as in worldgen.py.

Which workload finishes depends on what the scheduler decided, so the
loop keeps the running sets itself (RunningSets) and writes down every
event it sent, for the reference to replay (reference.py).
"""

from __future__ import annotations

import json
import math
import os
import random

HERE = os.path.dirname(os.path.abspath(__file__))


def read_mix(name: str, tiny: bool = False) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    if tiny:
        mix.update(mix.get("tiny", {}))
    mix["name"] = name
    return mix


class RunningSets:
    """Who runs in which ClusterQueue, with O(1) add, remove and draw."""

    def __init__(self, cq_names: list, running: list):
        self.index = {name: i for i, name in enumerate(cq_names)}
        self.sets: list = [[] for _ in cq_names]
        self.where: dict = {}
        for name, ci, _k, _at in running:
            self.add(name, ci)

    def add(self, name: str, ci: int) -> None:
        self.where[name] = (ci, len(self.sets[ci]))
        self.sets[ci].append(name)

    def remove(self, name: str) -> None:
        ci, pos = self.where.pop(name)
        last = self.sets[ci].pop()
        if last != name:
            self.sets[ci][pos] = last
            self.where[last] = (ci, pos)

    def draw(self, ci: int, u: float):
        s = self.sets[ci]
        return s[int(u * len(s))] if s else None

    def apply(self, verdicts: dict) -> None:
        """A cycle's verdicts: the admitted run, the victims wait."""
        for _head, victims in verdicts["preempting"]:
            for name in victims:
                if name in self.where:
                    self.remove(name)
        for name, cq, _flavor, _used in verdicts["admitted"]:
            self.add(name, self.index[cq])

    def count(self) -> int:
        return len(self.where)


class Generator:
    """events(k, sets): what the client sends before cycle k —
    (finishes [name], arrivals [(name, cq index, class index, created)],
    the engine's clock at the cycle)."""

    def __init__(self, mix: dict, world: dict):
        self.rng = random.Random(world["scenario"] * 7_919 + 3)
        self.place, self.tag = world["place"], world["tag"]
        self.n_cqs = len(world["cluster_queues"])
        self.per_cycle = max(1, round(mix["turnover_share"] * self.n_cqs))
        counts = [c["count"] for c in world["classes"]]
        unit = math.gcd(*counts)
        self.block = [k for k, n in enumerate(counts)
                      for _ in range(n // unit)]
        self.deal: list = []
        self.clock0 = world["clock0"]
        self.dt = mix["engine_seconds_per_cycle"]
        self.serial = 0

    def _class(self) -> int:
        if not self.deal:
            self.deal = list(self.block)
            self.rng.shuffle(self.deal)
        return self.deal.pop()

    def events(self, k: int, sets: RunningSets) -> tuple:
        rng = self.rng
        now = self.clock0 + self.dt * (k + 1)
        finishes, arrivals = [], []
        for q in sorted(rng.sample(range(self.n_cqs), self.per_cycle)):
            ci = self.place[q]
            done = sets.draw(ci, rng.random())
            if done is None:
                continue
            finishes.append(done)
            self.serial += 1
            # Created within the half second before the cycle, in the
            # order sent: FIFO within a priority is by this.
            arrivals.append((f"arr-{self.serial}-{self.tag}", ci,
                             self._class(),
                             now - 0.5 + 1e-5 * len(arrivals)))
        return finishes, arrivals, now
