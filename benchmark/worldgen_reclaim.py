"""The world builder of the returning-cohort kind
(benchmark/worlds/reclaim-any-1x1000-returning.json names it under
`modules`): worldgen.build_world's world — the same queues, classes,
backlog and draws — with the running set dealt round the *borrowing*
queues only. Nothing of the program is imported here.

`returning_share` of every cohort's queues were idle while the others
borrowed their nominal quota; they run nothing and have their backlog
waiting, and under `reclaimWithinCohort: Any` their heads take the quota
back (Kueue, concepts/cluster_queue: cohort borrowing). Which queues
return is drawn from the file's `scenario`, cohort by cohort, among the
scenario's queues: it goes through relabel()'s `place` like every other
queue index, so the seed still only relabels.
"""

from __future__ import annotations

import random

import worldgen


def returning_queues(cfg: dict) -> set:
    """The scenario's queues (before relabelling) that run nothing: the
    same share of every cohort, drawn from the scenario."""
    rng = random.Random(cfg["scenario"] * 1_000_003 + 29)
    n_cqs, n_cohorts = cfg["cluster_queues"], cfg["cohorts"]
    out: set = set()
    for co in range(n_cohorts):
        mine = list(range(co, n_cqs, n_cohorts))
        out.update(rng.sample(mine, round(cfg["returning_share"]
                                          * len(mine))))
    return out


def build_world(cfg: dict, seed: int) -> dict:
    world = worldgen.build_world(cfg, seed)
    place = world["place"]
    idle = returning_queues(cfg)
    # The same running workloads — names, classes, reservation times —
    # dealt round the cohort's borrowing queues class by class, as
    # worldgen deals them round all: every borrowing queue holds its
    # share of every class.
    rng = random.Random(cfg["scenario"] * 1_000_003 + 31)
    n_cohorts = cfg["cohorts"]
    borrowing: dict = {}
    for q in range(cfg["cluster_queues"]):
        if q not in idle:
            borrowing.setdefault(q % n_cohorts, []).append(place[q])
    for mine in borrowing.values():
        rng.shuffle(mine)
    at = dict.fromkeys(borrowing, 0)
    moved = {}
    for k in range(len(cfg["classes"])):
        for i, (_name, ci, kk, _at) in enumerate(world["running"]):
            if kk == k:
                co = ci % n_cohorts
                moved[i] = borrowing[co][at[co] % len(borrowing[co])]
                at[co] += 1
    running = [(name, moved[i], k, reserved)
               for i, (name, _ci, k, reserved) in enumerate(world["running"])]
    world["running"] = running
    world["returning"] = sorted(place[q] for q in idle)
    return world


def device_bytes(cfg: dict) -> dict:
    """What the cycle program should hold on the device: the flat
    one-flavor world's lattice (worldgen.device_bytes: a slot x padded
    per-cohort running workload) — the windows the preemptor's scan
    pages through reuse that launch's buffers — so the count is the
    first cell's."""
    out = worldgen.device_bytes(cfg)
    out["returning_cluster_queues"] = len(returning_queues(cfg))
    return out

