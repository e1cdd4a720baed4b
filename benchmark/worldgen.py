"""The world builder of the flat one-flavor kind, and of every world file
that names no other (run.DEFAULT_MODULES): it is handed the numbers of
benchmark/worlds/<config>.json and the seed, and returns plain records —
nothing of the program is imported here, so the system under test
(sut.py) and the reference (plain.py) are handed the same data.

A world is a full cluster: ClusterQueues in flat cohorts, one flavor,
one resource, each with the file's nominal quota and borrowing limit;
the file's running set holds the quota and the file's backlog waits
behind it. Which workload sits in which ClusterQueue, admitted or
created when, is drawn from the file's `scenario` number, not from the
seed: the seed relabels (relabel()) — it decides which ClusterQueue
index each queue of the scenario gets, and every workload's name. So
every seed does the same work on other rows, columns and names, and two
seeds' runs differ by the machine alone (PERF.md section 2).

Records (tuples, cheap at tens of thousands of objects):
    running  (name, cq index, class index, reserved_at)
    pending  (name, cq index, class index, created_at)
"""

from __future__ import annotations

import random


def pow2_bucket(n: int, floor: int) -> int:
    """The program's padding of a dynamic axis, restated
    (kueue_tpu/tensor/schema.py): next power of two, at least floor."""
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


def relabel(n_cqs: int, n_cohorts: int, seed: int) -> tuple:
    """(place, tag): the scenario's queue q is ClusterQueue place[q] —
    a seeded permutation that keeps every queue in its cohort (cohort of
    index i is i % n_cohorts) — and every workload's name ends in tag."""
    rng = random.Random(seed * 1_000_003 + 17)
    place = [0] * n_cqs
    for co in range(n_cohorts):
        mine = list(range(co, n_cqs, n_cohorts))
        to = list(mine)
        rng.shuffle(to)
        for q, i in zip(mine, to):
            place[q] = i
    return place, format(seed, "x")


def build_world(cfg: dict, seed: int) -> dict:
    rng = random.Random(cfg["scenario"] * 1_000_003 + 17)
    n_cqs, n_cohorts = cfg["cluster_queues"], cfg["cohorts"]
    place, tag = relabel(n_cqs, n_cohorts, seed)
    classes = cfg["classes"]
    cqs = [{"name": f"cq-{i}", "cohort": f"cohort-{i % n_cohorts}",
            "nominal_milli": cfg["nominal_milli"],
            "borrowing_limit_milli": cfg["borrowing_limit_milli"]}
           for i in range(n_cqs)]

    def deal(totals: dict) -> list:
        """(cq index, class index) for the cluster's totals of each
        class, dealt round the scenario's queues from a drawn start,
        then shuffled."""
        order = list(range(n_cqs))
        rng.shuffle(order)
        slots, at = [], 0
        for k, c in enumerate(classes):
            for _ in range(totals.get(c["name"], 0)):
                slots.append((place[order[at % n_cqs]], k))
                at += 1
        rng.shuffle(slots)
        return slots

    # The engine's clock is Unix time, as a cluster's is. Running: the
    # quota was reserved over the hour before the run starts, one
    # workload after another; pending: created in the ten seconds
    # before it, 100 us apart.
    base = float(cfg["epoch_seconds"])
    slots = deal(cfg["running"])
    step = 3500.0 / max(1, len(slots))
    running = [(f"run-{i}-{tag}", ci, k, base - 3600.0 + step * i)
               for i, (ci, k) in enumerate(slots)]
    # Waiting workloads are read back in name order, which is not the
    # order they were created in.
    slots = deal(cfg["pending"])
    born = list(range(len(slots)))
    rng.shuffle(born)
    pending = [(f"wait-{i}-{tag}", ci, k, base - 10.0 + 0.0001 * born[i])
               for i, (ci, k) in enumerate(slots)]
    return {"name": cfg["name"],
            "cohorts": [f"cohort-{i}" for i in range(n_cohorts)],
            "cluster_queues": cqs, "classes": classes,
            "preemption": cfg["preemption"],
            "running": running, "pending": pending,
            "clock0": base, "scenario": cfg["scenario"],
            "place": place, "tag": tag}


def device_bytes(cfg: dict) -> dict:
    """What the cycle program should hold on the device, by the
    compiler's count at PR 26 (benchmark/README.md): 546 bytes of temp a
    (ClusterQueue slot x padded per-cohort running workload) and 5 bytes
    of output a (slot x padded running workload)."""
    n_cqs = cfg["cluster_queues"]
    running = sum(cfg["running"].values())
    a_pad = pow2_bucket(running, 8)
    per_cohort = pow2_bucket(-(-running // cfg["cohorts"]), 8)
    return {"a_pad": a_pad, "per_cohort_pad": per_cohort,
            "w_pad": pow2_bucket(
                sum(cfg["pending"].values()), 64),
            "temp_bytes": 546 * n_cqs * per_cohort,
            "out_bytes": 5 * n_cqs * a_pad}
