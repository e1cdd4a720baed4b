"""The world builder of the several-flavors kind
(benchmark/worlds/fungible-3f2r-1000cq.json names it under `modules`):
file + seed -> plain records. Nothing of the program is imported here,
so the system under test (sut_flavors.py) and the reference
(plain_flavors.py) are handed the same data.

A world is a full cluster: ClusterQueues in flat cohorts, each with one
resource group that covers the file's `resources` and lists the file's
`flavors` in order, every (flavor, resource) with its nominal quota and
borrowing limit. The running set is not a list of the file: it is what
fill() makes of the file's quotas, classes and
`running_per_cluster_queue` — every flavor of every queue full, within
nominal, of one class. The file states the count that comes out
(`running_reckoned`) and the tests hold the builder to it.

As in worldgen.py every draw comes from the file's `scenario` number
and the seed relabels: which ClusterQueue index each queue of the
scenario gets, and every workload's name.

Records:
    running     (name, cq index, class index, reserved_at)
    running_on  flavor index of each, in the same order
    pending     (name, cq index, class index, created_at)
"""

from __future__ import annotations

import random

from worldgen import pow2_bucket, relabel


def fill(cfg: dict, rng: random.Random) -> list:
    """[(scenario queue, class index, flavor index)] in the order the
    quota was reserved. Every flavor of every queue holds one class,
    within its nominal quota and as many as fit: the smallest class (the
    file lists them smallest first), or — on drawn (queue, flavor)s — the
    next class that fits, one for several. As many are drawn, the same
    number in every cohort, as bring the cluster to the file's
    `running_per_cluster_queue`, the count the flat one-flavor world
    states. So every (queue, flavor) is full in one of its resources
    and no queue borrows."""
    resources, classes = cfg["resources"], cfg["classes"]
    n_cqs, n_cohorts = cfg["cluster_queues"], cfg["cohorts"]
    holds = [[min(fl["nominal"][r] // c["request"][r] for r in resources)
              for c in classes] for fl in cfg["flavors"]]
    fits = [[k for k, n in enumerate(per) if n] for per in holds]
    out = []
    for co in range(n_cohorts):
        mine = range(co, n_cqs, n_cohorts)
        sites = [(q, f) for q in mine for f, ks in enumerate(fits)
                 if len(ks) > 1]
        rng.shuffle(sites)
        count = len(mine) * sum(per[ks[0]] for per, ks in zip(holds, fits))
        want = cfg["running_per_cluster_queue"] * len(mine)
        larger = set()
        for q, f in sites:
            gain = holds[f][fits[f][0]] - holds[f][fits[f][1]]
            if count - gain / 2 < want:
                break
            larger.add((q, f))
            count -= gain
        for q in mine:
            for f, ks in enumerate(fits):
                k = ks[(q, f) in larger]
                out.extend([(q, k, f)] * holds[f][k])
    rng.shuffle(out)
    return out


def build_world(cfg: dict, seed: int) -> dict:
    rng = random.Random(cfg["scenario"] * 1_000_003 + 17)
    n_cqs, n_cohorts = cfg["cluster_queues"], cfg["cohorts"]
    if n_cqs % n_cohorts:
        raise ValueError("the cohorts hold the same number of queues")
    place, tag = relabel(n_cqs, n_cohorts, seed)
    classes = cfg["classes"]
    cqs = [{"name": f"cq-{i}", "cohort": f"cohort-{i % n_cohorts}",
            "flavors": cfg["flavors"]} for i in range(n_cqs)]

    base = float(cfg["epoch_seconds"])
    filled = fill(cfg, rng)
    step = 3500.0 / max(1, len(filled))
    running = [(f"run-{i}-{tag}", place[q], k, base - 3600.0 + step * i)
               for i, (q, k, _f) in enumerate(filled)]

    # The backlog: the file's totals of each class, dealt round the
    # scenario's queues and read back in name order, which is not the
    # order they were created in.
    order = list(range(n_cqs))
    rng.shuffle(order)
    slots, at = [], 0
    for k, c in enumerate(classes):
        for _ in range(cfg["pending"].get(c["name"], 0)):
            slots.append((place[order[at % n_cqs]], k))
            at += 1
    rng.shuffle(slots)
    born = list(range(len(slots)))
    rng.shuffle(born)
    pending = [(f"wait-{i}-{tag}", ci, k, base - 10.0 + 0.0001 * born[i])
               for i, (ci, k) in enumerate(slots)]
    return {"name": cfg["name"],
            "cohorts": [f"cohort-{i}" for i in range(n_cohorts)],
            "cluster_queues": cqs, "classes": classes,
            "resources": list(cfg["resources"]),
            "flavors": [f["name"] for f in cfg["flavors"]],
            "preemption": cfg["preemption"],
            "flavor_fungibility": cfg["flavor_fungibility"],
            "running": running,
            "running_on": [f for _q, _k, f in filled],
            "pending": pending,
            "clock0": base, "scenario": cfg["scenario"],
            "place": place, "tag": tag}


def device_bytes(cfg: dict) -> dict:
    """What a cycle's two programs should hold on the device, by the
    chip compiler's count at PR 30 (compiled.memory_analysis() for a v5e
    at this world's shapes: two resources, six flavor-resources, a
    per-cohort pad of 2,048): the cycle program with the fused preemptor
    1,364 bytes of temp a (ClusterQueue slot x padded per-cohort running
    workload) and 5 bytes of output a (slot x padded running workload);
    the sim program 1,321 bytes of temp a (row of its block x padded
    per-cohort running workload); their code 178,936,832 + 117,198,336
    bytes. The block is one row a ClusterQueue, to the next power of
    two, looped as often as a cycle's rows need. The runtime reserves
    for the larger program's temporaries, not for the sum (PR 30's chip
    runs), and holds code and outputs in use: `sum` is what
    `memory_peak_bytes` should read."""
    n_cqs = cfg["cluster_queues"]
    running = len(fill(cfg, random.Random(cfg["scenario"] * 1_000_003 + 17)))
    a_pad = pow2_bucket(running, 8)
    per_cohort = pow2_bucket(-(-running // cfg["cohorts"]), 8)
    block = pow2_bucket(n_cqs, 8)
    out = {"a_pad": a_pad, "per_cohort_pad": per_cohort,
           "w_pad": pow2_bucket(sum(cfg["pending"].values()), 64),
           "sim_block": block,
           "cycle_temp_bytes": 1364 * n_cqs * per_cohort,
           "sim_temp_bytes": 1321 * block * per_cohort,
           "code_bytes": 178_936_832 + 117_198_336,
           "out_bytes": 5 * n_cqs * a_pad}
    out["sum"] = max(out["cycle_temp_bytes"], out["sim_temp_bytes"]) \
        + out["code_bytes"] + out["out_bytes"]
    return out
