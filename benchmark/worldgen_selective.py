"""The world builder of the selective-workloads kind
(benchmark/worlds/selective-3f2r-1000cq.json names it under `modules`):
worldgen_flavors.build_world's world — the same queues, flavors, quotas,
fill rule, backlog and draws — whose ResourceFlavors carry nodeLabels and
nodeTaints and whose workloads carry the node constraints of one of the
file's `profiles` (a node selector, tolerations). Nothing of the program
is imported here.

A class of this kind is a (size, profile) pair: the file's `classes`
lists them, sizes outermost, each with the `count` the arrivals' mix
deals from (the counts of a size's classes add up to the size's). The
several-flavors builder is handed the file with its `sizes` in the
classes' place; every workload it makes then gets a class of its size:

  waiting   the size's classes in the ratio of their `count`, exactly;
  running   the same ratio among the classes whose profile's `eligible`
            list — data of the file, held to the rule by the reference
            and by the invariants — has the flavor the workload sits on.

Both are dealt from shuffled blocks drawn from the file's `scenario`, so
the seed still only relabels. An arrival of the run gets its class from
the traffic generator, which deals the classes' `count`s: its selector
and tolerations come with its class.
"""

from __future__ import annotations

import math
import random

import worldgen_flavors


def allowed_flavors(cfg: dict) -> dict:
    """For each profile, the indices of the file's flavors it may take:
    the file's own `eligible` lists, as data. The builder states no
    matching rule; the reference (plain_selective.py) and the invariants
    (invariants_selective.py) each hold the lists to theirs."""
    index = {fl["name"]: f for f, fl in enumerate(cfg["flavors"])}
    return {p["name"]: sorted(index[name] for name in p["eligible"])
            for p in cfg["profiles"]}


class Dealer:
    """Classes in the ratio of their `count` among those allowed,
    exactly over every block, a hand for each set of them."""

    def __init__(self, cfg: dict, rng: random.Random):
        self.rng = rng
        self.counts = [c["count"] for c in cfg["classes"]]
        self.hands: dict = {}

    def deal(self, among: tuple) -> int:
        hand = self.hands.setdefault(among, [])
        if not hand:
            unit = math.gcd(*(self.counts[k] for k in among))
            hand.extend(k for k in among
                        for _ in range(self.counts[k] // unit))
            self.rng.shuffle(hand)
        return hand.pop()


def check_classes(cfg: dict) -> None:
    """The file's classes are (size, profile) pairs, sizes outermost,
    with their size's request and priority, and a size's classes add up
    to its count."""
    sizes = {s["name"]: (i, s) for i, s in enumerate(cfg["sizes"])}
    profiles = {p["name"] for p in cfg["profiles"]}
    total = dict.fromkeys(sizes, 0)
    at = 0
    for c in cfg["classes"]:
        i, size = sizes[c["size"]]
        if c["profile"] not in profiles or i < at or (
                c["name"], c["request"], c["priority"]) != (
                f"{c['size']}.{c['profile']}", size["request"],
                size["priority"]):
            raise ValueError(f"class {c['name']} is not "
                             f"{c['size']} x {c['profile']}")
        at = i
        total[c["size"]] += c["count"]
    for name, (_i, size) in sizes.items():
        if total[name] != size["count"]:
            raise ValueError(f"the classes of size {name} count "
                             f"{total[name]}, the size {size['count']}")


def build_world(cfg: dict, seed: int) -> dict:
    check_classes(cfg)
    world = worldgen_flavors.build_world(
        dict(cfg, classes=cfg["sizes"]), seed)
    allowed = allowed_flavors(cfg)
    size_of = {s["name"]: i for i, s in enumerate(cfg["sizes"])}
    of_size = [tuple(k for k, c in enumerate(cfg["classes"])
                     if size_of[c["size"]] == i)
               for i in range(len(cfg["sizes"]))]
    on_flavor = [[tuple(k for k in ks
                        if f in allowed[cfg["classes"][k]["profile"]])
                  for f in range(len(cfg["flavors"]))] for ks in of_size]
    dealer = Dealer(cfg, random.Random(cfg["scenario"] * 1_000_003 + 41))
    world["running"] = [
        (name, ci, dealer.deal(on_flavor[k][f]), at)
        for (name, ci, k, at), f in zip(world["running"],
                                        world["running_on"])]
    world["pending"] = [(name, ci, dealer.deal(of_size[k]), at)
                        for name, ci, k, at in world["pending"]]
    world["classes"] = cfg["classes"]
    world["profiles"] = cfg["profiles"]
    world["flavor_specs"] = cfg["flavors"]
    return world


def device_bytes(cfg: dict) -> dict:
    """What a cycle's two programs should hold on the device: the
    several-flavors kind's buckets and lattices (a ClusterQueue slot, or
    a row of the sim program's block, x the fullest cohort's padded
    running set) at the bytes a lattice cell that the chip compiler
    counted for this world's shapes (`device_bytes_reckoned` in the
    world file, and how they were counted): the runtime reserves for the
    larger program's temporaries and holds code and outputs in use."""
    stated = cfg["device_bytes_reckoned"]
    out = worldgen_flavors.device_bytes(dict(cfg, classes=cfg["sizes"]))
    out["cycle_temp_bytes"] = stated["cycle_program_temp_per_cell"] \
        * cfg["cluster_queues"] * out["per_cohort_pad"]
    out["sim_temp_bytes"] = stated["sim_program_temp_per_cell"] \
        * out["sim_block"] * out["per_cohort_pad"]
    out["code_bytes"] = stated["code"]
    out["sum"] = max(out["cycle_temp_bytes"], out["sim_temp_bytes"]) \
        + out["code_bytes"] + out["out_bytes"]
    return out
