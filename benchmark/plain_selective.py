"""The plain reference of the selective-workloads kind
(benchmark/worlds/selective-3f2r-1000cq.json names it under `modules`):
plain_flavors.py's Plain — heads, cells, simulations, victims, commit
order, parking, all as stated there and not copied — with the one thing
this kind adds: a ResourceFlavor has nodeLabels and nodeTaints, a pod set
a node selector and tolerations, and **a flavor the pod set does not
match is skipped in the head's walk**. It imports nothing of the program
and nothing the adapter (sut_selective.py) imports.

The rule, from Kueue's source as the builder recalls it
(pkg/scheduler/flavorassigner/flavorassigner.go, findFlavorForPodSets ->
checkFlavorForPodSets; pkg/cache ResourceGroup.LabelKeys):

  taints     every NoSchedule / NoExecute taint of the flavor has to be
             tolerated by a toleration of the pod set or of the flavor's
             own `tolerations` (corev1 helper FindMatchingUntoleratedTaint;
             PreferNoSchedule keeps nobody off);
  selector   the pod set's node selector (and required node affinity,
             which no class of this world carries) is matched against the
             flavor's nodeLabels **restricted to the label keys of the
             resource group's flavors**: a key no flavor of the group has
             is the nodes' business, not the flavor's;
  skipped    a flavor that fails either is not a cell of the walk: it is
             neither classified nor simulated, it cannot be the walk's
             answer, and a head left with no flavor is NoFit and parks.

What goes with it: two waiting workloads are of one shape — and are
parked together — only if their node constraints are the same too
(workload.go SchedulingHash takes the pod sets' selectors and
tolerations).

The walk itself (`_nominate`) is plain_flavors.Plain's with the skip:
that method is not handed the class of the head, so it is stated here
again around the one line that differs.

`every_flavor` is the same reference with every mask all-true — what a
program that dropped the masks would decide: the cell's tests and one
chip run hold the program against it, and it has to part from it.
"""

from __future__ import annotations

import sys

import plain_flavors
from plain_flavors import BIG, FIT, NO_FIT, PREEMPT, preferred

count_admissions_on_a_later_flavor = \
    plain_flavors.count_admissions_on_a_later_flavor


def tolerates(toleration: dict, taint: dict) -> bool:
    if toleration.get("effect", "") not in ("", taint["effect"]):
        return False
    key = toleration.get("key", "")
    if key == "":
        return toleration.get("operator", "Equal") == "Exists"
    if key != taint["key"]:
        return False
    if toleration.get("operator", "Equal") == "Exists":
        return True
    return toleration.get("value", "") == taint.get("value", "")


def flavor_matches(flavor: dict, pod_set: dict, label_keys: set) -> bool:
    """checkFlavorForPodSets for one pod set: ``flavor`` has
    `node_labels`, `node_taints` and maybe `tolerations`; ``pod_set``
    `node_selector` and `tolerations`; ``label_keys`` the resource
    group's."""
    tolerations = list(pod_set["tolerations"]) \
        + list(flavor.get("tolerations", ()))
    for taint in flavor.get("node_taints", ()):
        if taint["effect"] not in ("NoSchedule", "NoExecute"):
            continue
        if not any(tolerates(t, taint) for t in tolerations):
            return False
    labels = flavor.get("node_labels", {})
    for key, value in pod_set["node_selector"].items():
        if key in label_keys and labels.get(key) != value:
            return False
    return True


def eligible_flavors(world: dict) -> list:
    """For each class of the world, the indices of the group's flavors
    its pod set matches, in the group's order. The world file lists each
    profile's flavors too (`eligible`: the builder deals the running set
    from it); a list that is not this rule's answer is an error."""
    specs = world["flavor_specs"]
    keys = {k for fl in specs for k in fl.get("node_labels", {})}
    by_rule = {p["name"]: tuple(f for f, fl in enumerate(specs)
                                if flavor_matches(fl, p, keys))
               for p in world["profiles"]}
    for p in world["profiles"]:
        stated = sorted(p.get("eligible", ()))
        if stated != sorted(specs[f]["name"] for f in by_rule[p["name"]]):
            raise ValueError(
                f"the world file lists {stated} as the flavors of the "
                f"profile {p['name']}; by checkFlavorForPodSets they are "
                f"{[specs[f]['name'] for f in by_rule[p['name']]]}")
    return [by_rule[c["profile"]] for c in world["classes"]]


class Request(tuple):
    """A class's request by resource, as plain_flavors.Plain carries it
    from a workload's record to its walk — with the class's profile and
    the flavors its pod set matches."""

    def __new__(cls, quantities, profile: str, eligible: tuple):
        self = super().__new__(cls, quantities)
        self.profile, self.eligible = profile, eligible
        return self


class Plain(plain_flavors.Plain):
    def __init__(self, world: dict, stamp=float, masks: bool = True):
        # The several-flavors reference over the same cluster, empty;
        # then this kind's classes, and the world's workloads in them.
        super().__init__(dict(world, running=[], running_on=[],
                              pending=[]), stamp)
        everywhere = tuple(range(self.F))
        self.classes = [
            (c["priority"], Request((c["request"][r]
                                     for r in self.resources),
                                    c["profile"],
                                    eligible if masks else everywhere))
            for c, eligible in zip(world["classes"],
                                   eligible_flavors(world))]
        for (name, ci, k, at), f in zip(world["running"],
                                        world["running_on"]):
            pri, req = self.classes[k]
            self._run(name, ci, pri, req, f, stamp(at), stamp(at))
        for name, ci, k, at in world["pending"]:
            self.submit(name, ci, k, at)

    def _park(self, ci: int, name: str, rec: list) -> None:
        """The head, and every waiting workload of its shape: priority,
        request and node constraints."""
        self.parked[ci][name] = rec
        active = self.active[ci]
        same = [n for n, r in active.items()
                if r[0] == rec[0] and r[1] == rec[1]
                and r[1].profile == rec[1].profile]
        for n in same:
            self.parked[ci][n] = active.pop(n)

    def _nominate(self, ci, priority, req, usage, used) -> dict:
        """plain_flavors.Plain._nominate over the flavors the head's
        pod set matches: findFlavorForPodSets skipping what
        checkFlavorForPodSets refuses, then GetTargets where the
        flavor's mode is Preempt."""
        best, best_mode = None, (NO_FIT, BIG)
        for f in range(self.F):
            if f not in req.eligible:
                continue
            rep, cells = (FIT, 0), {}
            for s, val in enumerate(req):
                if val == 0:
                    continue
                mode = self._cell(ci, f, s, val, priority, usage, used)
                if preferred(rep, mode):
                    rep = mode
                if rep[0] == NO_FIT:
                    break
                cells[s] = mode
            if not self._try_next(rep):
                best, best_mode = (f, cells), rep
                break
            if preferred(rep, best_mode):
                best, best_mode = (f, cells), rep
        if best is None or best_mode[0] == NO_FIT:
            return {"mode": NO_FIT, "borrows": 0, "flavor": None,
                    "targets": []}
        f, cells = best
        mode = min(m for m, _b in cells.values())
        out = {"mode": FIT if mode == FIT else PREEMPT, "flavor": f,
               "borrows": max(b for _m, b in cells.values()),
               "targets": []}
        if mode != FIT:
            needed = tuple(s for s, (m, _b) in cells.items() if m != FIT)
            targets, _after = self._take(
                ci, f, {s: q for s, q in enumerate(req) if q > 0}, needed,
                priority, usage, used)
            out["targets"] = [(n, r[1]) for n, r in targets]
        return out


def every_flavor(world: dict, stamp=float) -> Plain:
    """The reference with every mask all-true: no flavor is skipped."""
    return Plain(world, stamp, masks=False)


def narrowed_counts(world: dict, verdicts: list) -> tuple:
    """Over the reference's verdicts: (admissions of a workload whose
    pod set does not match some flavor of its group; of those, the ones
    on a flavor that comes after one it does not match — the walk went
    past it; evictions for a preemptor whose pod set does not match
    some flavor of its group). A workload's class is the world's; an
    arrival of the run, whose class the verdicts do not carry, is left
    out, so every count errs low."""
    eligible = eligible_flavors(world)
    index = {name: f for f, name in enumerate(world["flavors"])}
    klass = {name: k for name, _ci, k, _at
             in world["running"] + world["pending"]}

    def narrowed(name):
        k = klass.get(name)
        return k is not None and len(eligible[k]) < len(index)

    admissions = past = evictions = 0
    for v in verdicts:
        for head, victims in v["preempting"]:
            if narrowed(head):
                evictions += len(victims)
        for name, _cq, flavor, _used in v["admitted"]:
            if narrowed(name):
                admissions += 1
                past += any(f not in eligible[klass[name]]
                            for f in range(index[flavor[0][1]]))
    return admissions, past, evictions


def count_admissions_of_a_narrowed_head(world: dict, verdicts: list) -> int:
    admissions, past, evictions = narrowed_counts(world, verdicts)
    print(f"compared of narrowed heads: admissions = {admissions} (past "
          f"an excluded flavor = {past}), evictions = {evictions}",
          file=sys.stderr, flush=True)
    return admissions


def count_admissions_past_an_excluded_flavor(world: dict,
                                             verdicts: list) -> int:
    """Admissions on a flavor that comes after one the workload's mask
    excludes: the walk went past it — a workload pinned to `on-demand`
    or to `spot`, admitted there."""
    return narrowed_counts(world, verdicts)[1]


def count_evictions_for_a_narrowed_head(world: dict, verdicts: list) -> int:
    return narrowed_counts(world, verdicts)[2]
