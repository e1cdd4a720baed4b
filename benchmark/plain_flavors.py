"""The plain reference of the several-flavors kind: Kueue's admission
cycle, one workload at a time, for ClusterQueues in flat cohorts with
ONE resource group that covers several resources and lists several
ResourceFlavors in order, under `flavorFungibility`. It imports nothing
of the program and nothing the adapter (sut_flavors.py) imports: it is
handed the world's records (worldgen_flavors.py) and the events the
timed loop sent, and decides every cycle again.

What it states, from Kueue's own sources as the builder recalls them
(pkg/scheduler/scheduler.go, flavorassigner/flavorassigner.go,
preemption/preemption.go, preemption/preemption_oracle.go,
pkg/cache/queue/cluster_queue.go):

  heads       one a ClusterQueue a cycle: highest priority, then earliest
              creation time (BestEffortFIFO); parking as plain.py's.
  a cell      fitsResourceQuota for one (flavor, resource) of a head:
              NoFit where the request is over min(cohort quota, nominal +
              borrowingLimit); Fit where it is within what is available
              (cohort unused, and the queue within nominal + limit), with
              borrow 0 or 1 by whether the queue would be over nominal;
              else, where nominal >= request, the SIMULATION of a
              preemption for that one (flavor, resource)
              (preemption_oracle.go SimulatePreemption): Preempt with the
              borrow after the victims are gone, or NoCandidates; else
              NoFit.
  a flavor    its mode is the worst of its resources' (isPreferred); the
              flavors are walked in the group's order and the walk stops
              at the first whose mode need not try the next
              (shouldTryNextFlavor: NoFit and NoCandidates always try it;
              Preempt under whenCanPreempt TryNextFlavor; borrowing under
              whenCanBorrow TryNextFlavor); else the best seen wins. All
              the group's resources land on the chosen flavor. Every
              nomination starts at the first flavor: Kueue's
              LastTriedFlavorIdx resume is not stated (the program has
              none; under TryNextFlavor a walk that does not end on Fit
              has tried every flavor, and a skipped Fit is reset).
  victims     the final target selection (preemption.go GetTargets) for
              a head whose flavor's mode is Preempt: the lower-priority
              workloads of its own queue (withinClusterQueue
              LowerPriority) that hold any (flavor, resource) that needs
              preemption; lower priority first, later admitted first;
              taken until every resource of the head fits, then given
              back from the other end while it still does.
              reclaimWithinCohort is `Never` here, nothing else.
  order       the commit order is by the assignment's borrow (the worst
              over its resources, as each cell said it: a simulated
              cell's is the borrow after ITS victims), then higher
              priority, then earlier creation; each entry is checked
              again, on every (flavor, resource) it uses, against what the
              earlier ones took; a Preempt head with no victims keeps
              room (quotaResourcesToReserve) and parks.

``stamp`` is applied to every time the reference reads: the identity in
a benchmark run, a rounding to float32 in the control (control.py).
"""

from __future__ import annotations

import heapq
import sys

NO_FIT, NO_CANDIDATES, PREEMPT, FIT = 0, 1, 2, 4
BIG = 1 << 30


def preferred(a: tuple, b: tuple) -> bool:
    """isPreferred(a, b), the default preference (borrowing over
    preemption): a mode is (preemption mode, borrow)."""
    if a[0] == NO_FIT:
        return False
    if b[0] == NO_FIT:
        return True
    if a[0] != b[0]:
        return a[0] > b[0]
    return a[1] < b[1]


class Plain:
    def __init__(self, world: dict, stamp=float):
        self.stamp = stamp
        pre = world["preemption"]
        self.within = pre["within_cluster_queue"]
        if self.within not in ("NEVER", "LOWER_PRIORITY") \
                or pre["reclaim_within_cohort"] != "NEVER":
            raise ValueError(f"the stanza {pre!r} is not stated here")
        fung = world["flavor_fungibility"]
        for policy, other in ((fung["when_can_borrow"], "BORROW"),
                              (fung["when_can_preempt"], "PREEMPT")):
            if policy not in ("TRY_NEXT_FLAVOR", other):
                raise ValueError(f"fungibility {fung!r} is not stated here")
        self.borrow_tries_next = fung["when_can_borrow"] == "TRY_NEXT_FLAVOR"
        self.preempt_tries_next = \
            fung["when_can_preempt"] == "TRY_NEXT_FLAVOR"
        self.resources = list(world["resources"])
        self.flavors = list(world["flavors"])
        S, F = len(self.resources), len(self.flavors)
        self.S, self.F = S, F
        self.classes = [
            (c["priority"], tuple(c["request"][r] for r in self.resources))
            for c in world["classes"]]
        cqs = world["cluster_queues"]
        self.names = [cq["name"] for cq in cqs]
        # [queue][flavor * S + resource]
        self.nominal = [[cq["flavors"][f]["nominal"][r]
                         for f in range(F) for r in self.resources]
                        for cq in cqs]
        self.limit = [[cq["flavors"][f]["borrowing_limit"][r]
                       for f in range(F) for r in self.resources]
                      for cq in cqs]
        cohorts = {name: i for i, name in enumerate(world["cohorts"])}
        self.cohort = [cohorts[cq["cohort"]] for cq in cqs]
        self.members: list = [[] for _ in cohorts]
        self.quota = [[0] * (F * S) for _ in cohorts]
        for i, co in enumerate(self.cohort):
            self.members[co].append(i)
            for fr in range(F * S):
                self.quota[co][fr] += self.nominal[i][fr]
        self.usage = [[0] * (F * S) for _ in cqs]
        self.used = [[0] * (F * S) for _ in cohorts]
        # name -> (priority, request, flavor, reserved at, uid, created)
        self.running: list = [{} for _ in cqs]
        # Waiting: name -> [priority, request, created, serial, in heap]
        self.active: list = [{} for _ in cqs]
        self.parked: list = [{} for _ in cqs]
        self.heap: list = [[] for _ in cqs]
        self.home: dict = {}
        self.serial = 0
        for (name, ci, k, at), f in zip(world["running"],
                                        world["running_on"]):
            pri, req = self.classes[k]
            self._run(name, ci, pri, req, f, stamp(at), stamp(at))
        for name, ci, k, at in world["pending"]:
            self.submit(name, ci, k, at)

    # -- the client's side ------------------------------------------

    def submit(self, name: str, ci: int, k: int, created: float) -> None:
        pri, req = self.classes[k]
        self._push(ci, name, [pri, req, self.stamp(created), 0, False])

    def finish(self, name: str) -> None:
        ci = self.home.pop(name, None)
        if ci is None:
            return
        if name in self.running[ci]:
            self._stop(name, ci)
            self._unpark_cohort(self.cohort[ci])
        else:
            self.active[ci].pop(name, None)
            self.parked[ci].pop(name, None)

    # -- bookkeeping ------------------------------------------------

    def _run(self, name, ci, priority, req, f, at, created) -> None:
        self.running[ci][name] = (priority, req, f, at, "uid-" + name,
                                  created)
        self.home[name] = ci
        usage, used = self.usage[ci], self.used[self.cohort[ci]]
        for s, q in enumerate(req):
            usage[f * self.S + s] += q
            used[f * self.S + s] += q

    def _stop(self, name, ci) -> tuple:
        rec = self.running[ci].pop(name)
        usage, used = self.usage[ci], self.used[self.cohort[ci]]
        for s, q in enumerate(rec[1]):
            usage[rec[2] * self.S + s] -= q
            used[rec[2] * self.S + s] -= q
        return rec

    def _push(self, ci: int, name: str, rec: list) -> None:
        self.home[name] = ci
        self.parked[ci].pop(name, None)
        self.active[ci][name] = rec
        if not rec[4]:
            self.serial += 1
            rec[3], rec[4] = self.serial, True
            heapq.heappush(self.heap[ci], (-rec[0], rec[2], rec[3], name))

    def _pop(self, ci: int):
        heap, active, parked = self.heap[ci], self.active[ci], \
            self.parked[ci]
        while heap:
            _p, _ts, serial, name = heapq.heappop(heap)
            rec = active.get(name)
            if rec is not None and rec[3] == serial:
                del active[name]
                rec[4] = False
                return name, rec
            rec = parked.get(name)
            if rec is not None and rec[3] == serial:
                rec[4] = False
        return None

    def _park(self, ci: int, name: str, rec: list) -> None:
        """The head, and every waiting workload of its shape."""
        self.parked[ci][name] = rec
        active = self.active[ci]
        same = [n for n, r in active.items()
                if r[0] == rec[0] and r[1] == rec[1]]
        for n in same:
            self.parked[ci][n] = active.pop(n)

    def _unpark_cohort(self, co: int) -> None:
        for ci in self.members[co]:
            parked = self.parked[ci]
            if parked:
                for name, rec in list(parked.items()):
                    self._push(ci, name, rec)

    # -- quota, for one (flavor, resource) ---------------------------

    def _available(self, ci, fr, usage, used) -> int:
        co = self.cohort[ci]
        room = self.quota[co][fr] - used[co][fr]
        if self.limit[ci][fr] is not None:
            room = min(room, self.nominal[ci][fr] - usage[ci][fr]
                       + self.limit[ci][fr])
        return max(0, room)

    def _potential(self, ci, fr) -> int:
        cap = self.quota[self.cohort[ci]][fr]
        if self.limit[ci][fr] is not None:
            cap = min(cap, self.nominal[ci][fr] + self.limit[ci][fr])
        return cap

    # -- victims ----------------------------------------------------

    def _candidates(self, ci, priority, f, needed) -> list:
        """The queue's running workloads a head of ``priority`` may
        preempt that hold any of the ``needed`` resources on flavor
        ``f``, in the order they are taken."""
        if self.within == "NEVER":
            return []
        got = [(n, r) for n, r in self.running[ci].items()
               if r[0] < priority and r[2] == f
               and any(r[1][s] > 0 for s in needed)]
        got.sort(key=lambda c: (c[1][0], -c[1][3], c[1][4]))
        return got

    def _take(self, ci, f, request: dict, needed, priority, usage,
              used) -> list:
        """classicalPreemptions with every candidate of the head's own
        queue (so borrowing is allowed): victims (name, record) making
        room for ``request`` {resource index: quantity} on flavor ``f``,
        or [] where no set does. ``usage`` and ``used`` are left as they
        were."""
        S, co = self.S, self.cohort[ci]
        mine, ours = usage[ci], used[co]

        def fits() -> bool:
            return all(q <= self._available(ci, f * S + s, usage, used)
                       for s, q in request.items())

        def move(rec, sign) -> None:
            for s, q in enumerate(rec[1]):
                mine[f * S + s] += sign * q
                ours[f * S + s] += sign * q

        targets: list = []
        found = False
        for c in self._candidates(ci, priority, f, needed):
            move(c[1], -1)
            targets.append(c)
            if fits():
                found = True
                i = len(targets) - 2
                while i >= 0:
                    move(targets[i][1], +1)
                    if fits():
                        targets[i] = targets[-1]
                        targets.pop()
                    else:
                        move(targets[i][1], -1)
                    i -= 1
                break
        after = [mine[f * S + s] for s in range(S)]
        for c in targets:
            move(c[1], +1)
        if not found:
            return [], None
        return targets, after

    def _cell(self, ci, f, s, val, priority, usage, used) -> tuple:
        """fitsResourceQuota: (mode, borrow) of one (flavor, resource)."""
        fr = f * self.S + s
        if val > self._potential(ci, fr):
            return NO_FIT, 0
        borrow = int(usage[ci][fr] + val > self.nominal[ci][fr])
        if val <= self._available(ci, fr, usage, used):
            return FIT, borrow
        if self.nominal[ci][fr] < val:
            return NO_FIT, borrow
        targets, after = self._take(ci, f, {s: val}, (s,), priority,
                                    usage, used)
        if not targets:
            return NO_CANDIDATES, borrow
        return PREEMPT, int(after[s] + val > self.nominal[ci][fr])

    def _try_next(self, mode: tuple) -> bool:
        if mode[0] in (NO_FIT, NO_CANDIDATES):
            return True
        if mode[0] == PREEMPT and self.preempt_tries_next:
            return True
        return mode[1] > 0 and self.borrow_tries_next

    def _nominate(self, ci, priority, req, usage, used) -> dict:
        """findFlavorForPodSets over the one resource group, then
        GetTargets where the flavor's mode is Preempt."""
        best, best_mode = None, (NO_FIT, BIG)
        for f in range(self.F):
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

    # -- the cycle --------------------------------------------------

    def cycle(self, now: float) -> dict:
        now = self.stamp(now)
        heads = []
        for ci in range(len(self.names)):
            got = self._pop(ci)
            if got is not None:
                heads.append((ci,) + got)
        if not heads:
            return {"idle": True, "admitted": [], "preempting": []}
        S = self.S
        usage = [list(u) for u in self.usage]
        used = [list(u) for u in self.used]
        entries = []
        for ci, name, rec in heads:
            e = self._nominate(ci, rec[0], rec[1], usage, used)
            e.update(ci=ci, name=name, rec=rec, status="requeue")
            entries.append(e)
        order = sorted(entries, key=lambda e: (
            e["borrows"], -e["rec"][0], e["rec"][2]))
        preempted: dict = {}    # name -> (queue, flavor, request)
        committed = []

        def move(ci, f, req, sign) -> None:
            for s, q in enumerate(req):
                usage[ci][f * S + s] += sign * q
                used[self.cohort[ci]][f * S + s] += sign * q

        for e in order:
            ci, req, f = e["ci"], e["rec"][1], e["flavor"]
            if e["mode"] == NO_FIT:
                e["status"] = "park"
                continue
            if e["mode"] == PREEMPT and not e["targets"]:
                e["status"] = "park"
                # Room is kept for a head nobody can make room for
                # (reclaimWithinCohort is not Any).
                for s, q in enumerate(req):
                    fr = f * S + s
                    if q == 0:
                        continue
                    if e["borrows"]:
                        keep = q if self.limit[ci][fr] is None else min(
                            q, self.nominal[ci][fr] + self.limit[ci][fr]
                            - usage[ci][fr])
                    else:
                        keep = max(0, min(q, self.nominal[ci][fr]
                                          - usage[ci][fr]))
                    usage[ci][fr] += keep
                    used[self.cohort[ci]][fr] += keep
                continue
            if any(n in preempted for n, _r in e["targets"]):
                continue
            gone = list(preempted.values()) + [
                (ci, f, r) for _n, r in e["targets"]]
            for cj, fj, r in gone:
                move(cj, fj, r, -1)
            ok = all(q <= self._available(ci, f * S + s, usage, used)
                     for s, q in enumerate(req) if q > 0)
            for cj, fj, r in gone:
                move(cj, fj, r, +1)
            if not ok:
                continue
            for n, r in e["targets"]:
                preempted[n] = (ci, f, r)
            move(ci, f, req, +1)
            e["status"] = "preempt" if e["mode"] == PREEMPT else "admit"
            committed.append(e)

        admitted, preempting, evicting = [], [], set()
        for e in entries:
            ci, name, rec = e["ci"], e["name"], e["rec"]
            if e["status"] == "admit":
                self._run(name, ci, rec[0], rec[1], e["flavor"], now,
                          rec[2])
            elif e["status"] == "preempt":
                for n, _r in e["targets"]:
                    p, r, _f, _at, _uid, created = self._stop(n, ci)
                    evicting.add(self.cohort[ci])
                    self._push(ci, n, [p, r, created, 0, False])
                preempting.append(
                    (name, sorted(n for n, _r in e["targets"])))
                self._push(ci, name, rec)
            elif e["status"] == "park":
                self._park(ci, name, rec)
            else:
                self._push(ci, name, rec)
        for co in evicting:
            self._unpark_cohort(co)
        for e in committed:
            if e["status"] == "admit":
                flavor = self.flavors[e["flavor"]]
                admitted.append((
                    e["name"], self.names[e["ci"]],
                    tuple((r, flavor) for r, q in zip(
                        self.resources, e["rec"][1]) if q > 0),
                    tuple((r, flavor, q) for r, q in zip(
                        self.resources, e["rec"][1]) if q > 0)))
        return {"idle": False, "admitted": admitted,
                "preempting": sorted(preempting)}

    def state(self) -> dict:
        holds = sorted((n, self.names[ci], self.flavors[r[2]])
                       for ci, run in enumerate(self.running)
                       for n, r in run.items())
        waits = sorted(n for ci in range(len(self.names))
                       for n in list(self.active[ci]) + list(self.parked[ci]))
        return {"holds": holds, "waits": waits}

    def close(self) -> None:
        pass


def later_flavor_counts(world: dict, verdicts: list) -> tuple:
    """(admissions, preemptions) decided on a flavor past the group's
    first: an admission by its own verdict; a preemption by the flavor
    its victims hold, from the world's running set and the admissions
    before it."""
    first = world["flavors"][0]
    on = {rec[0]: world["flavors"][f]
          for rec, f in zip(world["running"], world["running_on"])}
    admissions = preemptions = 0
    for v in verdicts:
        for _head, victims in v["preempting"]:
            if victims and on.get(victims[0], first) != first:
                preemptions += 1
        for name, _cq, flavor, _used in v["admitted"]:
            on[name] = flavor[0][1]
            if flavor[0][1] != first:
                admissions += 1
    return admissions, preemptions


def count_admissions_on_a_later_flavor(world: dict, verdicts: list) -> int:
    """The world file's added minimum (`compared_at_least`); the count
    of preemptions on a later flavor is printed beside it."""
    admissions, preemptions = later_flavor_counts(world, verdicts)
    print(f"compared on a later flavor: admissions = {admissions}, "
          f"preemptions = {preemptions}", file=sys.stderr, flush=True)
    return admissions
