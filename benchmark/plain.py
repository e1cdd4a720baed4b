"""The plain reference: Kueue's admission cycle, one workload at a time,
for the worlds of this benchmark — ClusterQueues in flat cohorts, one
flavor, one resource, one pod set. It imports nothing of the program and
takes nothing the program has made: it is handed the world's records
(worldgen.py) and the events the timed loop sent (trafficgen.py), and
decides every cycle again.

What it states, from Kueue's own sources (pkg/scheduler/scheduler.go,
flavorassigner.go, preemption/, pkg/cache/queue/cluster_queue.go):

  heads       one a ClusterQueue a cycle: highest priority, then earliest
              creation time (BestEffortFIFO). A head that finds no room
              and no victim is parked with every waiting workload of its
              shape, until quota is freed somewhere in its cohort.
  fit         a request fits where the cohort has that much unused and
              the queue stays within nominal + borrowingLimit.
  victims     where it does not fit: candidates are the lower-priority
              workloads of its own queue (withinClusterQueue
              LowerPriority) and, under reclaimWithinCohort, those of
              the cohort's queues that run over their nominal quota;
              ordered other queues first, lower priority first, later
              admitted first; taken until the head fits, then given back
              from the other end while it still fits. Victims of other
              queues only for a head whose queue stays within nominal.
  order       the cycle commits heads that need not borrow first, then
              higher priority, then earlier creation; each is checked
              again against what the earlier ones took; a head whose
              victims overlap an earlier head's is skipped.
  after       the admitted run; victims are evicted at once and wait
              again under their creation time; a preempting head waits
              for the next cycle.

``stamp`` is applied to every time the reference reads: the identity in
a benchmark run, a rounding to float32 in the control (control.py).
"""

from __future__ import annotations

import heapq

FLAVOR = "default"
NO_FIT, PREEMPT, FIT = 0, 1, 2


class Plain:
    def __init__(self, world: dict, stamp=float):
        self.stamp = stamp
        pre = world["preemption"]
        self.within = pre["within_cluster_queue"]
        self.reclaim = pre["reclaim_within_cohort"]
        for policy in (self.within, self.reclaim):
            if policy not in ("NEVER", "LOWER_PRIORITY", "ANY"):
                raise ValueError(f"policy {policy!r} is not stated here")
        self.classes = world["classes"]
        cqs = world["cluster_queues"]
        self.names = [cq["name"] for cq in cqs]
        self.nominal = [cq["nominal_milli"] for cq in cqs]
        self.limit = [cq["borrowing_limit_milli"] for cq in cqs]
        cohorts = {name: i for i, name in enumerate(world["cohorts"])}
        self.cohort = [cohorts[cq["cohort"]] for cq in cqs]
        self.members: list = [[] for _ in cohorts]
        self.quota = [0] * len(cohorts)     # a cohort's nominal, summed
        for i, co in enumerate(self.cohort):
            self.members[co].append(i)
            self.quota[co] += self.nominal[i]
        self.usage = [0] * len(cqs)
        self.used = [0] * len(cohorts)
        # name -> (priority, request, reserved at, uid, created)
        self.running: list = [{} for _ in cqs]
        # Waiting: name -> [priority, request, created, serial, in heap]
        self.active: list = [{} for _ in cqs]
        self.parked: list = [{} for _ in cqs]
        self.heap: list = [[] for _ in cqs]
        self.home: dict = {}    # name -> ClusterQueue index, while known
        self.serial = 0
        self._others: dict = {}     # a cycle's borrowers, by cohort
        for name, ci, k, at in world["running"]:
            c = self.classes[k]
            self._run(name, ci, c["priority"], c["request_milli"],
                      stamp(at), stamp(at))
        for name, ci, k, at in world["pending"]:
            self.submit(name, ci, k, at)

    # -- the client's side ------------------------------------------

    def submit(self, name: str, ci: int, k: int, created: float) -> None:
        c = self.classes[k]
        self._push(ci, name, [c["priority"], c["request_milli"],
                              self.stamp(created), 0, False])

    def finish(self, name: str) -> None:
        ci = self.home.pop(name, None)
        if ci is None:
            return      # nobody the reference knows: a broken run's
        if name in self.running[ci]:
            self._stop(name, ci)
            self._unpark_cohort(self.cohort[ci])
        else:
            self.active[ci].pop(name, None)
            self.parked[ci].pop(name, None)

    # -- bookkeeping ------------------------------------------------

    def _run(self, name, ci, priority, request, at, created) -> None:
        self.running[ci][name] = (priority, request, at, "uid-" + name,
                                  created)
        self.home[name] = ci
        self.usage[ci] += request
        self.used[self.cohort[ci]] += request

    def _stop(self, name, ci) -> tuple:
        rec = self.running[ci].pop(name)
        self.usage[ci] -= rec[1]
        self.used[self.cohort[ci]] -= rec[1]
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
                rec[4] = False      # its node is gone; pushed anew later
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

    # -- quota ------------------------------------------------------

    def _available(self, ci, usage, used) -> int:
        room = self.quota[self.cohort[ci]] - used[self.cohort[ci]]
        if self.limit[ci] is not None:
            room = min(room, self.nominal[ci] - usage[ci] + self.limit[ci])
        return max(0, room)

    def _potential(self, ci) -> int:
        cap = self.quota[self.cohort[ci]]
        if self.limit[ci] is not None:
            cap = min(cap, self.nominal[ci] + self.limit[ci])
        return cap

    # -- victims ----------------------------------------------------

    @staticmethod
    def _may(policy: str, head_priority: int, priority: int) -> bool:
        if policy == "ANY":
            return True
        return policy == "LOWER_PRIORITY" and priority < head_priority

    def _borrowers(self, co, usage) -> list:
        """The running workloads of the cohort's ClusterQueues that are
        over their nominal quota, in the order victims are taken: the
        same list for every head of one cycle."""
        got = self._others.get(co)
        if got is None:
            got = [(n, cj, r) for cj in self.members[co]
                   if usage[cj] > self.nominal[cj]
                   for n, r in self.running[cj].items()]
            got.sort(key=lambda c: (c[2][0], -c[2][2], c[2][3]))
            self._others[co] = got
        return got

    def _targets(self, ci, priority, request, usage, used) -> list:
        """[(name, ClusterQueue index, request)], or [] where no set of
        victims makes room."""
        co = self.cohort[ci]
        same = [(n, ci, r) for n, r in self.running[ci].items()
                if self._may(self.within, priority, r[0])]
        same.sort(key=lambda c: (c[2][0], -c[2][2], c[2][3]))
        others: list = []
        if self.reclaim != "NEVER":
            others = [c for c in self._borrowers(co, usage) if c[1] != ci
                      and self._may(self.reclaim, priority, c[2][0])]
        # A head whose queue would stay within nominal reclaims with
        # the hierarchy's advantage; otherwise only without borrowing.
        advantage = self.nominal[ci] >= usage[ci] + request
        if not others or not self.nominal[ci] > usage[ci]:
            attempts = [True]
        elif not advantage:
            attempts = [False, True]
        else:
            attempts = [True, False]

        for borrow in attempts:
            usage2: dict = {ci: usage[ci]}
            used2 = {co: used[co]}

            def fits() -> bool:
                if not borrow and self.nominal[ci] < usage2[ci] + request:
                    return False
                return request <= self._available(ci, usage2, used2)

            def take(c, sign) -> None:
                usage2[c[1]] = usage2.get(c[1], usage[c[1]]) \
                    + sign * c[2][1]
                used2[co] += sign * c[2][1]

            # Other queues' workloads first; none of them while
            # borrowing, unless the head has the advantage.
            candidates = same if borrow and not advantage \
                else others + same
            targets: list = []
            for c in candidates:
                cj = c[1]
                if cj != ci and usage2.get(cj, usage[cj]) \
                        <= self.nominal[cj]:
                    continue
                take(c, -1)
                targets.append(c)
                if fits():
                    i = len(targets) - 2
                    while i >= 0:
                        take(targets[i], +1)
                        if fits():
                            targets[i] = targets[-1]
                            targets.pop()
                        else:
                            take(targets[i], -1)
                        i -= 1
                    return [(c[0], c[1], c[2][1]) for c in targets]
        return []

    def _nominate(self, ci, priority, request, usage, used) -> tuple:
        """(mode, borrows, victims)."""
        if request > self._potential(ci):
            return NO_FIT, 0, []
        borrows = int(usage[ci] + request > self.nominal[ci])
        if request <= self._available(ci, usage, used):
            return FIT, borrows, []
        if not (self.nominal[ci] >= request or not borrows):
            return NO_FIT, borrows, []
        targets = self._targets(ci, priority, request, usage, used)
        if targets:
            freed = sum(r for _n, cj, r in targets if cj == ci)
            borrows = int(usage[ci] - freed + request > self.nominal[ci])
        return PREEMPT, borrows, targets

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
        usage, used = list(self.usage), list(self.used)
        self._others = {}           # nomination reads one state
        entries = []
        for ci, name, rec in heads:
            mode, borrows, targets = self._nominate(
                ci, rec[0], rec[1], usage, used)
            entries.append({"ci": ci, "name": name, "rec": rec,
                            "mode": mode, "borrows": borrows,
                            "targets": targets, "status": "requeue"})
        order = sorted(entries, key=lambda e: (
            e["borrows"], -e["rec"][0], e["rec"][2]))
        preempted: dict = {}    # name -> (ClusterQueue index, request)
        committed = []
        for e in order:
            ci, request = e["ci"], e["rec"][1]
            co = self.cohort[ci]
            if e["mode"] == NO_FIT:
                e["status"] = "park"
                continue
            if e["mode"] == PREEMPT and not e["targets"]:
                e["status"] = "park"
                if self.reclaim != "ANY":
                    # Room is kept for a head nobody can make room for.
                    if e["borrows"]:
                        keep = request if self.limit[ci] is None else min(
                            request, self.nominal[ci] + self.limit[ci]
                            - usage[ci])
                    else:
                        keep = max(0, min(request,
                                          self.nominal[ci] - usage[ci]))
                    usage[ci] += keep
                    used[co] += keep
                continue
            if any(n in preempted for n, _cj, _r in e["targets"]):
                continue
            gone = list(preempted.values()) + [
                (cj, r) for _n, cj, r in e["targets"]]
            for cj, r in gone:
                usage[cj] -= r
                used[self.cohort[cj]] -= r
            ok = request <= self._available(ci, usage, used)
            for cj, r in gone:
                usage[cj] += r
                used[self.cohort[cj]] += r
            if not ok:
                continue
            for n, cj, r in e["targets"]:
                preempted[n] = (cj, r)
            usage[ci] += request
            used[co] += request
            e["status"] = "preempt" if e["mode"] == PREEMPT else "admit"
            committed.append(e)

        admitted, preempting, evicting = [], [], set()
        for e in entries:
            ci, name, rec = e["ci"], e["name"], e["rec"]
            if e["status"] == "admit":
                self._run(name, ci, rec[0], rec[1], now, rec[2])
            elif e["status"] == "preempt":
                for n, cj, _r in e["targets"]:
                    p, r, _at, _uid, created = self._stop(n, cj)
                    evicting.add(self.cohort[cj])
                    self._push(cj, n, [p, r, created, 0, False])
                preempting.append(
                    (name, sorted(n for n, _cj, _r in e["targets"])))
                self._push(ci, name, rec)
            elif e["status"] == "park":
                self._park(ci, name, rec)
            else:
                self._push(ci, name, rec)
        for co in evicting:
            self._unpark_cohort(co)
        for e in committed:
            if e["status"] == "admit":
                admitted.append((e["name"], self.names[e["ci"]], FLAVOR,
                                 e["rec"][1]))
        return {"idle": False, "admitted": admitted,
                "preempting": sorted(preempting)}

    def state(self) -> dict:
        holds = sorted((n, self.names[ci])
                       for ci, run in enumerate(self.running) for n in run)
        waits = sorted(n for ci in range(len(self.names))
                       for n in list(self.active[ci]) + list(self.parked[ci]))
        return {"holds": holds, "waits": waits}

    def close(self) -> None:
        pass
