"""The guarantees the returning-cohort world's file states, checked on
the timed run's own verdicts: invariants.check, and what that module
does not state under `reclaimWithinCohort: Any`:

  * a victim of another ClusterQueue comes from a queue that held more
    than its nominal quota when its cycle began;
  * and only for a head whose own queue is within nominal once the head
    is admitted: what the queue held when the cycle began, less the
    head's victims there, plus the head's request.

Plain bookkeeping over the events and the verdicts; nothing of the
program and not a line of the reference.
"""

from __future__ import annotations

import invariants


def check(world: dict, events: list, verdicts: list) -> list:
    bad = invariants.check(world, events, verdicts)
    classes = world["classes"]
    cqs = world["cluster_queues"]
    index = {cq["name"]: i for i, cq in enumerate(cqs)}
    nominal = [cq["nominal_milli"] for cq in cqs]
    request = [c["request_milli"] for c in classes]

    info = {name: (ci, k) for name, ci, k, _at
            in world["running"] + world["pending"]}
    running = {name: ci for name, ci, _k, _at in world["running"]}
    used = [0] * len(cqs)
    for name, ci in running.items():
        used[ci] += request[info[name][1]]

    def release(name: str) -> None:
        used[running.pop(name)] -= request[info[name][1]]

    for n, ((finishes, arrivals, _now), v) in enumerate(
            zip(events, verdicts)):
        for name in finishes:
            if name in running:
                release(name)
        for name, ci, k, _created in arrivals:
            info[name] = (ci, k)
        began = list(used)
        for head, victims in v["preempting"]:
            if head not in info:
                continue    # invariants.check has said so
            hci, hk = info[head]
            elsewhere = [x for x in victims
                         if running.get(x, hci) != hci]
            for name in elsewhere:
                vci = running[name]
                if began[vci] <= nominal[vci]:
                    bad.append(
                        f"cycle {n}: victim {name} of {cqs[vci]['name']}, "
                        "which was within its nominal quota")
            freed = sum(request[info[x][1]] for x in victims
                        if running.get(x) == hci)
            if elsewhere and began[hci] - freed + request[hk] \
                    > nominal[hci]:
                bad.append(
                    f"cycle {n}: {head} reclaims from other queues and "
                    f"{cqs[hci]['name']} would be over its nominal quota")
            for name in victims:
                if name in running:
                    release(name)
        for name, cq, _flavor, _quota in v["admitted"]:
            ci = index.get(cq)
            if ci is not None and name in info:
                running[name] = ci
                used[ci] += request[info[name][1]]
    return bad
