"""The guarantees the world's file states, checked on the timed run's own
verdicts by plain bookkeeping that imports nothing of the program and
takes nothing from it but those verdicts. It is weaker than the plain
reference (plain.py), which decides every verdict again, and does not
share a line with it: what one of them has wrong the other need not.

  * who is admitted was waiting — sent by the client, not running, not
    finished — in that ClusterQueue, and gets the one flavor and exactly
    its request;
  * at most one admission a ClusterQueue a cycle;
  * a ClusterQueue never holds more than its nominal quota plus its
    borrowing limit, and a cohort never more than its queues' nominal
    quota together;
  * a victim was running — in the preemptor's ClusterQueue, where
    reclaim within the cohort is `Never` — at a strictly lower priority
    (of another ClusterQueue under `Any`, at any);
  * nobody is admitted past a waiting workload of the same ClusterQueue
    with a higher priority and a request no larger than its own (such a
    one would have fitted wherever it did).
"""

from __future__ import annotations


def check(world: dict, events: list, verdicts: list) -> list:
    """The breaches, as short strings; empty where every guarantee
    held in every cycle."""
    classes = world["classes"]
    cqs = world["cluster_queues"]
    index = {cq["name"]: i for i, cq in enumerate(cqs)}
    cohort = [cq["cohort"] for cq in cqs]
    cap: dict = {}
    for cq in cqs:
        cap[cq["cohort"]] = cap.get(cq["cohort"], 0) + cq["nominal_milli"]
    reclaim = world["preemption"]["reclaim_within_cohort"] != "NEVER"

    info: dict = {}      # name -> (cq index, class index)
    waiting: list = [dict() for _ in cqs]   # name -> class index
    running: dict = {}   # name -> cq index
    used = [0] * len(cqs)
    used_cohort = dict.fromkeys(cap, 0)
    for name, ci, k, _at in world["running"]:
        info[name] = (ci, k)
        running[name] = ci
        used[ci] += classes[k]["request_milli"]
        used_cohort[cohort[ci]] += classes[k]["request_milli"]
    for name, ci, k, _at in world["pending"]:
        info[name] = (ci, k)
        waiting[ci][name] = k

    bad: list = []

    def release(name: str) -> None:
        ci = running.pop(name)
        req = classes[info[name][1]]["request_milli"]
        used[ci] -= req
        used_cohort[cohort[ci]] -= req

    for n, ((finishes, arrivals, _now), v) in enumerate(
            zip(events, verdicts)):
        for name in finishes:
            if name in running:
                release(name)
            elif name in info:
                waiting[info[name][0]].pop(name, None)
            else:
                bad.append(f"cycle {n}: {name} finishes, whom nobody sent")
        for name, ci, k, _created in arrivals:
            info[name] = (ci, k)
            waiting[ci][name] = k
        for head, victims in v["preempting"]:
            hci, hk = info.get(head, (None, None))
            if head not in (waiting[hci] if hci is not None else ()):
                bad.append(f"cycle {n}: {head} preempts but does not wait")
                continue
            for name in victims:
                if name not in running:
                    bad.append(f"cycle {n}: victim {name} does not run")
                    continue
                vci, vk = info[name]
                if not reclaim and vci != hci:
                    bad.append(f"cycle {n}: victim {name} of another "
                               "ClusterQueue")
                any_priority = vci != hci and world["preemption"][
                    "reclaim_within_cohort"] == "ANY"
                if not any_priority and classes[vk]["priority"] \
                        >= classes[hk]["priority"]:
                    bad.append(f"cycle {n}: victim {name} is not of "
                               "lower priority")
                release(name)
                waiting[vci][name] = vk
        seen: set = set()
        for name, cq, flavor, quota in v["admitted"]:
            ci = index.get(cq)
            if ci is None or name not in waiting[ci]:
                bad.append(f"cycle {n}: {name} admitted to {cq} but does "
                           "not wait there")
                continue
            k = waiting[ci].pop(name)
            req = classes[k]["request_milli"]
            if flavor != "default" or quota != req:
                bad.append(f"cycle {n}: {name} got {quota} of {flavor}, "
                           f"asked {req}")
            if ci in seen:
                bad.append(f"cycle {n}: two admissions to {cq}")
            seen.add(ci)
            pri = classes[k]["priority"]
            for other, ok in waiting[ci].items():
                if (classes[ok]["priority"] > pri
                        and classes[ok]["request_milli"] <= req):
                    bad.append(f"cycle {n}: {name} admitted past {other}")
                    break
            running[name] = ci
            used[ci] += req
            used_cohort[cohort[ci]] += req
        for ci in seen:
            limit = cqs[ci]["borrowing_limit_milli"]
            if limit is not None and used[ci] > cqs[ci]["nominal_milli"] \
                    + limit:
                bad.append(f"cycle {n}: {cqs[ci]['name']} over its "
                           "borrowing limit")
            if used_cohort[cohort[ci]] > cap[cohort[ci]]:
                bad.append(f"cycle {n}: {cohort[ci]} over its quota")
    return bad
