"""The guarantees the several-flavors world's file states, checked on
the timed run's own verdicts by plain bookkeeping that imports nothing of
the program and takes nothing from it but those verdicts. Weaker than
the plain reference (plain_flavors.py), and not a line shared with it.

  * who is admitted was waiting — sent by the client, not running, not
    finished — in that ClusterQueue, and gets exactly its request of
    every resource, all of them on ONE flavor of the queue's group;
  * at most one admission a ClusterQueue a cycle;
  * on no (flavor, resource) does a ClusterQueue hold more than its
    nominal quota plus its borrowing limit, or a cohort more than its
    queues' nominal quota together;
  * a victim was running in the preemptor's own ClusterQueue
    (reclaimWithinCohort is `Never`) at a strictly lower priority, and
    a head's victims all hold the same flavor;
  * nobody is admitted past a waiting workload of the same ClusterQueue
    with a higher priority and no larger a request of any resource.
"""

from __future__ import annotations


def check(world: dict, events: list, verdicts: list) -> list:
    """The breaches, as short strings; empty where every guarantee
    held in every cycle."""
    classes = world["classes"]
    resources, flavors = world["resources"], world["flavors"]
    cqs = world["cluster_queues"]
    index = {cq["name"]: i for i, cq in enumerate(cqs)}
    cohort = [cq["cohort"] for cq in cqs]
    frs = [(f, r) for f in flavors for r in resources]
    cap_cq = [{(fl["name"], r): (fl["nominal"][r],
                                 fl["borrowing_limit"][r])
               for fl in cq["flavors"] for r in resources} for cq in cqs]
    cap_co: dict = {}
    for ci, cq in enumerate(cqs):
        mine = cap_co.setdefault(cq["cohort"], dict.fromkeys(frs, 0))
        for fr in frs:
            mine[fr] += cap_cq[ci][fr][0]

    info: dict = {}      # name -> (cq index, class index)
    waiting: list = [dict() for _ in cqs]   # name -> class index
    running: dict = {}   # name -> (cq index, flavor)
    used = [dict.fromkeys(frs, 0) for _ in cqs]
    used_co = {co: dict.fromkeys(frs, 0) for co in cap_co}

    def hold(name: str, ci: int, flavor: str, sign: int) -> None:
        req = classes[info[name][1]]["request"]
        for r in resources:
            used[ci][(flavor, r)] += sign * req[r]
            used_co[cohort[ci]][(flavor, r)] += sign * req[r]

    for (name, ci, k, _at), f in zip(world["running"],
                                     world["running_on"]):
        info[name] = (ci, k)
        running[name] = (ci, flavors[f])
        hold(name, ci, flavors[f], +1)
    for name, ci, k, _at in world["pending"]:
        info[name] = (ci, k)
        waiting[ci][name] = k

    bad: list = []
    for n, ((finishes, arrivals, _now), v) in enumerate(
            zip(events, verdicts)):
        for name in finishes:
            if name in running:
                hold(name, *running.pop(name), -1)
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
            held = set()
            for name in victims:
                if name not in running:
                    bad.append(f"cycle {n}: victim {name} does not run")
                    continue
                vci, flavor = running.pop(name)
                held.add(flavor)
                if vci != hci:
                    bad.append(f"cycle {n}: victim {name} of another "
                               "ClusterQueue")
                if classes[info[name][1]]["priority"] \
                        >= classes[hk]["priority"]:
                    bad.append(f"cycle {n}: victim {name} is not of "
                               "lower priority")
                hold(name, vci, flavor, -1)
                waiting[vci][name] = info[name][1]
            if len(held) > 1:
                bad.append(f"cycle {n}: {head}'s victims hold "
                           f"{sorted(held)}")
        seen: set = set()
        for name, cq, flavor, quota in v["admitted"]:
            ci = index.get(cq)
            if ci is None or name not in waiting[ci]:
                bad.append(f"cycle {n}: {name} admitted to {cq} but does "
                           "not wait there")
                continue
            k = waiting[ci].pop(name)
            req = classes[k]["request"]
            on = {f for _r, f in flavor}
            if len(on) != 1 or not on <= set(flavors):
                bad.append(f"cycle {n}: {name}'s resources land on "
                           f"{sorted(on)}")
                continue
            on = on.pop()
            if tuple(quota) != tuple((r, on, req[r]) for r in resources
                                     if req[r] > 0):
                bad.append(f"cycle {n}: {name} got {quota}, asked {req}")
            if ci in seen:
                bad.append(f"cycle {n}: two admissions to {cq}")
            seen.add(ci)
            pri = classes[k]["priority"]
            for other, ok in waiting[ci].items():
                if classes[ok]["priority"] > pri and all(
                        classes[ok]["request"][r] <= req[r]
                        for r in resources):
                    bad.append(f"cycle {n}: {name} admitted past {other}")
                    break
            running[name] = (ci, on)
            hold(name, ci, on, +1)
        for ci in seen:
            co = cohort[ci]
            for fr in frs:
                nominal, limit = cap_cq[ci][fr]
                if limit is not None and used[ci][fr] > nominal + limit:
                    bad.append(f"cycle {n}: {cqs[ci]['name']} over its "
                               f"borrowing limit on {fr}")
                if used_co[co][fr] > cap_co[co][fr]:
                    bad.append(f"cycle {n}: {co} over its quota on {fr}")
    return bad
