"""The guarantees the selective-workloads world's file states, checked
on the timed run's own verdicts: invariants_flavors.check, and the one
this kind adds —

  * no workload ever holds quota on a flavor its pod set does not
    match: not one of the running set the world starts with, and not
    one a cycle admits. A flavor with a NoSchedule or NoExecute taint is
    for pod sets that tolerate it; a pod set that selects
    `instance-type: <name>` is for the flavor labelled so.

Plain bookkeeping over the world, the events and the verdicts: who is of
which class is the world's records and the client's arrivals. Nothing of
the program and not a line of the reference or of the world builder.
"""

from __future__ import annotations

import invariants_flavors


def may_hold(flavor: dict, profile: dict) -> bool:
    for taint in flavor["node_taints"]:
        if taint["effect"] == "PreferNoSchedule":
            continue
        covered = False
        for t in profile["tolerations"] + flavor.get("tolerations", []):
            if t.get("effect") not in (None, "", taint["effect"]):
                continue
            if t.get("key"):
                covered |= t["key"] == taint["key"] and (
                    t.get("operator") == "Exists"
                    or t.get("value", "") == taint.get("value", ""))
            else:
                covered |= t.get("operator") == "Exists"
        if not covered:
            return False
    labels = flavor["node_labels"]
    return all(labels[key] == value
               for key, value in profile["node_selector"].items()
               if key in labels)


def check(world: dict, events: list, verdicts: list) -> list:
    bad = invariants_flavors.check(world, events, verdicts)
    profiles = {p["name"]: p for p in world["profiles"]}
    by_profile = {name: {fl["name"] for fl in world["flavor_specs"]
                         if may_hold(fl, p)}
                  for name, p in profiles.items()}
    for name, p in profiles.items():
        # The builder dealt the running set from the file's own lists.
        if set(p.get("eligible", ())) != by_profile[name]:
            bad.append(f"the world: the file lists "
                       f"{sorted(p.get('eligible', ()))} as the flavors "
                       f"of the profile {name}; its pod set matches "
                       f"{sorted(by_profile[name])}")
    allowed = [by_profile[c["profile"]] for c in world["classes"]]
    klass = {name: k for name, _ci, k, _at
             in world["running"] + world["pending"]}
    for (name, _ci, k, _at), f in zip(world["running"],
                                      world["running_on"]):
        if world["flavors"][f] not in allowed[k]:
            bad.append(f"the world: {name} runs on {world['flavors'][f]}, "
                       "which its pod set does not match")
    for n, ((_finishes, arrivals, _now), v) in enumerate(
            zip(events, verdicts)):
        for name, _ci, k, _created in arrivals:
            klass[name] = k
        for name, _cq, flavor, _quota in v["admitted"]:
            if name not in klass:
                continue    # invariants_flavors.check has said so
            off = {f for _r, f in flavor} - allowed[klass[name]]
            if off:
                bad.append(f"cycle {n}: {name} "
                           f"({world['classes'][klass[name]]['profile']}) "
                           f"admitted on {sorted(off)}, which its pod "
                           "set does not match")
    return bad
