"""The sim-augmented nomination's share of the engine's cycle: the
window's `sim_nomination` seconds over its `schedule_once` seconds, both
the program's own spans."""


def reduce(trace, spans, counters):
    had = [c["phases"] for c in spans["cycles"]
           if "sim_nomination" in c["phases"]]
    total = sum(c["phases"].get("schedule_once", 0.0)
                for c in spans["cycles"])
    if not had or not total:
        return None
    return 100.0 * sum(p["sim_nomination"] for p in had) / total
