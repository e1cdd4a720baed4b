"""The comparison that decides `correct`.

The plain reference (plain.py: Kueue's admission cycle one workload at a
time, nothing of the program in it) is built over the same world from
the same seed and handed the very events the timed loop sent — every
finish and every arrival of every cycle, warm-up included, as the loop
wrote them down. It decides every cycle again; then every verdict of
every cycle is held against what the timed cycle itself decided: who was
admitted, to which ClusterQueue, on which flavor, with how much quota,
in which order within a cohort, and who preempted whom; and the two end
states — who holds quota where, who waits — are compared. One difference
is a wrong answer.
"""

from __future__ import annotations

import time


def replay(reference, log: list) -> list:
    """Send ``log``'s events to ``reference`` and return its verdicts,
    one per cycle, in the form of sut.Program.cycle()."""
    out = []
    for finishes, arrivals, now in log:
        for name in finishes:
            reference.finish(name)
        for name, ci, k, created in arrivals:
            reference.submit(name, ci, k, created)
        out.append(reference.cycle(now))
    return out


def by_cohort(admitted: list, cohort_of: dict) -> dict:
    """A cycle's admissions in commit order, cohort by cohort: cohorts
    share no quota, so only the order within one is a decision."""
    out: dict = {}
    for a in admitted:
        out.setdefault(cohort_of[a[1]], []).append(a)
    return out


def differing(got: list, want: list, cohort_of: dict) -> list:
    """Indices of the cycles whose verdicts differ."""
    return [i for i, (g, w) in enumerate(zip(got, want))
            if by_cohort(g["admitted"], cohort_of)
            != by_cohort(w["admitted"], cohort_of)
            or g["preempting"] != w["preempting"]]


def compare(world: dict, events: list, verdicts: list, make_reference,
            end_state: dict) -> dict:
    """Every cycle of the run against the reference. ``end_state`` is
    the program's sut.Program.state() after the run's last cycle. The
    reference's own verdicts go back under ``verdicts``: a world file's
    added minimums are counted over them (run.load_kind)."""
    t0 = time.perf_counter()
    ref = make_reference(world)
    want = replay(ref, events)
    cohort_of = {cq["name"]: cq["cohort"] for cq in world["cluster_queues"]}
    bad = differing(verdicts, want, cohort_of)
    return {"cycles_compared": len(want), "cycles_differing": len(bad),
            "first_differing_cycle": bad[0] if bad else None,
            "admissions_compared": sum(len(v["admitted"]) for v in want),
            "evictions_compared": sum(
                len(vs) for v in want for _h, vs in v["preempting"]),
            "end_state_differs": int(ref.state() != end_state),
            "verdicts": want,
            "reference_s": time.perf_counter() - t0}
