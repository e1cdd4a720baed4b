"""`reclaimWithinCohort: Any`, the source's own stanza, on the served
path at small sizes on the CPU (ISSUE 34): the device path — the fused
preemptor whose scan walks on past its first v_cap ordered candidates,
and the commit that lets a cohort's second preemptor through — against
the program's sequential core on the same events, cycle by cycle, and
both against the plain reference (benchmark/plain.py, which imports
nothing of the program).

The worlds are ISSUE 34's probes: one cohort of 64 ClusterQueues, and
that cohort with the running set dealt onto half of the queues, the
other half returning to their quota (benchmark/worldgen_reclaim.py);
the 8 cohorts of 6 are in test_reclaim_cohorts_of_six.py, which runs
beside this file.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import plain  # noqa: E402
import plain_reclaim  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut  # noqa: E402
import trafficgen  # noqa: E402
import worldgen  # noqa: E402
import worldgen_reclaim  # noqa: E402

ANY = {"within_cluster_queue": "LOWER_PRIORITY",
       "reclaim_within_cohort": "ANY"}
def config(**keys) -> dict:
    cfg = run.read_config("baseline-1x1000-noreclaim", tiny=True)
    cfg.update(preemption=ANY, **keys)
    return cfg


def drive(program, world: dict, cycles: int, turnover_share: float):
    """benchmark/run.py's loop without its clocks."""
    mix = dict(trafficgen.read_mix("trickle-turnover", tiny=True),
               turnover_share=turnover_share)
    gen = trafficgen.Generator(mix, world)
    sets = trafficgen.RunningSets(
        [cq["name"] for cq in world["cluster_queues"]], world["running"])
    events, verdicts = [], []
    for k in range(cycles):
        finishes, arrivals, now = gen.events(k, sets)
        for name in finishes:
            sets.remove(name)
            program.finish(name)
        for arrival in arrivals:
            program.submit(*arrival)
        v = program.cycle(now)
        sets.apply(v)
        events.append((finishes, arrivals, now))
        verdicts.append(v)
    return events, verdicts


def held_to_core_and_reference(world, cycles, turnover_share,
                               overflowing_roots=0):
    """Every cycle on the device, equal to the sequential core and to
    plain.py; returns (the counters, the reference's verdicts)."""
    device = sut.Program(world, "local")
    events, got = drive(device, world, cycles, turnover_share)
    counters = device.counters()
    cohort_of = {cq["name"]: cq["cohort"]
                 for cq in world["cluster_queues"]}
    core = sut.Program(world, "off")
    by_core = reference.replay(core, events)
    assert reference.differing(got, by_core, cohort_of) == []
    assert device.state() == core.state()
    ref = plain.Plain(world)
    want = reference.replay(ref, events)
    assert reference.differing(by_core, want, cohort_of) == []
    assert core.state() == ref.state()
    assert counters["device_cycles"] == cycles
    assert not counters["fallback_reasons"]
    assert counters["host_root_reasons"] == (
        {"preemption-overflow": overflowing_roots} if overflowing_roots
        else {})
    return counters, want


@pytest.mark.parametrize("scenario", [26, 28])
def test_one_cohort_of_sixty_four_under_any(scenario):
    world = worldgen.build_world(config(scenario=scenario), seed=1)
    held_to_core_and_reference(world, 60, 0.06)


@pytest.mark.parametrize("scenario,share", [(26, 0.5), (28, 0.5),
                                            (26, 0.2)])
def test_half_of_the_cohort_returns_to_its_quota(scenario, share):
    cfg = config(scenario=scenario, returning_share=share)
    world = worldgen_reclaim.build_world(cfg, seed=1)
    returning = set(world["returning"])
    assert len(returning) == round(share * cfg["cluster_queues"])
    assert not [r for r in world["running"] if r[1] in returning]
    _, want = held_to_core_and_reference(world, 60, 0.06)
    assert plain_reclaim.count_evictions_from_another_queue(
        world, want) >= 8
