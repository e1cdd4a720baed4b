"""`reclaimWithinCohort: Any` in cohorts of six (ISSUE 34's 8 x 6
probes, three scenarios, 90 cycles): the device path against the
program's sequential core and the plain reference, every cycle. Before
ISSUE 34 the device path differed in 15 cycles at scenario 26 (first at
70: the second preemptor of a cohort dropped) and in 27 at 28. The
helpers are test_reclaim_within_cohort.py's; the worlds are in a file
of their own so that the two run side by side.
"""

from __future__ import annotations

import pytest

from tests.test_reclaim_within_cohort import (
    config,
    held_to_core_and_reference,
    plain_reclaim,
    worldgen,
)

SIX_BY_EIGHT = dict(
    cluster_queues=48, cohorts=8,
    running={"small": 288, "medium": 58, "large": 19},
    pending={"small": 1680, "medium": 480, "large": 240})


# In its very first cycle one head of scenario 26 needs 33 victims
# (the same call with v_cap 64 finds them): more than the packed columns
# hold, the one meaning `overflow` keeps, and that cohort's cycle is the
# host's. No other cycle of the three worlds leaves the device.
@pytest.mark.parametrize("scenario,overflowing_roots",
                         [(26, 1), (27, 0), (28, 0)])
def test_cohorts_of_six_under_any(scenario, overflowing_roots):
    world = worldgen.build_world(
        config(scenario=scenario, **SIX_BY_EIGHT), seed=1)
    _, want = held_to_core_and_reference(world, 90, 0.25,
                                         overflowing_roots)
    assert plain_reclaim.count_evictions_from_another_queue(
        world, want) >= 8
    # Several preemptors of one cohort in one cycle: the second of them
    # is what the commit dropped before ISSUE 34's repair.
    cohort = {cq["name"]: cq["cohort"] for cq in world["cluster_queues"]}
    home = {name: f"cq-{ci}" for name, ci, _k, _at in world["pending"]}
    for v in want:
        home.update((name, cq) for name, cq, _f, _u in v["admitted"])
    assert any(
        len(heads) > len({cohort[home[h]] for h in heads})
        for heads in ([h for h, _vs in v["preempting"] if h in home]
                      for v in want))
