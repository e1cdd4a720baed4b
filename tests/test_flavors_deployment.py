"""The several-flavors kind of deployment (benchmark/worlds/
fungible-3f2r-1000cq.json) on the served path, at small sizes on the CPU:
three ResourceFlavors and two resources behind every ClusterQueue under
`flavorFungibility`, so every head's flavor is chosen by the
sim-augmented nomination (flavor_grid + the sim program + the
fungibility fold) and a Preempt-mode head's victims by the cycle
program's fused preemptor.

Every cycle's verdicts and the end state are held against the plain
reference (benchmark/plain_flavors.py, which imports nothing of the
program), with the program's own sequential core as a second witness on
the same events. The worlds are ISSUE 30's probe: 32 ClusterQueues in 1
or 4 cohorts, 20 waiting a queue, 2 finishes and arrivals a cycle.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import plain_flavors  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut_flavors  # noqa: E402
import trafficgen  # noqa: E402
import worldgen_flavors  # noqa: E402

from kueue_tpu.obs import span as span_mod  # noqa: E402
from kueue_tpu.oracle import engine_bridge  # noqa: E402
from kueue_tpu.scheduler import flavorassigner as fa  # noqa: E402

CONFIG = "fungible-3f2r-1000cq"
CYCLES = 14


def probe_world(cohorts: int, scenario: int, when_can_preempt: str,
                queues: int = 32) -> dict:
    cfg = run.read_config(CONFIG, tiny=True)
    cfg.update(cluster_queues=queues, cohorts=cohorts, scenario=scenario,
               pending={"small": 14 * queues, "medium": 4 * queues,
                        "large": 2 * queues})
    cfg["flavor_fungibility"] = dict(cfg["flavor_fungibility"],
                                     when_can_preempt=when_can_preempt)
    return worldgen_flavors.build_world(cfg, seed=5)


def drive(program, world: dict, cycles: int = CYCLES,
          per_cycle: int = 2, again: tuple = ()) -> tuple:
    """The loop of benchmark/run.py without its clocks: (events,
    verdicts, phases of each cycle). After a cycle in ``again`` the
    client says nothing and the program cycles once more: its phases
    are listed, its verdicts applied."""
    mix = dict(trafficgen.read_mix("trickle-turnover", tiny=True),
               turnover_share=per_cycle / len(world["cluster_queues"]))
    gen = trafficgen.Generator(mix, world)
    sets = trafficgen.RunningSets(
        [cq["name"] for cq in world["cluster_queues"]], world["running"])
    events, verdicts, phases = [], [], []
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
        phases.append(program.phases())
        if k in again:
            sets.apply(program.cycle(now))
            phases.append(program.phases())
    return events, verdicts, phases


def cohort_of(world: dict) -> dict:
    return {cq["name"]: cq["cohort"] for cq in world["cluster_queues"]}


@pytest.mark.parametrize("scenario", [30, 31, 33])
@pytest.mark.parametrize("when_can_preempt", ["TRY_NEXT_FLAVOR", "PREEMPT"])
@pytest.mark.parametrize("cohorts", [1, 4])
def test_the_served_path_decides_what_the_plain_reference_decides(
        cohorts, when_can_preempt, scenario):
    world = probe_world(cohorts, scenario, when_can_preempt)
    device = sut_flavors.Program(world, "local")
    events, got, _phases = drive(device, world)
    counters = device.counters()
    assert counters["device_cycles"] == CYCLES
    assert not counters["fallback_reasons"]
    assert not counters["host_root_reasons"]
    assert counters["hybrid_cycles"] == 0
    ref = plain_flavors.Plain(world)
    want = reference.replay(ref, events)
    assert reference.differing(got, want, cohort_of(world)) == []
    assert device.state() == ref.state()
    # The program's sequential core, a second witness, on the same
    # events.
    core = sut_flavors.Program(world, "off")
    assert reference.differing(reference.replay(core, events), want,
                               cohort_of(world)) == []
    assert core.state() == ref.state()
    if cohorts == 4:
        # The worlds decide something: flavors past the first, victims.
        later, _pre = plain_flavors.later_flavor_counts(world, want)
        assert later >= 1
        assert sum(len(vs) for v in want for _h, vs in v["preempting"])


def test_a_fit_resource_that_borrows_keeps_the_entry_behind():
    """The fault of ISSUE 30's item 3, held: a medium head whose cpu
    fits by borrowing (borrow 1) and whose memory needs eight victims
    (borrow 0 once they are gone) is an entry of borrow 1
    (Assignment.append takes the worst of its resources'), so it
    commits after the heads that do not borrow. The device path took
    the borrow of the final target selection, 0, and let it through
    ahead of them: scenario 30, four cohorts, cycle 5 on."""
    world = probe_world(4, 30, "TRY_NEXT_FLAVOR")
    device = sut_flavors.Program(world, "local")
    events, got, _phases = drive(device, world, cycles=10)
    want = reference.replay(plain_flavors.Plain(world), events)
    assert [v["preempting"] for v in got] == [
        v["preempting"] for v in want]
    assert len(want[5]["preempting"]) == 2


def test_the_sim_program_has_one_shape_whatever_the_rows():
    """48 ClusterQueues in 4 cohorts (the world file's `tiny`): the
    rows a cycle simulates vary more than 8-fold, from under one block
    (a row a queue, to the next power of two) to several, which loop
    it — where the first cycle's admissions are followed by no finish,
    every queue's next head meets full flavors at once; the sim program
    is launched with one shape, and so is the cycle program."""
    cfg = run.read_config(CONFIG, tiny=True)
    world = worldgen_flavors.build_world(cfg, seed=9)
    device = sut_flavors.Program(world, "local")
    _events, _got, phases = drive(device, world, cycles=24, again=(0,))
    rows = [p["n_sim_rows"] for p in phases if p.get("n_sim_rows")]
    assert max(rows) >= 8 * min(rows), rows
    assert len(device.sim_shapes) == 1
    assert len(device.signatures) == 1
    block = engine_bridge.OracleBridge._sim_block(
        device.eng.oracle._world_tensors())
    assert block == 64 and min(rows) <= block < max(rows)
    assert [p["n_sim_launches"] for p in phases if p.get("n_sim_rows")] \
        == [-(-n // block) for n in rows]
    (shape, _statics), = device.sim_shapes
    assert dict(shape)["slot_cq"] == (block,)


def test_the_nominations_spans_and_counts():
    """One tree a schedule_once(): `sim_nomination` is a container
    beside `host_encode` whose five leaves are the nomination's own;
    leaves + unattributed still sum to schedule_once; the counts are
    the span's attrs."""
    world = probe_world(4, 30, "TRY_NEXT_FLAVOR")
    device = sut_flavors.Program(world, "local")
    _events, _got, phases = drive(device, world, cycles=4)
    root = device.eng.spans.last()
    cycle = next(c for c in root.children if c.name == "cycle")
    names = [c.name for c in cycle.children]
    at = names.index("sim_nomination")
    assert names[at - 1] == names[at + 1] == "host_encode"
    box = cycle.children[at]
    assert [c.name for c in box.children] == [
        "flavor_grid", "sim_rows", "sim_launch", "fungibility_fold",
        "sim_targets"]
    launch, p = box.children[2].attrs, phases[-1]
    assert set(launch) == {"rows", "rows_padded", "launches",
                           "rows_classified", "bytes", "upload_s",
                           "device_wait_s", "readback_s", "launched_s"}
    assert launch["rows_padded"] == launch["launches"] * 32
    assert 0 < (launch["upload_s"] + launch["device_wait_s"]
                + launch["readback_s"]) <= p["sim_launch"]
    # The launches' windows: from the sim program's dispatch to its
    # answers being ready, inside the span.
    assert launch["device_wait_s"] <= launch["launched_s"] \
        <= p["sim_launch"]
    assert {"n_sim_heads", "n_sim_rows", "n_sim_launches",
            "n_sim_overflow", "n_sim_rows_classified"} <= span_mod.COUNT_KEYS
    assert p["n_sim_heads"] == box.attrs["heads"] > 0
    assert p["n_sim_rows"] == launch["rows"]
    assert p["n_sim_rows_classified"] == launch["rows_classified"]
    assert p["n_sim_launches"] == launch["launches"]
    assert p["n_sim_overflow"] == 0
    leaves = span_mod.leaf_phases(p)
    assert "sim_nomination" not in leaves
    assert sum(leaves.values()) == pytest.approx(p["schedule_once"])
    inside = sum(p[c.name] for c in box.children)
    assert inside <= p["sim_nomination"] <= inside + 0.005


def test_a_world_of_one_flavor_launches_nothing_new():
    """The flat one-flavor kind: no `sim_nomination` span, no count, no
    sim program — the cell that was there does not move."""
    import sut
    import worldgen

    world = worldgen.build_world(
        run.read_config("baseline-1x1000-noreclaim", tiny=True), seed=3)
    device = sut.Program(world, "local")
    calls = []
    executor = device.eng.oracle.executor
    executor.sim_targets = lambda *a, **k: calls.append(a)
    mix = trafficgen.read_mix("trickle-turnover", tiny=True)
    gen = trafficgen.Generator(mix, world)
    sets = trafficgen.RunningSets(device.cq_names, world["running"])
    for k in range(6):
        finishes, arrivals, now = gen.events(k, sets)
        for name in finishes:
            sets.remove(name)
            device.finish(name)
        for arrival in arrivals:
            device.submit(*arrival)
        sets.apply(device.cycle(now))
        assert not any(key.startswith(("sim_", "n_sim_", "flavor_grid",
                                       "fungibility"))
                       for key in device.phases())
    assert calls == []


def scalar_fold(pm, br, in_group, group_flavors, fung) -> tuple:
    """findFlavorForPodSets one slot at a time, with the sequential
    core's own is_preferred / should_try_next_flavor."""
    G, F, S = pm.shape
    choice = np.full(S, -1, np.int32)
    mode, borrow = int(fa.PMode.FIT), 0
    for g in range(G):
        res = [s for s in range(S) if in_group[g, s]]
        if not res:
            continue
        best, best_mode = None, fa.WORST
        for f in range(F):
            if group_flavors[g, f] < 0:
                continue
            rep = fa.BEST
            for s in res:
                m = fa.GranularMode(fa.PMode(int(pm[g, f, s])),
                                    int(br[g, f, s]))
                if fa.is_preferred(rep, m, fung):
                    rep = m
            if not fa.should_try_next_flavor(rep, fung):
                best, best_mode = f, rep
                break
            if fa.is_preferred(rep, best_mode, fung):
                best, best_mode = f, rep
        if best is None:
            return choice * 0 - 1, int(fa.PMode.NO_FIT), 0
        for s in res:
            choice[s] = group_flavors[g, best]
            mode = min(mode, int(pm[g, best, s]))
            borrow = max(borrow, int(br[g, best, s]))
    return choice, mode, borrow


@pytest.mark.parametrize("seed", range(8))
def test_the_array_fold_is_the_flavor_walk(seed):
    """_fold_fungibility against the walk one slot at a time, on drawn
    lattices, every policy and preference."""
    from kueue_tpu.api.types import (
        FlavorFungibility,
        FungibilityPolicy,
        FungibilityPreference,
    )

    rng = np.random.default_rng(seed)
    C, G, F, S = 64, 2, 4, 3
    pm = rng.choice([0, 1, 2, 3, 4], size=(C, G, F, S),
                    p=[0.15, 0.2, 0.25, 0.1, 0.3])
    br = rng.integers(0, 3, size=(C, G, F, S))
    group_of_res = rng.integers(0, G, size=(C, S))
    requested = rng.random((C, S)) < 0.8
    in_group = (group_of_res[:, None, :] == np.arange(G)[None, :, None]) \
        & requested[:, None, :]
    group_flavors = np.where(rng.random((C, G, F)) < 0.85,
                             rng.integers(0, 6, size=(C, G, F)), -1)
    b_try, p_try, pref = (rng.random(C) < 0.5 for _ in range(3))
    choice, mode, borrow = engine_bridge._fold_fungibility(
        pm, br, in_group, group_flavors, b_try, p_try, pref)
    for c in range(C):
        fung = FlavorFungibility(
            when_can_borrow=(FungibilityPolicy.TRY_NEXT_FLAVOR if b_try[c]
                             else FungibilityPolicy.BORROW),
            when_can_preempt=(FungibilityPolicy.TRY_NEXT_FLAVOR
                              if p_try[c] else FungibilityPolicy.PREEMPT),
            preference=(FungibilityPreference.PREEMPTION_OVER_BORROWING
                        if pref[c] else None))
        want = scalar_fold(pm[c], br[c], in_group[c], group_flavors[c],
                           fung)
        if want[1] == int(fa.PMode.NO_FIT):
            assert mode[c] == int(fa.PMode.NO_FIT), c
            continue
        assert (list(choice[c]), int(mode[c]), int(borrow[c])) == (
            list(want[0]), want[1], want[2]), c


def test_the_sim_program_crosses_the_serving_boundary():
    """The sidecar layout: the same world with the executor over a
    socket (oracle/service.py, op `sim_targets`) decides what the plain
    reference decides."""
    import re
    import subprocess

    from kueue_tpu.oracle.service import RemoteExecutor

    proc = subprocess.Popen(
        [sys.executable, "-m", "kueue_tpu.oracle.service", "--port", "0",
         "--platform", "cpu"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=ROOT)
    try:
        banner = proc.stdout.readline()
        m = re.search(r"listening on ([\d.]+):(\d+)", banner)
        assert m, banner
        world = probe_world(4, 30, "TRY_NEXT_FLAVOR")
        device = sut_flavors.Program(world, "local")
        device.eng.oracle.executor = RemoteExecutor(
            m.group(1), int(m.group(2)), spans=device.eng.spans)
        events, got, phases = drive(device, world, cycles=8)
        assert device.counters()["device_cycles"] == 8
        assert not device.counters()["fallback_reasons"]
        assert any(p.get("n_sim_rows") for p in phases)
        want = reference.replay(plain_flavors.Plain(world), events)
        assert reference.differing(got, want, cohort_of(world)) == []
    finally:
        proc.kill()
        proc.wait()
