"""Labelled and tainted ResourceFlavors on the device path: each head's
flavor mask through the cycle program's assign pass and the simulated
nomination alike.

ResourceFlavors with nodeLabels and nodeTaints, pod sets with node
selectors, required node affinity and tolerations
(kueue.sigs.k8s.io/docs/concepts/resource_flavor): a flavor a head's pod
set does not match is skipped in its walk (flavorassigner.go
checkFlavorForPodSets). The mask is evaluated on the host at row encode
(tensor/schema.flavor_eligibility_mask) and decides on the device: in
the cycle program's assign pass and — on preempting ClusterQueues with
several flavors — in the sim-augmented nomination's flavor grid, rows
and fold. The sequential engine is the witness: every cycle's verdicts
and the end state. Three parts: the selective-workloads kind of
deployment (benchmark/worlds/selective-3f2r-1000cq.json) at its `tiny`
size on the served path, against the sequential core and against the
kind's plain reference; one unit world for each rule of the match; and
the mask, the fold and a churned differential.
"""

import os
import sys

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.api.types import (  # noqa: E402
    ClusterQueue,
    ClusterQueuePreemption,
    Cohort,
    FlavorFungibility,
    FlavorQuotas,
    FungibilityPolicy,
    FungibilityPreference,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Taint,
    Toleration,
    Workload,
)
from kueue_tpu.controllers.engine import Engine  # noqa: E402
from kueue_tpu.oracle import engine_bridge  # noqa: E402
from kueue_tpu.scheduler import flavorassigner as fa  # noqa: E402
from kueue_tpu.scheduler.cycle import (  # noqa: E402
    EntryStatus,
    RequeueReason,
)
from kueue_tpu.tensor import schema  # noqa: E402
from kueue_tpu.workload_info import WorkloadInfo  # noqa: E402
from test_multiflavor_preempt_device import state_of  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import invariants_selective  # noqa: E402
import plain_selective  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut_selective  # noqa: E402
import worldgen_selective  # noqa: E402
from test_flavors_deployment import cohort_of, drive  # noqa: E402

KEY = "instance-type"
SPOT = Taint("spot", "true", "NoSchedule")
DRAINING = Taint("maintenance", "soon", "NoExecute")
TOLERATES_SPOT = Toleration("spot", "Equal", "true", "NoSchedule")

# The pod sets' node constraints: (node selector, required affinity,
# tolerations).
PROFILES = [
    ({}, (), ()),
    ({}, (), (TOLERATES_SPOT,)),
    ({KEY: "spot"}, (), (TOLERATES_SPOT,)),
    ({KEY: "reserved"}, (), ()),
    ({}, (((KEY, "In", ("on-demand", "reserved")),),), ()),
    ({}, (((KEY, "NotIn", ("on-demand",)),),), (Toleration(
        operator="Exists"),)),
    ({KEY: "spot"}, (), ()),  # selects the flavor its taint keeps it off
    ({"disk": "ssd"}, (((KEY, "In", ("spot",)),),
                       (("zone", "In", ("a",)), (KEY, "Exists", ()))),
     (Toleration("maintenance", "Exists"), TOLERATES_SPOT)),
]


def flavors(rng: random.Random) -> list:
    """Every flavor labelled by its name; `spot` tainted NoSchedule; in
    half the worlds `reserved` is being drained (NoExecute) and in a
    third `on-demand` carries a taint nobody has to tolerate
    (PreferNoSchedule) or one its own tolerations cover."""
    on_demand = ResourceFlavor("on-demand", {KEY: "on-demand"})
    extra = rng.choice(["none", "prefer", "own"])
    if extra == "prefer":
        on_demand.node_taints = (Taint("cost", "high", "PreferNoSchedule"),)
    elif extra == "own":
        on_demand.node_taints = (Taint("team", "batch", "NoSchedule"),)
        on_demand.tolerations = (Toleration("team", "Exists"),)
    reserved = ResourceFlavor("reserved", {KEY: "reserved", "zone": "a"})
    if rng.random() < 0.5:
        reserved.node_taints = (DRAINING,)
    return [on_demand, ResourceFlavor("spot", {KEY: "spot"}, (SPOT,)),
            reserved]


def build_engine(oracle: bool, rng: random.Random, resources,
                 when_can_preempt, n_cqs=3):
    eng = Engine()
    for rf in flavors(rng):
        eng.create_resource_flavor(rf)
    eng.create_cohort(Cohort("co"))
    for i in range(n_cqs):
        group = tuple(
            FlavorQuotas(f, dict.fromkeys(resources, ResourceQuota(
                rng.choice([1000, 2000, 3000]))))
            for f in rng.sample(["on-demand", "spot", "reserved"],
                                rng.choice([2, 3, 3])))
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort="co",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                reclaim_within_cohort=rng.choice(
                    [PreemptionPolicy.NEVER, PreemptionPolicy.ANY,
                     PreemptionPolicy.LOWER_PRIORITY])),
            flavor_fungibility=FlavorFungibility(
                when_can_preempt=when_can_preempt),
            resource_groups=(ResourceGroup(resources, group),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    if oracle:
        eng.attach_oracle()
    return eng


def verdicts_of(r) -> tuple:
    """A cycle's decisions: who was admitted on which flavors, who
    preempts whom."""
    if r is None:
        return None
    admitted, preempting = [], []
    for e in list(r.entries) + list(r.inadmissible):
        if e.status == EntryStatus.ASSUMED:
            psa = e.obj.status.admission.pod_set_assignments[0]
            admitted.append((e.obj.name, tuple(sorted(
                psa.flavors.items()))))
        elif e.status == EntryStatus.PREEMPTING:
            preempting.append((e.obj.name, tuple(sorted(
                t.workload.obj.name for t in e.preemption_targets))))
    return sorted(admitted), sorted(preempting)


def churn(eng, rng: random.Random, resources, n=44) -> tuple:
    """Submit, cycle and finish by the draws of ``rng``: every cycle's
    verdicts, and the cycles' heads the masks narrowed in the sim
    nomination, all told."""
    log, narrowed = [], [0]

    def cycle():
        r = eng.schedule_once()
        log.append(verdicts_of(r))
        narrowed[0] += eng.last_cycle_phases.get(
            "n_mask_narrowed_heads", 0)
        return r

    for i in range(n):
        eng.clock += 0.5
        selector, affinity, tolerations = rng.choice(PROFILES)
        eng.submit(Workload(
            name=f"w{i}", queue_name=f"lq{rng.randrange(3)}",
            priority=rng.choice([0, 2, 5, 9]),
            pod_sets=(PodSet("main", 1, {
                r: rng.choice([500, 900, 1500, 2500]) for r in resources},
                node_selector=dict(selector), node_affinity=affinity,
                tolerations=tolerations),)))
        if rng.random() < 0.4:
            cycle()
        if rng.random() < 0.2:
            admitted = [k for k, x in eng.workloads.items()
                        if x.is_admitted]
            if admitted:
                eng.finish(rng.choice(admitted))
    for _ in range(120):
        r = cycle()
        if r is None or (not r.assumed and not any(
                e.preemption_targets for e in r.entries)):
            break
        eng.tick(0.0)  # evictions land; victims requeue
    return log, narrowed[0]


@pytest.mark.parametrize("when_can_preempt", [
    FungibilityPolicy.PREEMPT, FungibilityPolicy.TRY_NEXT_FLAVOR],
    ids=["preempt", "try-next"])
@pytest.mark.parametrize("resources", [("cpu",), ("cpu", "memory")],
                         ids=len)
@pytest.mark.parametrize("seed", range(8))
def test_masked_heads_decide_on_the_device_as_the_sequential_engine(
        seed, resources, when_can_preempt):
    world = 3000 + seed + 100 * len(resources)
    seq = build_engine(False, random.Random(world), resources,
                       when_can_preempt)
    bat = build_engine(True, random.Random(world), resources,
                       when_can_preempt)
    want, _ = churn(seq, random.Random(seed), resources)
    got, narrowed = churn(bat, random.Random(seed), resources)
    assert got == want
    assert narrowed > 0
    assert state_of(bat) == state_of(seq)
    assert any(v and (v[0] or v[1]) for v in want)
    # Every ClusterQueue names a tainted, labelled flavor or shares its
    # cohort with one that does: nothing of that sends a root, or the
    # cycle, to the host.
    assert bat.oracle.cycles_on_device > 0
    assert not bat.oracle.fallback_reasons.get("world")
    assert not {"flavor-unsafe", "sim-flavor-mask", "head-ineligible"} \
        & set(bat.oracle.host_root_reasons), bat.oracle.host_root_reasons


def test_the_masks_narrow_the_sim_nomination_and_are_counted():
    """A queue full of low-priority work on `on-demand` and `spot`: a
    high-priority head that does not tolerate spot's taint simulates
    on-demand's cell alone and preempts there; one that does is
    admitted... on neither: spot is full too, and its cell is simulated
    as well. The counts are the spans' attrs."""
    def build(tolerations):
        eng = Engine()
        eng.create_resource_flavor(ResourceFlavor(
            "on-demand", {KEY: "on-demand"}))
        eng.create_resource_flavor(ResourceFlavor(
            "spot", {KEY: "spot"}, (SPOT,)))
        eng.create_cluster_queue(ClusterQueue(
            name="cq",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY),
            flavor_fungibility=FlavorFungibility(
                when_can_preempt=FungibilityPolicy.TRY_NEXT_FLAVOR),
            resource_groups=(ResourceGroup(("cpu",), (
                FlavorQuotas("on-demand", {"cpu": ResourceQuota(1000)}),
                FlavorQuotas("spot", {"cpu": ResourceQuota(1000)}))),)))
        eng.create_local_queue(LocalQueue("lq", "default", "cq"))
        eng.attach_oracle()
        for name in ("low-a", "low-b"):
            eng.clock += 1
            eng.submit(Workload(
                name=name, queue_name="lq", priority=0,
                pod_sets=(PodSet("main", 1, {"cpu": 1000},
                                 tolerations=(TOLERATES_SPOT,)),)))
            eng.schedule_once()
        eng.clock += 1
        eng.submit(Workload(
            name="high", queue_name="lq", priority=10,
            pod_sets=(PodSet("main", 1, {"cpu": 1000},
                             tolerations=tolerations),)))
        return eng, eng.schedule_once()

    eng, r = build(())
    assert verdicts_of(r) == ([], [("high", ("low-a",))])
    p = eng.last_cycle_phases
    assert (p["n_sim_heads"], p["n_sim_rows"], p["n_mask_narrowed_heads"],
            p["n_masked_flavor_cells"]) == (1, 1, 1, 1)
    box = eng.spans.last().find(lambda s: s.name == "sim_nomination")
    assert box.attrs["mask_narrowed_heads"] == 1
    assert box.attrs["masked_flavor_cells"] == 1
    eng, r = build((TOLERATES_SPOT,))
    # Both flavors can be preempted on; the later one is no better.
    assert verdicts_of(r) == ([], [("high", ("low-a",))])
    p = eng.last_cycle_phases
    assert (p["n_sim_rows"], p["n_mask_narrowed_heads"],
            p["n_masked_flavor_cells"]) == (2, 0, 0)


def test_a_head_with_no_eligible_flavor_parks_on_the_device():
    """Selector `instance-type: spot` and no toleration: spot's taint
    keeps it off the one flavor its selector leaves. NoFit: inadmissible,
    on both paths, and the root stays on the device."""
    def build(oracle):
        eng = Engine()
        eng.create_resource_flavor(ResourceFlavor(
            "on-demand", {KEY: "on-demand"}))
        eng.create_resource_flavor(ResourceFlavor(
            "spot", {KEY: "spot"}, (SPOT,)))
        eng.create_cluster_queue(ClusterQueue(
            name="cq",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY),
            resource_groups=(ResourceGroup(("cpu",), (
                FlavorQuotas("on-demand", {"cpu": ResourceQuota(1000)}),
                FlavorQuotas("spot", {"cpu": ResourceQuota(1000)}))),)))
        eng.create_local_queue(LocalQueue("lq", "default", "cq"))
        if oracle:
            eng.attach_oracle()
        eng.clock += 1
        eng.submit(Workload(
            name="nowhere", queue_name="lq", priority=5,
            pod_sets=(PodSet("main", 1, {"cpu": 500},
                             node_selector={KEY: "spot"}),)))
        eng.clock += 1
        eng.submit(Workload(
            name="anywhere", queue_name="lq", priority=0,
            pod_sets=(PodSet("main", 1, {"cpu": 500}),)))
        return eng, [verdicts_of(eng.schedule_once()) for _ in range(2)]

    seq, want = build(False)
    bat, got = build(True)
    assert got == want == [([], []),
                           ([("anywhere", (("cpu", "on-demand"),))], [])]
    assert state_of(bat) == state_of(seq)
    assert bat.oracle.cycles_on_device == 2
    assert not bat.oracle.host_root_reasons
    # `anywhere` has no filter of its own, and spot's taint narrows it.
    assert bat.last_cycle_phases["n_mask_narrowed_heads"] == 1


# -- the mask itself -------------------------------------------------


class World:
    """What flavor_eligibility_mask reads of a WorldTensors."""

    def __init__(self, *objects):
        self.flavor_objects = list(objects)
        self.flavor_names = [rf.name for rf in objects]
        self.num_flavors = len(objects)

    flavor_spec_token = schema.WorldTensors.flavor_spec_token
    any_flavor_tainted = schema.WorldTensors.any_flavor_tainted


def mask_of(world, **pod_set) -> list:
    info = WorkloadInfo(Workload(name="w", queue_name="lq", pod_sets=(
        PodSet("main", 1, {"cpu": 1}, **pod_set),)), "cq")
    return list(schema.flavor_eligibility_mask(info, world))


PLAIN = ResourceFlavor("plain", {KEY: "plain"})
MASK_CASES = {
    "an untolerated NoSchedule taint": (
        [PLAIN, ResourceFlavor("spot", {KEY: "spot"}, (SPOT,))],
        {}, [True, False]),
    "an untolerated NoExecute taint": (
        [PLAIN, ResourceFlavor("drained", {}, (DRAINING,))],
        {}, [True, False]),
    "PreferNoSchedule keeps nobody off": (
        [PLAIN, ResourceFlavor("dear", {}, (
            Taint("cost", "high", "PreferNoSchedule"),))],
        {}, [True, True]),
    "the flavor's own tolerations cover its taint": (
        [PLAIN, ResourceFlavor("team", {}, (
            Taint("team", "batch", "NoSchedule"),),
            (Toleration("team", "Equal", "batch"),))],
        {}, [True, True]),
    "the pod set tolerates": (
        [PLAIN, ResourceFlavor("spot", {KEY: "spot"}, (SPOT,))],
        {"tolerations": (TOLERATES_SPOT,)}, [True, True]),
    "a toleration of another effect does not": (
        [PLAIN, ResourceFlavor("spot", {KEY: "spot"}, (SPOT,))],
        {"tolerations": (Toleration("spot", "Exists", "", "NoExecute"),)},
        [True, False]),
    "a selector on the flavors' key": (
        [PLAIN, ResourceFlavor("spot", {KEY: "spot"}, (SPOT,))],
        {"node_selector": {KEY: "spot"},
         "tolerations": (TOLERATES_SPOT,)}, [False, True]),
    "a selector on a key no flavor has": (
        [PLAIN, ResourceFlavor("other", {KEY: "other"})],
        {"node_selector": {"disk": "ssd"}}, [True, True]),
    "required affinity, terms ORed": (
        [PLAIN, ResourceFlavor("other", {KEY: "other"}),
         ResourceFlavor("third", {KEY: "third"})],
        {"node_affinity": (((KEY, "In", ("plain",)),),
                           ((KEY, "In", ("third",)),))},
        [True, False, True]),
}


@pytest.mark.parametrize("case", sorted(MASK_CASES))
def test_the_mask_is_the_sequential_cores_match(case):
    objects, pod_set, want = MASK_CASES[case]
    world = World(*objects)
    assert mask_of(world, **pod_set) == want
    ps = PodSet("main", 1, {"cpu": 1}, **pod_set)
    assert [fa.flavor_matches_podset(rf, ps) is None
            for rf in objects] == want


def test_a_world_with_no_taint_keeps_the_all_ones_shortcut(monkeypatch):
    """No pod-set filter, no tainted flavor: no flavor is matched one by
    one. With a tainted flavor the same pod set is."""
    calls = []
    inner = fa.flavor_matches_podset
    monkeypatch.setattr(fa, "flavor_matches_podset",
                        lambda rf, ps: calls.append(rf.name) or inner(rf, ps))
    labelled = World(PLAIN, ResourceFlavor("dear", {KEY: "dear"}, (
        Taint("cost", "high", "PreferNoSchedule"),)))
    assert not labelled.any_flavor_tainted()
    assert mask_of(labelled) == [True, True] and calls == []
    tainted = World(PLAIN, ResourceFlavor("spot", {KEY: "spot"}, (SPOT,)))
    assert tainted.any_flavor_tainted()
    assert mask_of(tainted) == [True, False] and calls == ["plain", "spot"]


def test_the_mask_is_memoised_against_the_flavors_spec():
    info = WorkloadInfo(Workload(name="w", queue_name="lq", pod_sets=(
        PodSet("main", 1, {"cpu": 1}),)), "cq")
    before = World(PLAIN, ResourceFlavor("spot", {KEY: "spot"}))
    after = World(PLAIN, ResourceFlavor("spot", {KEY: "spot"}, (SPOT,)))
    first = schema.flavor_eligibility_mask(info, before)
    assert schema.flavor_eligibility_mask(info, before) is first
    assert list(first) == [True, True]
    assert list(schema.flavor_eligibility_mask(info, after)) == [True, False]


# -- the fold, with masks --------------------------------------------


def scalar_walk(pm, br, in_group, group_flavors, eligible, fung) -> tuple:
    """findFlavorForPodSets one slot at a time with the sequential
    core's own is_preferred / should_try_next_flavor, passing over a
    flavor the pod set does not match."""
    G, F, S = pm.shape
    choice = np.full(S, -1, np.int32)
    mode, borrow = int(fa.PMode.FIT), 0
    for g in range(G):
        res = [s for s in range(S) if in_group[g, s]]
        if not res:
            continue
        best, best_mode = None, fa.WORST
        for f in range(F):
            if group_flavors[g, f] < 0 or not eligible[g, f]:
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
def test_the_array_fold_passes_over_what_the_walk_passes_over(seed):
    """_fold_fungibility with masks against the walk one slot at a
    time, on drawn lattices, every policy and preference; a slot whose
    mask leaves a requested group no flavor is NoFit."""
    rng = np.random.default_rng(100 + seed)
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
    eligible = rng.random((C, G, F)) < 0.6
    eligible[:4] = False
    b_try, p_try, pref = (rng.random(C) < 0.5 for _ in range(3))
    # As the bridge hands them over: the group's flavors less the ones
    # the slot's pod set does not match.
    choice, mode, borrow = engine_bridge._fold_fungibility(
        pm, br, in_group, np.where(eligible, group_flavors, -1), b_try,
        p_try, pref)
    narrowed = 0
    for c in range(C):
        fung = FlavorFungibility(
            when_can_borrow=(FungibilityPolicy.TRY_NEXT_FLAVOR if b_try[c]
                             else FungibilityPolicy.BORROW),
            when_can_preempt=(FungibilityPolicy.TRY_NEXT_FLAVOR
                              if p_try[c] else FungibilityPolicy.PREEMPT),
            preference=(FungibilityPreference.PREEMPTION_OVER_BORROWING
                        if pref[c] else None))
        want = scalar_walk(pm[c], br[c], in_group[c], group_flavors[c],
                           eligible[c], fung)
        open_walk = scalar_walk(pm[c], br[c], in_group[c],
                                group_flavors[c], eligible[c] | True, fung)
        narrowed += (list(want[0]), want[1]) != (list(open_walk[0]),
                                                 open_walk[1])
        if want[1] == int(fa.PMode.NO_FIT):
            assert mode[c] == int(fa.PMode.NO_FIT), c
            continue
        assert (list(choice[c]), int(mode[c]), int(borrow[c])) == (
            list(want[0]), want[1], want[2]), c
    assert narrowed >= 8  # the masks decide something
    assert (mode[:4][in_group[:4].any(axis=(1, 2))]
            == int(fa.PMode.NO_FIT)).all()


# -- the deployment at its `tiny` size, on the served path -------------

CONFIG = "selective-3f2r-1000cq"
CYCLES = 22


def tiny_world(scenario: int, cohorts: int) -> dict:
    cfg = run.read_config(CONFIG, tiny=True)
    cfg.update(scenario=scenario, cohorts=cohorts)
    return worldgen_selective.build_world(cfg, seed=2 ** 31 + scenario)


@pytest.mark.parametrize("scenario", [37, 38, 39, 40])
@pytest.mark.parametrize("cohorts", [2, 4])
def test_the_tiny_deployment_decides_as_the_core_and_the_plain_reference(
        cohorts, scenario):
    """The batched engine over 22 cycles of the traffic, against the
    plain reference and the program's sequential core on the same
    events: every verdict, the end state, the guarantees; no root and
    no cycle goes to the host; the masks narrow heads in every cycle."""
    world = tiny_world(scenario, cohorts)
    device = sut_selective.Program(world, "local")
    events, got, phases = drive(device, world, cycles=CYCLES)
    counters = device.counters()
    assert counters["device_cycles"] == CYCLES
    assert not counters["fallback_reasons"]
    assert counters["host_root_reasons"] == {}
    assert counters["hybrid_cycles"] == 0
    ref = plain_selective.Plain(world)
    want = reference.replay(ref, events)
    assert reference.differing(got, want, cohort_of(world)) == []
    assert device.state() == ref.state()
    assert invariants_selective.check(world, events, got) == []
    core = sut_selective.Program(world, "off")
    assert reference.differing(reference.replay(core, events), want,
                               cohort_of(world)) == []
    assert core.state() == ref.state()
    assert all(0 < p["n_mask_narrowed_heads"] <= p["n_sim_heads"]
               and p["n_masked_flavor_cells"] >= p["n_mask_narrowed_heads"]
               for p in phases)
    # The worlds decide something of narrowed heads, and the reference
    # with every mask all-true parts from them.
    admissions, _past, evictions = plain_selective.narrowed_counts(
        world, want)
    assert admissions >= 4 and evictions >= 20
    open_ref = plain_selective.every_flavor(world)
    assert reference.differing(reference.replay(open_ref, events), want,
                               cohort_of(world))
    assert open_ref.state() != ref.state()


# -- one unit world for each rule ---------------------------------------

NAMES = ("reserved", "on-demand", "spot")
ANY = Toleration(operator="Exists")


def labelled(extra: dict = None) -> list:
    """The three flavors, each labelled `instance-type: <its name>`,
    `spot` tainted NoSchedule; ``extra`` name -> (labels, taints,
    tolerations) laid over that."""
    out = []
    for name in NAMES:
        labels, taints, tolerations = (extra or {}).get(name, ({}, (), ()))
        out.append(ResourceFlavor(
            name, dict({KEY: name}, **labels),
            tuple(taints) + ((SPOT,) if name == "spot" else ()),
            tuple(tolerations)))
    return out


# rule -> (what is laid over the flavors, the head's pod set, the flavors
# its mask leaves it, in order)
RULES = {
    "an untolerated NoSchedule taint": (
        None, {}, ["reserved", "on-demand"]),
    "the taint tolerated": (
        None, {"tolerations": (TOLERATES_SPOT,)},
        ["reserved", "on-demand", "spot"]),
    "PreferNoSchedule is ignored": (
        {"reserved": ({}, (Taint("cost", "high", "PreferNoSchedule"),), ())},
        {}, ["reserved", "on-demand"]),
    "the flavor's own tolerations count": (
        {"spot": ({}, (), (Toleration("spot", "Exists"),))},
        {}, ["reserved", "on-demand", "spot"]),
    "a selector on a key no flavor of the group defines": (
        None, {"node_selector": {"disk": "ssd"}}, ["reserved", "on-demand"]),
    "a selector pins the first flavor": (
        None, {"node_selector": {KEY: "reserved"}}, ["reserved"]),
    "a selector pins a middle flavor": (
        None, {"node_selector": {KEY: "on-demand"}}, ["on-demand"]),
    "a selector pins the last flavor": (
        None, {"node_selector": {KEY: "spot"},
               "tolerations": (TOLERATES_SPOT,)}, ["spot"]),
    "required affinity In [reserved, on-demand]": (
        None, {"node_affinity": (((KEY, "In", ("reserved", "on-demand")),),),
               "tolerations": (TOLERATES_SPOT,)}, ["reserved", "on-demand"]),
    "required affinity NotIn [reserved]": (
        None, {"node_affinity": (((KEY, "NotIn", ("reserved",)),),)},
        ["on-demand"]),
    "every flavor excluded": (
        None, {"node_selector": {KEY: "spot"}}, []),
}
# The resource group's resources, and the ones the head asks for: one
# resource; two; two with a row whose memory is inactive.
SHAPES = {"R1": (("cpu",), ("cpu",)),
          "R2": (("cpu", "memory"), ("cpu", "memory")),
          "R2-one-inactive": (("cpu", "memory"), ("cpu",))}


def unit_world(oracle: bool, extra, pod_set: dict, resources, asked,
               full: tuple):
    """One preempting ClusterQueue over the three flavors, the flavors
    in ``full`` each held by one low-priority workload pinned there;
    then the head. Returns the engine and the head's cycle."""
    eng = Engine()
    for rf in labelled(extra):
        eng.create_resource_flavor(rf)
    eng.create_cluster_queue(ClusterQueue(
        name="cq",
        preemption=ClusterQueuePreemption(
            within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY),
        flavor_fungibility=FlavorFungibility(
            when_can_preempt=FungibilityPolicy.TRY_NEXT_FLAVOR),
        resource_groups=(ResourceGroup(resources, tuple(
            FlavorQuotas(name, dict.fromkeys(resources,
                                             ResourceQuota(1000)))
            for name in NAMES)),)))
    eng.create_local_queue(LocalQueue("lq", "default", "cq"))
    if oracle:
        eng.attach_oracle()
    for name in full:
        eng.clock += 1
        eng.submit(Workload(
            name="low-" + name, queue_name="lq", priority=0,
            pod_sets=(PodSet("main", 1, dict.fromkeys(resources, 1000),
                             node_selector={KEY: name},
                             tolerations=(ANY,)),)))
        eng.schedule_once()
    eng.clock += 1
    eng.submit(Workload(
        name="head", queue_name="lq", priority=10,
        pod_sets=(PodSet("main", 1, dict.fromkeys(asked, 1000),
                         **pod_set),)))
    return eng, eng.schedule_once()


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("rule", sorted(RULES))
def test_a_rule_decides_the_heads_walk_on_the_device(rule, shape):
    """Every flavor full: the head preempts on the first flavor its
    mask leaves it — its only one where a selector pins it — or, with
    none left, is inadmissible; on the device, as the sequential core,
    with no host root; and the counts say what the mask left out."""
    extra, pod_set, eligible = RULES[rule]
    resources, asked = SHAPES[shape]
    seq, want = unit_world(False, extra, pod_set, resources, asked, NAMES)
    bat, got = unit_world(True, extra, pod_set, resources, asked, NAMES)
    assert verdicts_of(got) == verdicts_of(want) == ([], [
        ("head", ("low-" + eligible[0],))] if eligible else [])
    if not eligible:
        # NoFit: parked as the sequential core parks it.
        for eng, r in ((seq, want), (bat, got)):
            assert [(e.obj.name, e.requeue_reason) for e in r.entries] == [
                ("head", RequeueReason.NO_FIT)]
            pcq = eng.queues.cluster_queues["cq"]
            assert list(pcq.inadmissible) == ["default/head"]
            assert not pcq.items
    assert state_of(bat) == state_of(seq)
    assert bat.oracle.host_root_reasons == {}
    assert not bat.oracle.fallback_reasons
    assert bat.oracle.cycles_on_device == len(NAMES) + 1
    p = bat.last_cycle_phases
    excluded = len(NAMES) - len(eligible)
    assert p["n_mask_narrowed_heads"] == (1 if excluded else 0)
    assert p["n_masked_flavor_cells"] == excluded * len(asked)
    # A row a simulated cell: the eligible flavors' alone.
    assert p["n_sim_rows"] == len(eligible) * len(asked)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("rule", sorted(RULES))
def test_a_rule_decides_where_the_head_is_admitted(rule, shape):
    """Only the first flavor full: the head is admitted on the first
    free flavor its mask leaves it — past the excluded ones — preempts
    on `reserved` where that is all it may take, or is inadmissible."""
    extra, pod_set, eligible = RULES[rule]
    resources, asked = SHAPES[shape]
    seq, want = unit_world(False, extra, pod_set, resources, asked,
                           NAMES[:1])
    bat, got = unit_world(True, extra, pod_set, resources, asked, NAMES[:1])
    free = [name for name in eligible if name != "reserved"]
    if free:
        expected = ([("head", tuple((r, free[0]) for r in sorted(asked)))],
                    [])
    elif eligible:
        expected = ([], [("head", ("low-reserved",))])
    else:
        expected = ([], [])
    assert verdicts_of(got) == verdicts_of(want) == expected
    assert state_of(bat) == state_of(seq)
    assert bat.oracle.host_root_reasons == {}
    assert not bat.oracle.fallback_reasons
