"""Differential suite: multi-flavor preemption on the device fast path.

The flavor choice on a preemption-enabled ClusterQueue with multi-flavor
resource groups depends on preemption simulations
(flavorassigner.go:1198 + preemption_oracle.go:41): with the default
whenCanPreempt=Preempt the scan STOPS at the first preempt-capable
flavor even when a later flavor would fit. The bridge's sim-augmented
nomination must reproduce the sequential engine's decisions exactly.
"""

import random

import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.api.types import (  # noqa: E402
    ClusterQueue,
    Cohort,
    FlavorFungibility,
    FlavorQuotas,
    FungibilityPolicy,
    LocalQueue,
    PodSet,
    ClusterQueuePreemption,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controllers.engine import Engine  # noqa: E402


def build_engine(oracle: bool, rng: random.Random, n_cqs=3,
                 when_can_preempt=FungibilityPolicy.PREEMPT,
                 resources=("cpu",)):
    """``resources``: one group that covers them all, every flavor with
    the same quota in each (a head's simulated (flavor, resource) cells
    are then rows of the sim program with one column active)."""
    eng = Engine()
    for f in ("on-demand", "spot", "reserved"):
        eng.create_resource_flavor(ResourceFlavor(f))
    eng.create_cohort(Cohort("co"))
    for i in range(n_cqs):
        flavors = tuple(
            FlavorQuotas(f, dict.fromkeys(resources, ResourceQuota(
                rng.choice([1000, 2000, 4000]))))
            for f in rng.sample(["on-demand", "spot", "reserved"],
                                rng.choice([2, 3])))
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort="co",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                reclaim_within_cohort=rng.choice(
                    [PreemptionPolicy.NEVER, PreemptionPolicy.ANY,
                     PreemptionPolicy.LOWER_PRIORITY])),
            flavor_fungibility=FlavorFungibility(
                when_can_preempt=when_can_preempt),
            resource_groups=(ResourceGroup(resources, flavors),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    if oracle:
        eng.attach_oracle()
    return eng


def churn(eng, rng: random.Random, n=30, resources=("cpu",),
          podsets=("main",)):
    names = []
    for i in range(n):
        eng.clock += 0.5
        wl = Workload(
            name=f"w{i}", queue_name=f"lq{rng.randrange(3)}",
            priority=rng.choice([0, 2, 5, 9]),
            pod_sets=tuple(PodSet(ps, 1, {
                r: rng.choice([500, 900, 1500, 2500])
                for r in resources}) for ps in podsets))
        eng.submit(wl)
        names.append(wl.name)
        if rng.random() < 0.4:
            eng.schedule_once()
        if rng.random() < 0.2:
            admitted = [k for k, x in eng.workloads.items()
                        if x.is_admitted]
            if admitted:
                eng.finish(rng.choice(admitted))
    for _ in range(120):
        r = eng.schedule_once()
        if r is None or (not r.assumed and not any(
                e.preemption_targets for e in r.entries)):
            break
        # Complete issued evictions so preempted workloads requeue.
        eng.tick(0.0)
    return names


def state_of(eng):
    out = {}
    for key, wl in sorted(eng.workloads.items()):
        out[key] = (wl.is_admitted, wl.is_finished,
                    sorted((str(psa.flavors[r]), r)
                           for psa in (wl.status.admission.
                                       pod_set_assignments
                                       if wl.status.admission else ())
                           for r in psa.flavors))
    return out


# With two or three resources the root-local quota tables of the sim
# program and the cycle program hold several values a node (PR 35).
_RESOURCES = [("cpu",), ("cpu", "memory"), ("cpu", "memory", "gpu")]


# (Three resources: four seeds; seed 7 differs from the sequential
# engine there at the parent of PR 35 as well — ROADMAP D2.)
@pytest.mark.parametrize("seed,resources", [
    (seed, r) for r in _RESOURCES
    for seed in range(8 if len(r) < 3 else 4)],
    ids=lambda v: len(v) if isinstance(v, tuple) else v)
def test_multiflavor_preempt_matches_sequential(seed, resources):
    rng_seq = random.Random(seed)
    rng_bat = random.Random(seed)
    seq = build_engine(False, random.Random(1000 + seed),
                       resources=resources)
    bat = build_engine(True, random.Random(1000 + seed),
                       resources=resources)
    churn(seq, rng_seq, resources=resources)
    churn(bat, rng_bat, resources=resources)
    assert bat.oracle.cycles_on_device > 0, "fast path never used"
    assert state_of(seq) == state_of(bat)


@pytest.mark.parametrize("resources", _RESOURCES[:2], ids=len)
@pytest.mark.parametrize("seed", range(4))
def test_multiflavor_try_next_matches_sequential(seed, resources):
    """whenCanPreempt=TryNextFlavor: the scan continues past
    preempt-capable flavors; mode-lattice ranking of PREEMPT vs
    NO_CANDIDATES still needs the sims."""
    rng_seq = random.Random(seed)
    rng_bat = random.Random(seed)
    seq = build_engine(False, random.Random(2000 + seed),
                       when_can_preempt=FungibilityPolicy.TRY_NEXT_FLAVOR,
                       resources=resources)
    bat = build_engine(True, random.Random(2000 + seed),
                       when_can_preempt=FungibilityPolicy.TRY_NEXT_FLAVOR,
                       resources=resources)
    churn(seq, rng_seq, resources=resources)
    churn(bat, rng_bat, resources=resources)
    assert bat.oracle.cycles_on_device > 0
    assert state_of(seq) == state_of(bat)


def test_stops_at_preempt_capable_flavor():
    """The regression the sim-augmented nomination exists for: flavor 1
    is full but preempt-capable, flavor 2 is free; the host stops at
    flavor 1 and preempts — the device path must not admit on flavor 2.
    """
    def build(oracle):
        eng = Engine()
        eng.create_resource_flavor(ResourceFlavor("f1"))
        eng.create_resource_flavor(ResourceFlavor("f2"))
        eng.create_cluster_queue(ClusterQueue(
            name="cq",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY),
            flavor_fungibility=FlavorFungibility(
                when_can_preempt=FungibilityPolicy.PREEMPT),
            resource_groups=(ResourceGroup(("cpu",), (
                FlavorQuotas("f1", {"cpu": ResourceQuota(1000)}),
                FlavorQuotas("f2", {"cpu": ResourceQuota(1000)}),)),)))
        eng.create_local_queue(LocalQueue("lq", "default", "cq"))
        if oracle:
            eng.attach_oracle()
        eng.clock += 1
        eng.submit(Workload(name="low", queue_name="lq", priority=0,
                            pod_sets=(PodSet("main", 1,
                                             {"cpu": 1000}),)))
        eng.schedule_once()
        eng.clock += 1
        eng.submit(Workload(name="high", queue_name="lq", priority=10,
                            pod_sets=(PodSet("main", 1,
                                             {"cpu": 1000}),)))
        r = eng.schedule_once()
        return eng, r

    seq, seq_r = build(False)
    bat, bat_r = build(True)
    seq_pre = [e.obj.name for e in seq_r.entries if e.preemption_targets]
    bat_pre = [e.obj.name for e in bat_r.entries if e.preemption_targets]
    assert seq_pre == ["high"], "sequential must preempt on flavor f1"
    assert bat_pre == seq_pre, (
        "device path admitted on f2 instead of preempting on f1")
    assert bat.oracle.cycles_on_device > 0


def own_flavor_engine(oracle: bool, rng: random.Random):
    """Three ClusterQueues in one cohort, each on a flavor of its own
    that covers cpu and memory: the flavor-resource grid is 3 x 2 = 6
    columns, a head of two pod sets holds 4, and both of its pod sets
    sit on its queue's one flavor. No group has a second flavor, so no
    head is simulated: the cycle program's preemptor decides them at
    the head's own columns, the second pod set's merged into the first's
    (oracle/batched.preempt_columns)."""
    eng = Engine()
    flavors = ("on-demand", "spot", "reserved")
    for f in flavors:
        eng.create_resource_flavor(ResourceFlavor(f))
    eng.create_cohort(Cohort("co"))
    for i, f in enumerate(flavors):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort="co",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                reclaim_within_cohort=rng.choice(
                    [PreemptionPolicy.NEVER, PreemptionPolicy.ANY,
                     PreemptionPolicy.LOWER_PRIORITY])),
            resource_groups=(ResourceGroup(("cpu", "memory"), (FlavorQuotas(
                f, {r: ResourceQuota(rng.choice([3000, 5000]))
                    for r in ("cpu", "memory")}),)),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    if oracle:
        eng.attach_oracle()
    return eng


# Seeds 2, 3, 4 and 6 end in another state than the sequential engine's,
# on the parent of the packed columns as well and to the same digest
# there (two pod sets that preempt: ROADMAP D2 h).
@pytest.mark.parametrize("seed", [0, 1, 5, 7])
def test_two_pod_sets_on_one_flavor_preempt_as_sequential(seed):
    resources, podsets = ("cpu", "memory"), ("launcher", "workers")
    seq = own_flavor_engine(False, random.Random(3000 + seed))
    bat = own_flavor_engine(True, random.Random(3000 + seed))
    launches = []
    inner = bat.oracle.executor.cycle_step

    def tap(tensors, statics):
        out = inner(tensors, statics)
        launches.append((tensors["wl_req"].shape[1:],
                         int(out[14][0]), bool((out[12] >= 0).any())))
        return out

    bat.oracle.executor.cycle_step = tap
    churn(seq, random.Random(seed), resources=resources, podsets=podsets)
    churn(bat, random.Random(seed), resources=resources, podsets=podsets)
    assert bat.oracle.cycles_on_device > 0
    assert state_of(seq) == state_of(bat)
    # Two pod sets of two resources on a grid of six columns: the
    # preemptor ran at four, asked by heads, and chose victims.
    assert {shape for shape, _, _ in launches} == {(2, 2)}
    assert any(slots for _, slots, _ in launches)
    assert any(victims for _, _, victims in launches)
    (cycle,) = [c for c in bat.spans.last().children if c.name == "cycle"]
    assert cycle.attrs["preempt_columns"] == 4
