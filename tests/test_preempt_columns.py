"""The cycle program's fused preemptor at the head's own columns
(oracle/batched.preempt_columns): a head holds one flavor a (pod set,
resource), so its preemptor runs at pod sets x resources columns, not
at the flavor-resource grid's R = flavors x resources. The preemptor
reads a column by its id and masks the inactive ones in every
reduction, so each of its eight outputs is the one the dense [C, R]
form (batched.entry_columns) gives, element for element — on random
worlds of several flavors, with two pod sets on one flavor, and with
walks past the first window of candidates."""

import random

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from kueue_tpu.api.types import (  # noqa: E402
    BorrowWithinCohort,
    BorrowWithinCohortPolicy,
    ClusterQueue,
    ClusterQueuePreemption,
    Cohort,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controllers.engine import Engine  # noqa: E402
from kueue_tpu.oracle import batched  # noqa: E402
from kueue_tpu.ops import preempt as pops  # noqa: E402
from kueue_tpu.ops import quota as qops  # noqa: E402
from kueue_tpu.tensor.schema import (  # noqa: E402
    encode_admitted,
    encode_snapshot,
)

_targets = jax.jit(pops.classical_targets_impl,
                   static_argnames=("depth", "v_cap"))

_POLICY_CODE = {
    PreemptionPolicy.NEVER: pops.POLICY_NEVER,
    PreemptionPolicy.LOWER_PRIORITY: pops.POLICY_LOWER,
    PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY:
        pops.POLICY_LOWER_OR_NEWER_EQ,
    PreemptionPolicy.ANY: pops.POLICY_ANY,
}

RESOURCES = ("cpu", "memory", "gpu")
OUTPUTS = ("found", "overflow", "n_targets", "borrow_after", "v_ids",
           "taken", "v_variant", "skipped")


def full_world(rng, flavors, resources, two_level):
    """``flavors`` in one group covering ``resources``, on 4-6 preempting
    ClusterQueues under one cohort (or two mid cohorts); filled by
    admissions of low-priority workloads until nothing more fits, so
    every flavor of every queue holds candidates."""
    eng = Engine()
    names = [f"f{i}" for i in range(flavors)]
    for name in names:
        eng.create_resource_flavor(ResourceFlavor(name))
    eng.create_cohort(Cohort("root"))
    parents = ["root"]
    if two_level:
        parents = ["mid0", "mid1"]
        for name in parents:
            eng.create_cohort(Cohort(name, parent="root"))
    n_cqs = rng.randrange(4, 7)
    for i in range(n_cqs):
        bwc = None
        if rng.random() < 0.5:
            bwc = BorrowWithinCohort(
                policy=BorrowWithinCohortPolicy.LOWER_PRIORITY,
                max_priority_threshold=rng.choice([None, 1]))
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort=parents[i % len(parents)],
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                reclaim_within_cohort=rng.choice([
                    PreemptionPolicy.NEVER, PreemptionPolicy.LOWER_PRIORITY,
                    PreemptionPolicy.ANY]),
                borrow_within_cohort=bwc),
            resource_groups=(ResourceGroup(resources, tuple(
                FlavorQuotas(f, {r: ResourceQuota(
                    rng.choice([1000, 2000]),
                    borrowing_limit=rng.choice([None, 1000]),
                    lending_limit=rng.choice([None, 500, 1500]))
                    for r in resources}) for f in names)),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    for i in range(rng.randrange(40, 60)):
        eng.clock += rng.random()
        eng.submit(Workload(
            name=f"low{i}", queue_name=f"lq{rng.randrange(n_cqs)}",
            priority=rng.choice([0, 1, 2]),
            pod_sets=(PodSet("main", 1, {
                r: rng.choice([400, 800, 1200]) for r in resources}),)))
    for _ in range(120):
        r = eng.schedule_once()
        if r is None or not r.assumed:
            break
    return eng


def heads(rng, world, podsets, shared):
    """Every ClusterQueue's head as the cycle program holds it after its
    nomination: ``usage_fr`` int32[C, P, S] (a flavor a (pod set,
    resource), -1 now and then: none chosen) and ``h_req`` [C, P, S]
    (0 now and then: not asked). ``shared``: a second pod set takes the
    first one's flavor in every resource."""
    C, S = world.num_cqs, world.num_resources
    F = world.nominal.shape[1] // S
    flavor = np.array([[[rng.randrange(F) for _ in range(S)]
                        for _ in range(podsets)] for _ in range(C)])
    if shared:
        flavor[:, 1:] = flavor[:, :1]
    flavor[np.array([[[rng.random() < 0.08 for _ in range(S)]
                      for _ in range(podsets)] for _ in range(C)])] = -1
    usage_fr = np.where(flavor >= 0, flavor * S + np.arange(S), -1)
    h_req = np.array([[[rng.choice([0, 500, 900, 1500, 2500])
                        for _ in range(S)] for _ in range(podsets)]
                      for _ in range(C)], np.int64)
    return (jnp.asarray(usage_fr.astype(np.int32)), jnp.asarray(h_req))


def preemptor_args(rng, eng):
    """classical_targets_impl's arguments after the columns, every slot
    asking, and the by-root layout the bridge hands the cycle program."""
    now = eng.clock + 1.0
    snapshot = eng.cache.snapshot()
    world = encode_snapshot(snapshot, max_depth=4)
    admitted = [info for cqs in snapshot.cluster_queues.values()
                for info in cqs.workloads.values()]
    adm = encode_admitted(world, admitted, now=now)
    C = world.num_cqs
    wcq = np.zeros(C, np.int32)
    reclaim = np.zeros(C, np.int32)
    bwc_forbidden = np.ones(C, bool)
    bwc_threshold = np.full(C, pops.NO_THRESHOLD, np.int64)
    has_parent = np.zeros(C, bool)
    for i, name in enumerate(world.cq_names):
        spec = snapshot.cluster_queues[name].spec
        p = spec.preemption
        wcq[i] = _POLICY_CODE[p.within_cluster_queue]
        reclaim[i] = _POLICY_CODE[p.reclaim_within_cohort]
        if p.borrow_within_cohort is not None:
            bwc_forbidden[i] = False
            if p.borrow_within_cohort.max_priority_threshold is not None:
                bwc_threshold[i] = \
                    p.borrow_within_cohort.max_priority_threshold
        has_parent[i] = spec.cohort is not None
    usage = np.zeros((world.num_nodes, world.nominal.shape[1]), np.int64)
    usage[:C] = world.usage[:C]
    derived = qops.derive_world(
        jnp.asarray(world.nominal), jnp.asarray(world.lend_limit),
        jnp.asarray(world.borrow_limit), jnp.asarray(usage),
        jnp.asarray(world.parent), depth=world.depth)

    Rn = world.root_members.shape[0]
    root_of = world.root_of_cq[adm.cq]
    A_l = max(8, int(np.bincount(root_of, minlength=Rn).max()))
    by_root = np.full((Rn, A_l), -1, np.int32)
    for r in range(Rn):
        ids = np.nonzero(root_of == r)[0]
        by_root[r, :ids.size] = ids
    rank = np.empty(adm.num_admitted, np.int64)
    rank[np.lexsort((adm.uid_rank, -adm.qr_time, adm.priority))] = \
        np.arange(adm.num_admitted)

    slots = (jnp.ones((C,), bool),
             jnp.asarray([rng.choice([3, 5, 9]) for _ in range(C)],
                         jnp.int64),
             jnp.full((C,), now, jnp.float64))
    rest = (jnp.asarray(wcq), jnp.asarray(reclaim),
            jnp.asarray(bwc_forbidden), jnp.asarray(bwc_threshold),
            jnp.asarray(has_parent),
            jnp.asarray(adm.cq), jnp.asarray(adm.priority),
            jnp.asarray(adm.timestamp), jnp.asarray(adm.qr_time),
            jnp.asarray(adm.uid_rank), jnp.asarray(adm.evicted),
            jnp.asarray(adm.usage), derived["usage"],
            derived["subtree_quota"], jnp.asarray(world.lend_limit),
            jnp.asarray(world.borrow_limit), jnp.asarray(world.nominal),
            jnp.asarray(world.ancestors), jnp.asarray(world.height),
            jnp.asarray(world.local_chain), jnp.asarray(world.root_nodes),
            jnp.asarray(world.root_of_cq))
    layout = dict(adm_by_root=jnp.asarray(by_root),
                  adm_rank=jnp.asarray(rank))
    return world, slots, rest, layout


# (flavors, resources, pod sets, two pod sets on one flavor, v_cap):
# v_cap 2 puts many slots' targets past their first window.
_SHAPES = [(3, 2, 1, False, 32), (3, 3, 1, False, 32), (2, 2, 2, True, 32),
           (3, 2, 2, True, 32), (3, 2, 1, False, 2), (2, 2, 2, True, 2)]
_CASES = [shape + (seed, two_level) for shape in _SHAPES
          for seed, two_level in ((0, False), (1, True), (3, False))]


def _ids(case):
    f, s, p, shared, v_cap, seed, two_level = case
    return (f"{f}f{s}r{p}p" + ("-shared" if shared else "")
            + f"-v{v_cap}-s{seed}" + ("-2lvl" if two_level else ""))


def _world_and_heads(case):
    flavors, n_res, podsets, shared, v_cap, seed, two_level = case
    rng = random.Random(7919 * seed + 131 * flavors + 17 * n_res + podsets)
    eng = full_world(rng, flavors, RESOURCES[:n_res], two_level)
    world, slots, rest, layout = preemptor_args(rng, eng)
    usage_fr, h_req = heads(rng, world, podsets, shared)
    return world, slots, rest, layout, usage_fr, h_req


@pytest.mark.parametrize("case", _CASES, ids=_ids)
def test_packed_columns_decide_what_the_dense_grid_decides(case):
    flavors, n_res, podsets, _shared, v_cap, _seed, _two = case
    world, slots, rest, layout, usage_fr, h_req = _world_and_heads(case)
    R = world.nominal.shape[1]
    assert R == flavors * n_res
    dense = batched.entry_columns(usage_fr, h_req, R)
    packed = batched.pack_columns(usage_fr, h_req)
    assert packed[0].shape == packed[1].shape == (
        world.num_cqs, podsets * n_res)
    assert packed[0].dtype == dense[0].dtype == jnp.int32
    # The cycle program's choice: the packed form where it is narrower.
    chosen = batched.preempt_columns(usage_fr, h_req, *dense)
    assert chosen[0].shape[1] == min(podsets * n_res, R)

    def run(columns):
        return dict(zip(OUTPUTS, (np.asarray(o) for o in _targets(
            *slots, *columns, *rest, depth=world.depth, v_cap=v_cap,
            **layout))))

    want, got = run(dense), run(packed)
    for name in OUTPUTS:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    # Not vacuous: heads are decided, and with a window of two some walk
    # past it — a slot decided there holds its targets alone (-1 after
    # them) or more targets than V columns (overflow), or its scans
    # passed over more invalid candidates than two first windows hold.
    assert (want["found"] | want["overflow"]).any()
    if v_cap == 2:
        walked = (want["overflow"] | (want["skipped"] > 2 * v_cap)
                  | (want["found"] & (want["v_ids"] < 0).any(axis=1)))
        assert walked.any()


@pytest.mark.parametrize("case", [c for c in _CASES if c[2] > 1], ids=_ids)
def test_two_pod_sets_on_one_flavor_are_one_column(case):
    """The packed form of two pod sets: each live (flavor, resource) once,
    carrying what both ask, in the column of its first pod set; the
    repeat is dropped (-1, 0). Scattered back onto the grid it is the
    dense form."""
    world, _slots, _rest, _layout, usage_fr, h_req = _world_and_heads(case)
    R = world.nominal.shape[1]
    entry_fr_d, req_fr = batched.entry_columns(usage_fr, h_req, R)
    fr, req = (np.asarray(a) for a in batched.pack_columns(usage_fr, h_req))
    live = fr >= 0
    assert (req[~live] == 0).all() and (req[live] > 0).all()
    for c in range(fr.shape[0]):
        assert len(set(fr[c][live[c]])) == int(live[c].sum())
        grid = np.zeros(R, np.int64)
        grid[fr[c][live[c]]] = req[c][live[c]]
        np.testing.assert_array_equal(grid, np.asarray(req_fr)[c])
        np.testing.assert_array_equal(
            np.where(grid > 0, np.arange(R), -1), np.asarray(entry_fr_d)[c])
    # The heads' second pod set repeats the first one's flavors: where
    # both ask, a column is dropped.
    asked = (np.asarray(usage_fr) >= 0) & (np.asarray(h_req) > 0)
    merged = asked.reshape(fr.shape).sum(axis=1) - live.sum(axis=1)
    assert (merged > 0).any() and (merged >= 0).all()


def test_a_grid_no_wider_than_the_heads_is_handed_on_as_it_is():
    """One flavor (or more pod sets than the grid is wide): no op is
    added, the dense arrays themselves go to the preemptor."""
    usage_fr = jnp.zeros((4, 1, 2), jnp.int32) + jnp.arange(2, dtype=jnp.int32)
    h_req = jnp.full((4, 1, 2), 500, jnp.int64)
    dense = batched.entry_columns(usage_fr, h_req, 2)
    fr, req = batched.preempt_columns(usage_fr, h_req, *dense)
    assert fr is dense[0] and req is dense[1]
    two = jnp.zeros((4, 2, 1), jnp.int32)
    dense = batched.entry_columns(two, jnp.ones((4, 2, 1), jnp.int64), 2)
    assert batched.preempt_columns(two, jnp.ones((4, 2, 1), jnp.int64),
                                   *dense)[0] is dense[0]
    assert batched.preempt_width(1, 2, 6) == 2
    assert batched.preempt_width(2, 2, 4) == 4
    assert batched.preempt_width(1, 1, 1) == 1
