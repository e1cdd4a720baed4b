"""The fused preemptor's victims stay packed, [slots x v_cap], from the
selection to the host (ISSUE 31): the bridge's decode of the packed
columns against a dense mask and variant rebuilt from them and against
the sequential core; and the cycle program's own text — no [C, A] output,
no [C, A] scatter, and a readback of the packed outputs' size."""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.api.types import (  # noqa: E402
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
from kueue_tpu.scheduler.cycle import EntryStatus  # noqa: E402

V_CAP = 32
N_CQS = 3


def make_engine(oracle: bool) -> Engine:
    """Three ClusterQueues: cq0 alone, cq1 and cq2 sharing a cohort in
    which cq1 reclaims what cq2 borrowed."""
    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    eng.create_cohort(Cohort("solo"))
    eng.create_cohort(Cohort("shared"))
    pre = ClusterQueuePreemption(
        within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
        reclaim_within_cohort=PreemptionPolicy.ANY)
    for i, cohort in enumerate(["solo", "shared", "shared"]):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort=cohort, preemption=pre,
            resource_groups=(ResourceGroup(
                ("cpu",), (FlavorQuotas(
                    "default", {"cpu": ResourceQuota(1000)}),)),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    if oracle:
        eng.attach_oracle()
    return eng


def submit(eng, name, cpu, priority, lq):
    eng.clock += 0.5
    eng.submit(Workload(name=name, queue_name=lq, priority=priority,
                        pod_sets=(PodSet("main", 1, {"cpu": cpu}),)))


def settle(eng):
    for _ in range(8):
        r = eng.schedule_once()
        if r is None or not r.stats.admitted:
            break


def fill(eng):
    """Eleven running workloads. In cq0 the lowest priority was admitted
    last, so the candidate order (priority ascending) is not the
    admitted rows' order; cq2 borrows 800 of cq1's nominal."""
    submit(eng, "a-mid", 250, 3, "lq0")
    submit(eng, "a-low1", 250, 1, "lq0")
    submit(eng, "a-low2", 250, 1, "lq0")
    submit(eng, "a-low3", 250, 0, "lq0")
    submit(eng, "b-low", 200, 0, "lq1")
    for i in range(6):
        submit(eng, f"c-{i}", 300, 0, "lq2")
    submit(eng, "c-top", 100, 9, "lq2")
    settle(eng)
    return eng


def preemptors(eng):
    """Heads that each need several victims: cq0's takes three of its
    own, lowest priority first; cq1's reclaims from cq2."""
    submit(eng, "a-high", 700, 5, "lq0")
    submit(eng, "b-high", 800, 5, "lq1")


def targets_by_preemptor(result):
    return {e.info.key: sorted((t.workload.key, t.reason)
                               for t in e.preemption_targets)
            for e in result.entries
            if e.status == EntryStatus.PREEMPTING}


def test_decode_lists_packed_victims_as_a_dense_mask_would():
    eng = fill(make_engine(oracle=True))
    preemptors(eng)
    bridge = eng.oracle
    seen = {}
    commit, apply_ = bridge._commit_cycle, bridge._apply

    def commit_tap(enc):
        # The admitted rows are live: the evictions leave holes in them.
        seen["enc"], seen["admitted"] = enc, list(enc.admitted)
        return commit(enc)

    def apply_tap(*args, **kwargs):
        seen["targets"] = kwargs["preempt_targets"]
        return apply_(*args, **kwargs)

    bridge._commit_cycle, bridge._apply = commit_tap, apply_tap
    result = eng.schedule_once()
    assert result.stats.preempting >= 2

    enc = seen["enc"]
    ids, variant = np.asarray(enc.out[12]), np.asarray(enc.out[13])
    admitted = seen["admitted"]
    A = len(admitted)
    assert ids.shape == variant.shape == (N_CQS, V_CAP)
    # Several preempting slots, several victims in one, and at least one
    # row whose candidate order is not ascending admitted index: the
    # decode's sort is what keeps the list's order.
    rows = [ids[ci][ids[ci] >= 0] for ci in range(N_CQS)]
    assert sum(r.size > 0 for r in rows) >= 2
    assert any(r.size > 1 and (np.diff(r) < 0).any() for r in rows)

    # What the dense form held: a [C, A] mask and variant, scanned by
    # ascending admitted index.
    mask = np.zeros((N_CQS, A), bool)
    dense_variant = np.zeros((N_CQS, A), np.int32)
    for ci, k in zip(*np.nonzero(ids >= 0)):
        mask[ci, ids[ci, k]] = True
        dense_variant[ci, ids[ci, k]] = variant[ci, k]
    reason = bridge._variant_reason()
    dense = {int(ci): [(admitted[v], reason[int(dense_variant[ci, v])])
                       for v in np.nonzero(mask[ci])[0]]
             for ci in np.nonzero(mask.any(axis=1))[0]}
    assert seen["targets"] == dense
    assert len({r for ts in dense.values() for _v, r in ts}) >= 2

    # ... and what the sequential core selects on the same world.
    seq = fill(make_engine(oracle=False))
    preemptors(seq)
    assert targets_by_preemptor(result) == targets_by_preemptor(
        seq.schedule_once())


# -- the cycle program's own text ---------------------------------------


def launch(eng):
    """One schedule_once(); what the executor was handed, what it
    returned, and the span tree."""
    seen = {}
    inner = eng.oracle.executor.cycle_step

    def tap(tensors, statics):
        out = inner(tensors, statics)
        seen.setdefault("call", (dict(tensors), dict(statics), out))
        return out

    eng.oracle.executor.cycle_step = tap
    eng.schedule_once()
    return (*seen["call"], eng.spans.last())


def test_cycle_program_returns_and_scatters_nothing_slots_by_admitted():
    from kueue_tpu.oracle import batched as B

    eng = fill(make_engine(oracle=True))
    preemptors(eng)
    for i in range(70):  # a pending axis longer than the admitted one
        submit(eng, f"c-wait{i}", 300, 0, "lq2")
    tensors, statics, out, root = launch(eng)
    C, A = statics["num_cqs"], tensors["adm_cq"].shape[0]
    # No other axis of this program is as long as the admitted one.
    assert (C, A) == (N_CQS, 64) and tensors["pending"].shape == (128,)
    dense = f"tensor<{C}x{A}x"  # any element type

    text = B.cycle_step.lower(**tensors, **statics).as_text()
    (results,) = re.findall(
        r"func\.func public @main\(.*?\) -> \((.*?)\) \{", text, flags=re.S)
    results = re.findall(r"tensor<[^>]*>", results)
    assert len(results) == len(out) == 15
    assert not [r for r in results if r.startswith(dense)]
    assert results[12] == results[13] == f"tensor<{C}x{V_CAP}xi32>"
    assert results[14] == "tensor<2xi32>"
    # Every scatter's operand (the first type of its signature, after
    # its update region).
    operands = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \((tensor<[^>]*>)', text,
        flags=re.S)
    assert operands  # the commit's and the parking's are still there
    assert not [t for t in operands if t.startswith(dense)]

    # The readback is of these outputs, at the size the text gives them.
    def nbytes(tensor_type):
        *dims, dtype = tensor_type[len("tensor<"):-1].split("x")
        return int(np.prod([int(d) for d in dims])) * {
            "i1": 1, "i32": 4, "i64": 8, "f64": 8}[dtype]

    assert [nbytes(r) for r in results] == [
        np.asarray(o).nbytes for o in out]
    assert nbytes(results[12]) == nbytes(results[13]) == C * V_CAP * 4
    (cycle,) = [s for s in root.children if s.name == "cycle"]
    (readback,) = [s for s in cycle.children if s.name == "readback"]
    assert readback.attrs["bytes"] == sum(nbytes(r) for r in results)
