"""What the chip's compiler makes of the preemptor's root-local quota
state (ops/preempt.classical_targets_impl), asked without the chip
through tools/tpu_layouts.py: with two or three resources no array of a
`while` body may hold many times its data — a [K, S] table with the
resource axis last came out resource-minor, 2 values padded to 128
lanes, and the scans carried, copied and scattered into 64 times the
data (PERF.md, PR 35) — and with one resource the carry stays laid out
node-minor, as it was. In the cycle program the preemptor's columns are
the head's own (oracle/batched.preempt_columns): two for a head of one
pod set and two resources behind three flavors, not the grid's six.

The topology is described inside the module-scoped fixture, never at
import (tests/test_tpu_compile.py says why), and the tests are skipped
where libtpu cannot describe a v5e. Nothing executes: a compile that
passes is not a chip run."""

import os
import re

import pytest

import jax

from kueue_tpu.ops import preempt as pops
from tools import tpu_layouts

# The second benchmark cell's sim program (tools/tpu_layouts.py's
# defaults): what the compiler decides follows the shapes, and a
# compile costs the same at any.
ROWS, ROOT_NODES = 1_024, 201  # a block of rows; K nodes a cohort root
RUNNING_A_ROOT = 2_048  # the candidate axis, A_l


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc

    env = pytest.MonkeyPatch()  # describe_v5e's, taken back afterwards
    for key, value in tpu_layouts.V5E_ENV.items():
        env.setenv(key, os.environ.get(key, value))
    try:
        chip = tpu_layouts.describe_v5e()
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to the persistent cache cannot be read
    # back without a chip: the cache is off around these compiles.
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield chip
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    jax.clear_caches()  # no chip-only trace may outlive this module
    env.undo()


@pytest.fixture(scope="module")
def regions(one_chip):
    """resources -> the `while` regions of the sim program as compiled
    (~40 s each): those of its scans, and the one loop that classifies
    the block's live rows a chunk at a time (ops/preempt.sim_targets,
    named by its scope)."""
    made = {}

    def of(resources: int) -> tuple:
        if resources not in made:
            args, kwargs = tpu_layouts.sim_targets_args(
                ROWS, resources, ROOT_NODES, RUNNING_A_ROOT)
            text = tpu_layouts.compile_for_v5e(
                pops.sim_targets, *args, one_chip=one_chip,
                **kwargs).as_text()
            chunked = set(re.findall(
                r"^\s+%(while[\w.\-]*) = .*op_name=\"[^\"]*"
                r"kueue\.sim_classify_chunk/while\"", text, re.M))
            loops = [r for r in tpu_layouts.layout_report(
                text, ratio=8.0)  # over the tool's floor, 1 MiB
                if r.kind == "while"]
            made[resources] = (
                [r for r in loops if r.loop not in chunked],
                [r for r in loops if r.loop in chunked])
        return made[resources]

    return of


def _quota_carries(regions, resources):
    """The loop operands that hold a root-local table: K * S values a
    row."""
    return [a for r in regions for a in r.carry
            if ROWS in a.dims and any(
                d in (ROOT_NODES, ROOT_NODES * resources) for d in a.dims)
            and a.data_bytes >= ROWS * ROOT_NODES * resources * 4]


@pytest.mark.parametrize("resources", [2, 3])
def test_no_padded_array_in_a_loop_of_the_preemptor(regions, resources):
    loops, (classify,) = regions(resources)
    assert len(loops) >= 4  # the greedy scan, the fill-back, the walk
    padded = [(r.loop, a.shape, round(a.ratio, 1))
              for r in loops for a in r.padded]
    assert padded == []
    assert _quota_carries(loops, resources)  # the scans do carry it
    # The loop that classifies a chunk of rows at a time carries the
    # block's classified rows unpadded. What it holds padded is the
    # classify's own candidate tables (a root's rows gathered with
    # their resource or level axis minor, ROADMAP A1 c) and the running
    # set's, which the program's entry held before, for the whole block
    # at once: a chunk's rows of them, never the block's.
    assert [a for a in classify.carry if ROWS in a.dims
            and a.ratio > 2.0] == []
    assert classify.padded
    for a in classify.padded:
        assert ROWS not in a.dims, a
        assert a.dims[0] <= pops.SIM_CHUNK * RUNNING_A_ROOT, a


@pytest.mark.parametrize("resources", [1, 2, 3])
def test_quota_carry_is_not_resource_minor(regions, resources):
    """No loop carries a root-local table padded, whatever S: its minor
    axis is the nodes' (or the rows'), as it was with one resource
    before the tables were laid flat (`u32[B,K,1]{1,0,2}`) — no S is
    special-cased."""
    carries = _quota_carries(regions(resources)[0], resources)
    assert carries
    for a in carries:
        assert a.ratio <= 2.0, a
        assert a.dims[a.minor] in (ROWS, ROOT_NODES,
                                   ROOT_NODES * resources), a


# -- the cycle program's preemptor, at the head's columns ---------------


def _cycle_program_inputs(flavors, resources, cohorts=2, per_cohort=10):
    """What the bridge hands the cycle program (shapes and statics) in a
    world of ``flavors`` in one group covering ``resources``, two
    cohorts of preempting ClusterQueues each running four workloads: one
    real schedule_once() on the CPU, left at the executor's door."""
    from kueue_tpu.api.types import (
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
    from kueue_tpu.controllers.engine import Engine

    eng = Engine()
    names = [f"f{i}" for i in range(flavors)]
    for name in names:
        eng.create_resource_flavor(ResourceFlavor(name))
    queues = [f"cq{c}-{i}" for c in range(cohorts)
              for i in range(per_cohort)]
    for c in range(cohorts):
        eng.create_cohort(Cohort(f"co{c}"))
    for q in queues:
        eng.create_cluster_queue(ClusterQueue(
            name=q, cohort="co" + q[2:q.index("-")],
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY),
            resource_groups=(ResourceGroup(resources, tuple(
                FlavorQuotas(f, dict.fromkeys(resources, ResourceQuota(1000)))
                for f in names)),)))
        eng.create_local_queue(LocalQueue("lq" + q, "default", q))

    def submit(name, q):
        eng.clock += 0.01
        eng.submit(Workload(name=name, queue_name="lq" + q, pod_sets=(
            PodSet("main", 1, dict.fromkeys(resources, 200)),)))

    for q in queues:
        for j in range(4):
            submit(f"{q}-{j}", q)
    for _ in range(20):
        r = eng.schedule_once()
        if r is None or not r.assumed:
            break
    submit("next", queues[0])
    eng.attach_oracle()

    class Seen(Exception):
        pass

    def cycle_step(tensors, statics):
        raise Seen(tensors, statics)

    eng.oracle.executor.cycle_step = cycle_step
    with pytest.raises(Seen) as seen:
        eng.schedule_once()
    tensors, statics = seen.value.args
    assert "adm_by_root" in tensors  # the fused preemptor is in it
    return tensors, statics


def _lower_cycle_program(one_chip, flavors, resources):
    from kueue_tpu.oracle import batched

    tensors, statics = _cycle_program_inputs(flavors, resources)
    lowered = batched.cycle_step.lower(
        **{k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
           for k, v in tensors.items()}, **statics)
    K = tensors["root_nodes"].shape[1]
    return lowered, statics["num_cqs"], K


def test_cycle_programs_preemptor_carries_the_heads_two_columns(one_chip):
    """Three flavors x two resources: the quota carry of the preemptor's
    scans is [C, 2 · K] — it was [C, 6 · K], the whole grid, four of its
    six columns inactive in every slot."""
    lowered, C, K = _lower_cycle_program(one_chip, 3, ("cpu", "memory"))
    assert "kueue.preempt_columns" in lowered.as_text(debug_info=True)
    loops = [r for r in tpu_layouts.layout_report(
        lowered.compile().as_text()) if r.kind == "while"]
    carried = {a.dims for r in loops for a in r.carry}
    assert (C, 2 * K) in carried
    assert not any(6 * K in dims for dims in carried)


def test_one_column_world_lowers_no_packing(one_chip):
    """One flavor, one resource (the first and third benchmark cells'
    kind): the head's columns are the grid's, so the cycle program is
    traced without the packing — the choice is made at trace time, from
    the shapes."""
    lowered, _C, _K = _lower_cycle_program(one_chip, 1, ("cpu",))
    assert "kueue.preempt_columns" not in lowered.as_text(debug_info=True)
