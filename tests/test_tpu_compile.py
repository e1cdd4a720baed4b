"""The chip's compiler, asked without the chip: the programs of the main
path must keep compiling for a v5e at the shapes chip_smoke.py runs them
with. Nothing executes here — a compile that passes is not a chip run —
but what the TPU compiler refuses (a misaligned slice, too much VMEM, an
op Mosaic cannot lower) it refuses here, at no chip time.

The topology is described inside the module-scoped fixture, never at
import and never in conftest.py: describing it loads libtpu, which one
process at a time may hold, and every xdist worker imports this file.
Keep these tests in this one file, and compile in the test's own
process."""

import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from kueue_tpu.ops import pallas_kernels as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to the persistent cache cannot be read
    # back without a chip (the next run would warn and recompile), so
    # the cache is off around these compiles.
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    jax.clear_caches()  # no chip-only trace may outlive this module
    env.undo()


@pytest.fixture
def for_chip(monkeypatch):
    """What the kernels' dispatch would see on the chip. The code asks
    jax.default_backend(), which is the CPU here, so the test steers
    it — and drops traces made for the CPU, which a lowering with the
    same shapes would otherwise reuse."""
    monkeypatch.setenv("KUEUE_TPU_PALLAS", "1")
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    jax.clear_caches()


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("W,C", [(65_536, 1_000), (4_096, 100)])
def test_heads_kernel_compiles(one_chip, for_chip, W, C):
    """W: chip_smoke's flat and preempt_churn buckets."""
    compiled = pk._heads_pallas.lower(
        jax.ShapeDtypeStruct((W,), np.int64, sharding=one_chip),
        jax.ShapeDtypeStruct((W,), np.int32, sharding=one_chip),
        num_cqs=C).compile()
    assert _mosaic_calls(compiled) == 1


@pytest.mark.parametrize("leaves", [640, 5_120])
def test_leaf_kernel_compiles(one_chip, for_chip, leaves):
    quantities = jax.ShapeDtypeStruct((leaves, 2), np.int64,
                                      sharding=one_chip)
    compiled = pk._leaf_pallas.lower(
        quantities, quantities,
        jax.ShapeDtypeStruct((2,), np.int64, sharding=one_chip),
        jax.ShapeDtypeStruct((leaves,), np.bool_, sharding=one_chip),
    ).compile()
    assert _mosaic_calls(compiled) == 1


def test_flat_cycle_program_compiles_as_the_bridge_calls_it(
        one_chip, for_chip, monkeypatch):
    """The BASELINE world, 50,000 pending x 1,000 ClusterQueues: one real
    schedule_once() on the CPU shows what the bridge hands its executor
    (pow2 bucket and all); that very signature is then compiled for the
    chip."""
    from kueue_tpu.bench.scenario import baseline_like
    from kueue_tpu.oracle import batched

    monkeypatch.syspath_prepend(REPO)
    import bench

    eng = bench.build_cycle_engine(
        baseline_like(n_cohorts=200, n_workloads=50_000))

    class Seen(Exception):
        pass

    def cycle_step(tensors, statics):
        # The shapes are all this test needs of the cycle: leaving here
        # saves compiling and running it for the CPU as well.
        raise Seen(tensors, statics)

    eng.oracle.executor.cycle_step = cycle_step
    with pytest.raises(Seen) as seen:
        eng.schedule_once()
    tensors, statics = seen.value.args
    assert tensors["pending"].shape == (65_536,)
    assert statics["num_cqs"] == 1_000

    compiled = batched.cycle_step.lower(
        **{k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
           for k, v in tensors.items()}, **statics).compile()
    assert _mosaic_calls(compiled) == 1  # the heads kernel, in the program
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 16 * 2**30  # one v5e chip's HBM


def test_commit_loop_compiles_to_one_while_that_branches(one_chip):
    """commit_grouped at the first benchmark cell's shapes (one cohort of
    1,000 ClusterQueues, 8,192 running, depth 4): one sequential loop,
    and in its body the conditional that keeps the K-row victim removal
    out of the steps whose entries preempt nothing. The chip compiler's
    text names the ops as a device trace does, which is how the loop's
    cost was placed (PERF.md, PR 33)."""
    import re

    from kueue_tpu.ops import commit as cops

    C, K, V, A, D, R, Rn = 1_000, 1_001, 32, 8_192, 4, 1, 1

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    quota = arg(np.int64, K, R)
    compiled = cops.commit_grouped.lower(
        entry_key=arg(np.int64, C), entry_valid=arg(np.bool_, C),
        entry_fr=arg(np.int32, C, R), entry_req=arg(np.int64, C, R),
        entry_kind=arg(np.int32, C), entry_borrows=arg(np.int32, C),
        usage0=quota, subtree_quota=quota, lend_limit=quota,
        borrow_limit=quota, nominal=quota, ancestors=arg(np.int32, K, D),
        root_members=arg(np.int32, Rn, C), root_nodes=arg(np.int32, Rn, K),
        local_chain=arg(np.int32, C, D + 1),
        root_parent_local=arg(np.int32, Rn, K),
        slot_victim_row=arg(np.int32, C, V),
        slot_victim_vals=arg(np.int64, C, V, R),
        slot_victim_ids=arg(np.int32, C, V), claimed0=arg(np.bool_, A),
        depth=D).compile()
    text = compiled.as_text()
    (body,) = re.findall(r" while\(.*\bbody=%([\w.\-]+)", text)
    computation = re.search(
        r"^%" + re.escape(body) + r" \(.*?^\}", text, re.M | re.S).group(0)
    assert computation.count(" conditional(") == 1
    assert text.count(" conditional(") == 1


def test_flavor_grid_compiles_with_the_heads_masks(one_chip):
    """ops/assign.flavor_grid at the fourth benchmark cell's shapes
    (1,000 slots, three flavors in one group, two resources, five
    cohorts of 200; PR 37): the heads' flavor masks are an operand, and
    the walk's flavors come back beside the grid."""
    from kueue_tpu.ops import assign as aops
    from kueue_tpu.ops import quota as qops

    C, N, S, NF, G, F, D = 1_000, 1_005, 2, 3, 1, 3, 4
    R = NF * S

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    quota = arg(np.int64, N, R)
    derived = jax.eval_shape(
        lambda *a: qops.derive_world(*a, depth=D), quota, quota, quota,
        quota, arg(np.int32, N))
    derived = jax.tree_util.tree_map(
        lambda x: arg(x.dtype, *x.shape), derived)
    compiled = aops.flavor_grid.lower(
        arg(np.int32, C), arg(np.int64, C, S), arg(np.bool_, C, NF),
        derived, quota, arg(np.int32, N, D), arg(np.int32, N),
        arg(np.int32, C, S), arg(np.int32, C, G, F), arg(np.bool_, C),
        arg(np.bool_, C), depth=D, num_resources=S).compile()
    shapes = [tuple(o.shape) for o in jax.tree_util.tree_leaves(
        compiled.out_info)]
    assert shapes == [(C, G, F, S)] * 3 + [(C, G, S), (C, G, F)]
