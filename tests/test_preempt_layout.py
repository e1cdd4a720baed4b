"""What the chip's compiler makes of the preemptor's root-local quota
state (ops/preempt.classical_targets_impl), asked without the chip
through tools/tpu_layouts.py: with two or three resources no array of a
`while` body may hold many times its data — a [K, S] table with the
resource axis last came out resource-minor, 2 values padded to 128
lanes, and the scans carried, copied and scattered into 64 times the
data (PERF.md, PR 35) — and with one resource the carry stays laid out
node-minor, as it was.

The topology is described inside the module-scoped fixture, never at
import (tests/test_tpu_compile.py says why), and the tests are skipped
where libtpu cannot describe a v5e. Nothing executes: a compile that
passes is not a chip run."""

import os

import pytest

import jax

from kueue_tpu.ops import preempt as pops
from tools import tpu_layouts

# The second benchmark cell's sim program (tools/tpu_layouts.py's
# defaults): what the compiler decides follows the shapes, and a
# compile costs the same at any.
ROWS, ROOT_NODES = 1_024, 201  # a block of rows; K nodes a cohort root


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
    (~40 s each)."""
    made = {}

    def of(resources: int) -> list:
        if resources not in made:
            args, kwargs = tpu_layouts.sim_targets_args(
                ROWS, resources, ROOT_NODES, 2_048)
            text = tpu_layouts.compile_for_v5e(
                pops.sim_targets, *args, one_chip=one_chip,
                **kwargs).as_text()
            made[resources] = [
                r for r in tpu_layouts.layout_report(
                    text, ratio=8.0)  # over the tool's floor, 1 MiB
                if r.kind == "while"]
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
    loops = regions(resources)
    assert len(loops) >= 4  # the greedy scan, the fill-back, the walk
    padded = [(r.loop, a.shape, round(a.ratio, 1))
              for r in loops for a in r.padded]
    assert padded == []
    assert _quota_carries(loops, resources)  # the scans do carry it


@pytest.mark.parametrize("resources", [1, 2, 3])
def test_quota_carry_is_not_resource_minor(regions, resources):
    """No loop carries a root-local table padded, whatever S: its minor
    axis is the nodes' (or the rows'), as it was with one resource
    before the tables were laid flat (`u32[B,K,1]{1,0,2}`) — no S is
    special-cased."""
    carries = _quota_carries(regions(resources), resources)
    assert carries
    for a in carries:
        assert a.ratio <= 2.0, a
        assert a.dims[a.minor] in (ROWS, ROOT_NODES,
                                   ROOT_NODES * resources), a
