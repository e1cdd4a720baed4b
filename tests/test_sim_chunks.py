"""The sim program classifies only its block's live rows, a chunk at a
time inside its one launch (ops/preempt.sim_targets), and scans the
whole block once; the cycle program keeps one vmap over its slots.

The sim program's answers on the live rows are held, bit for bit, to
the classical preemptor over the whole block as the cycle program calls
it (ops/preempt.classical_targets_impl), at live counts around the
chunk and the block, and with a row that walks past its first window;
on the served path a cycle whose rows need two launches decides what
the plain reference decides, and the `sim_launch` span counts the rows
its launches classified."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from kueue_tpu.api.types import PodSet, Workload  # noqa: E402
from kueue_tpu.ops import preempt as pops  # noqa: E402
from kueue_tpu.oracle import engine_bridge  # noqa: E402

from tests.test_classical_preempt_device import (  # noqa: E402
    host_targets,
    lending_cohort,
    preemptor_inputs,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import plain_flavors  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut_flavors  # noqa: E402
import trafficgen  # noqa: E402
import worldgen_flavors  # noqa: E402

BLOCK, CHUNK, V_CAP = 16, 4, 32

_whole_block = jax.jit(pops.classical_targets_impl,
                       static_argnames=("depth", "v_cap"))


@pytest.fixture(scope="module")
def lending():
    """One cohort: `home` (idle, reclaimWithinCohort Any) and 41 queues
    that borrow from it, three workloads of 400 each (test_classical_
    preempt_device.lending_cohort). A row of `home` asking 9,000 finds
    its 21 targets only past its first 32 ordered candidates; smaller
    asks are decided in their first window; a row of a borrowing queue
    takes its own queue's workloads."""
    eng = lending_cohort(n_borrowers=41, per_queue=3, request=400)
    now = eng.clock + 1.0
    eng.clock = now
    wl = Workload(name="back", queue_name="lq-home", priority=5,
                  creation_time=now,
                  pod_sets=(PodSet("main", 1, {"cpu": 9000}),))
    eng.submit(wl)
    info = eng.queues.cluster_queues["home"].items[wl.key]
    assignment, _ = host_targets(eng, info, now)
    args, grouped, (world, _adm, home) = preemptor_inputs(
        eng, info, assignment, now, "by_root")
    fr = int(np.asarray(args[3])[home, 0])
    asks = [(home, 9000), (home, 2000), (home, 50), (1, 500), (2, 900),
            (home, 8000), (3, 1200), (home, 400)]
    rows = [asks[i % len(asks)] for i in range(BLOCK + BLOCK // 2)]
    return args[5:], grouped, world, fr, rows, now


def _block(rows, live, fr, now):
    """The bridge's block (engine_bridge._sim_launch): ``live`` rows
    first, the rest _SIM_ROW_FILLS."""
    cq = np.zeros(BLOCK, np.int32)
    need = np.zeros(BLOCK, bool)
    pri = np.zeros(BLOCK, np.int64)
    ts = np.zeros(BLOCK, np.float64)
    slot_fr = np.full((BLOCK, 1), -1, np.int32)
    req = np.zeros((BLOCK, 1), np.int64)
    for i, (c, ask) in enumerate(rows[:live]):
        cq[i], need[i], pri[i], ts[i] = c, True, 5, now
        slot_fr[i, 0], req[i, 0] = fr, ask
    fills = engine_bridge._SIM_ROW_FILLS
    assert (cq[live:] == fills["slot_cq"]).all()
    assert (need[live:] == fills["slot_need"]).all()
    return (jnp.asarray(need), jnp.asarray(pri), jnp.asarray(ts),
            jnp.asarray(slot_fr), jnp.asarray(req)), jnp.asarray(cq)


def _answers_of_the_whole_block(slots, slot_cq, world_args, grouped,
                                world):
    """What the sim program reports, from the classical preemptor over
    the whole block under one vmap (the cycle program's call)."""
    found, overflow, _n, borrow, v_ids, taken, _var, _skip = _whole_block(
        *slots, *world_args, slot_cq=slot_cq, depth=world.depth,
        v_cap=V_CAP, **grouped)
    adm_cq = world_args[5]
    same = jnp.any(taken & (v_ids >= 0)
                   & (adm_cq[jnp.maximum(v_ids, 0)] == slot_cq[:, None]),
                   axis=1)
    return [np.asarray(x) for x in (found, overflow, borrow, same)]


@pytest.mark.parametrize("chunk", [CHUNK, CHUNK - 1, pops.SIM_CHUNK],
                         ids=["divides", "does-not-divide", "over-block"])
@pytest.mark.parametrize("live", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1,
                                  BLOCK - 1, BLOCK])
def test_live_rows_answer_what_the_whole_block_answers(lending, live,
                                                       chunk):
    world_args, grouped, world, fr, rows, now = lending
    slots, slot_cq = _block(rows, live, fr, now)
    got = [np.asarray(x) for x in pops.sim_targets(
        *slots, *world_args, slot_cq=slot_cq, depth=world.depth,
        v_cap=V_CAP, chunk=chunk, **grouped)]
    want = _answers_of_the_whole_block(slots, slot_cq, world_args,
                                       grouped, world)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g[:live], w[:live])
    # A pad row finds nothing.
    assert not got[0][live:].any() and not got[1][live:].any()
    if live:
        # The first row walks past its first window and finds its 21
        # targets there; the worlds decide something.
        assert got[0][0] and not got[1][0] and not got[3][0]


def test_rows_over_a_block_loop_it_and_answer_alike(lending):
    """More rows than the block: the bridge launches the block once a
    block of rows (engine_bridge._sim_launch), each launch classifying
    its own live rows."""
    world_args, grouped, world, fr, rows, now = lending
    assert len(rows) > BLOCK
    got, want = [], []
    for lo in range(0, len(rows), BLOCK):
        part = rows[lo:lo + BLOCK]
        slots, slot_cq = _block(part, len(part), fr, now)
        out = pops.sim_targets(*slots, *world_args, slot_cq=slot_cq,
                               depth=world.depth, v_cap=V_CAP,
                               chunk=CHUNK, **grouped)
        got.append([np.asarray(x)[:len(part)] for x in out])
        want.append([x[:len(part)] for x in _answers_of_the_whole_block(
            slots, slot_cq, world_args, grouped, world)])
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, b)
    found = np.concatenate([g[0] for g in got])
    assert found.any() and not found.all()


def test_only_the_sim_program_lowers_a_chunk_loop(lending):
    """The chunk loop is the sim program's alone: the classical
    preemptor as the cycle program calls it is one vmap, with no
    `kueue.sim_classify_chunk` scope (the cycle program as launched:
    test_a_cycle_of_two_launches_classifies_whole_chunks)."""
    world_args, grouped, world, fr, rows, now = lending
    slots, slot_cq = _block(rows, CHUNK + 1, fr, now)
    whole = _whole_block.lower(
        *slots, *world_args, slot_cq=slot_cq, depth=world.depth,
        v_cap=V_CAP, **grouped).as_text(debug_info=True)
    sim = pops.sim_targets.lower(
        *slots, *world_args, slot_cq=slot_cq, depth=world.depth,
        v_cap=V_CAP, chunk=CHUNK, **grouped).as_text(debug_info=True)
    assert "kueue.sim_classify_chunk" not in whole
    assert "kueue.sim_classify_chunk" in sim


# -- on the served path --------------------------------------------------


def test_a_cycle_of_two_launches_classifies_whole_chunks(monkeypatch):
    """The several-flavors kind's tiny world (48 ClusterQueues: a block
    of 64 rows) with chunks of 8: the rows a cycle simulates go from
    under a chunk to several blocks. Every cycle decides what the plain
    reference decides, and the `sim_launch` span counts, a launch, its
    live rows in whole chunks."""
    monkeypatch.setattr(pops, "SIM_CHUNK", 8)
    cfg = run.read_config("fungible-3f2r-1000cq", tiny=True)
    world = worldgen_flavors.build_world(cfg, seed=9)
    device = sut_flavors.Program(world, "local")
    mix = dict(trafficgen.read_mix("trickle-turnover", tiny=True),
               turnover_share=2 / len(world["cluster_queues"]))
    gen = trafficgen.Generator(mix, world)
    sets = trafficgen.RunningSets(
        [cq["name"] for cq in world["cluster_queues"]], world["running"])
    executor = device.eng.oracle.executor
    inner, calls = executor.cycle_step, []

    def cycle_step(tensors, statics):
        calls[:] = [(tensors, statics)]
        return inner(tensors, statics)

    executor.cycle_step = cycle_step
    events, got, launches = [], [], []
    for k in range(12):
        finishes, arrivals, now = gen.events(k, sets)
        for name in finishes:
            sets.remove(name)
            device.finish(name)
        for arrival in arrivals:
            device.submit(*arrival)
        v = device.cycle(now)
        sets.apply(v)
        events.append((finishes, arrivals, now))
        got.append(v)
        launch = device.eng.spans.last().find(
            lambda s: s.name == "sim_launch")
        launches.append((device.phases(), launch))
        if k == 0:  # no finish: every queue's next head meets full flavors
            events.append(([], [], now))
            got.append(device.cycle(now))
            sets.apply(got[-1])
            launches.append((device.phases(), device.eng.spans.last().find(
                lambda s: s.name == "sim_launch")))
    block = engine_bridge.OracleBridge._sim_block(
        device.eng.oracle._world_tensors())
    assert block == 64
    seen = []
    for phases, launch in launches:
        n = phases.get("n_sim_rows", 0)
        want = sum(-(-min(block, n - lo) // 8) * 8
                   for lo in range(0, n, block))
        assert phases.get("n_sim_rows_classified", 0) == want
        if launch is not None:
            assert launch.attrs["rows_classified"] == want
        seen.append(n)
    assert max(seen) > block and any(0 < n % 8 for n in seen), seen
    assert len(device.sim_shapes) == 1 and len(device.signatures) == 1
    want = reference.replay(plain_flavors.Plain(world), events)
    cohort = {cq["name"]: cq["cohort"] for cq in world["cluster_queues"]}
    assert reference.differing(got, want, cohort) == []
    # The cycle program, as launched, has its preemptor and no chunk
    # loop.
    from kueue_tpu.oracle import batched

    tensors, statics = calls[0]
    assert "adm_by_root" in tensors
    text = batched.cycle_step.lower(**tensors, **statics).as_text(
        debug_info=True)
    assert "kueue.preempt" in text
    assert "kueue.sim_classify_chunk" not in text
