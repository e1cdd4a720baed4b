"""The engine's span tree (obs/span.py SpanRecorder, ISSUE 27): one real
tree per schedule_once(), recorded where the work happens; the phase
dict derived from it; the preemptor's branch counted per launch; the
device stages' scopes in the lowered cycle program; and the same tree
in a profiler capture."""

import contextlib
import glob
import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.api.types import (  # noqa: E402
    ClusterQueue,
    ClusterQueuePreemption,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    PreemptionPolicy,
    QueueingStrategy,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controllers.engine import Engine  # noqa: E402
from kueue_tpu.obs.span import (  # noqa: E402
    AGGREGATE_KEYS,
    CONTAINERS,
    COUNT_KEYS,
    WINDOW_KEYS,
    SpanRecorder,
    leaf_phases,
    phase_seconds,
)

LAUNCH = ["upload", "upload", "dispatch", "device_wait", "readback"]
ENCODE = ["host_encode"] + LAUNCH
COMMIT = ["verdict_decode", "apply", "finalize"]


def make_engine(oracle=True, cohorts=1, nominal=1000, preemption=True,
                strategy=QueueingStrategy.BEST_EFFORT_FIFO, groups=1,
                flavors=1):
    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    later = [f"later{f}" for f in range(1, flavors)]  # of the cpu group
    for name in later:
        eng.create_resource_flavor(ResourceFlavor(name))
    second = ()
    if groups == 2:  # memory, in a resource group and flavor of its own
        eng.create_resource_flavor(ResourceFlavor("dimm"))
        second = (ResourceGroup(("memory",), (FlavorQuotas(
            "dimm", {"memory": ResourceQuota(nominal)}),)),)
    for i in range(cohorts):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort=f"co{i}", queueing_strategy=strategy,
            preemption=(ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY)
                if preemption else ClusterQueuePreemption()),
            resource_groups=(ResourceGroup(
                ("cpu",), tuple(
                    FlavorQuotas(name, {"cpu": ResourceQuota(nominal)})
                    for name in ["default"] + later)),
            ) + second,
        ))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    if oracle:
        eng.attach_oracle()
    return eng


def submit(eng, name, cpu, priority=0, lq="lq0", memory=None, **podset):
    eng.clock += 0.5
    requests = {"cpu": cpu} if memory is None else {
        "cpu": cpu, "memory": memory}
    wl = Workload(name=name, queue_name=lq, priority=priority,
                  pod_sets=(PodSet("main", 1, requests, **podset),))
    eng.submit(wl)
    return wl


def cycle(eng):
    """One schedule_once(); evictions land before the next."""
    r = eng.schedule_once()
    if r is not None and r.stats.preempting:
        eng.tick(0.0)
    return r, eng.spans.last()


def names(span):
    return [c.name for c in span.children]


def child(span, name):
    (c,) = [c for c in span.children if c.name == name]
    return c


def assert_nested(span):
    """Children lie inside their parent, in order, without overlap; a
    root's ``intake`` ends where the root begins, and its tallies (the
    sums of calls that may interleave) lie inside it."""
    end = span.ts
    for c in span.children:
        if c.name == "intake":
            assert c.ts + c.dur == pytest.approx(span.ts)
            for t in c.children:
                assert c.ts <= t.ts and t.ts + t.dur <= span.ts + 1e-3
            continue
        assert c.ts >= end - 1e-3, (span.name, c.name)
        assert c.dur >= 0
        end = c.ts + c.dur
        assert_nested(c)
    assert end <= span.ts + span.dur + 1e-3, span.name


def assert_adds_up(phases):
    leaves = leaf_phases(phases)
    assert "unattributed" in leaves and "schedule_once" not in leaves
    assert sum(leaves.values()) == pytest.approx(phases["schedule_once"],
                                                 abs=1e-9)
    assert phases["unattributed"] >= -1e-9


# -- the recorder alone ------------------------------------------------


def test_recorder_nests_shares_instants_and_keeps_a_small_ring():
    t = [0.0]
    rec = SpanRecorder(retain=2, clock=lambda: t[0])
    assert rec.epoch[0] == 0.0 and rec.epoch[1] > 0
    for seq in range(3):
        with rec.span("schedule_once", seq=seq) as root:
            t[0] += 1.0
            a = rec.begin("a", n=1)
            t[0] += 2.0
            b = rec.next("b")          # ends a, begins b: one instant
            t[0] += 3.0
            rec.end(bytes=7)
            t[0] += 0.5
        assert rec.open_root() is None
        assert names(root) == ["a", "b"]
        assert (a.dur, b.dur, root.dur) == (2e6, 3e6, 6.5e6)
        assert b.ts == a.ts + a.dur
        assert a.attrs == {"n": 1} and b.attrs == {"bytes": 7}
    assert [r.attrs["seq"] for r in rec.trees] == [1, 2]
    assert rec.last() is root
    # No span points back at its parent: a dropped tree dies by
    # reference count, with the engine's collector switched off.
    assert not hasattr(a, "parent")


def test_recorder_unwinds_what_an_exception_leaves_open():
    rec = SpanRecorder()
    with pytest.raises(RuntimeError):
        with rec.span("schedule_once"):
            with rec.span("cycle"):
                rec.begin("host_encode")
                rec.next("upload")
                raise RuntimeError("executor fell over")
    root = rec.last()
    assert rec.open_root() is None
    assert names(root) == ["cycle"]
    assert names(root.children[0]) == ["host_encode", "upload"]
    with rec.span("schedule_once"):  # and records on, in step
        pass
    assert len(rec.trees) == 2


def test_add_sums_into_the_innermost_open_span():
    """Code inside a leaf reports bytes and seconds without a span of
    its own (the sim program's launches inside ``sim_launch``)."""
    rec = SpanRecorder()
    with rec.span("outer"):
        with rec.span("leaf"):
            rec.add(bytes=3, device_wait_s=0.5)
            rec.add(bytes=4)
        rec.add(bytes=1)
    root = rec.last()
    assert root.attrs == {"bytes": 1}
    assert root.children[0].attrs == {"bytes": 7, "device_wait_s": 0.5}


def test_the_simulators_clock_times_the_spans():
    """Engine.wall_clock is the recorder's clock: the simulator's
    virtual one makes the phases (and their histograms) deterministic."""
    eng = make_engine(oracle=False)
    ticks = iter(range(1000))
    eng.wall_clock = lambda: float(next(ticks))
    assert eng.spans.clock is eng.wall_clock
    submit(eng, "w", 500)
    eng.schedule_once()
    assert all(float(v).is_integer() for v in eng.last_cycle_phases.values())
    assert eng.last_cycle_phases["schedule_once"] >= 4.0


# -- the tree, path by path --------------------------------------------


def test_sequential_cycle_tree():
    eng = make_engine(oracle=False)
    submit(eng, "w", 500)
    _, root = cycle(eng)
    assert root.name == "schedule_once"
    assert root.attrs == {"seq": 0, "mode": "sequential"}
    assert names(root) == ["intake", "pre_hooks", "snapshot", "decide",
                           "apply", "listeners"]
    assert_nested(root)
    assert_adds_up(eng.last_cycle_phases)
    assert set(eng.last_cycle_phases) == {
        "pre_hooks", "snapshot", "decide", "apply", "listeners",
        "unattributed", "schedule_once"} | WINDOW_KEYS


def test_device_cycle_tree():
    eng = make_engine()
    submit(eng, "a", 400)
    submit(eng, "b", 400)
    _, root = cycle(eng)
    assert root.attrs == {"seq": 0, "mode": "device"}
    assert names(root) == ["intake", "pre_hooks", "cycle", "listeners"]
    cyc = child(root, "cycle")
    assert names(cyc) == ENCODE + COMMIT
    assert cyc.attrs == {"lattice": False, "preempt_slots": 0,
                         "preempt_skipped": 0, "preempt_columns": 1}
    host = child(cyc, "host_encode")
    assert names(host) == ["tas_place"]
    assert host.attrs == {"heads": 1, "pending": 2,
                          "mask_narrowed_heads": 0}
    assert child(cyc, "verdict_decode").attrs == {
        "lattice": False, "device_heads": 1, "victim_entries": 0,
        "reclaim_victims": 0}
    # The bridge's upload counts this cycle's tensors; the executor had
    # nothing left to convert; the verdicts came back as bytes.
    assert [c.attrs["bytes"] > 0 for c in cyc.children
            if c.name == "upload"] == [True, False]
    assert child(cyc, "readback").attrs["bytes"] > 0
    assert_nested(root)
    assert_adds_up(eng.last_cycle_phases)


@pytest.mark.parametrize("world,columns", [
    (dict(), 1),
    (dict(flavors=3), 1),             # one resource: 1 of 3 grid columns
    (dict(flavors=3, groups=2), 2),   # cpu and memory: 2 of 8
    (dict(preemption=False), 0),      # no preemptor in the program
], ids=["one_column", "three_flavors", "two_resources", "no_preemptor"])
def test_cycle_span_tells_the_preemptors_width(world, columns):
    """`preempt_columns` on the `cycle` span: the head's own columns
    (pod sets x resources) where that is narrower than the flavor-
    resource grid, whether or not the launch took the branch."""
    eng = make_engine(**world)
    submit(eng, "w", 400, memory=100 if world.get("groups") == 2 else None)
    _, root = cycle(eng)
    assert child(root, "cycle").attrs["preempt_columns"] == columns


def test_hybrid_cycle_tree_has_a_host_tail():
    eng = make_engine(cohorts=2)
    submit(eng, "dev", 400, lq="lq0")
    submit(eng, "partial", 400, lq="lq1", min_count=1)  # host-only head
    _, root = cycle(eng)
    assert eng.oracle.cycles_hybrid == 1
    assert root.attrs["mode"] == "hybrid"
    cyc = child(root, "cycle")
    assert names(cyc)[-4:] == COMMIT + ["host_tail"]
    # The sequential core's own spans are detail under the tail, not
    # leaves: `apply` stays the device cycle's.
    tail = child(cyc, "host_tail")
    assert names(tail) == ["snapshot", "decide", "apply"]
    ph = eng.last_cycle_phases
    assert ph["apply"] == pytest.approx(child(cyc, "apply").dur * 1e-6)
    assert ph["host_tail"] == pytest.approx(tail.dur * 1e-6)
    assert "snapshot" not in ph and "decide" not in ph
    assert_nested(root)
    assert_adds_up(ph)


def test_fallback_cycle_keeps_both_attempts_in_one_tree():
    eng = make_engine(preemption=False)
    submit(eng, "partial", 400, min_count=1)  # every root is the host's
    _, root = cycle(eng)
    assert eng.oracle.fallback_reasons == {"all-host": 1}
    assert names(root) == ["intake", "pre_hooks", "cycle", "snapshot",
                           "decide", "apply", "listeners"]
    assert names(child(root, "cycle")) == ["host_encode"]
    ph = eng.last_cycle_phases
    assert "encode" not in ph and "device" not in ph  # no verdict came
    assert_adds_up(ph)


# -- the phase dict ----------------------------------------------------


def test_phase_keys_are_sums_over_everything_that_ran():
    eng = make_engine()
    for i in range(3):
        submit(eng, f"w{i}", 300)
    _, root = cycle(eng)
    ph = eng.last_cycle_phases
    cyc = child(root, "cycle")

    def total(name):
        return sum(c.dur for c in cyc.children if c.name == name) * 1e-6

    # Leaves: `upload` is two spans a cycle (the bridge's, then the
    # executor's leftovers), one key.
    for name in ENCODE + COMMIT:
        assert ph[name] == pytest.approx(total(name))
    assert ph["schedule_once"] == pytest.approx(root.dur * 1e-6)
    assert ph["tas_place"] == pytest.approx(
        child(child(cyc, "host_encode"), "tas_place").dur * 1e-6)
    # Unattributed is the two containers' self time (the intake lies
    # before the root, outside it).
    self_time = sum(b.dur - sum(c.dur for c in b.children
                                if c.name != "intake")
                    for b in (root, cyc)) * 1e-6
    # (`sim_nomination`, the third, is in a tree only where a head's
    # flavor choice needs simulations: tests/test_flavors_deployment.py.)
    assert {b.name for b in (root, cyc)} == CONTAINERS - {"sim_nomination"}
    assert ph["unattributed"] == pytest.approx(self_time, abs=1e-9)
    # Legacy aggregates, read off the cycle's subtree, mark to mark as
    # the bridge's perf_counter marks were: the time between the spans
    # is `encode`'s too.
    vd = child(cyc, "verdict_decode")
    assert ph["encode"] == pytest.approx(
        (vd.ts - child(cyc, "host_encode").ts) * 1e-6)
    assert ph["encode"] >= sum(
        c.dur for c in cyc.children if c.name in ENCODE) * 1e-6
    assert ph["device"] == ph["verdict_decode"]
    assert {"encode", "device", "tas_place",
            "schedule_once"} <= AGGREGATE_KEYS
    # Counts, from the attrs of this tree's spans; nothing that adds
    # seconds up takes them in.
    assert {k: ph[k] for k in COUNT_KEYS if k in ph} == {
        "n_launches": 1, "n_lattice_launches": 0, "n_preempt_slots": 0,
        "n_preempt_skipped": 0, "n_device_cycles": 1, "n_device_heads": 1,
        "n_commit_victim_entries": 0, "n_reclaim_victims": 0,
        "n_mask_narrowed_heads": 0}
    assert not COUNT_KEYS & set(leaf_phases(ph))
    # The histogram takes the leaves, the whole and the intake before
    # it, no aggregate.
    h = eng.registry.histogram("scheduler_phase_duration_seconds")
    assert {k for (k,) in h.totals} == \
        set(leaf_phases(ph)) | {"schedule_once", "intake"}


def test_phases_are_set_before_the_listeners_and_closed_after():
    eng = make_engine(oracle=False)
    seen = {}
    eng.cycle_listeners.append(
        lambda seq, result: seen.update(eng.last_cycle_phases))
    submit(eng, "w", 500)
    eng.schedule_once()
    assert set(seen) == {"pre_hooks", "snapshot", "decide",
                         "apply"} | WINDOW_KEYS - {"host_bound"}
    assert set(eng.last_cycle_phases) - set(seen) == {
        "listeners", "unattributed", "schedule_once", "host_bound"}
    h = eng.registry.histogram("scheduler_phase_duration_seconds")
    assert {k for (k,) in h.totals} == \
        set(leaf_phases(eng.last_cycle_phases)) | {"schedule_once",
                                                    "intake"}
    # An idle cycle leaves the last deciding cycle's phases in place.
    before = dict(eng.last_cycle_phases)
    assert eng.schedule_once() is None
    assert eng.last_cycle_phases == before
    assert "mode" not in eng.spans.last().attrs


# -- the preemptor's branch --------------------------------------------


def _preemptor_predicate(tensors, statics):
    """The lax.cond predicate of batched._cycle_core, recomputed from a
    launch's inputs by the program's own first three stages."""
    import jax.numpy as jnp

    from kueue_tpu.oracle import batched as B

    t = {k: jnp.asarray(v) for k, v in tensors.items()}
    if "slot_maybe" not in t:
        return False
    C = statics["num_cqs"]
    cq_usage = jnp.where((jnp.arange(t["usage"].shape[0]) < C)[:, None],
                         t["usage"], 0)
    derived = B.qops.derive_world(
        t["nominal"], t["lend_limit"], t["borrow_limit"], cq_usage,
        t["parent"], depth=statics["depth"])
    active = t["pending"] & ~t["inadmissible"]
    eff = jnp.where(active, t["rank"], B.BIG_RANK)
    head_rank = jnp.full((C,), B.BIG_RANK).at[t["wl_cq"]].min(eff)
    is_head = active & (eff == head_rank[t["wl_cq"]]) & (eff < B.BIG_RANK)
    head_idx = jnp.full((C,), -1, jnp.int32).at[
        jnp.where(is_head, t["wl_cq"], C)].max(
        jnp.arange(eff.shape[0], dtype=jnp.int32), mode="drop")
    valid = head_idx >= 0
    h = jnp.maximum(head_idx, 0)
    h_cq = jnp.where(valid, t["wl_cq"][h], 0).astype(jnp.int32)
    h_req = jnp.where(valid[:, None, None], t["wl_req"][h], 0)
    _f, _p, _b, needs_oracle, _u = B.aops.assign_flavors(
        h_cq, h_req, derived, t["nominal"], t["ancestors"], t["height"],
        t["group_of_res"], t["group_flavors"], t["no_preemption"],
        t["can_pwb"], t["fung_borrow_try_next"],
        t["fung_pref_preempt_first"], depth=statics["depth"],
        num_resources=statics["num_resources"])
    return bool(jnp.any(needs_oracle & valid & t["slot_maybe"]))


def tap_predicate(eng):
    """Per launch: (the branch predicate recomputed from the launch's
    inputs, the cycle program's own output 14)."""
    truth = []
    inner = eng.oracle.executor.cycle_step

    def tap(tensors, statics):
        out = inner(tensors, statics)
        truth.append((_preemptor_predicate(tensors, statics),
                      bool(out[14][0] > 0)))
        return out

    eng.oracle.executor.cycle_step = tap
    return truth


def test_lattice_attr_is_the_cycle_programs_own_predicate():
    """Across a churn that admits, evicts, parks on the preemptor, parks
    without it and idles: the `lattice` attr of every launch is the
    cycle program's own output, which equals the branch predicate
    recomputed from that launch's inputs, and is true exactly in the
    cycles that evict or park on the preemptor."""
    eng = make_engine(cohorts=2, nominal=1000)
    truth = tap_predicate(eng)
    seen, told, counts = [], [], []

    def run(expect_lattice):
        n = len(truth)
        r, root = cycle(eng)
        counts.append(dict(eng.last_cycle_phases))
        cyc = child(root, "cycle")
        vd = child(cyc, "verdict_decode")
        # One launch a cycle, told on the container and on the decode.
        assert cyc.attrs["lattice"] is vd.attrs["lattice"]
        seen.append(vd.attrs["lattice"])
        assert [(vd.attrs["lattice"],) * 2] == truth[n:], truth[n:]
        told.append((vd.attrs["lattice"], expect_lattice))
        return r

    submit(eng, "low-a", 400, priority=0)
    submit(eng, "top-b", 600, priority=100, lq="lq1")
    submit(eng, "low-b", 400, priority=0, lq="lq1")
    run(False)                                  # heads fit
    run(False)                                  # low-b fits behind top-b
    submit(eng, "mid-a", 800, priority=10)      # needs low-a's room
    r = run(True)
    assert r.stats.preempting == 1              # ... and evicts it
    submit(eng, "mid-b", 900, priority=10, lq="lq1")
    r = run(True)   # mid-a fits now; mid-b's victims (low-b) are too few
    assert r.stats.admitted == 1 and r.stats.preempting == 0
    submit(eng, "low-c", 900, priority=0, lq="lq1")
    # low-c has no lower-priority neighbour: the host's precheck keeps
    # the preemptor off, and it parks without it.
    run(False)
    assert [a for a, _ in told] == [e for _, e in told]
    assert True in seen and False in seen

    def total(key):  # a schedule_once() that launched nothing has none
        return sum(c.get(key, 0) for c in counts)

    assert total("n_launches") == len(truth)
    assert total("n_lattice_launches") == sum(t for t, _ in truth)
    assert total("n_device_cycles") == 5 and total("n_device_heads") >= 5
    # mid-a's is the one victim set: mid-b's preemptor found too few.
    assert total("n_commit_victim_entries") == 1


@pytest.mark.parametrize("world", [
    dict(strategy=QueueingStrategy.STRICT_FIFO),
    dict(groups=2),
], ids=["strict_fifo", "two_resource_groups"])
def test_lattice_is_told_where_the_verdicts_could_not_tell(world):
    """A StrictFIFO head the preemptor turns down does not park, and with
    two resource groups a head can park with a flavor assigned in the
    other: no reading of the verdicts tells the branch there (the attr
    was None). The program's own output does: true where a head drove
    the preemptor, whatever it decided, and counted."""
    eng = make_engine(**world)
    memory = 100 if world.get("groups") == 2 else None
    truth = tap_predicate(eng)

    def told():
        r, root = cycle(eng)
        lattice = child(child(root, "cycle"), "verdict_decode").attrs[
            "lattice"]
        assert isinstance(lattice, bool)
        # From the tree: a cycle that decides nothing leaves the last
        # deciding cycle's phase dict in place.
        return r, lattice, phase_seconds(root)["n_lattice_launches"]

    submit(eng, "low", 600, priority=0, memory=memory)
    submit(eng, "tiny", 100, priority=0, memory=memory)
    # Nobody is running yet, then `tiny` fits: no head needs victims.
    for _ in range(2):
        r, lattice, ran = told()
        assert r.stats.admitted == 1 and lattice is False and ran == 0
    submit(eng, "high", 600, priority=10, memory=memory)
    r, lattice, ran = told()      # `low` goes, `tiny` is spared
    assert r.stats.preempting == 1 and lattice is True and ran == 1
    r, lattice, ran = told()
    assert r.stats.admitted == 1 and lattice is False and ran == 0
    submit(eng, "mid", 900, priority=5, memory=memory)
    # `tiny` is a candidate and too small: the preemptor runs, turns
    # `mid` down, and no verdict of this cycle shows that it ran.
    r, lattice, ran = told()
    assert r is None or (r.stats.preempting, r.stats.admitted) == (0, 0)
    assert lattice is True and ran == 1
    assert truth and all(mine == programs for mine, programs in truth)


# -- digest neutrality -------------------------------------------------


def test_decisions_do_not_depend_on_a_tracer_reading_the_spans():
    from kueue_tpu.replay.trace import canonical_decisions, decision_digest

    def drive(traced):
        eng = make_engine(cohorts=2)
        tracer = eng.attach_tracer() if traced else None
        digest = 0
        for i in range(12):
            submit(eng, f"w{i}", 300 + 100 * (i % 4), priority=i % 3,
                   lq=f"lq{i % 2}")
            if i % 3 == 2:
                r, _ = cycle(eng)
                digest = decision_digest(canonical_decisions(r), digest)
        for _ in range(6):
            r, _ = cycle(eng)
            if r is not None:
                digest = decision_digest(canonical_decisions(r), digest)
        return digest, tracer

    plain, _ = drive(False)
    traced, tracer = drive(True)
    assert plain == traced
    # What the tracer serves is the recorder's tree, as timed.
    root = tracer.spans[-1]
    (live,) = [t for t in tracer.engine.spans.trees
               if t.attrs["seq"] == root.attrs["seq"]]
    assert root.ts == live.ts and root.dur <= live.dur

    def same(adopted, src):
        assert adopted.name == "phase/" + src.name
        assert (adopted.kind, adopted.ts, adopted.dur) == \
            ("phase", src.ts, src.dur)
        theirs = [c for c in adopted.children if c.kind == "phase"]
        assert len(theirs) == len(src.children)
        for a, b in zip(theirs, src.children):
            same(a, b)

    phases = [c for c in root.children if c.kind == "phase"]
    # `listeners` was still open when the tracer, a listener, looked.
    assert names(live)[:-1] == [c.name[len("phase/"):] for c in phases]
    for a, b in zip(phases, live.children):
        same(a, b)
    assert child(root, "phase/cycle").children[-1].name == "phase/finalize"


# -- the device stages' names ------------------------------------------

SCOPES = ("kueue.heads", "kueue.assign", "kueue.preempt", "kueue.commit")


def test_lowered_cycle_program_carries_the_scopes_and_nothing_else_moved(
        monkeypatch):
    from kueue_tpu.oracle import batched as B

    eng = make_engine()
    captured = {}
    inner = eng.oracle.executor.cycle_step

    def tap(tensors, statics):
        captured.setdefault("call", (dict(tensors), dict(statics)))
        return inner(tensors, statics)

    eng.oracle.executor.cycle_step = tap
    submit(eng, "w", 400)
    eng.schedule_once()
    tensors, statics = captured["call"]
    assert "adm_cq" in tensors  # the fused preemptor is in the program

    def lowered():
        # A fresh function each time: no trace is reused.
        return jax.jit(lambda **kw: B._cycle_core(**kw, **statics)
                       ).lower(**tensors)

    with_scopes = lowered()
    text = with_scopes.as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lowered()
    assert "kueue." not in without.as_text(debug_info=True)

    def stripped(low):
        body = low.as_text(debug_info=True)
        body = re.sub(r"^#loc.*\n", "", body, flags=re.M)
        return re.sub(r" loc\((?:[^()]|\([^()]*\))*\)", "", body)

    assert stripped(with_scopes) == stripped(without)
    assert "loc(" not in stripped(with_scopes)


# -- the same tree in a profiler capture -------------------------------


def test_profiler_capture_holds_the_tree(tmp_path):
    try:
        from jax.profiler import ProfileData
    except ImportError:
        pytest.skip("jax.profiler.ProfileData is not in this JAX")
    eng = make_engine()
    for i in range(4):
        submit(eng, f"w{i}", 300)
    eng.schedule_once()  # compiles, outside the capture
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        submit(eng, "x", 100)
        eng.finish("default/w0")
        for _ in range(3):
            eng.schedule_once()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events if ev.name.startswith("kueue.")]

    def inside(name, outer):
        return [e for e in events if e[0] == name
                and outer[1] <= e[1] and e[2] <= outer[2]]

    roots = sorted(e for e in events if e[0] == "kueue.schedule_once")
    assert len(roots) == 3
    for root in roots:
        (cyc,) = inside("kueue.cycle", root)
        (host,) = inside("kueue.host_encode", cyc)
        assert len(inside("kueue.tas_place", host)) == 1
        (dispatch,) = inside("kueue.dispatch", cyc)
        (wait,) = inside("kueue.device_wait", cyc)
        (readback,) = inside("kueue.readback", cyc)
        (decode,) = inside("kueue.verdict_decode", cyc)
        assert host[2] <= dispatch[1] and dispatch[2] <= wait[1] \
            and wait[2] <= readback[1] and readback[2] <= decode[1]
        # The launch's window: from inside the dispatch to the end of
        # the wait.
        (launch,) = inside("kueue.launch", cyc)
        assert dispatch[1] <= launch[1] <= dispatch[2] \
            and wait[1] <= launch[2] <= wait[2]
    # The client's calls before the first cycle, on the same clock.
    (sub,) = [e for e in events if e[0] == "kueue.submit"]
    (fin,) = [e for e in events if e[0] == "kueue.finish"]
    assert sub[2] <= fin[1] and fin[2] <= roots[0][1]
