"""The engine's work between two cycles and its device launches, on the
span recorder (obs/span.py): the ``intake`` tree of tallies that a
cycle's root takes as its first child, the requeue and tick counts
where the work happens, every launch's window, the compiles on the span
that was open, and the keys of Engine.last_cycle_phases they make
(WINDOW_KEYS) — beside every key there was, which keeps its value."""

import contextlib
import dataclasses
import os
import sys
import threading

import pytest

jax = pytest.importorskip("jax")

from kueue_tpu.api.types import (  # noqa: E402
    ClusterQueue,
    FlavorQuotas,
    LocalQueue,
    PodSet,
    ResourceFlavor,
    ResourceGroup,
    ResourceQuota,
    Workload,
)
from kueue_tpu.controllers.engine import Engine  # noqa: E402
from kueue_tpu.obs.span import (  # noqa: E402
    TALLY_KINDS,
    WINDOW_KEYS,
    WORK_KINDS,
    SpanRecorder,
    close_phases,
    phase_seconds,
    window_keys,
)
from tests import test_span_tree as trees  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
from tools.setup_split import CompileSpans  # noqa: E402

READERS = ("intake_ms_per_cycle", "requeued_per_cycle",
           "host_bound_ms_per_cycle")


def ticking():
    """A clock that moves one second a read: every span's time is a
    whole number of reads."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]
    return clock


def workload(name, cpu, lq="lq0", priority=0):
    return Workload(name=name, queue_name=lq, priority=priority,
                    pod_sets=(PodSet("main", 1, {"cpu": cpu}),))


def world(queues, cohorts, nominal=1000):
    """Sequential engine: ``queues`` ClusterQueues spread over
    ``cohorts`` cohorts, none preempting."""
    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    for i in range(queues):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort=f"co{i % cohorts}",
            resource_groups=(ResourceGroup(("cpu",), (FlavorQuotas(
                "default", {"cpu": ResourceQuota(nominal)}),)),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    return eng


# -- the recorder alone ------------------------------------------------


def test_calls_between_roots_are_tallied_on_the_next_roots_intake():
    rec = SpanRecorder(clock=ticking())
    for kind in ("submit", "submit", "finish", "submit"):
        with rec.call(kind):
            rec.add(requeued=2)
    assert rec.last() is None  # no root yet: nothing in the ring
    with rec.span("schedule_once", seq=7) as root:
        pass
    assert rec.last() is root
    intake = root.children[0]
    assert (intake.name, intake.attrs) == ("intake", {"seq": 7})
    assert trees.names(intake) == ["submit", "finish"]
    submit, finish = intake.children
    # ts the first call's start, dur the calls' sum (two reads each).
    assert submit.attrs == {"calls": 3, "requeued": 6}
    assert finish.attrs == {"calls": 1, "requeued": 2}
    assert (submit.dur, finish.dur) == (3e6, 1e6)
    assert finish.ts - submit.ts == 4e6
    # From the first event to the instant the root opened.
    assert intake.ts == submit.ts
    assert intake.ts + intake.dur == root.ts
    # The next root takes what came after, and no more.
    with rec.span("schedule_once", seq=8) as second:
        pass
    assert second.children == []


def test_a_call_inside_a_span_is_that_spans_time():
    rec = SpanRecorder(clock=ticking())
    with rec.span("schedule_once") as root:
        with rec.call("finish"):
            rec.add(requeued=5)
        with rec.call("tick"):
            pass
    assert root.children == [] and root.attrs == {"requeued": 5}
    # Nor does a call inside a call tally itself.
    with rec.call("finish"):
        with rec.call("submit"):
            pass
    with rec.span("schedule_once") as root:
        pass
    assert [(t.name, t.attrs) for t in root.children[0].children] == [
        ("finish", {"calls": 1})]


def test_add_with_nothing_open_drops_and_a_raising_call_unwinds():
    rec = SpanRecorder(clock=ticking())
    rec.add(requeued=3)  # no span open: dropped, no error
    with pytest.raises(RuntimeError):
        with rec.call("submit"):
            rec.begin("left_open")
            raise RuntimeError("submit fell over")
    assert rec._open == [] and rec._scopes == []
    with rec.span("schedule_once") as root:
        pass
    (tally,) = root.children[0].children
    assert tally.attrs == {"calls": 1}
    assert trees.names(tally) == ["left_open"]


def test_a_call_from_another_thread_is_off_the_stack_of_a_root_opening():
    """An HTTP handler's submit that is still running as the loop opens
    a cycle's root: the root is the loop's own, and the handler's exit
    closes nothing of it."""
    rec = SpanRecorder(clock=ticking())
    entered, release = threading.Event(), threading.Event()
    failed = []

    def handler():
        try:
            with rec.call("submit"):
                rec.add(requeued=4)
                entered.set()
                release.wait(10)
        except Exception as e:  # noqa: BLE001 — reported below
            failed.append(e)

    worker = threading.Thread(target=handler)
    worker.start()
    assert entered.wait(10)
    with rec.span("schedule_once", seq=1) as root:
        rec.begin("pre_hooks")
        release.set()
        worker.join(10)
        rec.next("apply")
        rec.end()
    assert failed == []
    assert rec.last() is root and rec._open == [] and rec._scopes == []
    assert trees.names(root) == ["pre_hooks", "apply"]
    assert root.attrs == {"seq": 1}


def test_calls_from_another_thread_leave_the_loops_trees_whole():
    """A handler thread submitting without pause while the loop tallies
    its ticks and records cycles, the interpreter switching threads as
    often as it can: every tree is the loop's, whole."""
    rec = SpanRecorder()
    stop = threading.Event()
    calls, failed = [0], []

    def handler():
        try:
            while not stop.is_set():
                with rec.call("submit"):
                    rec.add(requeued=1)
                calls[0] += 1
        except Exception as e:  # noqa: BLE001 — reported below
            failed.append(e)

    roots = []
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    worker = threading.Thread(target=handler)
    worker.start()
    try:
        for seq in range(300):
            with rec.call("tick"):
                rec.add(tick_scanned=2)
            with rec.span("schedule_once", seq=seq) as root:
                rec.begin("pre_hooks")
                rec.next("apply")
                rec.end()
            assert rec.last() is root
            roots.append(root)
    finally:
        stop.set()
        worker.join(10)
        sys.setswitchinterval(interval)
    assert failed == [] and calls[0] > 0
    assert rec._open == [] and rec._scopes == []
    for seq, root in enumerate(roots):
        assert trees.names(root) == ["intake", "pre_hooks", "apply"]
        (tick,) = root.children[0].children
        assert (tick.name, tick.attrs) == (
            "tick", {"calls": 1, "tick_scanned": 2})
        assert root.attrs == {"seq": seq}
        assert all("requeued" not in s.attrs for s in root.walk())


def test_an_engine_submit_from_another_thread_is_annotated_not_tallied():
    eng = world(queues=2, cohorts=1)

    def from_handler(name):
        worker = threading.Thread(target=eng.submit,
                                  args=(workload(name, 1, lq="lq1"),))
        worker.start()
        worker.join(10)

    eng.submit(workload("first", 1))
    from_handler("between")  # no span open: the loop's intake is not its
    eng.pre_cycle_hooks.append(lambda seq, _: from_handler(f"mid{seq}"))
    eng.tick(0.0)
    assert eng.schedule_once() is not None
    root = eng.spans.last()
    assert root.name == "schedule_once" and eng.spans._open == []
    assert [(t.name, t.attrs) for t in root.children[0].children] == [
        ("submit", {"calls": 1}), ("tick", {"calls": 1, "tick_scanned": 0})]
    trees.assert_nested(root)
    assert {"default/between", "default/mid0"} <= set(eng.workloads)
    assert eng.last_cycle_phases["n_intake_calls"] == 2


def test_a_launch_marks_its_window_on_the_open_span():
    rec = SpanRecorder(clock=ticking())
    with rec.span("schedule_once") as root:
        rec.begin("dispatch")
        with rec.launch("cycle_step"):
            wait = rec.next("device_wait")
        rec.next("readback")
        rec.end()
        with rec.launch("outside_any_leaf"):
            pass
    assert wait.attrs == {"launched_s": 2.0}
    assert root.attrs == {"launched_s": 1.0}
    ph = phase_seconds(root)
    assert ph["device_launched"] == 3.0
    close_phases(ph, root)
    assert ph["host_bound"] == ph["intake"] + ph["schedule_once"] - 3.0


def test_what_jax_compiles_lands_on_the_span_open_in_its_thread():
    other = SpanRecorder()
    rec = SpanRecorder()
    x = jax.numpy.arange(5)
    with other.span("schedule_once"):
        with rec.span("schedule_once"):
            with rec.span("dispatch") as dispatch:
                # A new function object: always traced and compiled (or
                # read back from the persistent cache).
                jax.jit(lambda v: v * 3 + 1)(x)
            other_attrs = dict(other.open_root().attrs)
    assert dispatch.attrs == {"compiles": 1}
    assert other_attrs == {}  # the one that opened last, alone
    assert phase_seconds(rec.last())["n_compiles"] == 1
    # Another thread's compile is that thread's: none lands here.
    with rec.span("schedule_once") as root:
        worker = threading.Thread(
            target=lambda: jax.jit(lambda v: v - 7)(x))
        worker.start()
        worker.join()
    assert root.attrs == {}


@contextlib.contextmanager
def compile_spans(rec):
    """The set-up split's listener on ``rec``, for the test's length."""
    spans = CompileSpans(rec)
    try:
        yield spans
    finally:
        spans.close()


def test_a_trace_inside_a_trace_counts_once():
    """tools/setup_split.py's CompileSpans: a trace's seconds once."""
    import time

    @jax.jit
    def inner(v):
        time.sleep(0.2)
        return v + 1

    @jax.jit
    def outer(v):
        time.sleep(0.1)
        return inner(v) * 2

    x = jax.numpy.arange(3)
    rec = SpanRecorder()
    with compile_spans(rec):
        with rec.span("dispatch") as dispatch:
            outer(x)
    # JAX reports inner's 0.2 s and then outer's 0.3 s, which holds it.
    assert 0.29 <= dispatch.attrs["trace_s"] < 0.45
    assert dispatch.attrs["compile_s"] > 0


def test_a_trace_of_many_functions_counts_at_most_its_wall():
    """A cycle program traces thousands of functions inside its own
    trace: however many, their seconds are the outer trace's."""
    import time

    parts = [jax.jit(lambda v, k=k: v + k) for k in range(600)]

    @jax.jit
    def outer(v):
        for part in parts:
            v = part(v)
        time.sleep(0.05)
        return v

    rec = SpanRecorder()
    with compile_spans(rec):
        with rec.span("dispatch") as dispatch:
            outer(jax.numpy.arange(3))
    wall = dispatch.dur * 1e-6
    assert 0.05 <= dispatch.attrs["trace_s"] <= wall
    assert dispatch.attrs["compiles"] == 1


# -- the engine's entry points -----------------------------------------


def test_fifty_thousand_submits_leave_one_tally_a_kind():
    eng = world(queues=1, cohorts=1, nominal=10 ** 9)
    for i in range(3):
        eng.restore_workload(workload(f"r{i}", 1))
    for i in range(50_000):
        eng.submit(workload(f"w{i}", 1))
    eng.finish("default/w0")
    eng.finish("default/w1")
    eng.tick(0.0)
    assert eng.spans.last() is None
    eng.schedule_once()
    root = eng.spans.last()
    assert root.name == "schedule_once"
    intake = root.children[0]
    assert intake.name == "intake" and intake.attrs == {"seq": 0}
    assert trees.names(intake) == ["restore", "submit", "finish", "tick"]
    assert [t.attrs["calls"] for t in intake.children] == [3, 50_000, 2, 1]
    assert all(t.children == [] for t in intake.children)
    # /debug/trace's JSON holds it as the root's first child.
    served = root.to_dict()["children"][0]
    assert served["name"] == "intake" and served["attrs"] == {"seq": 0}
    assert [t["attrs"]["calls"] for t in served["children"]] == [
        3, 50_000, 2, 1]
    ph = eng.last_cycle_phases
    assert ph["n_intake_calls"] == 50_006
    assert ph["intake"] == pytest.approx(
        sum(ph["intake_" + kind] for kind in TALLY_KINDS))
    assert ph["intake_submit"] == pytest.approx(
        intake.children[1].dur * 1e-6)
    # The tally spans the engine's time on its calls, not the loop's
    # between them.
    assert 0 < ph["intake"] < intake.dur * 1e-6
    trees.assert_nested(root)
    trees.assert_adds_up(ph)


@pytest.mark.parametrize("cohorts", [1, 5], ids=["one_cohort",
                                                 "five_cohorts"])
def test_a_finish_counts_what_its_cohorts_requeue_moved(cohorts):
    eng = world(queues=10, cohorts=cohorts)
    for i in range(10):  # every queue full
        eng.submit(workload(f"run{i}", 1000, lq=f"lq{i}"))
    eng.schedule_once()
    for i in range(10):  # two a queue that fit nowhere, then park
        for n in range(2):
            eng.submit(workload(f"wait{i}-{n}", 600, lq=f"lq{i}"))
    while eng.schedule_once() is not None:
        pass
    mine = [f"cq{i}" for i in range(10) if i % cohorts == 0]
    parked = sum(len(eng.queues.cluster_queues[n].inadmissible)
                 for n in mine)
    assert parked == 2 * len(mine)
    eng.finish("default/run0")
    assert eng.schedule_once() is not None
    (finish,) = eng.spans.last().children[0].children
    assert finish.attrs == {"calls": 1, "requeued": parked,
                            "requeue_queues": len(mine)}
    ph = eng.last_cycle_phases
    assert (ph["n_requeued"], ph["n_requeue_queues"]) == (parked, len(mine))


def test_an_eviction_requeue_lands_on_the_cycle_and_a_tick_counts_the_running():
    eng = trees.make_engine(oracle=False, cohorts=2)
    trees.submit(eng, "other", 300, lq="lq1")
    trees.submit(eng, "low-a", 600)
    trees.submit(eng, "low-b", 600)
    trees.cycle(eng)          # low-a runs
    trees.cycle(eng)          # low-b parks: no fit
    trees.submit(eng, "high", 600, priority=10)
    r, root = trees.cycle(eng)  # high evicts low-a; the cohort requeues
    assert r.stats.preempting == 1
    (apply,) = [c for c in root.children if c.name == "apply"]
    assert apply.attrs == {"requeued": 1, "requeue_queues": 1}
    ph = eng.last_cycle_phases
    assert (ph["n_requeued"], ph["n_requeue_queues"]) == (1, 1)
    # The cycle's tick (trees.cycle's, as the benchmark's adapters do)
    # goes on the next cycle's intake: it looked at the one workload
    # left running, `other` (high waits for its victim's room).
    assert [w.name for w in eng.workloads.values()
            if w.is_admitted and not w.is_finished] == ["other"]
    trees.cycle(eng)
    (tick,) = [t for t in eng.spans.last().children[0].children
               if t.name == "tick"]
    assert tick.attrs == {"calls": 1, "tick_scanned": 1}
    assert eng.last_cycle_phases["n_tick_scanned"] == 1


def test_the_work_counts_run_on_as_metric_totals_idle_cycles_included():
    """scheduler_work_total, by kind, is the sum of every closed tree's
    window counts, an idle cycle's (one that decides nothing) too."""
    eng = world(queues=2, cohorts=1)
    for i in range(2):
        eng.submit(workload(f"run{i}", 1000, lq=f"lq{i}"))
    eng.submit(workload("wait", 600))
    sums = dict.fromkeys(WORK_KINDS, 0)
    idle = 0
    for step in range(5):
        if step == 2:
            eng.finish("default/run1")  # the cohort's requeue
        eng.tick(0.0)
        idle += eng.schedule_once() is None
        window = window_keys(eng.spans.last())
        for kind in WORK_KINDS:
            sums[kind] += window["n_" + kind]
    assert idle and sums["requeued"] > 0 and sums["tick_scanned"] > 0
    work = eng.registry.counter("scheduler_work_total")
    assert {k: work.get((k,)) for k in WORK_KINDS} == sums
    assert "kueue_tpu_scheduler_work_total{" in eng.registry.render()


# -- the phase dict ----------------------------------------------------


def test_every_key_there_was_keeps_its_value():
    """On the same events and the same clock, the tree less its intake
    gives every key that is not the window's the same value, and the
    leaves and unattributed add up to schedule_once."""
    eng = trees.make_engine(cohorts=2)
    eng.wall_clock = ticking()
    for i in range(4):
        trees.submit(eng, f"w{i}", 300 + 100 * i, priority=i,
                     lq=f"lq{i % 2}")
    trees.cycle(eng)
    eng.finish("default/w0")
    trees.submit(eng, "late", 500, priority=5)
    trees.cycle(eng)
    root, ph = eng.spans.last(), eng.last_cycle_phases
    assert root.children[0].name == "intake"
    bare = dataclasses.replace(root, children=root.children[1:])
    before = phase_seconds(bare)
    close_phases(before, bare)
    assert {k: v for k, v in ph.items() if k not in WINDOW_KEYS} == \
        {k: v for k, v in before.items() if k not in WINDOW_KEYS}
    assert WINDOW_KEYS <= set(ph)
    trees.assert_adds_up(ph)


def test_host_bound_and_the_launches_make_up_the_window():
    eng = trees.make_engine(cohorts=2)
    for i in range(6):
        trees.submit(eng, f"w{i}", 300, lq=f"lq{i % 2}")
    for k in range(3):
        if k:
            eng.finish(f"default/w{k}")
        _, root = trees.cycle(eng)
        ph = eng.last_cycle_phases
        assert ph["host_bound"] + ph["device_launched"] == pytest.approx(
            ph["intake"] + ph["schedule_once"])
        # One launch of the cycle program: its window opens in
        # `dispatch` and closes with `device_wait`.
        assert ph["device_wait"] <= ph["device_launched"] \
            <= ph["dispatch"] + ph["device_wait"]
        cyc = trees.child(root, "cycle")
        wait = trees.child(cyc, "device_wait")
        assert wait.attrs["launched_s"] == ph["device_launched"]
        assert eng.spans.last() is root and root.name == "schedule_once"
    # Between cycles the newest tree is still the last schedule_once's.
    eng.finish("default/w5")
    eng.tick(0.0)
    assert eng.spans.last() is root
    h = eng.registry.histogram("scheduler_phase_duration_seconds")
    assert ("intake",) in h.totals


# -- the benchmark's readers -------------------------------------------


def test_the_readers_read_the_window_and_nothing_on_a_parents_phases():
    spans = {"cycles": [
        {"phases": {"intake": 0.010, "n_requeued": 600,
                    "host_bound": 0.050, "schedule_once": 0.7}},
        {"phases": {"intake": 0.020, "n_requeued": 0,
                    "host_bound": 0.070, "schedule_once": 0.2}}]}
    got = {name: run.load_reader(name)(None, spans, {}) for name in READERS}
    assert got == pytest.approx({"intake_ms_per_cycle": 15.0,
                                 "requeued_per_cycle": 300.0,
                                 "host_bound_ms_per_cycle": 60.0})
    parent = {"cycles": [{"phases": {"schedule_once": 0.7, "apply": 0.1}},
                         {"phases": {}}]}
    for name in READERS:
        assert run.load_reader(name)(None, parent, {}) is None, name
    listed = {m["name"]: m for m in run.read_json(
        os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]}
    for name in READERS:
        assert listed[name]["moves"] == "cycle_mean_ms"
        assert len(listed[name]["workloads"]) == 4


def test_the_served_loop_gives_the_readers_something_to_read():
    cell = run.load_cell("baseline-1x1000-noreclaim.trickle-turnover",
                         tiny=True)
    kind = cell["modules"]
    built = kind["world_builder"].build_world(cell["world"], 3_900_000_123)
    # A process that ran these programs before holds them compiled: the
    # first cycle compiles (or reads back) only what it finds uncached.
    jax.clear_caches()
    program = kind["adapter"].Program(built, "local")
    loop = run.Loop(program, built, cell["mix"])
    try:
        cycles = [loop.step() for _ in range(6)]
    finally:
        program.close()
    first = cycles[0]["phases"]
    assert first["intake_restore"] > 0 and first["intake_submit"] > 0
    assert first["n_compiles"] > 0
    spans = {"cycles": cycles[1:]}
    for name in READERS:
        assert run.load_reader(name)(None, spans, {}) is not None, name
    assert run.load_reader("intake_ms_per_cycle")(None, spans, {}) > 0
    # The engine's own finish and submit are inside the harness's clock
    # around them.
    for c in cycles[1:]:
        ph = c["phases"]
        assert ph["intake_finish"] + ph["intake_submit"] \
            <= c["finish_s"] + c["submit_s"]
