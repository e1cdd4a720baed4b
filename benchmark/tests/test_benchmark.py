"""The benchmark checks itself, on the CPU at the files' tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Stamped cpu: it measures nothing. What it holds: a cell runs end to end
and its last line parses with `correct` true; the plain reference
decides small cases as worked out by hand, and as the program's own
sequential core does under three stanzas; the control (the reference in
float32 time) and each fault a cell can have come out as `correct`
false; the bucket-stays-put assertion fires; every seed runs one
scenario under other labels; the trace reduction gives the numbers
worked out by hand from a small recorded chip trace. What needs no
engine is in test_contract.py, and the seam between the harness and a
kind of deployment's own modules in test_seam.py.
"""

from __future__ import annotations

import io
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import control  # noqa: E402
import plain  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut  # noqa: E402
import trace_reduce  # noqa: E402
import trafficgen as traffic  # noqa: E402
import worldgen  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELLS = [c["name"] for c in run.read_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]
CYCLES = 14  # timed cycles of a test run, after the mix's warm-up


def run_tiny(cell_name, seed=5, world=None, **kwargs):
    cell = run.load_cell(cell_name, tiny=True)
    if world:
        cell["world"].update(world)
    out = io.StringIO()
    result = run.run_cell(cell, seed, 600.0, False, CPU, rehearsal=True,
                          max_cycles=CYCLES, out=out, **kwargs)
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1]) == result
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end(cell):
    r = run_tiny(cell)
    assert set(r) >= {"correct", "attempted", "failed", "metrics",
                      "device", "compared"}
    assert list(r)[-1] == "compared"
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] == CYCLES and r["failed"] == 0
    c = r["compared"]
    assert c["cycles_compared"]["value"] == CYCLES + 4
    assert c["admissions_compared"]["value"] > 0
    assert c["evictions_compared"]["value"] > 0
    assert c["end_state_differs"]["value"] == 0
    assert r["metrics"] == {}  # a CPU run prints no metric


def test_control_float32_clock_is_not_correct():
    """The control: the plain reference computed one precision below
    the configuration's float64 clock. At Unix time float32 cannot tell
    two workloads apart, FIFO falls to the tie-break, and verdicts
    differ."""
    r = run_tiny(CELLS[0], make_reference=lambda w: plain.Plain(
        w, stamp=control.float32))
    assert r["correct"] is False
    assert r["compared"]["cycles_differing"]["value"] > 0


def little_world(running, pending, within="LOWER_PRIORITY",
                 reclaim="NEVER", nominal=(20, 20), limit=100):
    """Two ClusterQueues in one cohort; classes small / medium / large
    at 1 / 5 / 20 units and priorities 50 / 100 / 200."""
    return {
        "cohorts": ["co"],
        "cluster_queues": [
            {"name": f"cq-{i}", "cohort": "co", "nominal_milli": n * 1000,
             "borrowing_limit_milli": limit * 1000}
            for i, n in enumerate(nominal)],
        "classes": [
            {"name": "small", "request_milli": 1000, "priority": 50},
            {"name": "medium", "request_milli": 5000, "priority": 100},
            {"name": "large", "request_milli": 20000, "priority": 200}],
        "preemption": {"within_cluster_queue": within,
                       "reclaim_within_cohort": reclaim},
        "running": running, "pending": pending}


def test_plain_reference_by_hand_within_queue():
    """cq-0 holds 20 smalls (its whole quota), cq-1 a large: the cohort
    is full. A medium waits in cq-0: it takes the five smalls admitted
    last, and is admitted one cycle later; the small behind it waits."""
    running = [(f"s{i:02d}", 0, 0, 100.0 + i) for i in range(20)]
    running.append(("big", 1, 2, 50.0))
    ref = plain.Plain(little_world(
        running, [("low", 0, 0, 1.0), ("mid", 0, 1, 2.0)]))
    v = ref.cycle(1000.0)
    assert v["admitted"] == []
    assert v["preempting"] == [("mid", ["s15", "s16", "s17", "s18",
                                        "s19"])]
    v = ref.cycle(1001.0)
    assert v["admitted"] == [("mid", "cq-0", "default", 5000)]
    assert v["preempting"] == []
    v = ref.cycle(1002.0)   # the victims and `low` wait: no room, and
    assert v == {"idle": False, "admitted": [], "preempting": []}
    assert ref.cycle(1003.0)["idle"]    # ... parked with their shape
    ref.finish("big")                   # room: they come back, oldest
    v = ref.cycle(1004.0)               # first (`low`, created at 1.0)
    assert v["admitted"] == [("low", "cq-0", "default", 1000)]


def test_plain_reference_by_hand_reclaim_and_give_back():
    """cq-1 borrows (30 of its 20: a medium at 10.0, then 25 smalls);
    cq-0 holds nothing. Under reclaimWithinCohort Any a large waiting
    in cq-0 reclaims from the borrower, lowest priority first and the
    latest admitted first — the smalls, from the back — until it fits
    (40 - 30 = 10 free, so ten more units), and the medium stays. Under
    Never it finds nobody and is parked."""
    running = [("m", 1, 1, 10.0)] + [
        (f"s{i:02d}", 1, 0, 20.0 + i) for i in range(25)]
    pending = [("big", 0, 2, 1.0)]
    ref = plain.Plain(little_world(running, pending, reclaim="ANY"))
    v = ref.cycle(1000.0)
    assert v["preempting"] == [("big", [f"s{i}" for i in range(15, 25)])]
    assert ref.cycle(1001.0)["admitted"] == [
        ("big", "cq-0", "default", 20000)]
    ref = plain.Plain(little_world(running, pending, reclaim="NEVER"))
    assert ref.cycle(1000.0) == {"idle": False, "admitted": [],
                                 "preempting": []}
    assert ref.cycle(1001.0)["idle"]


@pytest.mark.parametrize("reclaim,limit", [
    ("NEVER", 100_000), ("ANY", 100_000), ("LOWER_PRIORITY", 20_000)])
def test_plain_reference_decides_as_the_sequential_core(reclaim, limit):
    """A second witness: the program's own sequential core
    (sut.Program(world, "off")), driven through the loop, and the plain
    reference on the events it was sent — equal in every cycle and at
    the end, under the cell's stanza and under two that reclaim across
    ClusterQueues."""
    cell = run.load_cell(CELLS[0], tiny=True)
    cell["world"]["preemption"]["reclaim_within_cohort"] = reclaim
    cell["world"]["borrowing_limit_milli"] = limit
    cell["world"]["cohorts"] = 2
    cell["mix"]["turnover_share"] = 0.1
    world = worldgen.build_world(cell["world"], 7)
    core = sut.Program(world, "off")
    loop = run.Loop(core, world, cell["mix"])
    for _ in range(60):
        loop.step()
    got = reference.compare(world, loop.events, loop.verdicts,
                            plain.Plain, core.state())
    assert got["cycles_compared"] == 60
    assert got["cycles_differing"] == 0 and got["end_state_differs"] == 0
    assert got["admissions_compared"] > 20
    assert got["evictions_compared"] > 20


class FlippedVerdict(sut.Program):
    """One answer altered where it is produced: from the third cycle
    on, the first admission there is is reported for another workload."""

    n, done = 0, False

    def cycle(self, now):
        v = super().cycle(now)
        self.n += 1
        if self.n >= 3 and v["admitted"] and not self.done:
            self.done = True
            first = v["admitted"][0]
            v["admitted"][0] = ("someone-else",) + tuple(first[1:])
        return v


class FlippedVictim(sut.Program):
    """One answer altered where it is produced: the first eviction there
    is from the third cycle on is reported with one victim missing."""

    n, done = 0, False

    def cycle(self, now):
        v = super().cycle(now)
        self.n += 1
        if self.n >= 3 and v["preempting"] and not self.done:
            self.done = True
            head, victims = v["preempting"][0]
            v["preempting"][0] = (head, victims[1:])
        return v


class StateUnchanged(sut.Program):
    """A step that returns its state unchanged: the fifth cycle is not
    run at all."""

    n = 0

    def cycle(self, now):
        self.n += 1
        if self.n == 5:
            return {"idle": True, "admitted": [], "preempting": []}
        return super().cycle(now)


class HalfLeftOut(sut.Program):
    """Half of the batch left out: arrivals to the odd ClusterQueues
    never reach the engine."""

    def submit(self, name, ci, k, created):
        if ci % 2 == 0:
            super().submit(name, ci, k, created)


@pytest.mark.parametrize("broken", [FlippedVerdict, FlippedVictim,
                                    StateUnchanged, HalfLeftOut])
def test_broken_timed_path_is_not_correct(broken):
    r = run_tiny(CELLS[0], make_program=lambda w: broken(w, "local"))
    assert r["correct"] is False, r["compared"]
    c = r["compared"]
    assert c["cycles_differing"]["value"] + c["end_state_differs"]["value"]


def test_second_cycle_program_fails_the_run():
    """The bucket-stays-put assertion: a world whose running set starts
    just above a power of two loses it to evictions within the window,
    a second cycle program is launched, and the run says so."""
    r = run_tiny(CELLS[0], world={
        "running": {"small": 205, "medium": 41, "large": 14}})
    c = r["compared"]
    assert c["cycle_program_signatures"]["value"] > 1
    assert c["cycles_differing"]["value"] == 0  # decided right, though
    assert r["correct"] is False


def test_every_seed_runs_one_scenario_under_other_labels():
    cfg = run.read_config(CELLS[0].split(".")[0], tiny=True)

    def census(seed):
        w = worldgen.build_world(cfg, seed)
        times = sorted((kind, k, at) for kind in ("running", "pending")
                       for _name, _ci, k, at in w[kind])
        return times, [x[1] for x in w["running"]], w["running"][0][0]

    a, where_a, name_a = census(1)
    b, where_b, name_b = census(3_000_000_019)
    assert a == b and where_a != where_b and name_a != name_b
    mix = traffic.read_mix(CELLS[0].split(".")[1], tiny=True)

    def shape(seed):
        world = worldgen.build_world(cfg, seed)
        ref = plain.Plain(world)
        sets = traffic.RunningSets(
            [cq["name"] for cq in world["cluster_queues"]],
            world["running"])
        gen = traffic.Generator(mix, world)
        out = []
        for k in range(30):
            finishes, arrivals, now = gen.events(k, sets)
            for name in finishes:
                sets.remove(name)
                ref.finish(name)
            for arrival in arrivals:
                ref.submit(*arrival)
            v = ref.cycle(now)
            sets.apply(v)
            out.append((len(v["admitted"]),
                        [len(vs) for _h, vs in v["preempting"]]))
        return out

    assert shape(1) == shape(3_000_000_019)
    assert sum(n for n, _p in shape(1)) > 0


def test_running_sets_follow_the_verdicts():
    sets = traffic.RunningSets(["cq-0", "cq-1"],
                               [("a", 0, 0, 0.0), ("b", 0, 0, 0.0),
                                ("c", 1, 0, 0.0)])
    sets.apply({"admitted": [("d", "cq-1", "default", 1000)],
                "preempting": [("d", ["c"])]})
    assert sets.sets[1] == ["d"] and sets.count() == 3
    sets.remove("a")
    assert sets.sets[0] == ["b"] and sets.draw(0, 0.99) == "b"
    assert sets.draw(1, 0.0) == "d"


def test_trace_reduction_on_the_recorded_trace():
    """fixtures/trace_small.json: two cycles of a chip trace (its
    `origin` says which). By hand: three launches of the cycle program,
    17.056037 ms of device time between them; the operations' union is
    17.054155 ms busy; the window runs from the first harness span to
    the last."""
    events = run.read_json(os.path.join(BENCH, "fixtures",
                                        "trace_small.json"))
    r = trace_reduce.reduce_events(events)
    assert r["chips"] == 1
    assert r["busy_s"] == pytest.approx(0.017054155, abs=1e-8)
    assert r["window_s"] == pytest.approx(0.231092999, abs=1e-8)
    (name,) = [k for k in r["module_s"] if "_cycle_core" in k]
    assert r["module_n"][name] == 3
    assert r["module_s"][name] == pytest.approx(0.017056037, abs=1e-8)
    assert 0.017056037 / 3 <= r["module_max_s"][name] < 0.017056037
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert set(gaps) == {"bench.schedule_once", "bench.submit"}
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-8)
    assert len(r["breakdown"]["device_ops"]) == 10
    # The readers, on the same trace.
    counters = {"buckets": {"w_pad": 65536},
                "cfg": {"cluster_queues": 1000},
                "device_kind": "TPU v5 lite"}
    assert run.load_reader("cycle_program_ms")(r, {}, counters) == \
        pytest.approx(17.056037 / 3, abs=1e-6)
    assert run.load_reader("preemptor_launch_ms")(r, {}, counters) == \
        pytest.approx(r["module_max_s"][name] * 1e3)
    roof = run.load_reader("heads_kernel_roofline")(r, {}, counters)
    assert 0.0 < roof < 5.0
    idle = run.load_reader("device_idle_pct")(r, {}, counters)
    assert idle == pytest.approx(100 * (1 - 0.017054155 / 0.231092999),
                                 abs=1e-6)


def test_reader_with_nothing_to_read_returns_nothing():
    empty = {"module_s": {}, "module_n": {}, "module_max_s": {},
             "op_s": {}, "op_n": {}, "busy_s": 0.0, "window_s": 0.0}
    for name in ("cycle_program_ms", "preemptor_launch_ms",
                 "heads_kernel_roofline", "device_idle_pct"):
        assert run.load_reader(name)(empty, {}, {}) is None
        assert run.load_reader(name)(None, {}, {}) is None


def test_every_reader_loads_and_reads_host_spans():
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cycle = {"finish_s": 0.01, "submit_s": 0.02, "schedule_s": 0.1,
             "executor_calls_s": [0.015, 0.045],
             "phases": {"encode": 0.05, "device": 0.004, "apply": 0.01,
                        "finalize": 0.02}}
    spans = {"cycles": [cycle, cycle]}
    got = {m["name"]: run.load_reader(m["name"])(None, spans, {})
           for m in bench["per_layer"]}
    assert got["submit_ms_per_cycle"] == pytest.approx(30.0)
    assert got["executor_call_ms"] == pytest.approx(30.0)
    assert got["encode_ms"] == pytest.approx(50.0 - 15.0)
    assert got["verdict_decode_ms"] == pytest.approx(4.0)
    assert got["unused_speculation_ms"] == pytest.approx(100.0 - 84.0)
    assert got["apply_ms"] == pytest.approx(10.0)
    assert got["finalize_ms"] == pytest.approx(20.0)
    assert got["cycle_program_ms"] is None
    assert got["preemptor_launch_ms"] is None


def test_unknown_device_kind_is_an_error():
    import rooflines

    with pytest.raises(SystemExit):
        rooflines.peaks_for("TPU v9 imaginary")
