"""The selective-workloads cell (benchmark/worlds/
selective-3f2r-1000cq.json, BENCHMARK.json's fourth configuration) under
the harness, on the CPU at its world file's `tiny` sizes — kept here,
out of benchmark/tests/, so that the tier-1 run counts it: the cell
resolves to its own four modules by files alone and runs `correct` with
its minimums counted; BENCHMARK.json holds the configuration, the cell
and both new metrics with their readers; the builder makes the running
set its file states, deals the classes in their shares and puts no
running workload on a flavor its profile excludes; the seed only
relabels; the reference with every mask all-true, a program handed a
world without the selectors and the float32 control each read `correct:
false`; the adapter refuses a program without the count and a cycle
served from the host for a flavor or a mask; the added invariant catches
an admission on an excluded flavor; each of the cell's two new readers
reads its count, and nothing on a parent's phases.
"""

from __future__ import annotations

import copy
import io
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import control  # noqa: E402
import invariants_selective  # noqa: E402
import plain_selective  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import sut_selective  # noqa: E402
import trafficgen  # noqa: E402
import worldgen_selective  # noqa: E402

CONFIG = "selective-3f2r-1000cq"
CELL = CONFIG + ".trickle-turnover"
SECOND = "fungible-3f2r-1000cq.trickle-turnover"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CYCLES = 14  # timed cycles of a test run, after the mix's 4 of warm-up
# What the world file adds to what a run has to have compared, at full
# size and in its `tiny` overlay.
MINIMUMS = ["admissions_on_a_later_flavor",
            "admissions_of_a_narrowed_head",
            "admissions_past_an_excluded_flavor",
            "evictions_for_a_narrowed_head"]
NEW_READERS = {"mask_narrowed_heads_per_cycle": "n_mask_narrowed_heads",
               "masked_flavor_cells_per_cycle": "n_masked_flavor_cells"}
PROFILES = ["unconstrained", "spot-tolerant", "reserved-only",
            "on-demand-only", "spot-only"]
ELIGIBLE = [["reserved", "on-demand"], ["reserved", "on-demand", "spot"],
            ["reserved"], ["on-demand"], ["spot"]]


def the_cell(tiny: bool = True) -> dict:
    return run.load_cell(CELL, tiny)


def run_tiny(**kwargs) -> dict:
    return run.run_cell(the_cell(), 5, 600.0, False, CPU, rehearsal=True,
                        max_cycles=CYCLES, out=io.StringIO(), **kwargs)


@pytest.mark.parametrize("tiny", [False, True], ids=["full", "tiny"])
def test_the_cell_resolves_to_its_own_modules_by_files_alone(tiny):
    cell = the_cell(tiny)
    minimums, least = MINIMUMS, 1
    assert {role: os.path.relpath(m.__file__, BENCH)
            for role, m in cell["modules"].items()} == {
        "world_builder": "worldgen_selective.py",
        "adapter": "sut_selective.py", "reference": "plain_selective.py",
        "invariants": "invariants_selective.py"}
    assert list(cell["at_least"])[2:] == minimums
    for name in minimums:
        floor, count = cell["at_least"][name]
        assert floor >= least
        assert count is getattr(plain_selective, "count_" + name)


def test_the_world_file_states_its_deployment():
    cfg = run.read_config(CONFIG)
    second = run.read_config("fungible-3f2r-1000cq")
    # The second cell's resource group, quotas, sizes and stanza.
    assert [(f["name"], f["nominal"], f["borrowing_limit"])
            for f in cfg["flavors"]] == [
        (f["name"], f["nominal"], f["borrowing_limit"])
        for f in second["flavors"]]
    for key in ("resources", "preemption", "flavor_fungibility", "pending",
                "cluster_queues", "cohorts", "epoch_seconds"):
        assert cfg[key] == second[key], key
    assert cfg["sizes"] == second["classes"]
    assert cfg["running_per_cluster_queue"] == 16
    assert [(f["node_labels"], f["node_taints"]) for f in cfg["flavors"]] \
        == [({"instance-type": "reserved"}, []),
            ({"instance-type": "on-demand"}, []),
            ({"instance-type": "spot"}, [{"key": "spot", "value": "true",
                                          "effect": "NoSchedule"}])]
    # The classes, sizes outermost: who tolerates the taint (0.5 of
    # small, 0.25 of medium, of large the tenth that asks for spot) and
    # who is pinned (a tenth each to reserved, on-demand and spot).
    assert [p["name"] for p in cfg["profiles"]] == PROFILES
    assert [p["eligible"] for p in cfg["profiles"]] == ELIGIBLE
    counts = {(c["size"], c["profile"]): c["count"] for c in cfg["classes"]}
    assert [[counts.get((s["name"], p), 0) for p in PROFILES]
            for s in cfg["sizes"]] == [[105, 140, 35, 35, 35],
                                       [55, 15, 10, 10, 10],
                                       [35, 0, 5, 5, 5]]
    tolerant = {p["name"] for p in cfg["profiles"] if p["tolerations"]}
    for size, share in (("small", 0.5), ("medium", 0.25), ("large", 0.1)):
        total = sum(n for (s, _p), n in counts.items() if s == size)
        assert sum(n for (s, p), n in counts.items()
                   if s == size and p in tolerant) == share * total
    worldgen_selective.check_classes(cfg)
    for key in ("source", "as_published", "reduced", "reduced_why",
                "assumed", "guarantees", "modules", "compared_at_least",
                "device_bytes_reckoned", "tiny", "scenario"):
        assert cfg.get(key), key
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    assert set(cfg["compared_at_least"]) == set(MINIMUMS) \
        == set(cfg["tiny"]["compared_at_least"])


def test_benchmark_json_holds_the_config_the_cell_and_both_metrics():
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = run.read_config(CONFIG)
    entry = bench["configs"][-1]
    assert entry["name"] == CONFIG
    assert (entry["source"], entry["reduced"]) == (cfg["source"],
                                                   cfg["reduced"])
    assert os.path.isfile(os.path.join(ROOT, entry["file"]))
    assert entry["file"] == f"benchmark/worlds/{CONFIG}.json"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, "trickle-turnover", 1)
    # Every line of prose in the file is at most 200 characters.
    for section in ("configs", "workloads"):
        for e in bench[section]:
            for key in ("source", "why"):
                assert 1 <= len(e.get(key, "x")) <= 200, (e["name"], key)
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in mine] == list(NEW_READERS)
    for m in mine:
        assert m["workloads"] == [CELL]
        assert (m["unit"], m["moves"], m["source"], m["layer"]) == (
            "count", "cycle_mean_ms", "program_span", "sim nomination")
    # What the cell shares with the second cell, but for the
    # speculation that is gone (PR 26's other ten and the sim
    # nomination's nine), and the commit's and the preemptor's counts.
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == ({m["name"] for m in bench["per_layer"]
                       if SECOND in m.get("workloads", ())}
                      - {"unused_speculation_ms"}
                      | {"commit_victim_entries_per_cycle",
                         "preemptor_slots_per_lattice_launch"}
                      | set(NEW_READERS))
    for name in listed:
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", name + ".py")), name
    # Appended: every list the cell joins ends with it.
    assert all(m["workloads"][-1] == CELL for m in bench["per_layer"]
               if CELL in m.get("workloads", ()))


def test_the_cell_runs_correct_with_its_minimums_counted():
    r = run_tiny()
    c = r["compared"]
    assert r["correct"] is True, c
    assert c["cycles_compared"]["value"] == CYCLES + 4
    for name in MINIMUMS:
        assert c[name]["value"] >= c[name]["limit_min"] >= 1, name
    assert c["declined_by_bridge"]["value"] == 0
    assert c["cycle_program_signatures"]["value"] == 1
    assert c["guarantees_broken"]["value"] == 0
    assert sorted(list(c)[-len(MINIMUMS):]) == sorted(MINIMUMS)


@pytest.mark.parametrize("tiny", [True, False], ids=["tiny", "full"])
def test_the_builder_makes_the_running_set_its_file_states(tiny):
    cfg = run.read_config(CONFIG, tiny=tiny)
    world = worldgen_selective.build_world(cfg, seed=1)
    classes = cfg["classes"]
    allowed = worldgen_selective.allowed_flavors(cfg)
    assert [allowed[p] for p in PROFILES] == [[0, 1], [0, 1, 2], [0], [1],
                                              [2]]
    # No running workload on a flavor its profile excludes; on every
    # flavor the classes of a size that may sit there, in the ratio of
    # their counts.
    on: dict = {}
    for (_n, _ci, k, _at), f in zip(world["running"], world["running_on"]):
        assert f in allowed[classes[k]["profile"]], (classes[k]["name"], f)
        on.setdefault((f, classes[k]["size"]), {}).setdefault(k, 0)
        on[(f, classes[k]["size"])][k] += 1
    assert {f for f, _size in on} == {0, 1, 2}
    for (f, size), got in on.items():
        among = [k for k, c in enumerate(classes) if c["size"] == size
                 and f in allowed[c["profile"]]]
        assert sorted(got) == among
        unit = sum(got.values()) / sum(classes[k]["count"] for k in among)
        for k in among:
            assert abs(got[k] - unit * classes[k]["count"]) <= 24, (
                f, classes[k]["name"])
    # The waiting: a size's classes in the ratio of their counts.
    waiting = [0] * len(classes)
    for _n, _ci, k, _at in world["pending"]:
        waiting[k] += 1
    for size in cfg["sizes"]:
        total = cfg["pending"][size["name"]]
        mine = [k for k, c in enumerate(classes)
                if c["size"] == size["name"]]
        assert sum(waiting[k] for k in mine) == total
        assert all(abs(waiting[k] - total * classes[k]["count"]
                       / size["count"]) < 20 for k in mine)
    if tiny:
        return
    by_size = dict.fromkeys((s["name"] for s in cfg["sizes"]), 0)
    for _n, _ci, k, _at in world["running"]:
        by_size[classes[k]["size"]] += 1
    assert by_size == cfg["running_reckoned"]
    reckoned = worldgen_selective.device_bytes(cfg)
    stated = cfg["device_bytes_reckoned"]
    assert (reckoned["per_cohort_pad"], reckoned["a_pad"], reckoned["w_pad"],
            reckoned["sim_block"]) == (
        stated["per_cohort_pad"], stated["running_pad"],
        stated["pending_pad"], stated["sim_block"])
    assert (reckoned["cycle_temp_bytes"], reckoned["sim_temp_bytes"]) == (
        stated["cycle_program_temp"], stated["sim_program_temp"])
    assert reckoned["sum"] == stated["sum"] == max(
        stated["cycle_program_temp"], stated["sim_program_temp"]) \
        + stated["code"] + stated["outputs"]
    # Over a power of two in neither axis: the live lattice, not its pad.
    cohort = max(sum(1 for _n, ci, _k, _a in world["running"]
                     if ci % cfg["cohorts"] == co)
                 for co in range(cfg["cohorts"]))
    assert 0.75 * stated["per_cohort_pad"] < cohort \
        <= stated["per_cohort_pad"]
    assert 0.9 * stated["running_pad"] < len(world["running"]) \
        <= stated["running_pad"]


def test_the_seed_only_relabels():
    cfg = run.read_config(CONFIG, tiny=True)
    a = worldgen_selective.build_world(cfg, seed=1)
    b = worldgen_selective.build_world(cfg, seed=2 ** 31 + 5)
    assert a["place"] != b["place"]
    back_a = {ci: q for q, ci in enumerate(a["place"])}
    back_b = {ci: q for q, ci in enumerate(b["place"])}
    for key in ("running", "pending"):
        assert [(back_a[ci], k, at) for _n, ci, k, at in a[key]] \
            == [(back_b[ci], k, at) for _n, ci, k, at in b[key]]
    assert a["running_on"] == b["running_on"]


def drive(program, world: dict, cycles: int) -> tuple:
    mix = trafficgen.read_mix("trickle-turnover", tiny=True)
    gen = trafficgen.Generator(mix, world)
    sets = trafficgen.RunningSets(
        [cq["name"] for cq in world["cluster_queues"]], world["running"])
    events, verdicts = [], []
    for k in range(cycles):
        finishes, arrivals, now = gen.events(k, sets)
        for name in finishes:
            sets.remove(name)
            program.finish(name)
        for arrival in arrivals:
            program.submit(*arrival)
        v = program.cycle(now)
        sets.apply(v)
        events.append((finishes, arrivals, now))
        verdicts.append(v)
    return events, verdicts


def test_the_sequential_core_agrees_with_the_reference():
    """The second witness: the program with `oracle="off"` on the same
    events decides what plain_selective.py decides."""
    world = worldgen_selective.build_world(
        run.read_config(CONFIG, tiny=True), seed=4)
    core = sut_selective.Program(world, "off")
    events, got = drive(core, world, 12)
    ref = plain_selective.Plain(world)
    want = reference.replay(ref, events)
    cohort_of = {cq["name"]: cq["cohort"] for cq in world["cluster_queues"]}
    assert reference.differing(got, want, cohort_of) == []
    assert core.state() == ref.state()
    assert sum(len(vs) for v in want for _h, vs in v["preempting"]) > 0
    assert invariants_selective.check(world, events, got) == []


# -- faults planted under the harness -------------------------------


def without_the_selectors(world: dict) -> dict:
    wrong = copy.deepcopy(world)
    for p in wrong["profiles"]:
        p["node_selector"] = {}
    return wrong


def test_a_program_that_sees_no_selector_reads_not_correct():
    """(The program's own tests hold the taint alone,
    tests/test_selective_flavors_device.py.)"""
    r = run_tiny(make_program=lambda world: sut_selective.Program(
        without_the_selectors(world), "local"))
    assert r["correct"] is False
    assert r["compared"]["cycles_differing"]["value"] \
        + r["compared"]["guarantees_broken"]["value"] > 0


def test_the_reference_with_every_mask_all_true_parts_from_the_cell():
    """What a program that dropped the masks would decide: held against
    the program, it has to differ, in cycles and in the end state."""
    r = run_tiny(make_reference=plain_selective.every_flavor)
    assert r["correct"] is False
    assert r["compared"]["cycles_differing"]["value"] > 0
    assert r["compared"]["end_state_differs"]["value"] == 1


def test_the_control_reads_the_cell_as_not_correct():
    """control.py's control (it takes a cell of BENCHMARK.json by name):
    the kind's reference with every time in float32."""
    r = run_tiny(make_reference=lambda world: plain_selective.Plain(
        world, stamp=control.float32))
    assert r["correct"] is False
    assert r["compared"]["cycles_differing"]["value"] > 0


def test_the_adapter_refuses_a_program_that_counts_no_narrowed_head(
        monkeypatch):
    from kueue_tpu.obs import span

    monkeypatch.setattr(span, "COUNT_KEYS",
                        span.COUNT_KEYS - {"n_mask_narrowed_heads"})
    world = worldgen_selective.build_world(
        run.read_config(CONFIG, tiny=True), seed=1)
    with pytest.raises(SystemExit) as refused:
        sut_selective.Program(world, "local")
    assert "n_mask_narrowed_heads" in str(refused.value)


@pytest.mark.parametrize("reason", sut_selective.HOST_ROOTS + ("world",))
def test_a_cycle_served_from_the_host_for_a_flavor_raises(reason):
    world = worldgen_selective.build_world(
        run.read_config(CONFIG, tiny=True), seed=1)
    program = sut_selective.Program(world, "local")
    oracle = program.eng.oracle
    (oracle.fallback_reasons if reason == "world"
     else oracle.host_root_reasons)[reason] = 1
    with pytest.raises(RuntimeError, match=reason):
        program.cycle(world["clock0"] + 1.0)
    program.close()


# -- the added invariant ----------------------------------------------


def test_the_invariants_catch_an_admission_on_an_excluded_flavor():
    cfg = run.read_config(CONFIG, tiny=True)
    world = worldgen_selective.build_world(cfg, seed=2)
    classes = world["classes"]
    name, ci, k, _at = next(
        p for p in world["pending"]
        if classes[p[2]]["name"] == "small.unconstrained")
    req = classes[k]["request"]
    cq = world["cluster_queues"][ci]["name"]

    def admitted_on(flavor):
        return [{"idle": False, "preempting": [], "admitted": [(
            name, cq, (("cpu", flavor), ("memory", flavor)),
            (("cpu", flavor, req["cpu"]),
             ("memory", flavor, req["memory"])))]}]

    events = [([], [], 0.0)]
    bad = invariants_selective.check(world, events, admitted_on("spot"))
    assert any("does not match" in b for b in bad), bad
    ok = invariants_selective.check(world, events, admitted_on("on-demand"))
    assert not any("does not match" in b for b in ok), ok
    # And a world whose running set sits where it may not.
    moved = dict(world, running_on=[2] * len(world["running_on"]))
    assert any(b.startswith("the world:")
               for b in invariants_selective.check(moved, [], []))


# -- the cell's two new per-layer metrics -----------------------------


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_reads_its_count_and_nothing_on_a_parents_phases(name):
    reader = run.load_reader(name)
    key = NEW_READERS[name]
    spans = {"cycles": [{"phases": {key: 6, "n_sim_rows": 1}},
                        {"phases": {key: 0}}, {"phases": {key: 3}}]}
    assert reader(None, spans, {}) == 3.0
    parent = {"cycles": [{"phases": {"n_sim_rows": 4}}, {"phases": {}}]}
    assert reader(None, parent, {}) is None


def test_the_served_path_counts_what_the_readers_read():
    world = worldgen_selective.build_world(
        run.read_config(CONFIG, tiny=True), seed=3)
    program = sut_selective.Program(world, "local")
    cycles = []
    for k in range(3):
        program.cycle(world["clock0"] + 1.0 + k)
        cycles.append({"phases": program.phases()})
    program.close()
    for name in NEW_READERS:
        assert run.load_reader(name)(None, {"cycles": cycles}, {}) > 0, name
