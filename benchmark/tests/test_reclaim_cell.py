"""The returning-cohort kind of deployment (worlds/
reclaim-any-1x1000-returning.json) under the harness, on the CPU at its
world file's `tiny` sizes: the cell resolves to its own four modules by
files alone and runs `correct` with its minimum of evictions from another
queue counted; the builder deals nothing onto a returning queue, and the
seed only relabels; a planted fault (victims restricted to the head's
own queue) reads `correct: false`; the control reads not correct; the
adapter refuses a program without the preemptor's walk, and a cycle
served from the host for `preemption-overflow`; the added invariants
catch a victim of a queue within its quota; the three readers read their
counts and nothing where the program has none.
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import control  # noqa: E402
import invariants_reclaim  # noqa: E402
import plain_reclaim  # noqa: E402
import run  # noqa: E402
import sut_reclaim  # noqa: E402
import worldgen  # noqa: E402
import worldgen_reclaim  # noqa: E402
from test_benchmark import CYCLES, run_tiny  # noqa: E402

CONFIG = "reclaim-any-1x1000-returning"
CELL = CONFIG + ".trickle-turnover"
FIRST = "baseline-1x1000-noreclaim.trickle-turnover"
NEW_METRICS = ["reclaim_victims_per_cycle",
               "preempt_candidates_skipped_per_cycle",
               "preemptor_slots_per_lattice_launch"]


def test_the_cell_resolves_to_its_own_modules_by_files_alone():
    cell = run.load_cell(CELL, tiny=True)
    assert {role: os.path.relpath(m.__file__, BENCH)
            for role, m in cell["modules"].items()} == {
        "world_builder": "worldgen_reclaim.py",
        "adapter": "sut_reclaim.py", "reference": "plain_reclaim.py",
        "invariants": "invariants_reclaim.py"}
    assert cell["at_least"]["evictions_from_another_queue"] == (
        8, plain_reclaim.count_evictions_from_another_queue)
    assert cell["chips"] == 1 and cell["traffic"] == "trickle-turnover"
    # PR 26's metrics of the layers it shares with the flat kind's cell
    # (not the speculation's, which is gone), PR 33's count of the
    # commit's victim entries, then this PR's three. (PR 27's seven are
    # held to the first cell alone, letter for letter, by
    # test_span_readers.py, which this PR may not edit.)
    assert [m["name"] for m in cell["per_layer"]] == [
        "submit_ms_per_cycle", "encode_ms", "executor_call_ms",
        "cycle_program_ms", "preemptor_launch_ms", "verdict_decode_ms",
        "apply_ms", "finalize_ms", "heads_kernel_roofline",
        "device_idle_pct", "commit_victim_entries_per_cycle"] + NEW_METRICS
    first = [m["name"] for m in
             run.load_cell(FIRST, tiny=True)["per_layer"]]
    assert first[-1] == "preemptor_slots_per_lattice_launch"
    assert not set(NEW_METRICS[:2]) & set(first)


def test_the_world_file_states_the_stanza_as_published():
    cfg = run.read_config(CONFIG)
    first = run.read_config("baseline-1x1000-noreclaim")
    assert cfg["preemption"] == {"within_cluster_queue": "LOWER_PRIORITY",
                                 "reclaim_within_cohort": "ANY"}
    assert cfg["as_published"]["preemption"]["reclaimWithinCohort"] == "Any"
    assert "reclaimWithinCohort" not in cfg["reduced"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    for key in ("cluster_queues", "cohorts", "nominal_milli",
                "borrowing_limit_milli", "classes", "running", "pending",
                "scenario", "epoch_seconds"):
        assert cfg[key] == first[key], key
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"]
    assert worldgen_reclaim.device_bytes(cfg)["temp_bytes"] \
        == cfg["device_bytes_reckoned"]["temp"]


def test_the_cell_runs_correct_with_its_minimum_counted():
    r = run_tiny(CELL)
    c = r["compared"]
    assert r["correct"] is True, c
    assert c["cycles_compared"]["value"] == CYCLES + 4
    assert c["evictions_from_another_queue"]["value"] >= 8
    assert c["evictions_from_another_queue"]["limit_min"] == 8
    assert c["declined_by_bridge"]["value"] == 0
    assert c["cycle_program_signatures"]["value"] == 1
    assert list(c)[-1] == "evictions_from_another_queue"


@pytest.mark.parametrize("tiny", [True, False])
def test_the_builder_deals_nothing_onto_a_returning_queue(tiny):
    cfg = run.read_config(CONFIG, tiny=tiny)
    world = worldgen_reclaim.build_world(cfg, seed=3)
    flat = worldgen.build_world(cfg, seed=3)
    n = cfg["cluster_queues"]
    returning = set(world["returning"])
    assert len(returning) == n // 2
    on = {ci for _n, ci, _k, _at in world["running"]}
    assert on == set(range(n)) - returning
    # The same workloads, classes and times as the flat world's, and
    # its backlog untouched: every queue has its share waiting.
    assert [(r[0], r[2], r[3]) for r in world["running"]] \
        == [(r[0], r[2], r[3]) for r in flat["running"]]
    assert world["pending"] == flat["pending"]
    assert {ci for _n, ci, _k, _at in world["pending"]} == set(range(n))
    # Every borrowing queue is over its nominal quota.
    used = [0] * n
    for _n, ci, k, _at in world["running"]:
        used[ci] += cfg["classes"][k]["request_milli"]
    assert min(used[ci] for ci in on) > cfg["nominal_milli"]
    assert sum(used) == n * cfg["nominal_milli"] or tiny


def test_the_seed_only_relabels():
    cfg = run.read_config(CONFIG, tiny=True)
    a = worldgen_reclaim.build_world(cfg, seed=1)
    b = worldgen_reclaim.build_world(cfg, seed=2 ** 31 + 5)
    assert a["returning"] != b["returning"]
    back_a = {ci: q for q, ci in enumerate(a["place"])}
    back_b = {ci: q for q, ci in enumerate(b["place"])}
    assert {back_a[ci] for ci in a["returning"]} \
        == {back_b[ci] for ci in b["returning"]} \
        == worldgen_reclaim.returning_queues(cfg)
    assert sorted((back_a[ci], k, at) for _n, ci, k, at in a["running"]) \
        == sorted((back_b[ci], k, at) for _n, ci, k, at in b["running"])


# -- faults planted under the harness -------------------------------


def test_victims_of_the_heads_own_queue_only_reads_not_correct():
    """The program is handed the world with `reclaimWithinCohort:
    Never`: no head takes anything back from another queue."""
    def make_program(world):
        wrong = dict(world, preemption=dict(
            world["preemption"], reclaim_within_cohort="NEVER"))
        return sut_reclaim.Program(wrong, "local")

    r = run_tiny(CELL, make_program=make_program)
    assert r["correct"] is False
    assert r["compared"]["cycles_differing"]["value"] > 0


def test_the_control_reads_the_cell_as_not_correct(capsys):
    assert control.main(["--workload", CELL, "--seed", "3",
                         "--seconds", "1", "--tiny"]) == 0
    assert '"correct": false' in capsys.readouterr().out


def test_the_adapter_refuses_a_program_that_counts_no_skipped(monkeypatch):
    from kueue_tpu.obs import span

    monkeypatch.setattr(span, "COUNT_KEYS",
                        span.COUNT_KEYS - {"n_preempt_skipped"})
    world = worldgen_reclaim.build_world(
        run.read_config(CONFIG, tiny=True), seed=1)
    with pytest.raises(SystemExit) as refused:
        sut_reclaim.Program(world, "local")
    assert "n_preempt_skipped" in str(refused.value)


def test_a_cycle_served_from_the_host_for_overflow_raises():
    world = worldgen_reclaim.build_world(
        run.read_config(CONFIG, tiny=True), seed=1)
    program = sut_reclaim.Program(world, "local")
    program.eng.oracle.host_root_reasons["preemption-overflow"] = 1
    with pytest.raises(RuntimeError, match="preemption-overflow"):
        program.cycle(world["clock0"] + 1.0)
    program.close()


# -- the added invariants ------------------------------------------


def two_queues(usage_of_lender: int):
    """cq-0 runs nothing and has a large waiting; cq-1 runs smalls."""
    classes = [{"name": "small", "request_milli": 1000, "priority": 50},
               {"name": "large", "request_milli": 20000, "priority": 200}]
    return {"classes": classes, "cohorts": ["co"],
            "cluster_queues": [
                {"name": f"cq-{i}", "cohort": "co", "nominal_milli": 20000,
                 "borrowing_limit_milli": 100000} for i in range(2)],
            "preemption": {"within_cluster_queue": "LOWER_PRIORITY",
                           "reclaim_within_cohort": "ANY"},
            "running": [(f"s{i}", 1, 0, 100.0 + i)
                        for i in range(usage_of_lender)],
            "pending": [("big", 0, 1, 1.0), ("big2", 0, 1, 2.0)]}


def test_invariants_a_victim_of_a_queue_within_its_quota_is_a_breach():
    events = [([], [], 1000.0)]
    take = [{"idle": False, "admitted": [],
             "preempting": [("big", ["s0", "s1"])]}]
    assert invariants_reclaim.check(two_queues(40), events, take) == []
    bad = invariants_reclaim.check(two_queues(20), events, take)
    assert len(bad) == 2 and "within its nominal quota" in bad[0]


def test_invariants_only_for_a_head_that_ends_within_nominal():
    """cq-0 already runs a large; a second one reclaiming from cq-1
    would put cq-0 over its nominal quota."""
    world = two_queues(40)
    world["running"].append(("held", 0, 1, 50.0))
    world["pending"] = [("big2", 0, 1, 2.0)]
    verdicts = [{"idle": False, "admitted": [],
                 "preempting": [("big2", ["s0"])]}]
    bad = invariants_reclaim.check(world, [([], [], 1000.0)], verdicts)
    assert any("over its nominal quota" in b for b in bad)


# -- the three readers ----------------------------------------------


def cycle(**counts):
    return {"phases": dict({"n_launches": 1, "schedule_once": 1.0},
                           **counts)}


READINGS = {
    "reclaim_victims_per_cycle": (
        [cycle(n_reclaim_victims=14), cycle(n_reclaim_victims=0)], 7.0),
    "preempt_candidates_skipped_per_cycle": (
        [cycle(n_preempt_skipped=3000), cycle(n_preempt_skipped=0),
         cycle(n_preempt_skipped=0)], 1000.0),
    "preemptor_slots_per_lattice_launch": (
        [cycle(n_preempt_slots=700, n_lattice_launches=1),
         cycle(n_preempt_slots=0, n_lattice_launches=0),
         cycle(n_preempt_slots=900, n_lattice_launches=1)], 800.0),
}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_reads_its_count_and_nothing_on_the_parent(name):
    cycles, want = READINGS[name]
    reader = run.load_reader(name)
    assert reader(None, {"cycles": cycles}, {}) == pytest.approx(want)
    # The parent's program has no such count: nothing is reported.
    assert reader(None, {"cycles": [cycle(), cycle()]}, {}) is None
    assert reader(None, {"cycles": []}, {}) is None
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
        "preemptor", "cycle_mean_ms", "program_counter", "count")
    assert CELL in m["workloads"]


def test_no_lattice_launch_in_the_window_reports_no_slots():
    reader = run.load_reader("preemptor_slots_per_lattice_launch")
    quiet = cycle(n_preempt_slots=0, n_lattice_launches=0)
    assert reader(None, {"cycles": [quiet, quiet]}, {}) is None
