"""The several-flavors kind of deployment under the harness, on the CPU
at its world file's `tiny` sizes: the cell resolves to its own four
modules by files alone and runs `correct`; faults planted under the
harness — in the fungibility fold, in a simulation row's flavor, in a
borrowing limit — each read `correct: false`; the control (the cell's
own reference in float32 time) reads not correct; the world builder
makes the running set its file states; the reference decides small
cases as worked out by hand; the invariants catch a verdict off its
flavor.
"""

from __future__ import annotations

import copy
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
import invariants_flavors  # noqa: E402
import plain_flavors  # noqa: E402
import run  # noqa: E402
import sut_flavors  # noqa: E402
import worldgen_flavors  # noqa: E402
from test_benchmark import CYCLES, run_tiny  # noqa: E402

CONFIG = "fungible-3f2r-1000cq"
CELL = CONFIG + ".trickle-turnover"
GIB = 1 << 30


def test_the_cell_resolves_to_its_own_modules_by_files_alone():
    cell = run.load_cell(CELL, tiny=True)
    assert {role: os.path.relpath(m.__file__, BENCH)
            for role, m in cell["modules"].items()} == {
        "world_builder": "worldgen_flavors.py",
        "adapter": "sut_flavors.py", "reference": "plain_flavors.py",
        "invariants": "invariants_flavors.py"}
    assert cell["at_least"]["admissions_on_a_later_flavor"] == (
        1, plain_flavors.count_admissions_on_a_later_flavor)
    # PR 26's eleven metrics of the layers it shares with the flat
    # kind's cell, then its own. (The nine of PR 27 and 28 are held to
    # that cell alone, letter for letter, by tests this PR may not
    # edit: test_span_readers.py, test_discarded_launch_share.py.)
    assert [m["name"] for m in cell["per_layer"]] == [
        "submit_ms_per_cycle", "encode_ms", "executor_call_ms",
        "cycle_program_ms", "preemptor_launch_ms", "verdict_decode_ms",
        "unused_speculation_ms", "apply_ms", "finalize_ms",
        "heads_kernel_roofline", "device_idle_pct",
        "sim_nomination_ms", "flavor_grid_ms", "sim_launch_ms",
        "fungibility_fold_ms", "sim_rows_per_cycle",
        "sim_heads_per_cycle", "sim_nomination_share_pct",
        "sim_program_ms", "sim_launches_per_cycle"]


def test_the_cell_runs_correct_and_compares_a_later_flavor():
    r = run_tiny(CELL)
    c = r["compared"]
    assert r["correct"] is True, c
    assert c["cycles_compared"]["value"] == CYCLES + 4
    assert c["admissions_on_a_later_flavor"]["value"] >= 1
    assert c["evictions_compared"]["value"] >= 1
    assert c["cycle_program_signatures"]["value"] == 1
    assert list(c)[-1] == "admissions_on_a_later_flavor"


def test_the_builder_makes_the_running_set_its_file_states():
    cfg = run.read_config(CONFIG)
    world = worldgen_flavors.build_world(cfg, seed=1)
    by_class = [0] * len(cfg["classes"])
    for _name, _ci, k, _at in world["running"]:
        by_class[k] += 1
    assert dict(zip((c["name"] for c in cfg["classes"]), by_class)) \
        == cfg["running_reckoned"]
    # Every (queue, flavor) full in one of its resources, none borrowing.
    held: dict = {}
    for (_n, ci, k, _at), f in zip(world["running"], world["running_on"]):
        got = held.setdefault((ci, f), [0, 0])
        for s, r in enumerate(cfg["resources"]):
            got[s] += cfg["classes"][k]["request"][r]
    assert len(held) == cfg["cluster_queues"] * len(cfg["flavors"])
    for (_ci, f), got in held.items():
        nominal = [cfg["flavors"][f]["nominal"][r]
                   for r in cfg["resources"]]
        assert all(g <= n for g, n in zip(got, nominal))
        assert any(g == n for g, n in zip(got, nominal))
    reckoned = worldgen_flavors.device_bytes(cfg)
    stated = cfg["device_bytes_reckoned"]
    assert (reckoned["per_cohort_pad"], reckoned["a_pad"],
            reckoned["sim_block"]) == (stated["per_cohort_pad"],
                                       stated["running_pad"],
                                       stated["sim_block"])
    assert reckoned["sum"] == stated["sum"] == max(
        stated["cycle_program_temp"], stated["sim_program_temp"]) \
        + stated["code"] + stated["outputs"]


# -- faults planted under the harness -------------------------------


def fold_that_takes_the_simulated_borrow(program_module):
    """The fault this PR repaired, planted again: the entry's borrow is
    the worst MODE's, not the worst of its resources'."""
    from kueue_tpu.oracle import engine_bridge

    inner = engine_bridge._fold_fungibility

    def fold(pm, br, in_group, *rest):
        choice, mode, borrow = inner(pm, br, in_group, *rest)
        return choice, mode, borrow * 0

    return engine_bridge, "_fold_fungibility", fold


def sim_rows_on_the_next_flavor(program_module):
    """Every simulation row asks about the flavor after its own."""
    from kueue_tpu.oracle.engine_bridge import OracleBridge

    inner = OracleBridge._sim_launch

    def launch(self, w, adm, pcfg, usage, derived, rows, *rest):
        S = w.num_resources
        fr = rows["slot_fr"]
        rows = dict(rows, slot_fr=(fr + S * (fr >= 0)) % (
            w.nominal.shape[1]) * (fr >= 0) - (fr < 0))
        return inner(self, w, adm, pcfg, usage, derived, rows, *rest)

    return OracleBridge, "_sim_launch", launch


@pytest.mark.parametrize("plant", [fold_that_takes_the_simulated_borrow,
                                   sim_rows_on_the_next_flavor],
                         ids=["fold", "sim-row-flavor"])
def test_a_fault_in_the_nomination_reads_not_correct(plant, monkeypatch):
    owner, name, broken = plant(sut_flavors)
    monkeypatch.setattr(owner, name, broken)
    r = run_tiny(CELL)
    assert r["correct"] is False
    assert r["compared"]["cycles_differing"]["value"] > 0


def test_a_borrowing_limit_off_by_a_workload_reads_not_correct():
    """The program is handed a world whose every queue may borrow one
    small workload's memory less on `reserved` than the file says."""
    def make_program(world):
        wrong = copy.deepcopy(world)
        for cq in wrong["cluster_queues"]:
            cq["flavors"][0]["borrowing_limit"]["memory"] -= 4 * GIB
        return sut_flavors.Program(wrong, "local")

    # Borrowing is what the limit bounds: a world whose classes borrow.
    r = run_tiny(CELL, make_program=make_program, world={
        "flavors": [dict(f, borrowing_limit={
            "cpu": f["nominal"]["cpu"], "memory": f["nominal"]["memory"]})
            for f in run.read_config(CONFIG)["flavors"]]})
    assert r["correct"] is False
    assert r["compared"]["cycles_differing"]["value"] \
        + r["compared"]["end_state_differs"]["value"] > 0


def test_the_control_reads_the_cell_as_not_correct(capsys):
    assert control.main(["--workload", CELL, "--seed", "3",
                         "--seconds", "1", "--tiny"]) == 0
    assert '"correct": false' in capsys.readouterr().out


# -- the reference, by hand ------------------------------------------


def little_world(running, pending, when_can_preempt="TRY_NEXT_FLAVOR",
                 when_can_borrow="BORROW", queues=2):
    """ClusterQueues in one cohort, flavors a (8 cpu, 32 GiB) then b
    (4 cpu, 16 GiB), limits as large again; classes small / medium at
    (1 cpu, 4 GiB) / (5 cpu, 32 GiB), priorities 50 / 100. ``running``
    is (name, queue, class, reserved at, flavor index)."""
    flavors = [
        {"name": "a", "nominal": {"cpu": 8000, "memory": 32 * GIB},
         "borrowing_limit": {"cpu": 8000, "memory": 32 * GIB}},
        {"name": "b", "nominal": {"cpu": 4000, "memory": 16 * GIB},
         "borrowing_limit": {"cpu": 4000, "memory": 16 * GIB}}]
    return {
        "cohorts": ["co"], "resources": ["cpu", "memory"],
        "flavors": ["a", "b"],
        "cluster_queues": [{"name": f"cq-{i}", "cohort": "co",
                            "flavors": flavors} for i in range(queues)],
        "classes": [
            {"name": "small", "priority": 50,
             "request": {"cpu": 1000, "memory": 4 * GIB}},
            {"name": "medium", "priority": 100,
             "request": {"cpu": 5000, "memory": 32 * GIB}}],
        "preemption": {"within_cluster_queue": "LOWER_PRIORITY",
                       "reclaim_within_cohort": "NEVER"},
        "flavor_fungibility": {"when_can_borrow": when_can_borrow,
                               "when_can_preempt": when_can_preempt},
        "running": [r[:4] for r in running],
        "running_on": [r[4] for r in running], "pending": pending}


def on(flavor, cpu, memory):
    return ((("cpu", flavor), ("memory", flavor)),
            (("cpu", flavor, cpu), ("memory", flavor, memory)))


def test_reference_by_hand_a_small_lands_on_the_later_flavor():
    """cq-0's flavor a is full of eight smalls and so is cq-1's: a
    ninth small has no room on a (the cohort's a is spent), is no
    preemptor of its equals, and fits on b: admitted there."""
    running = [(f"s{i}", i % 2, 0, 100.0 + i, 0) for i in range(16)]
    ref = plain_flavors.Plain(little_world(running,
                                           [("new", 0, 0, 1.0)]))
    v = ref.cycle(1000.0)
    assert v["admitted"] == [("new", "cq-0") + on("b", 1000, 4 * GIB)]
    assert v["preempting"] == []
    assert plain_flavors.later_flavor_counts(
        little_world(running, []), [v]) == (1, 0)


def test_reference_by_hand_a_medium_preempts_where_no_flavor_fits():
    """cq-0: eight smalls on a; cq-1: a medium on a and four smalls on
    b, so the cohort's b has 16 GiB left. A medium head in cq-0 finds
    b NoFit (32 GiB is over what b can ever give it: nominal 16 + 16)
    and a needs all eight smalls gone: under TryNextFlavor and under
    Preempt alike it preempts on a. Once it runs, the evicted smalls
    come back one a cycle, on b, where the cohort still has room."""
    running = [(f"s{i}", 0, 0, 100.0 + i, 0) for i in range(8)]
    running.append(("m", 1, 1, 50.0, 0))
    running += [(f"t{i}", 1, 0, 60.0 + i, 1) for i in range(4)]
    for policy in ("TRY_NEXT_FLAVOR", "PREEMPT"):
        ref = plain_flavors.Plain(little_world(
            running, [("mid", 0, 1, 2.0)], when_can_preempt=policy))
        v = ref.cycle(1000.0)
        assert v["admitted"] == []
        assert v["preempting"] == [("mid", sorted(
            f"s{i}" for i in range(8)))]
        v = ref.cycle(1001.0)
        assert v["admitted"] == [("mid", "cq-0")
                                 + on("a", 5000, 32 * GIB)]
        v = ref.cycle(1002.0)
        assert v["admitted"] == [("s0", "cq-0") + on("b", 1000, 4 * GIB)]


def test_reference_by_hand_fit_by_borrowing_beats_preempting():
    """As above with cq-1's b empty: the medium fits b by borrowing
    (5 cpu of the cohort's 8, 32 GiB of its 32), and under
    whenCanBorrow Borrow a flavor that fits ends the walk, though a
    came first and could be preempted on."""
    running = [(f"s{i}", 0, 0, 100.0 + i, 0) for i in range(8)]
    running.append(("m", 1, 1, 50.0, 0))
    ref = plain_flavors.Plain(little_world(running, [("mid", 0, 1, 2.0)]))
    v = ref.cycle(1000.0)
    assert v["admitted"] == [("mid", "cq-0") + on("b", 5000, 32 * GIB)]
    assert v["preempting"] == []


def test_reference_by_hand_the_borrow_is_the_worst_of_its_resources():
    """Four queues, every b full of four smalls. cq-0 holds seven
    smalls on a (7 cpu, 28 GiB), cq-1 eight, cq-2 and cq-3 a medium
    each: the cohort's a has 7 cpu and 4 GiB left. A medium head in
    cq-0: its cpu fits a by borrowing (7 + 5 > 8: borrow 1), its memory
    needs the seven gone (then 32 <= 32: borrow 0), b is NoFit. The
    entry preempts on a with borrow 1 — the worst of its resources',
    not the simulated one's."""
    running = [(f"s{i}", 0, 0, 100.0 + i, 0) for i in range(7)]
    running += [(f"t{i}", 1, 0, 200.0 + i, 0) for i in range(8)]
    running += [("m2", 2, 1, 50.0, 0), ("m3", 3, 1, 51.0, 0)]
    running += [(f"b{q}{i}", q, 0, 10.0 + i, 1)
                for q in range(4) for i in range(4)]
    ref = plain_flavors.Plain(little_world(
        running, [("mid", 0, 1, 1.0)], queues=4))
    usage = [list(u) for u in ref.usage]
    used = [list(u) for u in ref.used]
    request = ref.classes[1][1]
    assert ref._cell(0, 0, 0, request[0], 100, usage, used) == (
        plain_flavors.FIT, 1)
    assert ref._cell(0, 0, 1, request[1], 100, usage, used) == (
        plain_flavors.PREEMPT, 0)
    assert ref._cell(0, 1, 1, request[1], 100, usage, used)[0] \
        == plain_flavors.NO_FIT
    got = ref._nominate(0, 100, request, usage, used)
    assert (got["mode"], got["flavor"], got["borrows"]) == (
        plain_flavors.PREEMPT, 0, 1)
    assert sorted(n for n, _r in got["targets"]) == [
        f"s{i}" for i in range(7)]
    assert usage == ref.usage and used == ref.used  # left as they were


def test_the_invariants_catch_a_verdict_off_its_flavor():
    cfg = run.read_config(CONFIG, tiny=True)
    world = worldgen_flavors.build_world(cfg, seed=2)
    name, ci, k, _at = world["pending"][0]
    req = world["classes"][k]["request"]
    cq = world["cluster_queues"][ci]["name"]
    split = ((("cpu", "reserved"), ("memory", "spot")),
             (("cpu", "reserved", req["cpu"]),
              ("memory", "spot", req["memory"])))
    bad = invariants_flavors.check(world, [([], [], 0.0)], [{
        "idle": False, "admitted": [(name, cq) + split],
        "preempting": []}])
    assert any("land on" in b for b in bad), bad
    whole = on("spot", req["cpu"], req["memory"])
    bad = invariants_flavors.check(world, [([], [], 0.0)], [{
        "idle": False, "admitted": [(name, cq) + whole],
        "preempting": []}])
    assert any("over its" in b for b in bad), bad
