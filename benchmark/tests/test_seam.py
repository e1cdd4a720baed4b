"""The seam between the harness and a kind of deployment's own modules,
driven end to end on the CPU at the tiny sizes, by files alone.

The next PR's tree is a copy of benchmark/ and BENCHMARK.json with files
and entries added and nothing that was there edited
(test_contract.copy_with). One process runs its cells through the copy's
own run.py — load_cell, then run_cell capped at the cycles the other tests
use — and what each result line says is held here: the module a world
file names is the one that decided `correct`, and a world file's added
minimum is held like the harness's own.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import control  # noqa: E402
import test_contract  # noqa: E402
from test_benchmark import CYCLES  # noqa: E402

# A reference of the new kind's own, which decides one admission
# differently: the first there is gets one milli-unit more.
ONE_ADMISSION_DIFFERENT = '''"""A reference that is wrong once."""
import plain


class Plain(plain.Plain):
    done = False

    def cycle(self, now):
        v = super().cycle(now)
        if v["admitted"] and not self.done:
            self.done = True
            name, cq, flavor, used = v["admitted"][0]
            v["admitted"][0] = (name, cq, flavor, used + 1)
        return v
'''

WORLDS = {
    "seam-named": {"modules": {"reference": "plain_wrong_once"}},
    "seam-unnamed": {},
    "seam-reached": {
        "modules": {"reference": "plain_counting"},
        "compared_at_least": {"preempting_heads": 1,
                              "evictions_compared": 2}},
    "seam-unreached": {
        "modules": {"reference": "plain_counting"},
        "compared_at_least": {"preempting_heads": 1_000_000}},
}
MODULES = {"plain_wrong_once": ONE_ADMISSION_DIFFERENT,
           "plain_counting": test_contract.COUNTING}

# What the one process runs: the copy's run.py, its loaders and its
# run_cell, on each cell in turn.
DRIVER = """
import json, sys
import run
import jax
jax.config.update("jax_enable_x64", True)
cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
for name in sys.argv[2:]:
    cell = run.load_cell(name, tiny=True)
    out = run.run_cell(cell, 5, 600.0, False, cpu, rehearsal=True,
                       max_cycles=int(sys.argv[1]), out=sys.stderr)
    out["modules"] = {r: m.__file__ for r, m in cell["modules"].items()}
    print(json.dumps(out), flush=True)
"""


@pytest.fixture(scope="module")
def next_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("next_pr"))
    cells = test_contract.copy_with(root, WORLDS, MODULES)
    assert test_contract.files_that_were_there_differ(root) == []
    bench = os.path.join(root, "benchmark")
    done = subprocess.run(
        [sys.executable, "-c", DRIVER, str(CYCLES), *cells.values()],
        capture_output=True, text=True, timeout=600, cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.pathsep.join([bench, ROOT])))
    assert done.returncode == 0, done.stderr[-4000:]
    lines = [json.loads(line) for line in done.stdout.splitlines()]
    assert len(lines) == len(cells)
    return {"root": root, "results": dict(zip(cells, lines))}


def test_the_named_reference_is_the_one_that_decides(next_tree):
    r = next_tree["results"]
    named, unnamed = r["seam-named"], r["seam-unnamed"]
    assert named["correct"] is False
    c = named["compared"]
    assert c["cycles_differing"]["value"] == 1 > c[
        "cycles_differing"]["limit"]
    assert c["end_state_differs"]["value"] == 0
    assert c["guarantees_broken"]["value"] == 0
    # The same world with the name left out: the default, and correct.
    assert unnamed["correct"] is True, unnamed["compared"]
    assert unnamed["compared"]["cycles_differing"]["value"] == 0
    assert unnamed["compared"]["cycles_compared"]["value"] == CYCLES + 4


def test_each_cell_ran_the_copys_own_modules(next_tree):
    bench = os.path.join(next_tree["root"], "benchmark")
    got = {w: {role: os.path.relpath(path, bench)
               for role, path in r["modules"].items()}
           for w, r in next_tree["results"].items()}
    defaults = {"world_builder": "worldgen.py", "adapter": "sut.py",
                "reference": "plain.py", "invariants": "invariants.py"}
    assert got["seam-unnamed"] == defaults
    assert got["seam-named"] == dict(defaults,
                                     reference="plain_wrong_once.py")
    assert got["seam-reached"] == dict(defaults,
                                       reference="plain_counting.py")


def test_an_added_minimum_is_held_like_the_harnesss_own(next_tree):
    r = next_tree["results"]
    reached, unreached = r["seam-reached"], r["seam-unreached"]
    c = reached["compared"]
    assert reached["correct"] is True, c
    assert c["preempting_heads"]["limit_min"] == 1
    assert 1 <= c["preempting_heads"]["value"] <= c[
        "evictions_compared"]["value"]
    # A world file raises one of the harness's own, and drops none.
    assert c["evictions_compared"]["limit_min"] == 2
    assert c["admissions_compared"]["limit_min"] == 1
    assert list(c)[-4:] == ["cycles_compared", "admissions_compared",
                            "evictions_compared", "preempting_heads"]
    # Not reached: every verdict equal, and the run is not correct.
    c = unreached["compared"]
    assert unreached["correct"] is False
    assert c["cycles_differing"]["value"] == 0
    assert c["end_state_differs"]["value"] == 0
    assert c["preempting_heads"] == {
        "value": reached["compared"]["preempting_heads"]["value"],
        "limit_min": 1_000_000}
    assert c["evictions_compared"]["limit_min"] == 1


def test_control_takes_the_cells_reference_and_exits_0(capsys):
    """control.py --tiny: the cell's own reference in float32 time, in
    the program's place, comes out as not correct — exit code 0."""
    cell = test_contract.CELL["name"]
    assert control.main(["--workload", cell, "--seed", "3",
                         "--seconds", "1", "--tiny"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["cycles_differing"]["value"] > 0
