"""The harness's contract, by Python alone: no engine, no JAX, seconds.

    python3 -m pytest benchmark/tests/test_contract.py -q

BENCHMARK.json keeps to the contract's shapes and every world file to the
seam's (each module it names is there; of the four roles only the
adapter reaches the program); the cell that is there resolves to the
defaults; a world file that names what is not there fails before anything
is built, naming the file; every per-layer metric has a reader that
loads; the verdict's contract takes a flavor and a quantity of several
resources. A copy of benchmark/ and BENCHMARK.json with files added
(``copy_with``) stands for the next PR's tree; test_seam.py runs cells of
such a copy.
"""

from __future__ import annotations

import ast
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference  # noqa: E402
import run  # noqa: E402
import trafficgen as traffic  # noqa: E402

BENCHMARK = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = BENCHMARK["workloads"][0]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def copy_with(root, worlds: dict, modules: dict) -> dict:
    """The next PR's tree under ``root``: benchmark/ and BENCHMARK.json
    as they are, plus — files and entries, no edit — for each name of
    ``worlds`` a world file (the first cell's, with those keys laid
    over it), a configuration and a cell under the first cell's traffic,
    and for each name of ``modules`` benchmark/<name>.py with that
    source. Returns cell name by world name."""
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", ".pytest_cache"))
    b = json.loads(json.dumps(BENCHMARK))
    config = next(c for c in b["configs"] if c["name"] == CELL["config"])
    base = run.read_json(os.path.join(ROOT, config["file"]))
    cells = {}
    for name, keys in worlds.items():
        path = os.path.join("benchmark", "worlds", name + ".json")
        with open(os.path.join(root, path), "x", encoding="utf-8") as f:
            json.dump(dict(base, **keys), f, indent=2)
        b["configs"].append(dict(config, name=name, file=path))
        cells[name] = f"{name}.{CELL['traffic']}"
        b["workloads"].append(dict(CELL, name=cells[name], config=name))
    for name, source in modules.items():
        with open(os.path.join(bench, name + ".py"), "x",
                  encoding="utf-8") as f:
            f.write(source)
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(b, f, indent=2)
    return cells


def files_that_were_there_differ(root) -> list:
    """Files of benchmark/ whose copy under ``root`` is not byte for
    byte what it was."""
    out = []
    for folder, _dirs, files in os.walk(BENCH):
        if "__pycache__" in folder or ".trace" in folder \
                or ".pytest_cache" in folder:
            continue
        for name in files:
            was = os.path.join(folder, name)
            now = os.path.join(root, os.path.relpath(was, ROOT))
            with open(was, "rb") as f, open(now, "rb") as g:
                if f.read() != g.read():
                    out.append(was)
    return out


def roles_of(world_file: str) -> dict:
    named = run.read_json(world_file).get("modules", {})
    return {role: named.get(role, default)
            for role, default in run.DEFAULT_MODULES.items()}


def reaches_the_program(module: str, seen=None) -> bool:
    """Whether benchmark/<module>.py imports kueue_tpu, anywhere in its
    source or through a module of benchmark/ that it imports."""
    seen = set() if seen is None else seen
    path = os.path.join(BENCH, module + ".py")
    if module in seen or not os.path.isfile(path):
        return False
    seen.add(module)
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module.split(".")[0])
    return "kueue_tpu" in names or any(
        reaches_the_program(n, seen) for n in names)


def test_benchmark_json_keeps_to_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        world_file = os.path.join(ROOT, c["file"])
        assert os.path.isfile(world_file)
        assert run.read_json(world_file)["source"] == c["source"]
        # The seam: every module the world file names is there, and of
        # the four only the adapter reaches the program.
        for role, module in roles_of(world_file).items():
            assert os.path.isfile(os.path.join(BENCH, module + ".py")), (
                c["file"], role, module)
            assert reaches_the_program(module) == (role == "adapter"), (
                c["file"], role, module)
    assert len({c["file"] for c in b["configs"]}) == len(configs)
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        cells.add(w["name"])
    assert {w["config"] for w in b["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_the_import_walk_finds_the_program_behind_a_module():
    """What the contract's seam check stands on: sut.py imports the
    program, inside its functions; plain.py does not; and run.py names
    its modules in a table and imports none of them."""
    assert reaches_the_program("sut")
    assert not reaches_the_program("plain")
    assert not reaches_the_program("run")


def test_the_cell_that_is_there_resolves_to_the_defaults():
    assert run.DEFAULT_MODULES == {
        "world_builder": "worldgen", "adapter": "sut",
        "reference": "plain", "invariants": "invariants"}
    cell = run.load_cell(CELL["name"], tiny=True)
    assert "modules" not in cell["world"]
    assert "compared_at_least" not in cell["world"]
    for role, module in cell["modules"].items():
        assert module.__name__ == run.DEFAULT_MODULES[role]
        assert os.path.abspath(module.__file__) == os.path.join(
            BENCH, module.__name__ + ".py")
        assert sys.modules[module.__name__] is module
    assert cell["at_least"] == {"admissions_compared": (1, None),
                                "evictions_compared": (1, None)}
    assert {"build_world", "device_bytes"} <= set(
        dir(cell["modules"]["world_builder"]))
    assert callable(cell["modules"]["adapter"].Program)
    assert callable(cell["modules"]["reference"].Plain)
    assert callable(cell["modules"]["invariants"].check)


COUNTING = '''"""The plain reference, with one count of its own."""
import plain

Plain = plain.Plain


def count_preempting_heads(world, verdicts):
    return sum(len(v["preempting"]) for v in verdicts)
'''

# world-file keys -> what the error has to name. Each fails in
# load_cell, before JAX is imported or anything is built.
REFUSED = {
    "a reference with no file": (
        {"modules": {"reference": "plain_of_the_next_kind"}},
        ["plain_of_the_next_kind.py", "worlds/refused.json"]),
    "an adapter that leads out of benchmark/": (
        {"modules": {"adapter": "../kueue_tpu/serve"}},
        ["../kueue_tpu/serve", "worlds/refused.json"]),
    "a role there is not": (
        {"modules": {"referee": "plain"}},
        ["referee", "worlds/refused.json"]),
    "evictions_compared dropped": (
        {"compared_at_least": {"evictions_compared": 0}},
        ["evictions_compared", "takes nothing away"]),
    "admissions_compared dropped": (
        {"compared_at_least": {"admissions_compared": False}},
        ["admissions_compared", "takes nothing away"]),
    "a minimum the reference cannot count": (
        {"modules": {"reference": "plain_counting"},
         "compared_at_least": {"second_flavor_admissions": 1}},
        ["count_second_flavor_admissions", "plain_counting.py"]),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_a_world_file_that_names_what_is_not_there_is_refused(
        case, tmp_path):
    keys, has_to_name = REFUSED[case]
    cells = copy_with(str(tmp_path), {"refused": keys},
                      {"plain_counting": COUNTING})
    done = subprocess.run(
        [sys.executable, "-X", "importtime",
         os.path.join(str(tmp_path), "benchmark", "run.py"),
         "--workload", cells["refused"], "--seed", "1", "--tiny"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode != 0 and done.stdout == ""
    said = done.stderr.splitlines()[-1]
    for part in has_to_name:
        assert part in said, said
    # Nothing was built: neither JAX nor the program was imported.
    imported = {line.split("|")[-1].strip()
                for line in done.stderr.splitlines() if "|" in line}
    assert "json" in imported
    assert not {"jax", "kueue_tpu", "numpy"} & imported


def test_every_per_layer_metric_has_a_reader_that_loads():
    readers = os.path.join(BENCH, "layer_metrics")
    declared = {m["name"] for m in BENCHMARK["per_layer"]}
    for name in declared:
        assert callable(run.load_reader(name))
    # And no reader is left without its metric.
    assert {f[:-3] for f in os.listdir(readers)
            if f.endswith(".py") and not f.startswith("_")} == declared
    assert "speculation_discarded_pct" not in declared


# An admission is (name, ClusterQueue, flavor, used): the last two any
# plain comparable data. For several resources, a tuple of (resource,
# flavor, milli) in each place — here as one kind of deployment might
# say it.
WIDE = ("w1", "cq-1", (("cpu", "spot"), ("gpu", "a100")),
        (("cpu", "spot", 4000), ("gpu", "a100", 1000)))


@pytest.mark.parametrize("admission", [
    ("w1", "cq-1", "default", 1000), WIDE], ids=["one", "several"])
def test_the_verdicts_contract_takes_any_comparable_flavor_and_quantity(
        admission):
    sets = traffic.RunningSets(["cq-0", "cq-1"], [("a", 0, 0, 0.0)])
    sets.apply({"admitted": [admission], "preempting": [("w1", ["a"])]})
    assert sets.sets == [[], ["w1"]]
    cohort_of = {"cq-0": "co", "cq-1": "co"}
    v = {"admitted": [admission], "preempting": []}
    assert reference.differing([v], [v], cohort_of) == []
    other = list(admission)
    other[3] = 999 if admission[3] == 1000 else admission[3][:1]
    assert reference.differing(
        [v], [{"admitted": [tuple(other)], "preempting": []}],
        cohort_of) == [0]


def test_phase_means_gives_counts_as_counts():
    cycles = [{"phases": {"apply": 0.002, "n_launches": 1,
                          "n_device_heads": 1000}},
              {"phases": {"apply": 0.004, "n_launches": 2}}]
    ms, counts = run.phase_means(cycles)
    assert ms == {"apply": 3.0}
    assert counts == {"n_device_heads": 500.0, "n_launches": 1.5}
