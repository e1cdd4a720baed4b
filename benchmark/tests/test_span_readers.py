"""The readers of the program's own spans and their counts (PR 27), each
on a hand-written input: the mean per cycle of the key it names, or the
ratio of the window's own counts, and nothing (never 0) where the
program has no such key — as the parent commit has not, which the
driver runs these same files over."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
import test_contract  # noqa: E402

CELL = "baseline-1x1000-noreclaim.trickle-turnover"

# Three cycles as Program.phases() hands them over: one that launched
# the cycle program twice (its own call and a speculation's, both into
# the preemptor's branch) after throwing a speculation away, one served
# by nothing but its own call, and one served by a speculation, whose
# one launch is the next speculation's.
CYCLES = [
    {"phases": {"host_encode": 0.030, "upload": 0.012, "dispatch": 0.002,
                "device_wait": 4.0, "readback": 0.040, "speculate": 2.1,
                "unattributed": 0.003, "schedule_once": 4.3,
                "encode": 2.0, "device": 0.006,
                "n_launches": 2, "n_lattice_launches": 2,
                "n_spec_used": 0, "n_spec_discarded": 1,
                "n_device_cycles": 1, "n_device_heads": 1000}},
    {"phases": {"host_encode": 0.010, "upload": 0.004, "dispatch": 0.001,
                "device_wait": 2.0, "readback": 0.020, "speculate": 0.0,
                "unattributed": 0.001, "schedule_once": 2.1,
                "encode": 2.0, "device": 0.006,
                "n_launches": 1, "n_lattice_launches": 0,
                "n_spec_used": 0, "n_spec_discarded": 1,
                "n_device_cycles": 1, "n_device_heads": 998}},
    {"phases": {"host_encode": 0.020, "upload": 0.008, "dispatch": 0.0015,
                "device_wait": 3.0, "readback": 0.030, "speculate": 1.05,
                "unattributed": 0.002, "schedule_once": 3.2,
                "encode": 0.0001, "device": 0.006, "spec_encode": 2.0,
                "n_launches": 1, "n_lattice_launches": 0,
                "n_spec_used": 1, "n_spec_discarded": 0,
                "n_device_cycles": 1, "n_device_heads": 999}},
]
# The bridge's counts from engine start, warm-up included: no reader of
# this PR's takes them.
PIPELINE = {"speculated": 80, "used": 40, "discarded": 40, "skipped": 2}

SPAN_READERS = {
    "host_encode_ms": 20.0,
    "upload_ms": 8.0,
    "device_wait_ms": 3000.0,
    "readback_ms": 30.0,
    "speculation_ms": 1050.0,
    "schedule_once_unattributed_ms": 2.0,
}
COUNT_READERS = {
    "preemptor_launch_share_pct": 50.0,
    "heads_per_cycle": 999.0,
}


@pytest.mark.parametrize("name,want", sorted(SPAN_READERS.items()))
def test_span_reader_is_the_mean_per_cycle(name, want):
    got = run.load_reader(name)(None, {"cycles": CYCLES},
                                {"pipeline": PIPELINE})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name,want", sorted(COUNT_READERS.items()))
def test_count_reader_is_the_ratio_of_the_windows_own_counts(name, want):
    got = run.load_reader(name)(None, {"cycles": CYCLES},
                                {"pipeline": PIPELINE})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted({**SPAN_READERS, **COUNT_READERS}))
def test_reader_returns_nothing_where_the_program_has_no_such_key(name):
    """The parent's phases and pipeline counts, and no counters at all."""
    old_cycle = {"phases": {"encode": 2.0, "device": 0.006, "apply": 0.001,
                            "finalize": 0.0005, "tas_place": 0.0}}
    reader = run.load_reader(name)
    assert reader(None, {"cycles": [old_cycle, old_cycle]}, {}) is None
    old_pipeline = {"speculated": 0, "used": 0, "discarded": 0,
                    "skipped": 0}
    assert reader(None, {"cycles": [old_cycle]},
                  {"pipeline": old_pipeline}) is None


def test_a_ratio_over_nothing_is_nothing_not_zero():
    none_yet = {"phases": dict(CYCLES[0]["phases"], n_launches=0,
                               n_spec_discarded=0, n_device_cycles=0)}
    for name in COUNT_READERS:
        reader = run.load_reader(name)
        assert reader(None, {"cycles": []}, {"pipeline": PIPELINE}) is None
        assert reader(None, {"cycles": [none_yet]},
                      {"pipeline": PIPELINE}) is None


def test_launch_share_is_nothing_where_one_launch_could_not_tell():
    """Where the verdicts cannot tell a launch's branch (a StrictFIFO
    head could have driven it) the program leaves n_lattice_launches
    out of that schedule_once(); a share over the rest would read low."""
    blind = {"phases": {k: v for k, v in CYCLES[1]["phases"].items()
                        if k != "n_lattice_launches"}}
    reader = run.load_reader("preemptor_launch_share_pct")
    assert reader(None, {"cycles": CYCLES + [blind]}, {}) is None
    assert reader(None, {"cycles": CYCLES}, {}) == pytest.approx(50.0)


def test_every_new_metric_is_declared_for_the_cell_with_its_reader():
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in {**SPAN_READERS, **COUNT_READERS}:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "cycle_mean_ms"
        assert m["source"] == "program_span"
    # The eleven that were there come first, untouched and in order.
    assert [m["name"] for m in bench["per_layer"][:11]] == [
        "submit_ms_per_cycle", "encode_ms", "executor_call_ms",
        "cycle_program_ms", "preemptor_launch_ms", "verdict_decode_ms",
        "unused_speculation_ms", "apply_ms", "finalize_ms",
        "heads_kernel_roofline", "device_idle_pct"]


def test_benchmark_json_still_keeps_to_the_contract():
    test_contract.test_benchmark_json_keeps_to_the_contract()
