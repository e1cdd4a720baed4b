"""`discarded_launch_share_pct` (PR 28): launches made for speculations
that were thrown away, of the window's launches; and what the readers of
the speculation's counts say of a window in which the gate let no
speculation through."""

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

CELL = "baseline-1x1000-noreclaim.trickle-turnover"
NAME = "discarded_launch_share_pct"


def cycle(launches, discarded=None, skipped=None):
    """The counts of one schedule_once() as obs/span.py leaves them: a
    key is there only where its span was."""
    phases = {"n_launches": launches, "n_device_cycles": 1,
              "speculate": 1.3 * (launches - 1), "schedule_once": 3.0}
    if discarded is not None:
        phases.update(n_spec_discarded=discarded, n_spec_used=0)
    if skipped is not None:
        phases["n_spec_skipped"] = skipped
    return {"phases": phases}


# PR 27's window under the one-cycle backoff: 17 cycles; every other one
# learns of a discard and sits the next speculation out, the ones between
# find nothing to take and launch a speculation beside their own call.
BACKOFF = [cycle(1, discarded=1), cycle(2)] * 8 + [cycle(1, discarded=1)]
# The same loop behind the gate: every cycle launches once and skips.
GATED = [cycle(1, skipped=1)] * 29

WINDOWS = {
    "backoff: 9 of 25": (BACKOFF, 36.0),
    "gated: launches, no speculation": (GATED, 0.0),
    "gated: 0 used and 0 discarded, counted": (
        [{"phases": dict(c["phases"], n_spec_used=0, n_spec_discarded=0)}
         for c in GATED], 0.0),
    "drain: every speculation used": (
        [{"phases": {"n_launches": 1, "n_spec_used": 1,
                     "n_spec_discarded": 0}}] * 5, 0.0),
    "no cycle": ([], None),
    "no launch counted": ([{"phases": {"encode": 2.0, "device": 0.006}}] * 3,
                          None),
    "a window of fallbacks": ([{"phases": {"snapshot": 0.1,
                                           "n_launches": 0}}], None),
}


def test_the_fixture_is_the_ledgers_window():
    counts = [c["phases"] for c in BACKOFF]
    assert sum(p["n_launches"] for p in counts) == 25 and len(counts) == 17
    assert sum(p.get("n_spec_discarded", 0) for p in counts) == 9


@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_share_of_launches_thrown_away(window):
    cycles, want = WINDOWS[window]
    got = run.load_reader(NAME)(None, {"cycles": cycles}, {})
    assert got == want if want is None else got == pytest.approx(want)
    assert want is None or isinstance(got, float)


def test_speculation_ms_of_an_empty_span_is_a_number():
    assert run.load_reader("speculation_ms")(
        None, {"cycles": GATED}, {}) == pytest.approx(0.0)


def test_the_metric_is_declared_last_with_its_reader():
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NAME) == 19 and len(set(names)) == len(names)
    assert bench["per_layer"][19] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "speculation",
        "moves": "cycle_mean_ms", "workloads": [CELL]}
    assert names[15:19] == [
        "speculation_ms", "preemptor_launch_share_pct", "heads_per_cycle",
        "schedule_once_unattributed_ms"]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", NAME + ".py"))
