"""`commit_victim_entries_per_cycle` (PR 33): the entries the fused
preemptor gave a victim set, a cycle of the window — the most steps of
the commit's loop that take the branch removing victims."""

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

NAME = "commit_victim_entries_per_cycle"
# The second cell runs the same loop and its phase log holds the count;
# tests/test_flavors_cell.py holds that cell's list of metrics letter
# for letter, so listing it there takes a `benchmark` PR.
CELLS = ["baseline-1x1000-noreclaim.trickle-turnover"]


def cycle(victim_entries=None):
    """The counts of one schedule_once() as obs/span.py leaves them: the
    key is there only where a `verdict_decode` span had the attr."""
    phases = {"n_launches": 1, "n_device_cycles": 1, "n_device_heads": 1000,
              "device": 0.003, "schedule_once": 1.2}
    if victim_entries is not None:
        phases["n_commit_victim_entries"] = victim_entries
    return {"phases": phases}


WINDOWS = {
    "one entry every third cycle": ([cycle(0), cycle(0), cycle(1)] * 4,
                                    1 / 3),
    "several entries in a cycle": ([cycle(0), cycle(45), cycle(3)], 16.0),
    "no entry had victims": ([cycle(0)] * 5, 0.0),
    # A cycle the bridge declined before a verdict has no such span; it
    # is one of the window's cycles all the same.
    "a cycle without the span": ([cycle(2), cycle(), cycle(4)], 2.0),
    "the parent: no cycle has the key": ([cycle()] * 3, None),
    "no cycle": ([], None),
}


@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_victim_entries_over_the_windows_cycles(window):
    cycles, want = WINDOWS[window]
    got = run.load_reader(NAME)(None, {"cycles": cycles}, {})
    assert got == want if want is None else got == pytest.approx(want)
    assert want is None or isinstance(got, float)


def test_declared_last_with_its_reader():
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    m = bench["per_layer"][-1]
    assert m == {"name": NAME, "unit": "count", "better": "lower",
                 "source": "program_span", "layer": "cycle program",
                 "moves": "cycle_mean_ms", "workloads": CELLS}
    assert m["layer"] in {x["layer"] for x in bench["per_layer"][:-1]}
