"""Ordered candidates the preemptor's scans passed over as invalid
before their slot fit or ran out, a cycle of the window:
`n_preempt_skipped` (obs/span.py COUNT_KEYS) over the window's cycles,
from the `preempt_skipped` attr of the `cycle` span — the cycle
program's own output (ops/preempt.classical_targets_impl: candidates an
attempt may not take while borrowing, and those whose queue has come
back within its nominal quota). They are what used to fill the scan's
first v_cap positions and send the slot to the host. A program without
the output gives no cycle the key, and nothing is reported."""

from _counts import window_count


def reduce(trace, spans, counters):
    skipped = window_count(spans, "n_preempt_skipped")
    if skipped is None:
        return None
    return skipped / len(spans["cycles"])
