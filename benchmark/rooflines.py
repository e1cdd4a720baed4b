"""What a kernel's algorithm needs, as functions of shapes alone — the
same whatever implements it — and the chips' peaks. Kept with the
benchmark so that no later PR can change the yardstick."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(kind: str) -> dict:
    """The peaks of one chip of ``kind`` (benchmark/peaks.json, which
    names its source). A device that is not in the table is an error,
    not a default."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)["peaks"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         "benchmark/peaks.json")
    return table[kind]


def head_selection_bytes(w_rows: int, n_cqs: int) -> int:
    """Head selection is a segment-min: per ClusterQueue the least rank
    among its pending rows. It has to read one rank and one queue id a
    row — 4 bytes each: ranks are positions in an order over fewer than
    2**31 rows — and write one 4-byte value a ClusterQueue. It does W
    compares, so on any chip it is bound by memory, not arithmetic."""
    return 8 * w_rows + 4 * n_cqs


def least_seconds(n_bytes: int, peaks: dict) -> float:
    """The memory-bound floor: bytes over the chip's HBM bandwidth."""
    return n_bytes / peaks["hbm_bytes_per_s"]
