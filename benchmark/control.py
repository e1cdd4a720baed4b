#!/usr/bin/env python3
"""The control of `correct`, at a cell's own size on the chip:

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>

One run of run.py's whole path with the cell's own plain reference (the
module its world file names, or run.DEFAULT_MODULES') computed one
precision below what the configuration states: every time it reads —
the clock, creation and reservation times — in float32 instead of
float64. At Unix time float32 cannot tell two workloads of one day
apart, so first-in-first-out within a priority and latest-admitted-first
among victims fall to their tie-breaks, and the verdicts differ: the
line it prints has to say `correct: false`, with `cycles_differing`
above its limit. The benchmark's own runs never call this; tests/ holds
the same control at the tiny sizes.
"""

from __future__ import annotations

import argparse
import struct
import sys

import run


def float32(t: float) -> float:
    return struct.unpack("f", struct.pack("f", t))[0]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)
    cell = run.load_cell(args.workload, args.tiny)

    import jax

    jax.config.update("jax_enable_x64", True)
    device = run.find_device(cell["chips"], rehearsal=args.tiny)
    result = run.run_cell(
        cell, args.seed, args.seconds, False, device,
        make_reference=lambda w: cell["modules"]["reference"].Plain(
            w, stamp=float32),
        rehearsal=device["platform"] != "tpu")
    return 0 if not result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
