"""From a profiler trace to numbers. Two steps, so that the second can
be checked on a small recorded trace (fixtures/, tests/):

  load_events(xplane.pb)  the profiler's planes as plain lists: device
                          operations and programs per chip, and the
                          harness's own host spans (bench.*), all in
                          seconds on the trace's clock
  reduce_events(events)   busy seconds (union of the intervals in which
                          an operation ran, mean over the chips), the
                          traced window, seconds per operation and per
                          program, and every idle gap named by the
                          harness span that covered most of it

Nothing here is read from the program.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_OP = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text: str) -> str:
    """A device operation's event name is its whole HLO instruction.
    Keep the instruction's name, its result's first shape, its opcode
    and, of a custom call, the target: `%fusion.149 (u32[1200] fusion`,
    `%custom-call.5 u32[1200,1] custom-call X64SplitLow`."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text[:80]
    parts = [name, rest.split("{", 1)[0][:40].strip()]
    op = _OP.search(" " + rest)
    if op:
        parts.append(op.group(1))
        target = _TARGET.search(rest)
        if target:
            parts.append(target.group(1))
    return " ".join(parts)


def load_events(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = chips.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = (short_name(ev.name) if key == "ops"
                            else ev.name)
                    chip[key].append([name, ev.start_ns / 1e9,
                                      ev.duration_ns / 1e9])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, ev.start_ns / 1e9,
                                      ev.duration_ns / 1e9])
    spans.sort(key=lambda s: s[1])
    return {"chips": chips, "spans": spans}


def union(intervals: list) -> list:
    """Disjoint [start, end] covering the same time, in order."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce_events(events: dict) -> dict:
    chips, spans = events["chips"], events["spans"]
    if not any(c["ops"] for c in chips.values()):
        raise SystemExit("the trace holds no device operation")
    # The traced window: from the first harness span's start to the
    # last one's end (what the loop did under the profiler), widened to
    # hold every device operation.
    starts = [s[1] for s in spans] + [
        op[1] for c in chips.values() for op in c["ops"]]
    ends = [s[1] + s[2] for s in spans] + [
        op[1] + op[2] for c in chips.values() for op in c["ops"]]
    t0, t1 = min(starts), max(ends)
    busy, op_s, op_n, module_s, module_n = [], {}, {}, {}, {}
    module_max: dict = {}
    gaps: dict = {}
    for chip in chips.values():
        covered = union([[op[1], op[1] + op[2]] for op in chip["ops"]])
        busy.append(sum(e - s for s, e in covered))
        for name, _start, dur in chip["ops"]:
            op_s[name] = op_s.get(name, 0.0) + dur
            op_n[name] = op_n.get(name, 0) + 1
        for name, _start, dur in chip["modules"]:
            module_s[name] = module_s.get(name, 0.0) + dur
            module_n[name] = module_n.get(name, 0) + 1
            module_max[name] = max(module_max.get(name, 0.0), dur)
        # Idle gaps, each named by the harness span that covers most
        # of it.
        edges = [t0] + [x for s, e in covered for x in (s, e)] + [t1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            best, best_cover = "outside the harness's spans", 0.0
            for name, s, d in spans:
                cover = min(ge, s + d) - max(gs, s)
                if cover > best_cover:
                    best, best_cover = name, cover
            gaps[best] = gaps.get(best, 0.0) + (ge - gs)
    n_chips = max(1, len(chips))
    span_n: dict = {}
    for name, _s, _d in spans:
        span_n[name] = span_n.get(name, 0) + 1
    return {"busy_s": sum(busy) / n_chips, "window_s": t1 - t0,
            "chips": len(chips),
            "op_s": {k: v / n_chips for k, v in op_s.items()},
            "op_n": op_n,
            "module_s": {k: v / n_chips for k, v in module_s.items()},
            "module_n": module_n, "module_max_s": module_max,
            "span_n": span_n,
            "breakdown": {
                "device_ops": top({k: v / n_chips
                                   for k, v in op_s.items()}),
                "idle_gaps": top({k: v / n_chips
                                  for k, v in gaps.items()})}}


def reduce_dir(trace_dir: str) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise SystemExit(f"{len(paths)} traces under {trace_dir}")
    return reduce_events(load_events(paths[0]))
