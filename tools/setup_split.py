#!/usr/bin/env python3
"""Where a benchmark cell's set-up goes, from the program's own spans:

    python3 tools/setup_split.py --workload <cell> --seed <n>

builds the cell as `benchmark/run.py` does (world records, the adapter's
engine over them, the warm-up cycles) and prints one JSON line:

  * `stages_s`: records, world (the adapter: the engine, its objects,
    the running set restored and the backlog submitted, the oracle
    attached), each warm-up cycle, and the whole;
  * `ingestion_s`: the first cycle's `intake_restore` and
    `intake_submit` (the engine's own time on the set-up's
    restore_workload and submit calls, obs/span.py WINDOW_KEYS) and
    their calls;
  * `compiled`: per warm-up cycle, the spans the compiles landed on
    (the program's attr `compiles`; `compile_s` and `trace_s`, which
    this tool's CompileSpans adds) and, by JAX's own names, each program
    compiled or read from the persistent cache, seconds.

A first run in a checkout whose compile cache is empty is the cold
split, the next the warm one. It needs the chip, as the benchmark does;
`JAX_PLATFORMS=cpu ... --tiny` rehearses it at the world files' tiny
sizes.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)

import run  # noqa: E402


class CompileSpans:
    """What JAX compiles (or reads from its persistent cache) and
    traces, on the span of ``rec`` open when JAX reports it: attrs
    ``compile_s`` and ``trace_s``, a trace nested in another counted
    once. ``close()`` stops listening."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile_s",
              "/jax/core/compile/jaxpr_trace_duration": "trace_s"}

    def __init__(self, rec):
        from jax import monitoring

        self.rec = rec
        self.traces: list = []  # (start, end) of the outermost traces
        monitoring.register_event_time_span_listener(self.on_event)

    def close(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_time_span_listener(self.on_event)

    def outermost(self, start: float, end: float) -> float:
        """Of a trace from ``start`` to ``end`` that just ended, the
        seconds no trace reported before it covers: JAX reports a
        function traced inside another (thousands of them in a cycle
        program) before the outer one, whose span holds theirs."""
        covered = 0.0
        while self.traces and self.traces[-1][0] >= start:
            inner_start, inner_end = self.traces.pop()
            covered += inner_end - inner_start
        self.traces.append((start, end))
        return end - start - covered

    def on_event(self, event: str, start: float, end: float, **_) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.rec.add(**{key: self.outermost(start, end)
                            if key == "trace_s" else end - start})


def compiled_on(root) -> list:
    """[span name, compiles, compile_s, trace_s] of each span of a
    cycle's tree that JAX compiled or traced in."""
    return [[s.name, s.attrs.get("compiles", 0),
             round(s.attrs.get("compile_s", 0.0), 3),
             round(s.attrs.get("trace_s", 0.0), 3)]
            for s in root.walk()
            if "compiles" in s.attrs or "trace_s" in s.attrs]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)
    cell = run.load_cell(args.workload, args.tiny)

    import jax

    jax.config.update("jax_enable_x64", True)
    device = run.find_device(cell["chips"], rehearsal=args.tiny)
    kind = cell["modules"]
    clog = run.CompileLog()
    stages = {"imports": time.perf_counter() - T0}
    t = time.perf_counter()
    world = kind["world_builder"].build_world(cell["world"], args.seed)
    stages["records"] = time.perf_counter() - t
    t = time.perf_counter()
    program = kind["adapter"].Program(world, "local")
    stages["world"] = time.perf_counter() - t
    loop = run.Loop(program, world, cell["mix"])
    spans = CompileSpans(program.eng.spans)
    cycles, compiled = [], []
    for _ in range(cell["mix"]["warmup_cycles"]):
        mark = clog.mark()
        t = time.perf_counter()
        phases = loop.step()["phases"]
        cycles.append(time.perf_counter() - t)
        programs = clog.since(mark)["programs"]
        compiled.append({
            "on_spans": compiled_on(program.eng.spans.last()),
            "programs": [[name, round(secs, 3)] for name, secs in programs]})
        if len(cycles) == 1:
            first = phases
    stages["warmup_cycles"] = cycles
    stages["total"] = time.perf_counter() - T0
    print(json.dumps({
        "cell": cell["name"], "seed": args.seed, "device": device,
        "stages_s": stages,
        "ingestion_s": {k: first.get(k) for k in (
            "intake_restore", "intake_submit", "n_intake_calls")},
        "compiled": compiled}), flush=True)
    spans.close()
    program.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
