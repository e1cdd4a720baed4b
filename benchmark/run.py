#!/usr/bin/env python3
"""One run of one cell of the benchmark (BENCHMARK.json at the repo's root).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One loop drives every cell, from the client's side, in the one process
that holds the chip:

    build the world from its file, relabelled by the seed (world builder)
    the system under test over it (adapter): Engine + attach_oracle(local)
    for k in 0 .. :                       # `warmup_cycles` first: set-up
        finish and submit what the traffic file says (trafficgen.py)
        schedule_once()                   # the clock stops when the
                                          # verdicts are applied on the host
        note the verdicts; the admitted run, the victims wait
        stop when --seconds are over

Then, with the window closed, the peak memory read and the program's
state freed, the cell's plain reference decides every cycle of the
run again from the events the loop wrote down, and every verdict and the
end state are compared (reference.py).

A cell is files: its world benchmark/worlds/<config>.json, its traffic
benchmark/traffic/<mix>.json, and for each per-layer metric one reader
benchmark/layer_metrics/<name>.py, all found by the names in
BENCHMARK.json; and the four modules that state its kind of deployment
— world builder, adapter, reference, invariants — which the world file
names under `modules` (DEFAULT_MODULES where it names none). A new
cell, metric or kind of deployment is new files and new entries, and no
edit here (benchmark/README.md).

It exits non-zero before building anything unless JAX reports a TPU
with the chips the cell asks for. `JAX_PLATFORMS=cpu ... --tiny` is the
CPU rehearsal: the files' `tiny` sizes, stamped cpu, no metric printed.

The last line of standard output is the result, one JSON object.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reference  # noqa: E402
import trafficgen as traffic_mod  # noqa: E402

TRACE_DIR = os.path.join(HERE, ".trace")  # git-ignored, emptied per run

# The modules that state a kind of deployment, by role (README.md), for
# a world file that names no other under `modules`. These four are
# named here and nowhere else in run.py or control.py: every use goes
# through what load_cell resolved.
DEFAULT_MODULES = {"world_builder": "worldgen", "adapter": "sut",
                   "reference": "plain", "invariants": "invariants"}
# What every run has to have compared, whatever its world: a world
# file's `compared_at_least` adds to these and raises them, never less.
BASE_MINIMUMS = {"admissions_compared": 1, "evictions_compared": 1}
MODULE_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


def log(obj) -> None:
    print(json.dumps(obj) if not isinstance(obj, str) else obj,
          file=sys.stderr, flush=True)


def read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def read_config(name: str, tiny: bool = False) -> dict:
    """benchmark/worlds/<name>.json; with ``tiny`` the file's `tiny`
    sizes (the CPU tests' and rehearsals') laid over the real ones."""
    cfg = read_json(os.path.join(HERE, "worlds", name + ".json"))
    if tiny:
        cfg.update(cfg.get("tiny", {}))
    cfg["name"] = name
    return cfg


def module_from_file(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_module(name, asked_by: str):
    """benchmark/<name>.py, under its own name in sys.modules, so that a
    kind's modules can import one another. A name with no file is an
    error that names the file, never a fall back to a default."""
    path = os.path.join(HERE, f"{name}.py")
    if not MODULE_NAME.match(str(name)) or not os.path.isfile(path):
        raise SystemExit(f"{asked_by} names the module {name!r}: there "
                         f"is no file {path}")
    loaded = sys.modules.get(name)
    if loaded is None or os.path.abspath(
            getattr(loaded, "__file__", None) or "") != path:
        loaded = sys.modules[name] = module_from_file(name, path)
    return loaded


def load_kind(cfg: dict) -> tuple:
    """(modules, at_least) of a world file. The module of each role of
    DEFAULT_MODULES: the file's own where its `modules` names one. And
    what a run of it has to have compared, name -> (minimum, count):
    BASE_MINIMUMS, raised where its `compared_at_least` says more and
    counted by the comparison itself (count None); then the names that
    key adds, each counted by count_<name>(world, verdicts) of its
    reference module."""
    asked_by = os.path.join(HERE, "worlds", cfg["name"] + ".json")
    named = cfg.get("modules", {})
    if set(named) - set(DEFAULT_MODULES):
        raise SystemExit(
            f"{asked_by}: `modules` has the roles "
            f"{sorted(DEFAULT_MODULES)}, not "
            f"{sorted(set(named) - set(DEFAULT_MODULES))}")
    modules = {role: load_module(named.get(role, default), asked_by)
               for role, default in DEFAULT_MODULES.items()}
    at_least = {name: (least, None)
                for name, least in BASE_MINIMUMS.items()}
    for name, least in cfg.get("compared_at_least", {}).items():
        if isinstance(least, bool) or not isinstance(least, int) \
                or least < 1:
            raise SystemExit(
                f"{asked_by}: `compared_at_least` holds {name} to "
                f"{least!r}; a minimum is a whole number, 1 or more — "
                "a world file adds to what a run has to have compared "
                "and takes nothing away")
        if name in BASE_MINIMUMS:
            at_least[name] = (max(least, BASE_MINIMUMS[name]), None)
            continue
        count = getattr(modules["reference"], "count_" + name, None)
        if count is None:
            raise SystemExit(
                f"{asked_by}: `compared_at_least` names {name}, and "
                f"{modules['reference'].__file__} has no function "
                f"count_{name}(world, verdicts)")
        at_least[name] = (least, count)
    return modules, at_least


def load_cell(name: str, tiny: bool = False) -> dict:
    """The cell's entry in BENCHMARK.json with its world and its
    traffic mix read in, the modules its world file names and what a
    run of it has to have compared (load_kind), and the metrics it has
    to report."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it "
                         f"has {sorted(cells)}")
    cell = dict(cells[name])

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    cell["end_to_end"] = mine(bench["end_to_end"])
    cell["per_layer"] = mine(bench["per_layer"])
    cell["world"] = read_config(cell["config"], tiny)
    cell["mix"] = traffic_mod.read_mix(cell["traffic"], tiny)
    cell["modules"], cell["at_least"] = load_kind(cell["world"])
    return cell


def load_reader(name: str):
    """benchmark/layer_metrics/<name>.py, whose reduce(trace, spans,
    counters) returns the metric's value, or None where it found
    nothing to read."""
    readers = os.path.join(HERE, "layer_metrics")
    if readers not in sys.path:
        sys.path.insert(0, readers)  # the readers share _common.py
    path = os.path.join(readers, name + ".py")
    return module_from_file(
        "layer_metrics_" + name.replace(".", "_").replace("-", "_"),
        path).reduce


def find_device(chips: int, rehearsal: bool) -> dict:
    """The device as JAX reports it. No TPU, or fewer chips than the
    cell asks for, ends the run with no result."""
    import jax

    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    cpu_by_name = rehearsal and os.environ.get("JAX_PLATFORMS") == "cpu"
    if stamp["platform"] != "tpu" and not cpu_by_name:
        raise SystemExit(f"no TPU: JAX's platform here is "
                         f"{stamp['platform']!r}")
    if stamp["platform"] == "tpu" and stamp["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chips and JAX "
                         f"reports {stamp['count']}")
    return stamp


class CompileLog:
    """What JAX compiles, or reads back from its persistent cache, and
    traces, through jax.monitoring: one entry per program."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs: list = []
        self.traces = 0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs.append((str(fun_name), secs))
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def mark(self) -> tuple:
        return len(self.programs), self.traces

    def since(self, mark: tuple) -> dict:
        return {"programs": self.programs[mark[0]:],
                "traces": self.traces - mark[1]}


@contextlib.contextmanager
def span(name: str, on: bool):
    """A host span in the profiler's own trace, in a traced run."""
    if not on:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


def start_trace() -> None:
    """The profiler with Python call tracing off: at a thousand submits
    a cycle it would be most of the trace and of the host's time."""
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)


def device_peak_bytes(stats: dict) -> int:
    """What the process held of one chip's memory at its peak. The TPU
    runtime keeps two books: `peak_bytes_in_use` for buffers (arguments,
    results, the program's code) and `peak_bytes_reserved` for the
    loaded programs' temporaries, which no buffer can use while the
    program stays loaded. The chip holds both; the second counts here
    because the traffic makes the cycle program write it — a run whose
    window never takes the preemptor's branch is not `correct`
    (`evictions_compared`). Both books are printed beside the sum."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def phase_means(cycles: list) -> tuple:
    """(ms, counts): the mean per cycle of every key of the program's
    phases — its spans in milliseconds, and its counts (the `n_*` keys,
    obs.span.COUNT_KEYS) as counts."""
    keys = sorted({k for c in cycles for k in c["phases"]})
    mean = {k: sum(c["phases"].get(k, 0.0) for c in cycles) / len(cycles)
            for k in keys}
    return ({k: round(mean[k] * 1e3, 3) for k in keys
             if not k.startswith("n_")},
            {k: round(mean[k], 3) for k in keys if k.startswith("n_")})


class Loop:
    """The client: sends the traffic's events, calls the cycle, keeps
    the running sets, writes down what it sent and what was decided."""

    def __init__(self, program, world: dict, mix: dict):
        self.program = program
        self.gen = traffic_mod.Generator(mix, world)
        self.sets = traffic_mod.RunningSets(
            [cq["name"] for cq in world["cluster_queues"]],
            world["running"])
        self.events: list = []
        self.verdicts: list = []
        self.k = 0
        self.n_calls = len(program.executor_calls)

    def step(self, traced: bool = False) -> dict:
        p, sets = self.program, self.sets
        t0 = time.perf_counter()
        finishes, arrivals, now = self.gen.events(self.k, sets)
        with span("bench.finish", traced):
            for name in finishes:
                sets.remove(name)
                p.finish(name)
        t1 = time.perf_counter()
        with span("bench.submit", traced):
            for name, ci, k, created in arrivals:
                p.submit(name, ci, k, created)
        t2 = time.perf_counter()
        with span("bench.schedule_once", traced):
            v = p.cycle(now)
        t3 = time.perf_counter()
        sets.apply(v)
        self.events.append((finishes, arrivals, now))
        self.verdicts.append(v)
        self.k += 1
        calls = p.executor_calls[self.n_calls:]
        self.n_calls = len(p.executor_calls)
        return {"executor_calls_s": [e - s for s, e in calls],
                "finish_s": t1 - t0, "submit_s": t2 - t1,
                "schedule_s": t3 - t2,
                "cycle_s": time.perf_counter() - t0,
                "admitted": len(v["admitted"]),
                "evicted": sum(len(vs) for _h, vs in v["preempting"]),
                "idle": v["idle"], "mode": p.mode(),
                "phases": p.phases()}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, make_program=None, make_reference=None,
             rehearsal: bool = False, max_cycles: int | None = None,
             out=None) -> dict:
    """Everything after the look for a chip: set-up, warm-up, the
    window, the metrics, and — once the window has closed, the peak has
    been read and the program's state is freed — the comparison with
    the reference. ``make_program(world)`` builds the system under test
    (the tests pass broken ones) and ``make_reference(world)`` the
    reference (the control passes one in a lower precision); left out,
    they are the cell's own adapter and reference."""
    import jax

    kind = cell["modules"]
    make_program = make_program or (
        lambda w: kind["adapter"].Program(w, "local"))
    make_reference = make_reference or kind["reference"].Plain
    mix, cfg = cell["mix"], cell["world"]
    stages = {"imports": time.perf_counter() - T0}
    clog = CompileLog()

    t = time.perf_counter()
    world = kind["world_builder"].build_world(cfg, seed)
    stages["records"] = time.perf_counter() - t
    t = time.perf_counter()
    program = make_program(world)
    stages["world"] = time.perf_counter() - t
    loop = Loop(program, world, mix)

    # Warm-up: the cycles that compile (or read the cache) and fill the
    # bridge's caches. They are cycles like any other, and the
    # reference decides them too.
    t = time.perf_counter()
    for _ in range(mix["warmup_cycles"]):
        loop.step()
    stages["warmup_cycles"] = time.perf_counter() - t
    sizes0 = program.sizes()
    setup_s = time.perf_counter() - T0

    mark = clog.mark()
    n_trace = mix.get("traced_cycles", 4) if trace else 0
    cycles: list = []
    trace_window = None
    if n_trace:
        start_trace()
        t_trace = time.perf_counter()
    w0 = time.perf_counter()
    paused = 0.0  # writing the trace out is no part of the window
    while True:
        cycles.append(loop.step(traced=len(cycles) < n_trace))
        if n_trace and len(cycles) == n_trace:
            # Drain the device before the trace stops, so that what the
            # last traced cycle launched is in it.
            t = time.perf_counter()
            jax.effects_barrier()
            trace_window = t - t_trace
            jax.profiler.stop_trace()
            paused = time.perf_counter() - t
        now = time.perf_counter()
        if now - w0 - paused >= seconds or (
                max_cycles and len(cycles) >= max_cycles):
            break
    window_s = now - w0 - paused
    compiled = clog.since(mark)
    # The fullest chip's books.
    stats = max((d.memory_stats() or {} for d in jax.local_devices()),
                key=device_peak_bytes)
    peak = device_peak_bytes(stats)
    counters = program.counters()
    sizes1 = program.sizes()
    signatures = set(program.signatures)  # warm-up's and the window's
    calls = [d for c in cycles for d in c["executor_calls_s"]]
    end_state = program.state()
    program.close()
    del program

    # -- what a run must not have done ------------------------------
    not_device = sum(1 for c in cycles if c["mode"] != "device" or c["idle"])
    declined = (sum(counters["fallback_reasons"].values())
                + counters["hybrid_cycles"]
                + sum(counters["host_root_reasons"].values()))
    checks = {
        "cycles_not_on_device": (not_device, 0),
        "declined_by_bridge": (declined, 0),
        "breaker_not_closed": (int(counters["breaker"] != "closed"), 0),
        "compiles_in_window": (len(compiled["programs"])
                               + compiled["traces"], 0),
        "cycle_program_signatures": (len(signatures), 1),
    }

    # -- the comparison with the reference --------------------------
    cmp_ = reference.compare(world, loop.events, loop.verdicts,
                             make_reference, end_state)
    breaches = kind["invariants"].check(world, loop.events, loop.verdicts)
    checks["guarantees_broken"] = (len(breaches), 0)
    checks["cycles_differing"] = (cmp_["cycles_differing"], 0)
    checks["end_state_differs"] = (cmp_["end_state_differs"], 0)
    # What has to have been compared: every cycle, and in them the
    # layers the cell's `why` names — admissions and evictions both, in
    # every world; and what the world file adds of its own, counted by
    # its reference module over the reference's verdicts.
    at_least = {"cycles_compared": (cmp_["cycles_compared"],
                                    len(loop.events))}
    for name, (least, count) in cell["at_least"].items():
        at_least[name] = (count(world, cmp_["verdicts"]) if count
                          else cmp_[name], least)
    correct = all(v <= lim for v, lim in checks.values()) \
        and all(v >= lim for v, lim in at_least.values())

    # -- metrics ----------------------------------------------------
    n = len(cycles)
    admitted = sum(c["admitted"] for c in cycles)
    cycle_ms = [c["cycle_s"] * 1e3 for c in cycles]
    e2e = {"cycle_mean_ms": (window_s * 1e3 / n, "ms"),
           "setup_s": (setup_s, "s")}
    dev = dict(device, memory_peak_bytes=int(peak),
               peak_bytes_in_use=int(stats.get("peak_bytes_in_use", 0)),
               peak_bytes_reserved=int(stats.get("peak_bytes_reserved",
                                                 0)))
    result = {"correct": bool(correct), "attempted": n,
              "failed": not_device, "metrics": {}, "device": dev}

    spans = {"cycles": cycles, "window_s": window_s,
             "traced_cycles": n_trace}
    info = {"sizes": sizes1, "cfg": cfg, "device_kind": device["kind"],
            "pipeline": counters["pipeline"],
            "buckets": kind["world_builder"].device_bytes(cfg)}
    if trace:
        import trace_reduce

        reduced = trace_reduce.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        for m in cell["per_layer"]:
            value = load_reader(m["name"])(reduced, spans, info)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = reduced["breakdown"]
    else:
        for m in cell["end_to_end"]:
            value, unit = e2e[m["name"]]
            result["metrics"][m["name"]] = {"value": value, "unit": unit}
    if rehearsal:
        result["metrics"] = {}  # a CPU run measures nothing
    result["compared"] = dict(
        {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()},
        **{k: {"value": v, "limit_min": lim}
           for k, (v, lim) in at_least.items()})

    phase_ms, phase_counts = phase_means(cycles)
    log({"run": {
        "cell": cell["name"], "seed": seed, "cycles": n,
        "warmup_cycles": mix["warmup_cycles"], "admitted": admitted,
        "evicted": sum(c["evicted"] for c in cycles),
        "window_s": window_s, "traced_window_s": trace_window,
        "setup_stages_s": {k: round(v, 3) for k, v in stages.items()},
        "sizes_after_warmup": {k: sizes0[k] for k in ("pending", "running")},
        "sizes_at_end": {k: sizes1[k] for k in ("pending", "running")},
        "max_running_in_a_cohort": sizes1["max_running_in_a_cohort"],
        "signatures": sorted(
            str({k: v for k, v in sig[0]
                 if k in ("pending", "adm_cq", "adm_by_root")})
            for sig in signatures),
        "compiled_in_window": compiled, "counters": counters,
        "memory_stats": stats,
        "phase_ms_mean": phase_ms, "phase_count_mean": phase_counts,
        "executor_call_ms_mean": (sum(calls) * 1e3 / len(calls)
                                  if calls else None),
        "cycle_ms": [round(x) for x in cycle_ms],
        "call_ms": [[round(d * 1e3) for d in c["executor_calls_s"]]
                    for c in cycles],
        "client_ms_mean": {
            k: sum(c[k + "_s"] for c in cycles) * 1e3 / n
            for k in ("finish", "submit", "schedule")},
        "first_differing_cycle": cmp_["first_differing_cycle"],
        "guarantees_broken": breaches[:5],
        "reference_s": round(cmp_["reference_s"], 3),
        "e2e": {k: v for k, (v, _u) in e2e.items()}}})
    log("compared: " + "; ".join(
        f"{k} = {v['value']} (limit "
        + (f"{v['limit']}" if "limit" in v else f">= {v['limit_min']}")
        + ")"
        for k, v in result["compared"].items()))
    print(json.dumps(result), file=out or sys.stdout, flush=True)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--trace", type=int, default=0, choices=(0, 1))
    parser.add_argument("--tiny", action="store_true",
                        help="the files' tiny sizes; with "
                             "JAX_PLATFORMS=cpu, and only then, the CPU "
                             "is accepted, and no metric is printed")
    args = parser.parse_args(argv)
    cell = load_cell(args.workload, args.tiny)

    import jax

    jax.config.update("jax_enable_x64", True)
    device = find_device(cell["chips"], rehearsal=args.tiny)
    run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
             rehearsal=device["platform"] != "tpu")
    # `correct` false is a result, and the line says so; the exit code
    # is for a run that could not be made.
    return 0


if __name__ == "__main__":
    sys.exit(main())
