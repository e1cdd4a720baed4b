#!/usr/bin/env python3
"""The quickest proof that the admission cycle still starts — and decides
right — on the attached chip.

One process drives the main path once, through the entry points a user
calls, at the size a user would call real, and compares each phase
with the repo's plain reference: the sequential core in
kueue_tpu/scheduler/, driven by an Engine with no oracle attached, on
the same world built from the same seed. Decision digests, cycle for
cycle — except preempt: final admission map only (see run_local).

  kernels       both Pallas kernels against their jnp references at the
                shapes below
  flat_serving  Engine + attach_oracle() + schedule_once() on the
                BASELINE world, 50,000 pending x 1,000 ClusterQueues
                (what `python -m kueue_tpu.serve` runs): CYCLES
                cycles, the first counted as set-up
  flat_drain    BatchedDrainSolver.solve() — the whole drain as one
                device program — on the same world, against the
                reference's whole drain
  preempt       the fused classical preemptor on the preempt_churn world
                (4,000 pending, 20 cohorts), by who is admitted at the end
  fair          the fair-sharing commit on the hier_fair world (40,000
                workloads, 500 ClusterQueues), whole drain
  tas           the batched TAS planner on the 640-node world (800 gangs)

A digest mismatch, a whole-cycle fallback, a missing Mosaic kernel in
the compiled cycle program or a Pallas kernel in interpret mode is a
failure, not a note. Everything else printed (seconds, counters, cache
state, peak memory) is one run's observation, labelled as such — not a
metric.

    python3 chip_smoke.py                 # the chip, real size
    python3 chip_smoke.py --sidecar       # only the two-process layout of
                                          # deploy/: oracle service on the
                                          # chip, engine beside it
    JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny    # CPU rehearsal

Without --tiny the script needs a TPU whatever JAX_PLATFORMS says, and
exits non-zero before it builds anything when there is none. It reads
nothing git would not commit: the native heap is rebuilt from
native/kueue_native.cpp (or the Python heap serves), the TAS calibration
record is pointed at an absent file under chiprun_out/, and the compile
cache is wherever JAX_COMPILATION_CACHE_DIR says, else .jax_cache/ here.

One JSON object per phase; the last line of standard output is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import time
import zlib

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# flat: (cohorts of 5 ClusterQueues, workloads); preempt: (pending,
# cohorts of 5); fair: (roots of 10, workloads); tas: (gangs, queues).
REAL = dict(flat=(200, 50_000), preempt=(4_000, 20), fair=(50, 40_000),
            tas=(800, 8))
TINY = dict(flat=(10, 600), preempt=(120, 4), fair=(4, 600), tas=(60, 4))
# Serving cycles on the flat world, the first counted as set-up.
CYCLES = 12


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def crc(obj) -> str:
    return f"{zlib.crc32(json.dumps(obj, sort_keys=True).encode()):08x}"


class CompileLog:
    """What JAX says about its compiles, through jax.monitoring: seconds
    of backend compile per program (a persistent-cache hit counts its
    retrieval), seconds of tracing and of lowering to MLIR — which no
    cache saves — and the persistent cache's requests / hits / writes."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs: list = []
        self.front = {"trace": 0.0, "lower": 0.0}
        self.cache = {"requests": 0, "hits": 0, "writes": 0}
        self.run_cache = dict(self.cache)  # what take() has handed out
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs.append((str(fun_name), secs))
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.front["trace"] += secs
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.front["lower"] += secs

    def _event(self, event, **_):
        key = {"/jax/compilation_cache/compile_requests_use_cache":
               "requests",
               "/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "writes"}.get(event)
        if key:
            self.cache[key] += 1

    def take(self) -> dict:
        """Since the last take: the compiles, slowest first, and whether
        the cache mostly served them (warm), served none (cold) or
        neither. A program near the cache's compile-time threshold is
        written in one run and not the next, so a warm run can still
        write one or two."""
        progs, self.programs = self.programs, []
        front, self.front = self.front, {"trace": 0.0, "lower": 0.0}
        cache, self.cache = self.cache, {"requests": 0, "hits": 0,
                                         "writes": 0}
        for key, n in cache.items():
            self.run_cache[key] += n
        progs.sort(key=lambda p: -p[1])
        return {"programs": len(progs),
                "seconds": round(sum(s for _, s in progs), 3),
                "slowest": [[n, round(s, 3)] for n, s in progs[:4]],
                "trace_s": round(front["trace"], 3),
                "lower_s": round(front["lower"], 3),
                "cache": dict(cache, state=self.state(cache))}

    @staticmethod
    def state(cache: dict) -> str:
        return ("untouched" if not cache["requests"] else
                "cold" if not cache["hits"] else
                "warm" if cache["hits"] > cache["writes"] else "mixed")


def fresh_heap() -> str:
    """Which pending-queue heap serves this run: the C++ one, built anew
    from native/kueue_native.cpp so that no git-ignored library is
    picked up, or — with no toolchain — the Python one. Called before
    the first heap is made: a loaded library stays loaded."""
    from kueue_tpu.utils import native

    shutil.rmtree(os.path.join(ROOT, "native", "build"), ignore_errors=True)
    native.ensure_built(block=True)
    return "native" if native.native_available() else "python"


def drive(eng, max_cycles: int) -> dict:
    """schedule_once() until the world is quiet (bench._drain_engine's
    loop) or ``max_cycles``. The clock around each call stops when the
    verdicts are applied on the host. ``chain[k]`` is the decision digest
    after k+1 deciding cycles; a cycle that decides nothing is skipped,
    as the simulator's host-vs-device differential does
    (sim/harness.py), because the host path reports it as an entry-less
    result and the device path as idle."""
    from kueue_tpu.replay.trace import canonical_decisions, decision_digest

    digest, chain, seconds, phases, modes = 0, [], [], [], []
    admitted = preempting = 0
    order: list = []  # [cycle, key, cluster queue, flavors], commit order
    for _ in range(max_cycles):
        t0 = time.perf_counter()
        r = eng.schedule_once()
        seconds.append(time.perf_counter() - t0)
        if r is None:
            break
        phases.append(dict(eng.last_cycle_phases))
        modes.append(eng.last_cycle_mode)
        decisions = canonical_decisions(r)
        if decisions:
            digest = decision_digest(decisions, digest)
            chain.append(f"{digest:08x}")
        for e in sorted(r.assumed, key=lambda e: e.commit_position):
            adm = e.obj.status.admission
            order.append([len(chain) - 1, e.info.key, adm.cluster_queue,
                          dict(adm.pod_set_assignments[0].flavors)])
        admitted += r.stats.admitted
        preempting += r.stats.preempting
        if r.stats.preempting:
            eng.tick(0.0)  # evictions land; victims requeue
        elif not r.stats.admitted:
            break
    return {"cycles": len(phases), "admitted": admitted,
            "preempting": preempting, "chain": chain, "order": order,
            "seconds": seconds, "phases": phases, "modes": modes}


def bridge_counters(eng) -> dict:
    """bench._device_share plus the breaker."""
    import bench

    return dict(bench._device_share(eng),
                breaker=eng.oracle.supervisor.status()["state"])


def check_bridge(eng, flat: bool) -> list:
    """The degradation contract stays in the product; here a cycle the
    device path declined is a failure. The one whole-cycle hand-over
    that is not a decline is the quiet end of a drain
    (`idle-inadmissible`: only parked workloads left, nothing to
    decide), which the flat phase never reaches."""
    b = eng.oracle
    bad = []
    declined = {k: v for k, v in b.fallback_reasons.items()
                if flat or k != "idle-inadmissible"}
    if declined:
        bad.append(f"whole-cycle fallback: {declined}")
    if b.supervisor.status()["state"] != "closed":
        bad.append(f"breaker {b.supervisor.status()['state']}")
    if b.cycles_on_device == 0:
        bad.append("no device cycle")
    if flat and (b.cycles_fallback or b.cycles_hybrid
                 or b.host_root_reasons):
        bad.append(f"{b.cycles_fallback} fallback and {b.cycles_hybrid} "
                   f"hybrid cycles on the flat world: "
                   f"{b.host_root_reasons}")
    return bad


def tap_executor(eng) -> dict:
    """Record, from outside, what the bridge hands its executor: the
    first cycle_step call's shapes and statics (to compile the very
    program again and read its text) and the seconds every call held
    the host."""
    import jax

    ex = eng.oracle.executor
    inner = ex.cycle_step
    tap: dict = {"seconds": []}

    def cycle_step(tensors, statics):
        if "statics" not in tap:
            tap["statics"] = dict(statics)
            tap["shapes"] = {
                k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in tensors.items()}
        t0 = time.perf_counter()
        out = inner(tensors, statics)
        tap["seconds"].append(time.perf_counter() - t0)
        return out

    ex.cycle_step = cycle_step
    return tap


def cycle_program_text(tap: dict) -> tuple:
    """Compile the cycle program exactly as the bridge called it and
    return (its text, seconds). JAX's in-memory caches hold what the
    first cycle compiled, so this compiles nothing again."""
    from kueue_tpu.oracle import batched

    t0 = time.perf_counter()
    text = batched.cycle_step.lower(
        **tap["shapes"], **tap["statics"]).compile().as_text()
    return text, time.perf_counter() - t0


def count_calls(module, name) -> list:
    """Count, from outside, the calls of ``module.name``; returns the
    one-element counter."""
    inner, n = getattr(module, name), [0]

    def counted(*args, **kwargs):
        n[0] += 1
        return inner(*args, **kwargs)

    setattr(module, name, counted)
    return n


def rounded(xs) -> list:
    return [round(x, 4) for x in xs]


def mean_phases(phases: list) -> dict:
    keys = sorted({k for p in phases for k in p})
    return {k: round(sum(p.get(k, 0.0) for p in phases)
                     / max(1, len(phases)), 4) for k in keys}


# --------------------------------------------------------------- phases


def phase_kernels(sizes, seed, on_tpu, leaf_kernel_calls) -> dict:
    """Both Pallas kernels against their jnp references. No engine path
    launches the leaf-count kernel today (ops/tas.tas_place and its
    batched forms count leaves inline; `leaf_states` has no caller
    outside tests), so this is the only place the chip runs it — the
    `end` line's count of its calls by the other phases says so."""
    import jax
    import numpy as np

    from kueue_tpu.ops import pallas_kernels as pk
    from kueue_tpu.ops.tas import _leaf_states_jnp
    from kueue_tpu.oracle.batched import BIG_RANK
    from kueue_tpu.tensor.schema import pow2_bucket

    bad = []
    if on_tpu and not pk.pallas_enabled():
        bad.append("pallas_enabled() is false on the chip")
    if on_tpu and pk._interpret():
        bad.append("Pallas kernels would run in interpret mode")
    rng = np.random.default_rng(seed)
    n_cqs = sizes["flat"][0] * 5
    W = pow2_bucket(sizes["flat"][1], 64)
    rank = rng.permutation(W).astype(np.int64)
    rank[rng.random(W) < 0.2] = BIG_RANK  # inactive rows
    wl_cq = rng.integers(0, n_cqs, W).astype(np.int32)
    heads = {}
    if pk.pallas_enabled():
        got = np.asarray(pk.select_heads(rank, wl_cq, n_cqs, BIG_RANK))
        want = np.asarray(jax.ops.segment_min(rank, wl_cq,
                                              num_segments=n_cqs))
        heads = {"W": W, "C": n_cqs,
                 "equal": bool(np.array_equal(got, want))}
        if not heads["equal"]:
            bad.append("select_heads disagrees with segment_min")
    leaves = {}
    for L in (640, 5_120):
        free = rng.integers(0, 64_000, (L, 2)).astype(np.int64)
        used = rng.integers(0, 32_000, (L, 2)).astype(np.int64)
        zero = np.zeros_like(used)
        per_pod = np.array([1000, 0], np.int64)
        mask = rng.random(L) < 0.9
        before = leaf_kernel_calls[0]
        got = np.asarray(pk.leaf_fit_counts(free, used, zero, per_pod,
                                            mask))
        served = ("kernel" if leaf_kernel_calls[0] > before
                  else "reference")
        want = np.asarray(_leaf_states_jnp(free, used, zero, per_pod,
                                           mask))
        leaves[str(L)] = {"served": served,
                          "equal": bool(np.array_equal(got, want))}
        if not leaves[str(L)]["equal"]:
            bad.append(f"leaf_fit_counts disagrees at {L} leaves")
        if on_tpu and served != "kernel":
            bad.append(f"the reference served {L} int32-sized leaves")
    # Memory in bytes reaches 2^31: the kernel gives way, by design.
    big = np.full((640, 1), 1 << 33, np.int64)
    gives_way = not pk.leaf_fit_counts_in_range(
        big, np.zeros_like(big), np.zeros_like(big),
        np.array([1 << 30], np.int64))
    return {"phase": "kernels", "ok": not bad, "failures": bad,
            "pallas_enabled": pk.pallas_enabled(),
            "interpret": pk._interpret(), "select_heads": heads,
            "leaf_fit_counts": leaves,
            "leaf_kernel_gives_way_at_2^31": gives_way}


def flat_engine(sizes, seed, oracle) -> tuple:
    """The BASELINE world in an engine, as bench.py's cycle_latency
    builds it; returns (engine, seconds it took)."""
    import bench
    from kueue_tpu.bench.scenario import baseline_like

    n_cohorts, n_workloads = sizes["flat"]
    t0 = time.perf_counter()
    eng = bench.build_cycle_engine(
        baseline_like(n_cohorts=n_cohorts, n_workloads=n_workloads,
                      seed=seed), oracle=oracle)
    return eng, time.perf_counter() - t0


def flat_reference(sizes, seed) -> dict:
    """The sequential core's whole drain of the BASELINE world."""
    eng, built = flat_engine(sizes, seed, "off")
    ref = drive(eng, 10_000)
    ref["engine"] = eng
    ref["build_s"] = built
    return ref


def phase_flat_serving(sizes, seed, eng, built, ref, clog, on_tpu) -> dict:
    n_cohorts, n_workloads = sizes["flat"]
    local = type(eng.oracle.executor).__name__ == "LocalExecutor"
    tap = tap_executor(eng)
    run = drive(eng, CYCLES)
    compiles = clog.take()
    bad = check_bridge(eng, flat=True)
    if run["cycles"] != CYCLES or any(m != "device" for m in run["modes"]):
        bad.append(f"{run['cycles']} of {CYCLES} cycles ran, modes "
                   f"{sorted(set(run['modes']))}")
    want = ref["chain"][:len(run["chain"])]
    if not run["chain"] or run["chain"] != want:
        bad.append("decision digests differ from the sequential core")
    out = {"phase": "flat_serving",
           "oracle": "local" if local else "sidecar",
           "workloads": n_workloads, "cqs": n_cohorts * 5, "seed": seed,
           "cycles": run["cycles"], "admitted": run["admitted"],
           "digest_device": run["chain"][-1] if run["chain"] else None,
           "digest_sequential": want[-1] if want else None,
           "build_s": round(built, 3),
           "setup_cycle_s": round(run["seconds"][0], 3),
           "later_cycles_s": rounded(run["seconds"][1:]),
           "mean_phases_later_s": mean_phases(run["phases"][1:]),
           "executor_call_s": rounded(tap["seconds"]),
           "compiles": compiles, **bridge_counters(eng)}
    if local:
        text, secs = cycle_program_text(tap)
        out["cycle_program"] = {
            "W_bucket": tap["shapes"]["pending"].shape[0],
            "args": len(tap["shapes"]),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "x64_split_calls": text.count("X64SplitLow"),
            "recompile_s": round(secs, 3)}
        if on_tpu and not out["cycle_program"]["tpu_custom_calls"]:
            bad.append("no tpu_custom_call in the compiled cycle program")
    out["ok"], out["failures"] = not bad, bad
    return out


def whole_drain(scen, fair: bool) -> tuple:
    """BatchedDrainSolver.solve() as bench.py's drain cells run it: one
    cycle first to compile (set-up), then the whole drain. Returns
    (solver, rows [cycle, key, cluster queue, flavors] in commit order,
    stats, set-up seconds, drain seconds — readback and decode of the
    decisions included)."""
    from kueue_tpu.cache.snapshot import build_snapshot
    from kueue_tpu.oracle.batched import BatchedDrainSolver

    snap = build_snapshot(scen.cluster_queues, scen.cohorts, scen.flavors,
                          [])
    infos = scen.pending_infos()
    t0 = time.perf_counter()
    BatchedDrainSolver(snap, infos, fair=fair).solve(max_cycles=1)
    setup = time.perf_counter() - t0
    solver = BatchedDrainSolver(snap, infos, fair=fair)
    t0 = time.perf_counter()
    decisions, stats = solver.solve()
    elapsed = time.perf_counter() - t0
    rows = [[d.cycle, d.key, d.cluster_queue, d.flavors] for d in decisions]
    return solver, rows, stats, setup, elapsed


def phase_flat_drain(sizes, seed, ref, clog) -> dict:
    """The checks __graft_entry__.dryrun_multichip makes between two
    device drains, made here between the device drain and the
    sequential core: admitted count, final usage, and per workload its
    cycle, its place in the commit order and its flavors."""
    import numpy as np

    from kueue_tpu.bench.scenario import baseline_like

    n_cohorts, n_workloads = sizes["flat"]
    solver, got, stats, setup, elapsed = whole_drain(
        baseline_like(n_cohorts=n_cohorts, n_workloads=n_workloads,
                      seed=seed), fair=False)
    bad = []
    if stats["needs_oracle"]:
        bad.append("the drain flagged workloads for the host preemptor")
    if not got or got != ref["order"]:
        bad.append("admission order, cycles or flavors differ from the "
                   "sequential core")
    w = solver.world
    rows = np.zeros((w.num_cqs, w.nominal.shape[1]), np.int64)
    for ci, name in enumerate(w.cq_names):
        for fr, v in ref["engine"].cache.cq_usage.get(name, {}).items():
            rows[ci, w.flavor_names.index(fr.flavor) * w.num_resources
                 + w.resource_names.index(fr.resource)] = v
    if not np.array_equal(stats["final_usage"][:w.num_cqs], rows):
        bad.append("final usage differs from the sequential core")
    return {"phase": "flat_drain", "ok": not bad, "failures": bad,
            "workloads": n_workloads, "cqs": w.num_cqs, "seed": seed,
            "cycles": stats["cycles"], "admitted": stats["admitted"],
            "digest_device": crc(got), "digest_sequential": crc(ref["order"]),
            "sequential_cycles": ref["cycles"],
            "sequential_drain_s": round(sum(ref["seconds"]), 3),
            "setup_s": round(setup, 3), "drain_s": round(elapsed, 3),
            "compiles": clog.take()}


def admission_map(eng) -> list:
    """Who holds quota where, with which flavors, at the end."""
    return sorted(
        [k, w.status.admission.cluster_queue,
         [sorted(psa.flavors.items())
          for psa in w.status.admission.pod_set_assignments]]
        for k, w in eng.workloads.items() if w.is_admitted)


def engine_pair_phase(name, build, seed, clog, by_cycle) -> dict:
    """A world driven to quiet twice — device path and sequential core —
    and compared: ``by_cycle`` by the decision digest, cycle for cycle;
    otherwise by the per-workload admission map at the end, with the
    cycle digests printed beside it."""
    t0 = time.perf_counter()
    ref_eng = build(seed, "off")
    ref = drive(ref_eng, 5_000)
    ref_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng = build(seed, "local")
    built = time.perf_counter() - t0
    run = drive(eng, 5_000)
    got, want = admission_map(eng), admission_map(ref_eng)
    bad = check_bridge(eng, flat=False)
    if not got or got != want:
        bad.append("admission maps differ from the sequential core")
    if by_cycle and run["chain"] != ref["chain"]:
        bad.append("decision digests differ from the sequential core")
    return {"phase": name, "ok": not bad, "failures": bad, "seed": seed,
            "compared": "cycle digests" if by_cycle else "admission map",
            "cycles": run["cycles"], "admitted": run["admitted"],
            "preempting": run["preempting"], "admitted_at_end": len(got),
            "digest_device": run["chain"][-1] if by_cycle else crc(got),
            "digest_sequential": (ref["chain"][-1] if by_cycle
                                  else crc(want)),
            "cycle_digests_equal": run["chain"] == ref["chain"],
            "sequential_cycles": ref["cycles"],
            "build_and_fill_s": round(built, 3),
            "drain_s": round(sum(run["seconds"]), 3),
            "sequential_s": round(ref_s, 3),
            "mean_phases_s": mean_phases(run["phases"]),
            "compiles": clog.take(), **bridge_counters(eng)}


def phase_fair(sizes, seed, clog) -> dict:
    """hier_fair's whole drain: the device tournament against the
    sequential fair-sharing iterator, per workload its cycle and
    flavors (fair-mode positions are rounds within a root, not a global
    order, so order inside a cycle is not compared)."""
    import bench
    from kueue_tpu.bench.scenario import hierarchical_fair

    n_roots, n_workloads = sizes["fair"]

    def world():
        return hierarchical_fair(n_roots=n_roots, n_workloads=n_workloads,
                                 seed=seed)

    t0 = time.perf_counter()
    ref = drive(bench.build_cycle_engine(world(), fair=True, oracle="off"),
                10_000)
    ref_s = time.perf_counter() - t0
    solver, got, stats, setup, elapsed = whole_drain(world(), fair=True)
    got.sort()
    want = sorted(ref["order"])
    bad = []
    if not got or got != want:
        bad.append("admitted set, cycles or flavors differ from the "
                   "sequential fair-sharing core")
    return {"phase": "fair", "ok": not bad, "failures": bad,
            "workloads": n_workloads, "cqs": solver.world.num_cqs,
            "seed": seed, "cycles": stats["cycles"],
            "admitted": stats["admitted"],
            "digest_device": crc(got), "digest_sequential": crc(want),
            "sequential_cycles": ref["cycles"],
            "sequential_s": round(ref_s, 3),
            "setup_s": round(setup, 3), "drain_s": round(elapsed, 3),
            "compiles": clog.take()}


# ------------------------------------------------------------ the runs


def run_local(args, sizes, device) -> list:
    """Every phase in this one process, which holds the chip."""
    import jax

    import bench
    from kueue_tpu.ops import pallas_kernels
    from kueue_tpu.utils.startup import configure_compile_cache

    on_tpu = device["platform"] == "tpu"
    cache_dir = configure_compile_cache()
    clog = CompileLog()
    emit({"phase": "start", "device": device, "seed": args.seed,
          "tiny": args.tiny, "jax": jax.__version__,
          "pending_queue_heap": fresh_heap(),
          "compile_cache_dir": cache_dir,
          "compile_cache_entries_at_start": (
              len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
              else 0),
          "tas_calibration_record": os.path.exists(
              os.environ["KUEUE_TPU_TAS_CALIBRATION"])})

    leaf_kernel_calls = count_calls(pallas_kernels, "_leaf_pallas")
    results = [phase_kernels(sizes, args.seed, on_tpu, leaf_kernel_calls)]
    emit(results[-1])
    in_kernels_phase = leaf_kernel_calls[0]
    ref = flat_reference(sizes, args.seed)
    eng, built = flat_engine(sizes, args.seed, "local")
    results.append(phase_flat_serving(sizes, args.seed, eng, built, ref,
                                      clog, on_tpu))
    emit(results[-1])
    del eng
    results.append(phase_flat_drain(sizes, args.seed, ref, clog))
    emit(results[-1])
    del ref
    n_pending, n_cohorts = sizes["preempt"]
    # By cycle since PR 34: the two paths parted where a cohort's entry
    # was skipped for a target it shared with an earlier preemptor and
    # the device's commit booked its usage all the same.
    results.append(engine_pair_phase(
        "preempt",
        lambda seed, oracle: bench.preempt_churn_engine(
            n_pending, n_cohorts=n_cohorts, seed=seed, oracle=oracle),
        args.seed + 7, clog, by_cycle=True))
    emit(results[-1])
    results.append(phase_fair(sizes, args.seed + 1, clog))
    emit(results[-1])
    n_workloads, n_cqs = sizes["tas"]
    results.append(engine_pair_phase(
        "tas",
        lambda seed, oracle: bench.tas_engine(
            n_workloads, n_cqs=n_cqs, seed=seed, oracle=oracle),
        args.seed + 11, clog, by_cycle=True))
    emit(results[-1])
    stats = jax.local_devices()[0].memory_stats() or {}
    emit({"phase": "end", "seconds": round(time.perf_counter() - T0, 1),
          "compile_cache": dict(clog.run_cache,
                                state=clog.state(clog.run_cache)),
          "leaf_kernel_calls_by_engine_phases": (
              leaf_kernel_calls[0] - in_kernels_phase),
          "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
          "bytes_limit": stats.get("bytes_limit")})
    return results


def run_sidecar(args, sizes) -> tuple:
    """deploy/'s layout on one machine: the oracle service holds the
    chip, and this process — the engine — must open no accelerator.
    Nothing here touches JAX before attach_oracle(remote_address=...)."""
    from kueue_tpu.utils.startup import measurement_device

    log_path = os.path.join(OUT, "oracle_service.log")
    env = dict(os.environ, PYTHONPATH=ROOT, PYTHONUNBUFFERED="1")
    with open(log_path, "w", encoding="utf-8") as log:
        child = subprocess.Popen(
            [sys.executable, "-m", "kueue_tpu.oracle.service",
             "--host", "127.0.0.1", "--port", "0"],
            stdout=log, stderr=subprocess.STDOUT, env=env, cwd=ROOT)
    try:
        line = None
        deadline = time.monotonic() + 180
        while line is None and time.monotonic() < deadline:
            if child.poll() is not None:
                break
            with open(log_path, encoding="utf-8") as f:
                line = next((ln for ln in f if "listening on" in ln), None)
            time.sleep(0.2)
        if line is None:
            with open(log_path, encoding="utf-8") as f:
                sys.stderr.write(f.read()[-4000:])
            raise SystemExit("the oracle service did not come up")
        m = re.search(r"listening on ([\d.]+):(\d+) device=(\{.*\})", line)
        device = measurement_device(rehearsal=args.tiny,
                                    stamp=json.loads(m.group(3)))
        emit({"phase": "start", "layout": "sidecar", "device": device,
              "oracle_service": f"{m.group(1)}:{m.group(2)}",
              "seed": args.seed, "tiny": args.tiny,
              "pending_queue_heap": fresh_heap()})

        # The engine under test first: it is attach_oracle that must
        # keep this process off the chip, not a pin made here.
        eng, built = flat_engine(sizes, args.seed,
                                 f"{m.group(1)}:{m.group(2)}")
        import jax

        result = phase_flat_serving(
            sizes, args.seed, eng, built, flat_reference(sizes, args.seed),
            CompileLog(), device["platform"] == "tpu")
        platforms = sorted({d.platform for d in jax.devices()})
        result["engine_process"] = {
            "jax_platforms": jax.config.jax_platforms,
            "backends_opened": platforms}
        if platforms != ["cpu"] or child.poll() is not None:
            result["ok"] = False
            result["failures"].append(
                f"engine process opened {platforms}; oracle service "
                f"exit code {child.poll()}")
        emit(result)
        return [result], device
    finally:
        child.terminate()
        try:
            child.wait(timeout=20)
        except subprocess.TimeoutExpired:
            child.kill()
            child.wait()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="world seed. 0 builds bench.py's worlds and "
                             "is the one seed known to pass, on the chip "
                             "and at --tiny. At 1, 2 and 3 the preempt or "
                             "the fair phase fails at --tiny on the CPU: "
                             "there the device path and the sequential "
                             "core disagree (ROADMAP A0), whatever the "
                             "device")
    parser.add_argument("--tiny", action="store_true",
                        help="rehearsal sizes; with JAX_PLATFORMS=cpu, and "
                             "only then, the CPU is accepted")
    parser.add_argument("--sidecar", action="store_true",
                        help="run only the two-process layout: oracle "
                             "service on the chip, engine beside it")
    args = parser.parse_args(argv)
    sizes = TINY if args.tiny else REAL

    os.makedirs(OUT, exist_ok=True)
    # No calibration record, and no library from outside the checkout.
    record = os.path.join(OUT, "tas_crossover.json")
    if os.path.exists(record):
        os.unlink(record)
    os.environ["KUEUE_TPU_TAS_CALIBRATION"] = record
    os.environ.pop("KUEUE_TPU_NATIVE_LIB", None)

    if args.sidecar:
        results, device = run_sidecar(args, sizes)
    else:
        import jax

        from kueue_tpu.utils.startup import measurement_device

        jax.config.update("jax_enable_x64", True)
        device = measurement_device(rehearsal=args.tiny)
        results = run_local(args, sizes, device)

    failed = [r["phase"] for r in results if not r["ok"]]
    if failed:
        emit({"ok": False, "failed": failed, "device": device})
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
