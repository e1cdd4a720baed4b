#!/usr/bin/env python
"""Profile the serving-path apply span at bench scale (VERDICT r3 #1).

Builds the bench_cycle_latency world (50k workloads x 1k CQs by
default), runs schedule_once under cProfile for the timed cycles, and
prints the top apply-phase costs.
"""

import cProfile
import io
import os
import pstats
import sys
import time


def main():
    # A host-time profiler (cProfile): pinned to the CPU backend.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from kueue_tpu.utils.startup import configure_compile_cache

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    configure_compile_cache()

    n_workloads = int(os.environ.get("PROF_WORKLOADS", "50000"))
    n_cohorts = int(os.environ.get("PROF_COHORTS", "200"))
    n_cycles = int(os.environ.get("PROF_CYCLES", "4"))
    fair = os.environ.get("PROF_FAIR") == "1"

    from bench import build_cycle_engine
    from kueue_tpu.bench.scenario import baseline_like, hierarchical_fair

    if fair:
        scen = hierarchical_fair(n_workloads=n_workloads)
    else:
        scen = baseline_like(n_cohorts=n_cohorts, n_workloads=n_workloads)
    eng = build_cycle_engine(scen, fair=fair)
    eng.attach_perf()
    eng.apply_serving_gc_posture()

    # untimed first cycle: compile + initial encode
    t0 = time.perf_counter()
    r = eng.schedule_once()
    print(f"cycle 0 (compile): {time.perf_counter()-t0:.2f}s "
          f"admitted={r.stats.admitted}", file=sys.stderr)

    prof = cProfile.Profile()
    times = []
    phases = []
    for k in range(n_cycles):
        t0 = time.perf_counter()
        prof.enable()
        r = eng.schedule_once()
        prof.disable()
        el = time.perf_counter() - t0
        times.append(el)
        ph = dict(getattr(eng, "last_cycle_phases", {}))
        phases.append(ph)
        print(f"cycle {k+1}: {el*1000:.1f}ms admitted={r.stats.admitted} "
              f"phases={ {p: round(v*1000,1) for p,v in ph.items()} }",
              file=sys.stderr)
        if not r.stats.admitted:
            break

    mean = {p: sum(ph.get(p, 0) for ph in phases) / len(phases)
            for p in ("encode", "device", "apply", "finalize")}
    print(f"mean phases (ms): "
          f"{ {p: round(v*1000,1) for p,v in mean.items()} }",
          file=sys.stderr)

    # The always-on attribution table, in the same apply.* vocabulary
    # as /metrics and the bench detail — so cProfile rows below and
    # production telemetry name the same sub-steps.
    subs = eng.perf.subphases()
    if subs:
        print("\nobs/perf apply-subphase attribution "
              f"(all timed cycles, n={len(phases)}):")
        print(f"  {'subphase':<26} {'n':>5} {'sum_ms':>9} "
              f"{'mean_ms':>9} {'p95_ms':>9}")
        for name in sorted(subs):
            h = subs[name]
            mean_ms = (h.sum / h.total * 1000.0) if h.total else 0.0
            print(f"  {name:<26} {h.total:>5} {h.sum * 1000.0:>9.2f} "
                  f"{mean_ms:>9.3f} {h.quantile(0.95) * 1000.0:>9.3f}")
    else:
        print("\nobs/perf apply-subphase attribution: no samples "
              "(perf recorder not attached?)")

    s = io.StringIO()
    ps = pstats.Stats(prof, stream=s).sort_stats("cumulative")
    ps.print_stats(45)
    print(s.getvalue())
    s = io.StringIO()
    ps = pstats.Stats(prof, stream=s).sort_stats("tottime")
    ps.print_stats(35)
    print(s.getvalue())


if __name__ == "__main__":
    main()
