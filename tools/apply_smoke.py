#!/usr/bin/env python
"""apply-smoke: the end-to-end columnar-apply check behind ``make
apply-smoke``.

Three proofs over the cycle commit path (controllers/colapply.py,
oracle/engine_bridge.py):

  * digest identity: both KUEUE_TPU_COLUMNAR arms drain the same churn
    world (priority preemption, requeues — both the fast and the slow
    apply shapes) to byte-identical chained decision digests and final
    admitted state;
  * crash mid-apply (subprocess): a child draining on the columnar path
    is SIGKILLed by the fault layer at the Nth admission — the ordinal
    counts bulk-path admissions — then rebuilt from its journal; the
    converged admitted set must equal an uninterrupted control's: zero
    lost, zero duplicate admissions;
  * torn journal tail (subprocess): same child, but the fault plants a
    flushed newline-less fragment before dying; the rebuild must trim
    it and still converge.

Exits non-zero on the first failure.
"""

import os
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ARMS = (("serial", "0"), ("columnar", "1"))

KILL_AT = 12
STAGE_TIMEOUT = 180


def fail(msg: str) -> int:
    print(f"apply-smoke FAIL: {msg}", file=sys.stderr)
    return 1


# -- the world: a compact preemption-churn cell. Low-priority fill,
# then high-priority arrivals that preempt — admissions, evictions and
# requeues every cycle, so the columnar fast path AND the per-entry
# slow path both run.

def build_world(journal_path=None):
    from kueue_tpu.api.types import (
        ClusterQueue,
        ClusterQueuePreemption,
        Cohort,
        FlavorQuotas,
        LocalQueue,
        PodSet,
        PreemptionPolicy,
        ResourceFlavor,
        ResourceGroup,
        ResourceQuota,
        Workload,
    )
    from kueue_tpu.controllers.engine import Engine
    from kueue_tpu.store.journal import attach_new_journal

    eng = Engine()
    eng.create_resource_flavor(ResourceFlavor("default"))
    for c in range(2):
        eng.create_cohort(Cohort(f"co{c}"))
    for i in range(6):
        eng.create_cluster_queue(ClusterQueue(
            name=f"cq{i}", cohort=f"co{i % 2}",
            preemption=ClusterQueuePreemption(
                within_cluster_queue=PreemptionPolicy.LOWER_PRIORITY,
                reclaim_within_cohort=PreemptionPolicy.LOWER_PRIORITY),
            resource_groups=(ResourceGroup(
                ("cpu",), (FlavorQuotas("default",
                                        {"cpu": ResourceQuota(4000)}),)),)))
        eng.create_local_queue(LocalQueue(f"lq{i}", "default", f"cq{i}"))
    if journal_path:
        attach_new_journal(eng, journal_path, fsync=False)
    for i in range(24):
        eng.clock += 0.01
        eng.submit(Workload(
            name=f"low{i}", queue_name=f"lq{i % 6}", priority=0,
            pod_sets=(PodSet("main", 1, {"cpu": 1000}),)))
    return eng


def run_churn(eng):
    from kueue_tpu.api.types import PodSet, Workload

    for k in range(20):
        if k < 14:
            eng.clock += 0.01
            eng.submit(Workload(
                name=f"high{k}", queue_name=f"lq{k % 6}", priority=10,
                pod_sets=(PodSet("main", 1, {"cpu": 2000}),)))
        r = eng.schedule_once()
        if r is not None and r.stats.preempting:
            eng.tick(0.0)
        yield k


def drain(eng, cycles=120):
    for _ in range(cycles):
        r = eng.schedule_once()
        if r is None:
            break
        if r.stats.preempting:
            eng.tick(0.0)
        elif not r.stats.admitted:
            break


def fingerprint(eng):
    out = {}
    for key, wl in eng.workloads.items():
        adm = wl.status.admission
        out[key] = (wl.is_admitted, wl.is_finished,
                    None if adm is None else (
                        adm.cluster_queue,
                        tuple((psa.name,
                               tuple(sorted(psa.flavors.items())),
                               psa.count)
                              for psa in adm.pod_set_assignments)))
    usage = {name: {(fr.flavor, fr.resource): v for fr, v in u.items()
                    if v}
             for name, u in eng.cache.cq_usage.items() if u}
    return out, {k: v for k, v in usage.items() if v}


def _digest_arm(columnar: str):
    from kueue_tpu.replay.trace import canonical_decisions, decision_digest

    os.environ["KUEUE_TPU_COLUMNAR"] = columnar
    eng = build_world()
    eng.attach_oracle()
    state = {"digest": 0, "cycles": 0}

    def listener(seq, result):
        if result is not None:
            state["cycles"] += 1
            state["digest"] = decision_digest(
                canonical_decisions(result), state["digest"])

    eng.cycle_listeners.append(listener)
    for _ in run_churn(eng):
        pass
    drain(eng)
    return eng, f"{state['digest']:08x}", state["cycles"]


# -- child mode: drain the journalled world on the columnar path until
# the armed fault kills us.

def child_main(journal_path: str, spec: str) -> int:
    os.environ["KUEUE_TPU_COLUMNAR"] = "1"
    from kueue_tpu.replay.faults import arm_faults

    eng = build_world(journal_path)
    eng.attach_oracle()
    arm_faults(eng, spec)
    for k in run_churn(eng):
        print(f"cycle {k}", flush=True)
    drain(eng)
    print("done", flush=True)
    return 0


def _crash_stage(label: str, spec: str, control_fp) -> int:
    from kueue_tpu.api.types import PodSet, Workload
    from kueue_tpu.store.journal import rebuild_engine

    path = os.path.join(tempfile.mkdtemp(prefix="apply-smoke-"),
                        "j.jsonl")
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", path,
         spec],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    deadline = time.monotonic() + STAGE_TIMEOUT
    while child.poll() is None and time.monotonic() < deadline:
        time.sleep(0.2)
    if child.poll() is None:
        child.kill()
        return fail(f"{label}: child hung past {STAGE_TIMEOUT}s")
    out = child.stdout.read()
    if child.returncode != -signal.SIGKILL:
        return fail(f"{label}: exit={child.returncode} "
                    f"out={out[-300:]} err={child.stderr.read()[-600:]}")
    if "done" in out:
        return fail(f"{label}: child finished — fault never fired")
    if spec.startswith("torn-tail"):
        with open(path, "rb") as fh:
            if fh.read().endswith(b"\n"):
                return fail(f"{label}: journal tail not torn")
    # Reboot from the journal (sequential path), re-drive the inputs
    # the child never submitted, converge, compare.
    os.environ["KUEUE_TPU_COLUMNAR"] = "0"
    rebuilt = rebuild_engine(path)
    if not rebuilt.workloads:
        return fail(f"{label}: journal rebuilt an empty world")
    for k in range(14):
        name = f"default/high{k}"
        if name not in rebuilt.workloads:
            rebuilt.clock += 0.01
            rebuilt.submit(Workload(
                name=f"high{k}", queue_name=f"lq{k % 6}", priority=10,
                pod_sets=(PodSet("main", 1, {"cpu": 2000}),)))
    drain(rebuilt)
    if fingerprint(rebuilt) != control_fp:
        return fail(f"{label}: recovery diverged from the "
                    "uninterrupted control — lost or duplicate "
                    "admissions")
    print(f"{label} OK (child died by SIGKILL, rebuild converged "
          "to the control)")
    return 0


def main() -> int:
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        return child_main(sys.argv[2], sys.argv[3])

    # 1. Digest identity across both COLUMNAR arms.
    results = {}
    for label, columnar in ARMS:
        eng, digest, cycles = _digest_arm(columnar)
        if cycles == 0:
            return fail(f"{label}: no cycles ran")
        results[label] = (digest, fingerprint(eng))
    base_digest, base_fp = results["serial"]
    for label, (digest, fp) in results.items():
        if digest != base_digest:
            return fail(f"digest drift: {label}={digest} "
                        f"serial={base_digest}")
        if fp != base_fp:
            return fail(f"final-state drift: {label} != serial")
    print(f"digest identity OK (both arms {base_digest})")

    # 2/3. Crash recovery under the columnar path. The control is the
    # uninterrupted serial drain from stage 1.
    rc = _crash_stage("sigkill mid-apply",
                      f"sigkill@admission:{KILL_AT}", base_fp)
    if rc:
        return rc
    rc = _crash_stage("torn tail", "torn-tail@cycle:4", base_fp)
    if rc:
        return rc

    print("apply-smoke OK: two-arm digest identity and mid-apply crash "
          "recovery both validate")
    return 0


if __name__ == "__main__":
    sys.exit(main())
