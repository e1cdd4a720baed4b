"""The system under test, as the loop sees it: an Engine with
`serve.attach_oracle(eng, "local")` — what `python -m kueue_tpu.serve
--oracle local` runs, no journal — driven through Engine.submit,
Engine.finish, Engine.tick and Engine.schedule_once(). With
``oracle="off"`` the same engine decides by the program's sequential
core in kueue_tpu/scheduler/ alone: a second witness for the tests,
never the reference (that is plain.py, which imports none of this).

This file is the only one of the benchmark that imports the program. It
takes from it the entry points, the bridge's phase clocks and counters,
and the executor to time from outside (chip_smoke.tap_executor's seam).
"""

from __future__ import annotations

import time

CPU = "cpu"
FLAVOR = "default"


def _policy(name: str):
    from kueue_tpu.api.types import PreemptionPolicy

    return PreemptionPolicy[name]


class Program:
    """One engine over one world (worldgen.build_world's records)."""

    def __init__(self, world: dict, oracle: str = "local"):
        from kueue_tpu.api.types import (
            Admission,
            ClusterQueue,
            ClusterQueuePreemption,
            Cohort,
            FlavorQuotas,
            LocalQueue,
            PodSetAssignmentStatus,
            ResourceFlavor,
            ResourceGroup,
            ResourceQuota,
            WorkloadConditionType,
        )
        from kueue_tpu.controllers.engine import Engine
        from kueue_tpu.serve import attach_oracle
        from kueue_tpu.utils import native

        # The C++ pending heap, built once per checkout (native/build/,
        # git-ignored); with no toolchain the Python heap serves.
        native.ensure_built(block=True)
        self.heap = "native" if native.native_available() else "python"
        self.classes = world["classes"]
        self.cq_names = [cq["name"] for cq in world["cluster_queues"]]
        self.executor_calls: list = []  # (start, end), host clock
        # The distinct cycle programs launched: every argument's shape
        # and every static, so one entry is one compiled program.
        self.signatures: set = set()
        self.cohort_of = {cq["name"]: cq["cohort"]
                          for cq in world["cluster_queues"]}

        eng = Engine()
        eng.create_resource_flavor(ResourceFlavor(FLAVOR))
        for name in world["cohorts"]:
            eng.create_cohort(Cohort(name))
        pre = world["preemption"]
        stanza = ClusterQueuePreemption(
            within_cluster_queue=_policy(pre["within_cluster_queue"]),
            reclaim_within_cohort=_policy(pre["reclaim_within_cohort"]))
        for i, cq in enumerate(world["cluster_queues"]):
            eng.create_cluster_queue(ClusterQueue(
                name=cq["name"], cohort=cq["cohort"], preemption=stanza,
                resource_groups=(ResourceGroup(
                    (CPU,), (FlavorQuotas(FLAVOR, {CPU: ResourceQuota(
                        cq["nominal_milli"],
                        borrowing_limit=cq["borrowing_limit_milli"])}),)),
                )))
            eng.create_local_queue(LocalQueue(f"lq-{i}", "default",
                                              cq["name"]))
        # The running set, as a restarted control plane reads admitted
        # Workloads back: status intact, quota re-assumed in the cache.
        admissions: dict = {}
        for name, ci, k, at in world["running"]:
            wl = self._workload(name, ci, k, at)
            adm = admissions.get((ci, k))
            if adm is None:
                req = self.classes[k]["request_milli"]
                adm = admissions[(ci, k)] = Admission(
                    self.cq_names[ci], (PodSetAssignmentStatus(
                        "main", {CPU: FLAVOR}, {CPU: req}, 1),))
            wl.status.admission = adm
            wl.set_condition(WorkloadConditionType.QUOTA_RESERVED, True,
                             reason="QuotaReserved", now=at)
            wl.set_condition(WorkloadConditionType.ADMITTED, True,
                             reason="Admitted", now=at)
            eng.restore_workload(wl)
        for name, ci, k, at in world["pending"]:
            eng.clock = at
            eng.submit(self._workload(name, ci, k, at))
        eng.clock = world["clock0"]
        attach_oracle(eng, oracle)
        if oracle != "off":
            inner = eng.oracle.executor.cycle_step
            calls, signatures = self.executor_calls, self.signatures

            def cycle_step(tensors, statics):
                signatures.add((
                    tuple(sorted((k, tuple(v.shape))
                                 for k, v in tensors.items())),
                    tuple(sorted(statics.items()))))
                t0 = time.perf_counter()
                out = inner(tensors, statics)
                calls.append((t0, time.perf_counter()))
                return out

            eng.oracle.executor.cycle_step = cycle_step
            eng.apply_serving_gc_posture()
        self.eng = eng

    def _workload(self, name: str, ci: int, k: int, created: float):
        from kueue_tpu.api.types import PodSet, Workload

        c = self.classes[k]
        return Workload(
            name=name, uid=f"uid-{name}", queue_name=f"lq-{ci}",
            priority=c["priority"], creation_time=created,
            pod_sets=(PodSet("main", 1, {CPU: c["request_milli"]}),))

    # -- the client's side ------------------------------------------

    def submit(self, name: str, ci: int, k: int, created: float) -> None:
        self.eng.clock = created
        self.eng.submit(self._workload(name, ci, k, created))

    def finish(self, name: str) -> None:
        self.eng.finish("default/" + name)

    def cycle(self, now: float) -> dict:
        """One schedule_once() at engine time ``now``; the clock of a
        cycle stops when its verdicts are applied on the host. Returns
        the cycle's verdicts as plain data: who was admitted (name,
        ClusterQueue, flavor, quota used — in commit order) and who
        preempts whom."""
        from kueue_tpu.scheduler.cycle import EntryStatus

        eng = self.eng
        eng.clock = now
        r = eng.schedule_once()
        admitted, preempting = [], []
        if r is not None:
            for e in list(r.entries) + list(r.inadmissible):
                if e.status == EntryStatus.ASSUMED:
                    adm = e.obj.status.admission
                    psa = adm.pod_set_assignments[0]
                    admitted.append((e.commit_position, e.obj.name,
                                     adm.cluster_queue, psa.flavors[CPU],
                                     psa.resource_usage[CPU]))
                elif e.status == EntryStatus.PREEMPTING:
                    preempting.append((e.obj.name, sorted(
                        t.workload.obj.name for t in e.preemption_targets)))
            if r.stats.preempting:
                eng.tick(0.0)  # evictions land; victims requeue
        admitted.sort()
        return {"idle": r is None,
                "admitted": [a[1:] for a in admitted],
                "preempting": sorted(preempting)}

    def state(self) -> dict:
        """Who holds quota where, and who waits, at the end."""
        eng = self.eng
        holds = sorted((w.name, w.status.admission.cluster_queue)
                       for w in eng.workloads.values()
                       if w.is_admitted and not w.is_finished)
        waits = sorted(w.name for w in eng.workloads.values()
                       if not w.is_admitted and not w.is_finished)
        return {"holds": holds, "waits": waits}

    # -- what the harness reads of the bridge -----------------------

    def phases(self) -> dict:
        return dict(self.eng.last_cycle_phases)

    def mode(self) -> str:
        return self.eng.last_cycle_mode

    def counters(self) -> dict:
        b = self.eng.oracle
        return {"device_cycles": b.cycles_on_device,
                "fallback_cycles": b.cycles_fallback,
                "hybrid_cycles": b.cycles_hybrid,
                "fallback_reasons": dict(b.fallback_reasons),
                "host_root_reasons": dict(b.host_root_reasons),
                "breaker": b.supervisor.status()["state"],
                "pipeline": dict(b.pipeline_stats)}

    def sizes(self) -> dict:
        """Pending and running counts: what the pow2 buckets pad."""
        eng = self.eng
        running: dict = {}
        for info in eng.cache.workloads.values():
            running[info.cluster_queue] = running.get(
                info.cluster_queue, 0) + 1
        by_cohort: dict = {}
        for cq, n in running.items():
            co = self.cohort_of[cq]
            by_cohort[co] = by_cohort.get(co, 0) + n
        return {"pending": sum(
                    len(pcq.items) + len(pcq.inadmissible)
                    for pcq in eng.queues.cluster_queues.values()),
                "running": sum(running.values()),
                "max_running_in_a_cohort": max(by_cohort.values(),
                                               default=0)}

    def close(self) -> None:
        """Drop the engine and what the bridge keeps on the device."""
        import gc

        self.eng = None
        gc.enable()
        gc.unfreeze()
        gc.collect()
