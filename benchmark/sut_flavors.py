"""The adapter of the several-flavors kind: the system under test as the
loop sees it — an Engine with `serve.attach_oracle(eng, "local")` over a
world of worldgen_flavors.py: every ClusterQueue with one resource group
that covers the world's resources and lists its ResourceFlavors in
order, under the world's `flavorFungibility`. With ``oracle="off"`` the
same engine decides by the program's sequential core alone: a second
witness for the tests, never the reference (plain_flavors.py).

Of this kind's modules only this one imports the program; what it
shares with the flat one-flavor kind's adapter (sut.py) — the client's
side, the bridge's clocks and counters — it takes from there.

A verdict's `flavor` is a tuple of (resource, flavor) and its `used` a
tuple of (resource, flavor, quantity), resources in the world's order.
`signatures` counts the cycle program's distinct compiled shapes;
`counters()["sim_program_shapes"]` the sim program's.
"""

from __future__ import annotations

import time

import sut


def shape_of(tensors: dict, statics: dict) -> tuple:
    """What makes one compiled program: every argument's shape and
    every static."""
    return (tuple(sorted((k, tuple(v.shape)) for k, v in tensors.items())),
            tuple(sorted(statics.items())))


class Program(sut.Program):
    """One engine over one world (worldgen_flavors.build_world's)."""

    def __init__(self, world: dict, oracle: str = "local"):
        from kueue_tpu.api.types import (
            Admission,
            ClusterQueue,
            ClusterQueuePreemption,
            Cohort,
            FlavorFungibility,
            FlavorQuotas,
            FungibilityPolicy,
            LocalQueue,
            PodSetAssignmentStatus,
            PreemptionPolicy,
            ResourceFlavor,
            ResourceGroup,
            ResourceQuota,
            WorkloadConditionType,
        )
        from kueue_tpu.controllers.engine import Engine
        from kueue_tpu.serve import attach_oracle
        from kueue_tpu.utils import native

        native.ensure_built(block=True)
        self.heap = "native" if native.native_available() else "python"
        self.classes = world["classes"]
        self.resources = list(world["resources"])
        self.cq_names = [cq["name"] for cq in world["cluster_queues"]]
        self.executor_calls: list = []
        self.signatures: set = set()
        self.sim_shapes: set = set()
        self.sim_launches: list = []
        self.cohort_of = {cq["name"]: cq["cohort"]
                          for cq in world["cluster_queues"]}

        eng = Engine()
        for f in world["flavors"]:
            eng.create_resource_flavor(ResourceFlavor(f))
        for name in world["cohorts"]:
            eng.create_cohort(Cohort(name))
        pre, fung = world["preemption"], world["flavor_fungibility"]
        stanza = ClusterQueuePreemption(
            within_cluster_queue=PreemptionPolicy[
                pre["within_cluster_queue"]],
            reclaim_within_cohort=PreemptionPolicy[
                pre["reclaim_within_cohort"]])
        fungibility = FlavorFungibility(
            when_can_borrow=FungibilityPolicy[fung["when_can_borrow"]],
            when_can_preempt=FungibilityPolicy[fung["when_can_preempt"]])
        for i, cq in enumerate(world["cluster_queues"]):
            eng.create_cluster_queue(ClusterQueue(
                name=cq["name"], cohort=cq["cohort"], preemption=stanza,
                flavor_fungibility=fungibility,
                resource_groups=(ResourceGroup(
                    tuple(self.resources), tuple(
                        FlavorQuotas(fl["name"], {
                            r: ResourceQuota(
                                fl["nominal"][r],
                                borrowing_limit=fl["borrowing_limit"][r])
                            for r in self.resources})
                        for fl in cq["flavors"])),)))
            eng.create_local_queue(LocalQueue(f"lq-{i}", "default",
                                              cq["name"]))
        admissions: dict = {}
        for (name, ci, k, at), f in zip(world["running"],
                                        world["running_on"]):
            wl = self._workload(name, ci, k, at)
            adm = admissions.get((ci, k, f))
            if adm is None:
                req = self.classes[k]["request"]
                flavor = world["flavors"][f]
                adm = admissions[(ci, k, f)] = Admission(
                    self.cq_names[ci], (PodSetAssignmentStatus(
                        "main", {r: flavor for r in self.resources},
                        {r: req[r] for r in self.resources}, 1),))
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
            executor = eng.oracle.executor
            if not hasattr(executor, "sim_targets"):
                # A program from before PR 30: its simulations compile in
                # the window and hold ~36 GB at this world's size.
                raise SystemExit("this program has no bounded sim program "
                                 "(executor.sim_targets): the several-"
                                 "flavors kind cannot be run on it")
            inner = executor.cycle_step
            calls, signatures = self.executor_calls, self.signatures

            def cycle_step(tensors, statics):
                signatures.add(shape_of(tensors, statics))
                t0 = time.perf_counter()
                out = inner(tensors, statics)
                calls.append((t0, time.perf_counter()))
                return out

            executor.cycle_step = cycle_step
            inner_sim = executor.sim_targets
            sim_shapes = self.sim_shapes

            def sim_targets(tensors, statics, **kwargs):
                sim_shapes.add(shape_of(tensors, statics))
                return inner_sim(tensors, statics, **kwargs)

            executor.sim_targets = sim_targets
            eng.apply_serving_gc_posture()
        self.eng = eng

    def _workload(self, name: str, ci: int, k: int, created: float):
        from kueue_tpu.api.types import PodSet, Workload

        c = self.classes[k]
        return Workload(
            name=name, uid=f"uid-{name}", queue_name=f"lq-{ci}",
            priority=c["priority"], creation_time=created,
            pod_sets=(PodSet("main", 1, dict(c["request"])),))

    def cycle(self, now: float) -> dict:
        from kueue_tpu.scheduler.cycle import EntryStatus

        eng = self.eng
        eng.clock = now
        r = eng.schedule_once()
        launch = eng.spans.last().find(lambda s: s.name == "sim_launch")
        if launch is not None:
            # Where each cycle's sim launches blocked: a stalled cycle
            # shows here, in the result line's counters.
            self.sim_launches.append([round(v, 4) for v in (
                launch.dur * 1e-6, *(launch.attrs.get(key, 0.0) for key in (
                    "upload_s", "device_wait_s", "readback_s")))])
        admitted, preempting = [], []
        if r is not None:
            for e in list(r.entries) + list(r.inadmissible):
                if e.status == EntryStatus.ASSUMED:
                    adm = e.obj.status.admission
                    psa = adm.pod_set_assignments[0]
                    on = [r_ for r_ in self.resources if r_ in psa.flavors]
                    admitted.append((
                        e.commit_position, e.obj.name, adm.cluster_queue,
                        tuple((r_, psa.flavors[r_]) for r_ in on),
                        tuple((r_, psa.flavors[r_], psa.resource_usage[r_])
                              for r_ in on)))
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
        """Who holds quota where and on which flavor, and who waits."""
        eng = self.eng
        holds = sorted(
            (w.name, w.status.admission.cluster_queue, next(iter(
                w.status.admission.pod_set_assignments[0]
                .flavors.values())))
            for w in eng.workloads.values()
            if w.is_admitted and not w.is_finished)
        waits = sorted(w.name for w in eng.workloads.values()
                       if not w.is_admitted and not w.is_finished)
        return {"holds": holds, "waits": waits}

    def counters(self) -> dict:
        """`sim_launch_s`: a cycle's [span, upload, device wait,
        readback] seconds, every cycle since the start."""
        return dict(super().counters(),
                    sim_program_shapes=len(self.sim_shapes),
                    sim_launch_s=list(self.sim_launches))
