"""Engine <-> batched-oracle bridge: hybrid device/host scheduling cycles.

This is the serving-path form of the north star (BASELINE.json): the
control plane snapshots its caches into dense tensors, the device solves
nominate+order+commit for every ClusterQueue head at once
(oracle/batched.cycle_step), and verdicts are applied through the same
assume/patch path the sequential scheduler uses. The BestEffortFIFO
sequential path remains both the fallback and the decision-equivalence
oracle (tests/test_oracle_engine.py).

Hybrid partitioning (round 2): admissions only interact within a cohort
root subtree (all quota math stays under the root), so eligibility is
decided PER ROOT, not per cycle. A root runs on device unless one of its
member ClusterQueues needs the host this cycle:
  * its current head is not fast-path encodable (partial admission,
    pod sets whose flavor masks disagree, uncovered resources, a TAS
    request the batched planner cannot express);
  * one of its flavors carries a topology and the batched TAS planner
    is off or the flavor is tainted as well (host assigner path);
  * its head needs preemption outside the device preemptor's scope:
    fair-sharing preemption strategies, or more than v_cap targets once
    the preemptor has given back what it can (`preemption-overflow`;
    how many candidates it walks past on the way does not matter).

Multi-flavor resource groups on preemption-enabled ClusterQueues run
the sim-augmented nomination: the pre-oracle flavor grid
(ops/assign.flavor_grid) plus per-cell preemption simulations (the sim
program, ops/preempt.sim_targets, standing in for
preemption_oracle.go:41: one fixed block of rows a world), folded
through the fungibility lattice as array code (_fold_fungibility), then
committed via the cycle program's slot overrides; a Preempt-mode head's
victims are the cycle program's fused preemptor's, on the chosen flavor.
Node labels, taints and tolerations are a per-workload flavor mask
(tensor/schema.flavor_eligibility_mask, rowcache.flavor_ok): a flavor a
head's pod set does not match is skipped in its walk — by the cycle
program's assign pass and by the sim-augmented nomination alike, from
the one mask — as checkFlavorForPodSets skips it.
Host roots are handed to the engine's sequential path in the same
schedule_once() call (engine._sequential_cycle); because roots never
share quota, device-then-host commit order is cycle-equivalent to the
reference's single interleaved cycle (scheduler.go:286).

Remaining whole-cycle fallbacks (conservative, correctness-first):
  * WaitForPodsReady admission blocking.

Admission fair sharing runs on device: AFS-scoped CQs' head ordering
(LocalQueue decayed usage first) is folded into the rank vector — the
row cache stores each workload's heap sort key (AFS usage frozen at
push time, cluster_queue.go:208) and ranks with those, so device and
host head order are identical by construction — and entry penalties
flow through the shared engine on_admit hook when device verdicts are
applied.

Per-cycle encoding is incremental (round 2): the queue manager's
WorkloadRowCache (tensor/rowcache.py) keeps the pending set as live
tensor rows updated on every queue transition; a cycle re-encodes only
rows that changed, instead of the whole pending world.

Fair sharing runs on device for arbitrary cohort forests: the
hierarchical LCA tournament is ops/commit.commit_grouped_fair.

There is one way through a cycle: try_cycle's checks, _encode_cycle
(encode, launch, wait, read back: the executor returns with the
verdicts on the host) and _commit_cycle, every schedule_once() from the
engine's state as it then is. Nothing of the next cycle is prepared
ahead.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from kueue_tpu.api.types import FlavorResource
from kueue_tpu.obs import perf as _obs_perf
from kueue_tpu.scheduler.cycle import (
    CycleResult,
    Entry,
    EntryStatus,
    RequeueReason,
)
from kueue_tpu.scheduler.flavorassigner import (
    Assignment,
    FlavorAssignment,
    Mode,
    PodSetAssignment,
)

_HOST_BIG = np.int64(1) << 60


# The cycle_step arguments _encode_cycle converts from host arrays every
# cycle (the ``upload`` span's bytes); the rest is device-resident by
# spec / admitted-set version.
_PER_CYCLE_UPLOADS = (
    "rank", "commit_rank", "wl_cq", "wl_req", "wl_priority", "wl_has_qr",
    "wl_hash", "wl_ts", "wl_flavor_ok", "pending", "usage", "slot_maybe",
    "slot_kind_override", "slot_borrows_override", "slot_flavor_override")


# The sim program's per-row inputs and what pads a block's unused rows
# (slot_need False: a padded row simulates nothing).
_SIM_ROW_FILLS = {"slot_cq": 0, "slot_need": False, "slot_pri": 0,
                  "slot_ts": 0.0, "slot_fr": -1, "slot_req": 0}


def _fold_fungibility(pm, br, in_group, group_flavors, borrow_try_next,
                      preempt_try_next, pref_preempt_first):
    """findFlavorForPodSets (flavorassigner.go:932) for every slot at
    once, on the granular modes of its cells after the simulations:
    ``pm`` / ``br`` int[C, G, F, S] (PMode, borrow), ``in_group``
    bool[C, G, S] (the slot requests this resource of this group),
    ``group_flavors`` int[C, G, F] the flavors of the slot's walk, in
    order (-1: none here — the group has fewer, or the slot's pod set
    does not match the flavor, checkFlavorForPodSets): such a flavor is
    not a tried one, whatever its cells say.

    A flavor's representative mode is the worst of its resources'
    (isPreferred); the group's flavors are walked in order, the walk
    stops at the first that need not try the next (shouldTryNextFlavor),
    else the best seen wins. Returns per slot (flavor int32[C, S] per
    resource, -1 none; mode int[C], the worst PMode over the chosen
    flavors' resources, NO_FIT where a group found none; borrow
    int[C], the assignment's borrow level — the MAX over those
    resources' own, Assignment.append: a Fit resource that borrows
    keeps the entry behind the non-borrowing ones even where the
    simulated resource's victims would end its queue's borrowing)."""
    from kueue_tpu.scheduler.flavorassigner import PMode

    C, G, F, S = pm.shape
    big = np.int64(1) << 20
    bottom = -big * big
    pm, br = pm.astype(np.int64), br.astype(np.int64)
    key = np.where(pref_preempt_first[:, None, None, None],
                   -br * big + pm, pm * big - br)
    key = np.where(pm == int(PMode.NO_FIT), bottom, key)
    mask = in_group[:, :, None, :]
    worst = np.where(mask, key, np.iinfo(np.int64).max).argmin(
        axis=3)[..., None]
    rep_key = np.take_along_axis(key, worst, 3)[..., 0]  # [C, G, F]
    rep_pm = np.take_along_axis(pm, worst, 3)[..., 0]
    rep_br = np.take_along_axis(br, worst, 3)[..., 0]
    active = in_group.any(axis=2)  # [C, G]
    valid = (group_flavors >= 0) & active[:, :, None]
    try_next = (
        (rep_pm <= int(PMode.NO_CANDIDATES))
        | (((rep_pm == int(PMode.PREEMPT)) | (rep_pm == int(PMode.RECLAIM)))
           & preempt_try_next[:, None, None])
        | ((rep_br > 0) & borrow_try_next[:, None, None]))
    best_key = np.full((C, G), bottom, np.int64)
    best_f = np.full((C, G), -1, np.int64)
    stopped = np.zeros((C, G), bool)
    for f in range(F):
        consider = valid[:, :, f] & ~stopped
        stop = consider & ~try_next[:, :, f]
        take = stop | (consider & (rep_key[:, :, f] > best_key))
        best_key = np.where(take, rep_key[:, :, f], best_key)
        best_f = np.where(take, f, best_f)
        stopped |= stop
    at = np.maximum(best_f, 0)[:, :, None, None]
    pm_at = np.take_along_axis(pm, at, 2)[:, :, 0, :]  # [C, G, S]
    br_at = np.take_along_axis(br, at, 2)[:, :, 0, :]
    found = (best_f >= 0)[:, :, None] & in_group
    missing = (active & (best_f < 0)).any(axis=1)
    mode = np.where(in_group, pm_at, int(PMode.FIT)).min(axis=(1, 2))
    mode = np.where(missing, int(PMode.NO_FIT), mode)
    borrow = np.where(found, br_at, 0).max(axis=(1, 2))
    flavor = np.take_along_axis(group_flavors, np.maximum(best_f, 0)[
        :, :, None], 2)[:, :, 0]  # [C, G]
    choice = np.where(found, flavor[:, :, None], -1).max(axis=1)
    return choice.astype(np.int32), mode, borrow


def _flavor_unsafe(rf, tas_batched: bool) -> bool:
    """A flavor whose ClusterQueues take the host flavorassigner path:
    one with a topology, where the batched TAS planner is off (the
    legacy predicate) or the flavor is tainted as well — the planner
    (tas/batched.plan_cycle) nominates placements for TAS heads inside
    the hybrid cycle and demotes per head only when a request needs an
    unsupported TAS feature, but matches no toleration. Taints, labels
    and tolerations of a flavor without a topology are the heads' flavor
    masks (rowcache.flavor_ok) and demote nothing."""
    if rf is None or not rf.topology_name:
        return False
    return not tas_batched or bool(rf.node_taints)


class OracleBridge:
    # Read by benchmark/sut.py counters() ("pipeline"), which no reader
    # uses, and by nothing else: the speculative cycle loop these four
    # counted is gone. Leaves with that reader (ROADMAP C13, item 12).
    pipeline_stats = {"speculated": 0, "used": 0, "discarded": 0,
                      "skipped": 0}

    def __init__(self, engine, max_depth: int = 4, executor=None,
                 supervisor=None):
        self.engine = engine
        self.max_depth = max_depth
        if executor is None:
            from kueue_tpu.oracle.service import LocalExecutor
            executor = LocalExecutor(engine.spans)
        # Where device programs run: in-process (LocalExecutor) or a
        # standalone oracle service over the socket boundary
        # (service.RemoteExecutor).
        self.executor = executor
        if supervisor is None:
            import os

            from kueue_tpu.oracle.supervisor import OracleSupervisor
            supervisor = OracleSupervisor(
                metrics=getattr(engine, "registry", None),
                max_attempts=int(os.environ.get(
                    "KUEUE_TPU_ORACLE_RETRIES", "3")),
                threshold=int(os.environ.get(
                    "KUEUE_TPU_ORACLE_BREAKER_N", "3")),
                cooldown_cycles=int(os.environ.get(
                    "KUEUE_TPU_ORACLE_BREAKER_COOLDOWN", "8")))
        # Retry + circuit breaker around executor calls; breaker-open
        # cycles are refused up front in try_cycle (host path decides,
        # digest-identical) until a half-open probe re-promotes.
        self.supervisor = supervisor
        self.cycles_on_device = 0
        self.cycles_fallback = 0
        self.cycles_hybrid = 0  # device cycles with a host-root tail
        # Why try_cycle returned None, by label (diagnostics + tests).
        self.fallback_reasons: dict[str, int] = {}
        # Why individual roots were handed to the host path.
        self.host_root_reasons: dict[str, int] = {}
        # CRC-32 of the last device cycle's raw verdict tensors
        # (replay/trace.py records it per cycle for kernel-vs-apply
        # divergence attribution).
        self.last_verdict_digest: Optional[int] = None
        # Batched-TAS planner accounting (bench tas/tas_large detail):
        # per-phase totals and the heads-per-launch histogram.
        self.tas_stats: dict[str, float] = {
            "plan_cycles": 0, "heads_planned": 0, "placed_device": 0,
            "placed_host": 0, "memo_hits": 0, "commit_drops": 0,
            "encode_s": 0.0, "place_s": 0.0, "decode_s": 0.0}
        self.tas_heads_per_launch: dict[int, int] = {}

    def world_is_fast_path_safe(self) -> bool:
        eng = self.engine
        if (eng.pods_ready is not None
                and eng.pods_ready.admission_blocked()):
            # BlockAdmission (scheduler.go:535): the host path owns the
            # hold-everything requeue bookkeeping.
            return False
        # When EVERY CQ with pending work is flavor-unsafe (a topology
        # with the batched planner off, or tainted as well), every root
        # would demote and the snapshot+solver built here would be
        # thrown away — skip straight to the sequential path. Computed
        # from the cache (no snapshot needed).
        from kueue_tpu.tas import batched as _tb
        tas_batched = _tb.enabled()
        any_safe = False
        any_pending = False
        for name, pcq in eng.queues.cluster_queues.items():
            if not pcq.items:
                continue
            any_pending = True
            cq = eng.cache.cluster_queues.get(name)
            if cq is None:
                continue
            if not any(_flavor_unsafe(
                    eng.cache.resource_flavors.get(fq.name), tas_batched)
                       for rg in cq.resource_groups
                       for fq in rg.flavors):
                any_safe = True
                break
        if any_pending and not any_safe:
            return False
        return True

    def _fallback(self, reason: str) -> None:
        """Count a cycle handed whole to the sequential path. Callers
        write ``return self._fallback(reason)``: the None is try_cycle's
        request for that path."""
        self.fallback_reasons[reason] = \
            self.fallback_reasons.get(reason, 0) + 1
        self._count("oracle_fallback_total", (reason,))

    def _host_root(self, reason: str, count: int = 1) -> None:
        self.host_root_reasons[reason] = \
            self.host_root_reasons.get(reason, 0) + count
        self._count("oracle_host_root_total", (reason,), count)

    def _exec_call(self, site: str, fn, *args, **kwargs):
        """Route one executor call through the supervisor: transient
        RemoteOracleErrors are retried with backoff, a call that still
        fails feeds the circuit breaker before propagating (the engine
        falls back sequentially for this cycle either way)."""
        sup = self.supervisor
        if sup is None:
            return fn(*args, **kwargs)
        try:
            out = sup.call(site, fn, *args, **kwargs)
        except Exception:
            sup.record_failure(self.engine.cycle_seq)
            raise
        sup.record_success()
        return out

    def _count(self, family: str, labels: tuple,
               amount: float = 1.0) -> None:
        """Mirror a bridge diagnostic into the registry so it is
        visible on /metrics in production, not just in bench detail
        blobs. Write-only; tolerant of registries predating the
        oracle_* families (journal-rebuilt old engines)."""
        try:
            self.engine.registry.counter(family).inc(labels, amount)
        except KeyError:
            pass

    def _world_tensors(self):
        """World structure tensors memoized by the cache's spec version;
        only the usage matrix is refilled per cycle from the live
        per-CQ aggregates. The full snapshot + encode ran every cycle in
        round 1 — at 1k CQs that was ~45ms of pure Python per cycle for
        structure that almost never changes."""
        from kueue_tpu.tensor.schema import encode_snapshot

        cache = self.engine.cache
        cached = getattr(self, "_world_cache", None)
        if cached is None or cached[0] != cache.spec_version:
            w = encode_snapshot(cache.snapshot(), max_depth=self.max_depth)
            cq_idx = {n: i for i, n in enumerate(w.cq_names)}
            fl_idx = {n: i for i, n in enumerate(w.flavor_names)}
            s_idx = {n: i for i, n in enumerate(w.resource_names)}
            cached = (cache.spec_version, w, cq_idx, fl_idx, s_idx)
            self._world_cache = cached
        _, w, cq_idx, fl_idx, s_idx = cached
        S = w.num_resources
        usage = np.zeros_like(w.usage)
        for name, cqu in cache.cq_usage.items():
            ci = cq_idx.get(name)
            if ci is None:
                continue
            for fr, v in cqu.items():
                fi = fl_idx.get(fr.flavor)
                si = s_idx.get(fr.resource)
                if fi is not None and si is not None:
                    usage[ci, fi * S + si] = v
        w.usage = usage
        return w

    def _device_world_args(self, w) -> dict:
        """Device-resident copies of the world-STRUCTURE tensors, keyed
        by the spec version that produced ``w``. Re-uploading ~25 static
        arrays every cycle cost more host time than the device solve
        itself (bench preempt_churn profile)."""
        import jax.numpy as jnp

        ver = self.engine.cache.spec_version
        cached = getattr(self, "_dev_world_cache", None)
        if cached is None or cached[0] != ver:
            dev = dict(
                nominal=jnp.asarray(w.nominal),
                lend_limit=jnp.asarray(w.lend_limit),
                borrow_limit=jnp.asarray(w.borrow_limit),
                parent=jnp.asarray(w.parent),
                ancestors=jnp.asarray(w.ancestors),
                height=jnp.asarray(w.height),
                group_of_res=jnp.asarray(w.group_of_res),
                group_flavors=jnp.asarray(w.group_flavors),
                no_preemption=jnp.asarray(w.no_preemption),
                can_pwb=jnp.asarray(w.can_preempt_while_borrowing),
                can_always_reclaim=jnp.asarray(w.can_always_reclaim),
                best_effort=jnp.asarray(w.best_effort),
                fung_borrow_try_next=jnp.asarray(w.fung_borrow_try_next),
                fung_pref_preempt_first=jnp.asarray(
                    w.fung_pref_preempt_first),
                root_members=jnp.asarray(w.root_members),
                root_nodes=jnp.asarray(w.root_nodes),
                local_chain=jnp.asarray(w.local_chain),
                fair_weight=jnp.asarray(w.fair_weight),
                child_rank=jnp.asarray(w.child_rank),
                local_depth=jnp.asarray(w.local_depth),
                root_parent_local=jnp.asarray(w.root_parent_local),
            )
            cached = (ver, dev)
            self._dev_world_cache = cached
        return dict(cached[1])

    def _cq_has_selector(self, w):
        """bool[C] mask of CQs with a namespace selector, or None when
        no CQ has one (the common case — skips per-head checks).
        Memoized by spec version."""
        cached = getattr(self, "_sel_cache", None)
        ver = self.engine.cache.spec_version
        if cached is None or cached[0] != ver:
            mask = np.zeros(w.num_cqs, bool)
            for ci, name in enumerate(w.cq_names):
                if self.engine.cache.cluster_queues[name] \
                        .namespace_selector is not None:
                    mask[ci] = True
            cached = (ver, mask if mask.any() else None)
            self._sel_cache = cached
        return cached[1]

    def _cq_flavor_safe(self, w) -> np.ndarray:
        """bool[C]: none of the CQ's flavors demotes to the host
        flavorassigner path (_flavor_unsafe). With the batched TAS
        planner on, topology-carrying CQs stay and get their placements
        from tas/batched.plan_cycle (which applies its own per-head
        demotion matrix) unless the flavor is tainted too."""
        from kueue_tpu.tas import batched as _tb

        eng = self.engine
        tas_batched = _tb.enabled()
        safe = np.ones(w.num_cqs, bool)
        for ci, name in enumerate(w.cq_names):
            spec = eng.cache.cluster_queues[name]
            safe[ci] = not any(
                _flavor_unsafe(eng.cache.resource_flavors.get(fq.name),
                               tas_batched)
                for rg in spec.resource_groups for fq in rg.flavors)
        return safe

    def _cq_tas_mask(self, w):
        """bool[C] mask of CQs referencing at least one TAS flavor, or
        None when the world has none (the common case — skips the
        whole TAS planning block). Memoized by spec version."""
        cached = getattr(self, "_tas_mask_cache", None)
        ver = self.engine.cache.spec_version
        if cached is None or cached[0] != ver:
            from kueue_tpu.tas import batched as _tb
            info = _tb.cq_tas_info(self.engine.cache)
            mask = np.zeros(w.num_cqs, bool)
            for ci, name in enumerate(w.cq_names):
                if name in info:
                    mask[ci] = True
            cached = (ver, mask if mask.any() else None)
            self._tas_mask_cache = cached
        return cached[1]

    def _cq_policy_cfg(self, w):
        """Per-CQ preemption-policy encoding for the device classical
        preemptor (ops/preempt.classical_targets_impl), which covers the
        full classical policy surface. Memoized by spec version."""
        from kueue_tpu.api.types import (
            BorrowWithinCohortPolicy,
            PreemptionPolicy,
        )
        from kueue_tpu.ops import preempt as pops

        cached = getattr(self, "_pcfg_cache", None)
        ver = self.engine.cache.spec_version
        if cached is not None and cached[0] == ver and cached[1] is w:
            return cached[2]

        policy_code = {
            PreemptionPolicy.NEVER: pops.POLICY_NEVER,
            PreemptionPolicy.LOWER_PRIORITY: pops.POLICY_LOWER,
            PreemptionPolicy.LOWER_OR_NEWER_EQUAL_PRIORITY:
                pops.POLICY_LOWER_OR_NEWER_EQ,
            PreemptionPolicy.ANY: pops.POLICY_ANY,
        }
        C = w.num_cqs
        wcq_policy = np.zeros(C, np.int32)
        reclaim_policy = np.zeros(C, np.int32)
        bwc_forbidden = np.ones(C, bool)
        bwc_threshold = np.full(C, pops.NO_THRESHOLD, np.int64)
        cq_has_parent = np.zeros(C, bool)
        for ci, name in enumerate(w.cq_names):
            spec = self.engine.cache.cluster_queues[name]
            p = spec.preemption
            wcq_policy[ci] = policy_code[p.within_cluster_queue]
            reclaim_policy[ci] = policy_code[p.reclaim_within_cohort]
            if (p.borrow_within_cohort is not None
                    and p.borrow_within_cohort.policy
                    != BorrowWithinCohortPolicy.NEVER):
                bwc_forbidden[ci] = False
                thr = p.borrow_within_cohort.max_priority_threshold
                if thr is not None:
                    bwc_threshold[ci] = thr
            cq_has_parent[ci] = spec.cohort is not None
        import jax.numpy as jnp

        cfg = dict(wcq_policy=wcq_policy, reclaim_policy=reclaim_policy,
                   bwc_forbidden=bwc_forbidden,
                   bwc_threshold=bwc_threshold,
                   cq_has_parent=cq_has_parent)
        # Device-resident copies, uploaded once per spec change: the
        # fused-preemption cycle ships these every cycle, and per-cycle
        # host->device transfers of spec-static arrays were a measurable
        # slice of the preemption-churn cycle floor.
        cfg["j"] = {k: jnp.asarray(v) for k, v in cfg.items()}
        cfg["j"]["root_of_cq"] = jnp.asarray(w.root_of_cq)
        self._pcfg_cache = (ver, w, cfg)
        return cfg

    def _encode_admitted(self, w):
        """Admitted tensors for the preemption kernels: an incremental
        row set (tensor/rowcache.AdmittedRows) updated from the cache's
        admitted-change log — churn cycles touch a handful of rows, not
        O(A). Rows are holes-allowed; `info_of` maps kernel victim ids
        back to WorkloadInfos."""
        from kueue_tpu.tensor.rowcache import AdmittedRows, \
            WorkloadRowCache

        sig = (WorkloadRowCache.world_signature(w), tuple(w.flavor_names))
        ar = getattr(self, "_adm_rows", None)
        if ar is None or ar.signature != sig:
            ar = AdmittedRows(w)
            self._adm_rows = ar
        adm = ar.sync(self.engine.cache, now=self.engine.clock)
        return ar.info_of, adm

    def _adm_padded(self, adm, w) -> dict:
        """Bucket-pad the admitted axis so churn cycles with a drifting
        admitted count reuse one compiled program per bucket. Padded
        rows have cq=-1 and zero usage, so they never classify as
        candidates. Memoized per encoded-tensor object (the encode
        itself is cached by admitted-set version)."""
        from kueue_tpu.tensor.schema import pad_axis0, pow2_bucket

        cached = getattr(self, "_adm_pad_cache", None)
        if cached is not None and cached[0] is adm:
            return cached[1]
        import jax.numpy as jnp

        A = adm.num_admitted
        Ap = pow2_bucket(A, 8)
        # Root-grouped admitted ids (preempt kernels scan candidates per
        # root, O(max per root) instead of O(A)).
        Rn = w.root_members.shape[0]
        root_of = np.where(adm.cq >= 0, w.root_of_cq[np.maximum(
            adm.cq, 0)], Rn) if A else np.zeros(0, np.int64)
        counts = np.bincount(root_of, minlength=Rn + 1)[:Rn]
        A_l = pow2_bucket(int(counts.max()) if counts.size else 1, 8)
        adm_by_root = np.full((max(Rn, 1), A_l), -1, np.int32)
        if A:
            order = np.argsort(root_of, kind="stable")
            sr = root_of[order]
            pos = np.arange(A) - np.searchsorted(sr, sr)
            valid = sr < Rn
            adm_by_root[sr[valid], pos[valid]] = order[valid]
        # Precomputed candidate-ordering rank (priority asc, reservation
        # recency desc, uid asc — common/ordering.go:42): lets the
        # preempt kernels order candidates with ONE composite argsort
        # per slot instead of a 6-key lexsort.
        rank = np.empty(A, np.int64)
        rank[np.lexsort((adm.uid_rank, -adm.qr_time, adm.priority))] = \
            np.arange(A)
        ap = dict(
            adm_cq=pad_axis0(adm.cq, Ap, -1),
            adm_pri=pad_axis0(adm.priority, Ap, 0),
            adm_ts=pad_axis0(adm.timestamp, Ap, 0.0),
            adm_qrt=pad_axis0(adm.qr_time, Ap, 0.0),
            adm_uid=(np.concatenate(
                [adm.uid_rank, np.arange(A, Ap, dtype=np.int64)])
                if Ap != A else adm.uid_rank),
            adm_ev=pad_axis0(adm.evicted, Ap, False),
            adm_rank=(np.concatenate(
                [rank, np.arange(A, Ap, dtype=np.int64)])
                if Ap != A else rank),
            adm_by_root=adm_by_root,
            adm_usage=pad_axis0(adm.usage, Ap, 0))
        # Device-resident for in-process execution: the encode is cached
        # across cycles by admitted-set version, so transfer once. A
        # RemoteExecutor serializes host-side — keep numpy there or
        # every cycle would pay a device->host readback instead.
        from kueue_tpu.oracle.service import LocalExecutor

        if isinstance(self.executor, LocalExecutor):
            ap = {k: jnp.asarray(v) for k, v in ap.items()}
        self._adm_pad_cache = (adm, ap)
        return ap

    def _slot_maybe(self, w, pcfg, adm, head_pri) -> np.ndarray:
        """bool[C]: this slot's head COULD have preemption candidates —
        exact-conservative host precheck against the admitted set
        (candidate_generator.go's policy tests): False only when
        provably no admitted workload can classify as a candidate.
        Cross-CQ reclaim is never prechecked (conservatively maybe);
        within-CQ policies are checked against per-CQ admitted priority
        minima. Most converged-world cycles have zero maybe-slots, which
        lets the kernels skip preemption target selection entirely.
        Memoized per (adm, pcfg, heads) — the sim-nomination and fused
        setup both need it within one cycle."""
        from kueue_tpu.ops import preempt as pops

        memo = getattr(self, "_maybe_memo", None)
        if (memo is not None and memo[0] is adm and memo[1] is pcfg
                and np.array_equal(memo[2], head_pri)):
            return memo[3]

        C = w.num_cqs
        maybe = ((pcfg["reclaim_policy"] != pops.POLICY_NEVER)
                 & pcfg["cq_has_parent"])
        wcq = pcfg["wcq_policy"]
        A = adm.num_admitted
        if A:
            valid = adm.cq >= 0
            cq_safe = np.where(valid, adm.cq, 0)
            minpri = np.full(C, np.iinfo(np.int64).max, np.int64)
            np.minimum.at(minpri, cq_safe,
                          np.where(valid, adm.priority,
                                   np.iinfo(np.int64).max))
            count = np.bincount(cq_safe, weights=valid, minlength=C)
            within = np.where(
                wcq == pops.POLICY_ANY, count > 0,
                np.where(wcq == pops.POLICY_LOWER, minpri < head_pri,
                         np.where(wcq == pops.POLICY_LOWER_OR_NEWER_EQ,
                                  minpri <= head_pri, False)))
            maybe = maybe | within
        self._maybe_memo = (adm, pcfg, np.array(head_pri), maybe)
        return maybe

    @staticmethod
    def _sim_block(w) -> int:
        """Rows of the sim program's one launch shape for this world:
        one a ClusterQueue, to the next power of two. A cycle has one
        head a queue and few of a head's (flavor, resource) cells need
        a simulation, so most cycles are one launch; a cycle with more
        rows loops the block (_sim_launch). The program compiles once
        (in warm-up) and its device bytes are bounded by the world —
        its temporaries are a (block row x padded running workload of
        the fullest cohort root) lattice — not by the cycle."""
        from kueue_tpu.tensor.schema import pow2_bucket

        return pow2_bucket(w.num_cqs, 8)

    def _sim_launch(self, w, adm, pcfg, usage, derived, rows: dict,
                    block: int, v_cap: int):
        """The simulation rows through the sim program (the executor's
        sim_targets), ``block`` rows a launch and as many launches as
        the rows need, the last padded: numpy (found, overflow,
        borrow_after, same_cq) per row. An in-process executor adds to
        the open ``sim_launch`` span what the launches moved between
        host and device (bytes) and where their wall time went
        (upload_s, device_wait_s, readback_s)."""
        from kueue_tpu.tensor.schema import pad_axis0

        n = rows["slot_cq"].shape[0]
        ap = self._adm_padded(adm, w)
        # The world's structure and the policy config are device-resident
        # by spec version, the admitted set by its own: a launch uploads
        # its rows and nothing else.
        dev, pj = self._device_world_args(w), pcfg["j"]
        tensors = dict(
            wcq_policy=pj["wcq_policy"],
            reclaim_policy=pj["reclaim_policy"],
            bwc_forbidden=pj["bwc_forbidden"],
            bwc_threshold=pj["bwc_threshold"],
            cq_has_parent=pj["cq_has_parent"],
            root_of_cq=pj["root_of_cq"],
            adm_cq=ap["adm_cq"], adm_pri=ap["adm_pri"],
            adm_ts=ap["adm_ts"], adm_qrt=ap["adm_qrt"],
            adm_uid=ap["adm_uid"], adm_ev=ap["adm_ev"],
            adm_rank=ap["adm_rank"], adm_by_root=ap["adm_by_root"],
            adm_usage=ap["adm_usage"], usage=usage,
            **{k: dev[k] for k in (
                "nominal", "lend_limit", "borrow_limit", "parent",
                "ancestors", "height", "local_chain", "root_nodes")})
        parts = []
        for lo in range(0, n, block):
            for key, fill in _SIM_ROW_FILLS.items():
                tensors[key] = pad_axis0(rows[key][lo:lo + block], block,
                                         fill)
            got = self._exec_call(
                "sim_targets", self.executor.sim_targets, tensors,
                {"depth": w.depth, "v_cap": v_cap}, derived=derived)
            parts.append([o[:n - lo] for o in got])
        return [np.concatenate(col) for col in zip(*parts)]

    def _sim_nomination(self, box, w, wls, usage, head_idx, sim_slots,
                        head_ok, adm, pcfg, v_cap=32):
        """Sim-augmented nomination for heads whose flavor choice depends
        on preemption simulations (multi-flavor groups on
        preemption-enabled CQs): run the pre-oracle flavor grid on
        device (span ``flavor_grid``), turn each Preempt-gated (head,
        group, flavor, resource) cell into a row (``sim_rows``),
        simulate the rows with the device classical preemptor
        (``sim_launch``; preemption_oracle.go:41 SimulatePreemption),
        fold the fungibility lattice as array code with the
        scheduler/flavorassigner semantics (``fungibility_fold``;
        findFlavorForPodSets), and hand the cycle program its slot
        overrides (``sim_targets``): a head whose chosen flavor's mode
        is Preempt gets its victims from the cycle program's fused
        preemptor, on that flavor. ``head_ok`` bool[C, NF] is each
        slot's head's flavor mask (rowcache.flavor_ok, the rows the cycle
        program's assign pass reads): a flavor it excludes is not in the
        head's walk — no cell of it is simulated and the fold does not
        try it (checkFlavorForPodSets). ``box`` is the open
        ``sim_nomination`` span, whose attrs carry the cycle's counts.

        Returns (override, borrows_override, flavor_override,
        demote_cq bool[C])."""
        import jax
        import jax.numpy as jnp

        from kueue_tpu.ops import assign as aops
        from kueue_tpu.ops import commit as cops
        from kueue_tpu.ops import preempt as pops
        from kueue_tpu.ops import quota as qops
        from kueue_tpu.scheduler.flavorassigner import PMode

        spans = self.engine.spans
        C, S = w.num_cqs, w.num_resources
        dev = self._device_world_args(w)

        grid = spans.begin("flavor_grid")
        slots = np.nonzero(sim_slots)[0]
        # Sim heads are single-podset by construction (try_cycle demotes
        # multi-podset heads on sim-needing CQs): podset 0 carries the
        # whole request. Head CQ == slot for valid heads.
        h_cq = np.where(sim_slots, np.arange(C), 0).astype(np.int32)
        h_req = np.zeros((C, S), np.int64)
        h_req[slots] = wls.requests[head_idx[slots], 0]
        with spans.launch("flavor_grid"):
            derived = qops.derive_world(
                dev["nominal"], dev["lend_limit"], dev["borrow_limit"],
                usage, dev["parent"], depth=w.depth)
            grid_out = aops.flavor_grid(
                jnp.asarray(h_cq), jnp.asarray(h_req), jnp.asarray(head_ok),
                derived,
                dev["nominal"], dev["ancestors"], dev["height"],
                dev["group_of_res"], dev["group_flavors"],
                dev["no_preemption"], dev["can_pwb"],
                depth=w.depth, num_resources=S)
            jax.block_until_ready(grid_out)
        g_pmode, g_borrow, g_sim, in_group, in_walk = grid_out
        pm = np.array(g_pmode)  # writable copies: the fold's lattice
        br = np.array(g_borrow)
        g_sim = np.asarray(g_sim) & sim_slots[:, None, None, None]
        in_group = np.asarray(in_group) & sim_slots[:, None, None]
        # The flavors of each slot's walk: its groups' less those its
        # mask excludes.
        in_walk = np.asarray(in_walk)
        walk_flavors = np.where(in_walk, w.group_flavors, -1)
        masked = in_group[:, :, None, :] & (
            (w.group_flavors >= 0) & ~in_walk)[..., None]
        box.attrs["heads"] = grid.attrs["heads"] = int(slots.size)
        box.attrs["masked_flavor_cells"] = int(np.count_nonzero(masked))

        # One row per cell to simulate. Cells whose slot provably has no
        # candidates (_slot_maybe) skip the kernel and resolve to
        # found=False, SimulatePreemption's no-candidates outcome.
        spans.next("sim_rows")
        head_pri = self._head_pri(wls, head_idx)
        maybe = self._slot_maybe(w, pcfg, adm, head_pri)
        adm_live = adm.live if adm.live is not None else adm.num_admitted
        ci, g, f, s_ = np.nonzero(
            g_sim & maybe[:, None, None, None]) if adm_live else (
            np.zeros(0, np.int64),) * 4
        n_rows = int(ci.size)
        r = np.arange(n_rows)
        slot_fr = np.full((n_rows, S), -1, np.int32)
        slot_fr[r, s_] = w.group_flavors[ci, g, f] * S + s_
        slot_req = np.zeros((n_rows, S), np.int64)
        slot_req[r, s_] = h_req[ci, s_]
        rows = dict(slot_cq=ci.astype(np.int32),
                    slot_need=np.ones(n_rows, bool),
                    slot_pri=head_pri[ci],
                    slot_ts=self._head_ts(wls, head_idx)[ci],
                    slot_fr=slot_fr, slot_req=slot_req)

        launch = spans.next("sim_launch")
        demote_cq = np.zeros(C, bool)
        block = self._sim_block(w)
        launches = -(-n_rows // block)
        if n_rows:
            found, overflow, borrow_after, same = self._sim_launch(
                w, adm, pcfg, usage, derived, rows, block, v_cap)
            demote_cq[ci[overflow]] = True
        box.attrs.update(rows=n_rows, launches=launches,
                         overflow=int(np.count_nonzero(demote_cq)))
        # A launch classifies its live rows in whole chunks
        # (ops/preempt.sim_targets).
        chunk = min(pops.SIM_CHUNK, block)
        launch.attrs.update(
            rows=n_rows, rows_padded=launches * block, launches=launches,
            rows_classified=sum(-(-min(block, n_rows - lo) // chunk) * chunk
                                for lo in range(0, n_rows, block)))

        # The fungibility fold (findFlavorForPodSets) over every head at
        # once. A simulated cell is Preempt or Reclaim with the borrow
        # after its victims, else NoCandidates with the borrow it had.
        spans.next("fungibility_fold")
        pm[g_sim] = int(PMode.NO_CANDIDATES)
        if n_rows:
            hit = (ci[found], g[found], f[found], s_[found])
            pm[hit] = np.where(same[found], int(PMode.PREEMPT),
                               int(PMode.RECLAIM))
            br[hit] = borrow_after[found]
        choice, mode, borrow = _fold_fungibility(
            pm, br, in_group, walk_flavors, w.fung_borrow_try_next,
            w.fung_preempt_try_next, w.fung_pref_preempt_first)

        # What the cycle program is handed (NO_FIT heads get nothing:
        # its own assign pass parks them identically). Fit commits on
        # the chosen flavor; any Preempt-mode nomination — NoCandidates
        # included, preemption.go:129 runs GetTargets for it — has its
        # victims selected by the cycle program's fused preemptor.
        spans.next("sim_targets")
        # (A positive request no group covers is NoFit whatever the
        # groups say, flavorassigner.go:939.)
        uncovered = ((h_req > 0) & (w.group_of_res < 0)).any(axis=1)
        decided = sim_slots & ~demote_cq & ~uncovered \
            & (mode > int(PMode.NO_FIT))
        override = np.where(
            decided, np.where(mode == int(PMode.FIT), cops.ENTRY_FIT,
                              cops.ENTRY_PREEMPT), -1).astype(np.int32)
        borrows_override = np.where(decided, borrow, -1).astype(np.int32)
        flavor_override = np.where(decided[:, None], choice,
                                   -1).astype(np.int32)
        spans.end()
        return override, borrows_override, flavor_override, demote_cq

    @staticmethod
    def _head_pri(wls, head_idx):
        h = np.maximum(head_idx, 0)
        return np.where(head_idx >= 0, wls.priority[h], 0)

    @staticmethod
    def _head_ts(wls, head_idx):
        h = np.maximum(head_idx, 0)
        return np.where(head_idx >= 0, wls.timestamp[h], 0.0)

    @staticmethod
    def _variant_reason():
        from kueue_tpu.ops import preempt as pops
        from kueue_tpu.scheduler.preemption import (
            IN_CLUSTER_QUEUE,
            IN_COHORT_RECLAIM_WHILE_BORROWING,
            IN_COHORT_RECLAMATION,
        )
        return {
            pops.V_WITHIN_CQ: IN_CLUSTER_QUEUE,
            pops.V_HIERARCHICAL_RECLAIM: IN_COHORT_RECLAMATION,
            pops.V_RECLAIM_WITHOUT_BORROWING: IN_COHORT_RECLAMATION,
            pops.V_RECLAIM_WHILE_BORROWING:
                IN_COHORT_RECLAIM_WHILE_BORROWING,
        }

    def try_cycle(self) -> Optional[CycleResult]:
        """One hybrid cycle, inside its ``cycle`` span: the breaker and
        world checks, then _encode_cycle (encode, launch, wait, read
        back) and _commit_cycle on its verdicts. Returns None to
        request full sequential fallback (nothing has been mutated in
        that case)."""
        eng = self.engine
        with eng.spans.span("cycle") as box:
            if (self.supervisor is not None
                    and not self.supervisor.allow_cycle(eng.cycle_seq)):
                # Breaker open: the device path is known-bad, skip
                # straight to the host path without paying retries or
                # timeouts.
                return self._fallback("breaker-open")
            if not self.world_is_fast_path_safe():
                return self._fallback("world")

            if not any(pcq.items for pcq in
                       eng.queues.cluster_queues.values()):
                if any(pcq.inadmissible for pcq in
                       eng.queues.cluster_queues.values()):
                    # Only parked workloads remain; the sequential path
                    # owns the inadmissible re-queueing bookkeeping.
                    return self._fallback("idle-inadmissible")
                return CycleResult()

            enc = self._encode_cycle()
            if enc is None:
                return None
            box.attrs["lattice"] = enc.lattice
            box.attrs["preempt_slots"] = enc.preempt_slots
            box.attrs["preempt_skipped"] = enc.preempt_skipped
            box.attrs["preempt_columns"] = enc.preempt_columns
            return self._commit_cycle(enc)

    def _commit_tas_stats(self, tas_plan) -> None:
        st = self.tas_stats
        st["plan_cycles"] += 1
        st["heads_planned"] += len(tas_plan.placements) + sum(
            len(v) for v in tas_plan.demote.values())
        st["placed_device"] += tas_plan.placed_device
        st["placed_host"] += tas_plan.placed_host
        st["memo_hits"] += tas_plan.memo_hits
        st["encode_s"] += tas_plan.timings["encode"]
        st["place_s"] += tas_plan.timings["place"]
        st["decode_s"] += tas_plan.timings["decode"]
        for n in tas_plan.launch_sizes:
            self.tas_heads_per_launch[n] = \
                self.tas_heads_per_launch.get(n, 0) + 1

    def _encode_cycle(self):
        """The encode phase: world + row tensors, head selection with
        hold-back, per-root host/device partitioning, batched TAS
        nomination, sim-augmented multi-flavor nomination, and the
        executor call. Returns the solved cycle for _commit_cycle, or
        None after counting the fallback (held-head-churn, all-host)."""
        import jax.numpy as jnp

        eng = self.engine
        spans = eng.spans
        host = spans.begin("host_encode")
        now = eng.clock
        # Incremental encoding: the queue manager's row cache carries the
        # pending world as live tensors; a cycle pays only for rows that
        # changed since the last one (tensor/rowcache.py), and the world
        # structure tensors are memoized by spec version with only the
        # usage matrix refilled per cycle (_world_tensors).
        rows = eng.queues.rows
        rows.maybe_compact()
        w = self._world_tensors()
        rows.refresh_held(now)
        wl = rows.tensors(w)
        pending_infos = rows.info_of
        W = wl.num_workloads
        C = w.num_cqs
        Rn = w.root_members.shape[0]

        # --- host-side head + root partitioning ---
        ready = rows.requeue_at <= now
        active = rows.active & ready & (wl.cq >= 0)
        rank = rows.head_ranks()
        cq_safe_idx = np.maximum(wl.cq, 0)

        # Head selection with live hold-back checks: requeue-at can be
        # mutated on status without a queue transition, and only heads
        # gate on it (ClusterQueue.Pop skips held entries,
        # cluster_queue.go:715). Re-read it for each candidate head; a
        # held head yields to the next-ranked workload of its CQ.
        for _hold_round in range(16):
            eff = np.where(active, rank, _HOST_BIG)
            head_rank = np.full(C, _HOST_BIG, np.int64)
            np.minimum.at(head_rank, cq_safe_idx,
                          np.where(wl.cq >= 0, eff, _HOST_BIG))
            has_head = head_rank < _HOST_BIG
            is_head = active & (wl.cq >= 0) \
                & (eff == head_rank[cq_safe_idx])
            head_wid = np.full(C, -1, np.int64)
            head_wid[wl.cq[is_head]] = np.nonzero(is_head)[0]
            held = []
            for wid in head_wid[has_head]:
                ra = pending_infos[wid].obj.status.requeue_at
                rows.requeue_at[wid] = -np.inf if ra is None else ra
                if ra is not None and ra > now:
                    held.append(wid)
            if not held:
                break
            active[held] = False
        else:
            # Pathological hold churn: give up on the fast path.
            spans.end()
            return self._fallback("held-head-churn")

        head_eligible = np.zeros(C, bool)
        head_eligible[has_head] = wl.eligible[head_wid[has_head]]
        flavor_safe = self._cq_flavor_safe(w)

        # Namespace-selector CQs: a mismatched head parks as
        # inadmissible at nomination (scheduler.go:636); the host path
        # owns that bookkeeping, so those heads' roots demote. Checked
        # only for the (rare) CQs that carry a selector.
        ns_mismatch = np.zeros(C, bool)
        sel_cqs = self._cq_has_selector(w)
        if sel_cqs is not None:
            from kueue_tpu.workload_info import namespace_selector_mismatch
            for ci in np.nonzero(has_head & sel_cqs)[0]:
                if namespace_selector_mismatch(
                        eng.cache.cluster_queues[w.cq_names[ci]]
                        .namespace_selector,
                        eng.namespace_labels.get(
                            pending_infos[head_wid[ci]].obj.namespace)):
                    ns_mismatch[ci] = True

        root_of_cq = w.root_of_cq
        host_root = np.zeros(Rn, bool)

        def demote(cq_mask: np.ndarray, reason: str) -> None:
            """Hand every root containing a flagged CQ to the host path;
            reason counters are per newly-demoted root."""
            roots = np.unique(root_of_cq[cq_mask])
            new = roots[~host_root[roots]]
            if new.size:
                self._host_root(reason, int(new.size))
                host_root[new] = True

        demote(has_head & ~head_eligible, "head-ineligible")
        demote(has_head & ~flavor_safe, "flavor-unsafe")
        demote(ns_mismatch, "namespace-mismatch")
        # Closed preemption gates (orchestrated preemption /
        # ConcurrentAdmission): the gate semantics — block preemption,
        # raise BlockedOnPreemptionGates — live in the host path
        # (cycle.py _process_entry), so gated heads go there.
        gated = np.zeros(C, bool)
        for ci in np.nonzero(has_head)[0]:
            if pending_infos[head_wid[ci]].obj.has_closed_preemption_gate():
                gated[ci] = True
        demote(gated, "preemption-gated")

        # --- batched TAS planning (tas/batched.py) ---
        # Nominate a topology assignment for every device-eligible TAS
        # head BEFORE the quota kernel launches; heads needing an
        # unsupported TAS feature (or whose placement fails) demote
        # only their root. With the planner off (KUEUE_TPU_TAS_BATCH=0)
        # _cq_flavor_safe already demoted every TAS CQ above.
        from kueue_tpu.tas import batched as _tb
        tas_plan = None
        tas_cq = None
        spans.begin("tas_place")
        if _tb.enabled():
            tas_cq = self._cq_tas_mask(w)
            # The serving rows keep topology heads device-eligible on
            # the planner's behalf (schema.serving_shape_eligible); a
            # topology head on a CQ with no TAS flavor can't be placed
            # by anyone — the host path owns its inadmissible verdict.
            topo = np.zeros(C, bool)
            for ci in np.nonzero(has_head)[0]:
                inf = pending_infos[head_wid[ci]]
                h = getattr(inf, "_has_topo_req", None)
                if h is None:
                    h = any(ps.topology_request is not None
                            for ps in inf.obj.pod_sets)
                    inf._has_topo_req = h
                topo[ci] = h
            demote(topo if tas_cq is None else (topo & ~tas_cq),
                   "tas-flavor-mismatch")
        if tas_cq is not None:
            # Preemption-enabled TAS CQs: the host owns the
            # PREEMPT -> simulate-empty ladder AND the sim-grid never
            # sees TAS flavors (pre-demoting keeps sim_cq clean).
            demote(has_head & tas_cq & ~w.no_preemption,
                   "tas-preemption")
            need = has_head & tas_cq & ~host_root[root_of_cq]
            if need.any():
                tas_plan = _tb.plan_cycle(eng, w, head_wid, need)
                for reason, cis in sorted(tas_plan.demote.items()):
                    m = np.zeros(C, bool)
                    m[cis] = True
                    demote(m, reason)
                # Shared-forest closure: forests also committed by
                # host-root TAS heads must serialize through one path.
                closed = _tb.closure_demotions(
                    tas_plan, _tb.cq_tas_info(eng.cache), w, has_head,
                    tas_cq, host_root)
                if closed:
                    m = np.zeros(C, bool)
                    m[closed] = True
                    demote(m, "tas-forest-shared")
                self._commit_tas_stats(tas_plan)
        spans.end()
        cq_on_device = ~host_root[root_of_cq]

        # Multi-flavor groups on preemption-enabled CQs: the flavor
        # choice depends on preemption simulations
        # (flavorassigner.go:1198 + preemption_oracle.go:30), so those
        # heads get the sim-augmented nomination before the cycle runs.
        if w.group_flavors.shape[2] > 1:
            mf = np.any(w.group_flavors[:, :, 1:] >= 0, axis=(1, 2))
        else:
            mf = np.zeros(C, bool)
        sim_cq = (mf & ~w.no_preemption & has_head & head_eligible
                  & flavor_safe & cq_on_device)
        # Each head's flavor mask (labels, taints, tolerations, evaluated
        # at row encode): the rows the cycle program's assign pass reads,
        # so the sim nomination and it walk the same flavors. A head is
        # narrowed where its mask excludes a flavor its queue's groups
        # name.
        head_ok = np.ones((C, wl.flavor_ok.shape[1]), bool)
        head_ok[has_head] = wl.flavor_ok[head_wid[has_head]]
        narrowed_heads = int(np.count_nonzero(has_head & np.any(
            (w.group_flavors >= 0) & ~head_ok[
                np.arange(C)[:, None, None],
                np.maximum(w.group_flavors, 0)], axis=(1, 2))))
        if sim_cq.any():
            # The sim grid is single-podset; multi-podset heads needing
            # it go host.
            multi_ps = np.zeros(C, bool)
            for ci in np.nonzero(sim_cq)[0]:
                if len(pending_infos[head_wid[ci]].total_requests) > 1:
                    multi_ps[ci] = True
            if multi_ps.any():
                demote(multi_ps, "sim-multi-podset")
                cq_on_device = ~host_root[root_of_cq]
                sim_cq = sim_cq & cq_on_device
        pre = None
        pcfg = adm = admitted = None
        if sim_cq.any():
            if eng.cycle.enable_fair_sharing:
                # Fair-sharing preemption stays host-side; so do heads
                # whose nomination would need it.
                demote(sim_cq, "fair-needs-sim")
                cq_on_device = ~host_root[root_of_cq]
            else:
                pcfg = self._cq_policy_cfg(w)
                admitted, adm = self._encode_admitted(w)
                # A container beside host_encode, which runs on after
                # it: its leaves are the nomination's own.
                box = spans.next("sim_nomination")
                box.attrs["mask_narrowed_heads"] = narrowed_heads
                pre = self._sim_nomination(
                    box, w, wl, jnp.asarray(w.usage), head_wid, sim_cq,
                    head_ok, adm, pcfg)
                spans.next("host_encode")
                demote_cq = pre[3]
                if demote_cq.any():
                    demote(demote_cq, "sim-overflow")
                    cq_on_device = ~host_root[root_of_cq]
                    for arr in pre[:3]:
                        arr[~cq_on_device] = -1

        if pre is None:  # no nomination ran: host_encode has the count
            host.attrs["mask_narrowed_heads"] = narrowed_heads

        device_w = active & wl.eligible & (wl.cq >= 0) \
            & cq_on_device[cq_safe_idx]
        if not device_w.any():
            spans.end()
            return self._fallback("all-host")
        host.attrs["heads"] = int(np.count_nonzero(has_head))
        host.attrs["pending"] = int(np.count_nonzero(device_w))

        # --- device cycle ---
        upload = spans.next("upload")
        # World-structure arrays are device-resident across cycles
        # (re-uploaded only on spec changes); per-cycle uploads are just
        # the row tensors + usage.
        args = dict(
            rank=jnp.asarray(rank),
            commit_rank=jnp.asarray(rows.commit_ranks()),
            wl_cq=jnp.asarray(wl.cq), wl_req=jnp.asarray(wl.requests),
            wl_priority=jnp.asarray(wl.priority),
            wl_has_qr=jnp.asarray(wl.has_quota_reservation),
            wl_hash=jnp.asarray(wl.hash_id),
            wl_ts=jnp.asarray(wl.timestamp),
        )
        # Per-workload flavor eligibility (taints/selectors/affinity):
        # node-filtered rows ride the dense path instead of demoting
        # their root. Always passed — one cycle program whether or not a
        # row is narrowed.
        args["wl_flavor_ok"] = jnp.asarray(wl.flavor_ok)
        args.update(self._device_world_args(w))
        # Bucket-pad the workload axis so recurring cycles with varying
        # pending counts reuse one compiled program per bucket.
        from kueue_tpu.tensor.schema import (
            WL_PAD_FILLS,
            pad_axis0,
            pow2_bucket,
        )

        Wp = pow2_bucket(W, 64)
        device_w_padded = device_w
        if Wp != W:
            for key, fill in WL_PAD_FILLS.items():
                if key in args:  # optional tensors (wl_flavor_ok)
                    args[key] = jnp.asarray(pad_axis0(args[key], Wp, fill))
            device_w_padded = pad_axis0(device_w, Wp, False)
        pending = jnp.asarray(device_w_padded)
        inadmissible = jnp.zeros(Wp, bool)
        usage = jnp.asarray(w.usage)
        statics = dict(depth=w.depth, num_resources=w.num_resources,
                       num_cqs=w.num_cqs,
                       fair_mode=eng.cycle.enable_fair_sharing,
                       num_flavors=max(w.num_flavors, 1))
        pre_kwargs = {}
        if pre is not None:
            pre_kwargs = dict(
                slot_kind_override=jnp.asarray(pre[0]),
                slot_borrows_override=jnp.asarray(pre[1]),
                slot_flavor_override=jnp.asarray(pre[2]))

        # Fused classical preemption: with any preemption-enabled CQ in
        # a classical world, ship the admitted tensors + policy config so
        # preempt-flagged slots get their victim sets selected inside
        # the cycle program — one launch instead of three.
        fused = (not eng.cycle.enable_fair_sharing
                 and bool(np.any(~w.no_preemption)))
        slot_maybe = None
        if fused:
            if pcfg is None:
                pcfg = self._cq_policy_cfg(w)
            if adm is None:
                admitted, adm = self._encode_admitted(w)
            ap = self._adm_padded(adm, w)
            pre_kwargs.update(
                adm_cq=ap["adm_cq"], adm_pri=ap["adm_pri"],
                adm_ts=ap["adm_ts"], adm_qrt=ap["adm_qrt"],
                adm_uid=ap["adm_uid"], adm_evicted=ap["adm_ev"],
                adm_usage=ap["adm_usage"],
                pc_wcq_policy=pcfg["j"]["wcq_policy"],
                pc_reclaim_policy=pcfg["j"]["reclaim_policy"],
                pc_bwc_forbidden=pcfg["j"]["bwc_forbidden"],
                pc_bwc_threshold=pcfg["j"]["bwc_threshold"],
                pc_cq_has_parent=pcfg["j"]["cq_has_parent"],
                root_of_cq=pcfg["j"]["root_of_cq"],
                adm_rank=ap["adm_rank"],
                adm_by_root=ap["adm_by_root"])
            slot_maybe = self._slot_maybe(
                w, pcfg, adm, self._head_pri(wl, head_wid))
            pre_kwargs["slot_maybe"] = jnp.asarray(slot_maybe)
        _inputs = dict(pending=pending, inadmissible=inadmissible,
                       usage=usage, **args, **pre_kwargs)
        # What this cycle handed over; the world and the admitted set
        # are device-resident by version and not counted.
        upload.attrs["bytes"] = sum(
            _inputs[k].nbytes for k in _PER_CYCLE_UPLOADS if k in _inputs)
        spans.end()
        _obs_perf.device_call("cycle_step", _inputs, statics)
        # The executor blocks until the verdicts are on the host
        # (service._run_cycle_step records dispatch / device_wait /
        # readback).
        out = self._exec_call("cycle_step", self.executor.cycle_step,
                              _inputs, statics)
        # The program's own counts (batched._cycle_core): the slots its
        # fused preemptor was run for — the launch took that branch
        # where there is one — and the ordered candidates the scans
        # passed over as invalid.
        preempt_slots, preempt_skipped = (int(n) for n in out[14])
        lattice = preempt_slots > 0
        # The width the program's preemptor runs at (0: it has none).
        from kueue_tpu.oracle.batched import preempt_width
        preempt_columns = preempt_width(
            wl.requests.shape[1], w.num_resources,
            w.nominal.shape[1]) if fused else 0

        from types import SimpleNamespace
        return SimpleNamespace(
            out=out, w=w, wl=wl, pending_infos=pending_infos, now=now,
            W=W, C=C, cq_safe_idx=cq_safe_idx, device_w=device_w,
            cq_on_device=cq_on_device, host_root=host_root,
            root_of_cq=root_of_cq, has_head=has_head,
            tas_plan=tas_plan, fused=fused, admitted=admitted,
            lattice=lattice, preempt_slots=preempt_slots,
            preempt_skipped=preempt_skipped,
            preempt_columns=preempt_columns)

    def _commit_cycle(self, enc) -> Optional[CycleResult]:
        """Commit the cycle from the verdicts the executor read back:
        verdict decode, TAS commit-order recheck, columnar apply,
        finalize, host tail. ``enc`` is this cycle's _encode_cycle."""
        from kueue_tpu.tas import batched as _tb

        eng = self.engine
        spans = eng.spans
        decode = spans.begin("verdict_decode", lattice=enc.lattice)
        w, wl, out = enc.w, enc.wl, enc.out
        pending_infos = enc.pending_infos
        now, W, C = enc.now, enc.W, enc.C
        cq_safe_idx, device_w = enc.cq_safe_idx, enc.device_w
        cq_on_device = enc.cq_on_device
        host_root, root_of_cq = enc.host_root, enc.root_of_cq
        has_head = enc.has_head
        tas_plan, fused = enc.tas_plan, enc.fused
        admitted = enc.admitted
        preempt_targets: dict[int, list] = {}

        def demote(cq_mask: np.ndarray, reason: str) -> None:
            roots = np.unique(root_of_cq[cq_mask])
            new = roots[~host_root[roots]]
            if new.size:
                self._host_root(reason, int(new.size))
                host_root[new] = True

        if _obs_perf.ACTIVE is not None:
            _obs_perf.device_result("cycle_step", out)
        (new_pending, new_inadmissible, usage2, wl_admitted, slot_admitted,
         slot_position, flavor_of_res, any_oracle, slot_oracle,
         slot_preempting, head_idx, slot_overflow, victim_ids,
         victim_variant, _preempt_counts) = out
        # The victims come packed, [C, v_cap] admitted ids (-1 where a
        # column holds no target; [C, 0] with no fused preemptor), each
        # with its variant. A slot's targets are listed by ascending
        # admitted index: events, journal lines and the eviction order
        # follow that list.
        vids = np.asarray(victim_ids)
        by_id = np.argsort(vids, axis=1)
        vids = np.take_along_axis(vids, by_id, axis=1)
        # Slots with a selected victim set.
        found_any = (vids >= 0).any(axis=1)
        decode.attrs["victim_entries"] = int(np.count_nonzero(found_any))
        decode.attrs["reclaim_victims"] = 0

        if fused:
            overflow = np.asarray(slot_overflow) & cq_on_device
            if overflow.any():
                # More victims than v_cap: the host preemptor owns those
                # roots this cycle.
                demote(overflow, "preemption-overflow")
                cq_on_device = ~host_root[root_of_cq]
            # Host-side Target lists for the preempting slots, from the
            # in-program victim selection.
            sp = np.asarray(slot_preempting)
            # Of the slots with a victim set, committed ones become
            # PREEMPTING entries; uncommitted ones (capacity claimed by
            # an earlier entry) are the reference's skipped preemptions
            # and are counted by _apply.
            if (sp | found_any).any():
                vvar = np.take_along_axis(np.asarray(victim_variant),
                                          by_id, axis=1)
                from kueue_tpu.ops import preempt as pops
                decode.attrs["reclaim_victims"] = int(np.count_nonzero(
                    (vids >= 0) & (vvar != pops.V_WITHIN_CQ)
                    & (sp & cq_on_device)[:, None]))
                variant_reason = self._variant_reason()
                from kueue_tpu.scheduler.preemption import IN_CLUSTER_QUEUE
                for ci in np.nonzero((sp | found_any) & cq_on_device)[0]:
                    preempt_targets[int(ci)] = [
                        (admitted[v],
                         variant_reason.get(int(var), IN_CLUSTER_QUEUE))
                        for v, var in zip(vids[ci], vvar[ci]) if v >= 0]
        if bool(any_oracle):
            flagged = np.asarray(slot_oracle)
            if eng.cycle.enable_fair_sharing:
                # Fair-sharing preemption strategies stay host-side.
                demote(flagged, "preemption-scope")
                cq_on_device = ~host_root[root_of_cq]
            # Defensive: any slot still flagged must be on a host root
            # (classical worlds decide preemption in-program).
            still = np.asarray(slot_oracle) & cq_on_device
            if still.any():
                demote(still, "unexpected-oracle-flag")
                cq_on_device = ~host_root[root_of_cq]

        self.cycles_on_device += 1
        decode.attrs["device_heads"] = int(
            np.count_nonzero(has_head & cq_on_device))
        # Replay capture point: a cheap fingerprint of the raw device
        # verdicts (before host decode/apply), recorded into traces so a
        # decision-stream divergence can be attributed to the kernel
        # (digest differs) vs the apply path (digest equal).
        import zlib as _zlib
        _vd = 0
        for _arr in (wl_admitted, slot_admitted, slot_position,
                     slot_preempting, vids):
            _vd = _zlib.crc32(np.ascontiguousarray(_arr).tobytes(), _vd)
        self.last_verdict_digest = _vd
        spans.next("apply")

        # Commit-order re-check for planned TAS admits: serialize them
        # through the overlay (tas/batched.commit_plan); a nominated
        # placement beaten to its leaves by an earlier slot DROPS its
        # admit verdict — the batched form of the sequential commit
        # skip. Dropped rows were never popped, so they simply stay
        # pending for the next cycle.
        tas_attach = None
        tas_drops: list = []
        wl_admitted = np.asarray(wl_admitted)
        slot_position = np.asarray(slot_position)
        flavor_of_res = np.asarray(flavor_of_res)
        if tas_plan is not None and tas_plan.placements:
            for _round in range(C + 1):
                tas_attach, tas_drops, demote_cis = _tb.commit_plan(
                    eng, w, wl, tas_plan, wl_admitted, slot_position,
                    flavor_of_res, cq_on_device, W)
                if not demote_cis:
                    break
                # A drop on a multi-CQ root invalidates the root's
                # later quota verdicts; the host re-runs the root.
                m = np.zeros(C, bool)
                m[demote_cis] = True
                demote(m, "tas-commit-conflict")
                cq_on_device = ~host_root[root_of_cq]
            if tas_drops:
                self.tas_stats["commit_drops"] += len(tas_drops)
                wl_admitted = wl_admitted.copy()
                wl_admitted[tas_drops] = False

        apply_rows = device_w & cq_on_device[cq_safe_idx]
        result, finalize = self._apply(
            w, wl, pending_infos,
            wl_admitted,
            np.asarray(new_inadmissible),
            slot_position,
            flavor_of_res,
            apply_rows=apply_rows,
            slot_mask=cq_on_device,
            slot_preempting=np.asarray(slot_preempting),
            head_idx=np.asarray(head_idx),
            preempt_targets=preempt_targets,
            tas_attach=tas_attach)
        for i in tas_drops:
            # Sequential stats/entry parity for commit skips
            # (_process_entry's "no longer fits" verdict). Invisible to
            # the canonical decision stream, like sequential skips.
            e = Entry(info=pending_infos[i])
            e.status = EntryStatus.SKIPPED
            e.inadmissible_msg = (
                "Workload no longer fits after processing another "
                "workload")
            result.entries.append(e)
            result.stats.skipped += 1
        # apply: decode + cache assume, what the reference's cycle
        # blocks on. finalize: status + metric + journal writes — the
        # reference's ASYNC status PATCH (scheduler.go:870), still
        # inside this cycle's wall time.
        spans.next("finalize")
        finalize()
        spans.end()

        # --- host tail: sequential cycle over the host roots ---
        eng.last_cycle_mode = "device"
        host_cqs = np.nonzero(has_head & ~cq_on_device)[0]
        if host_cqs.size:
            self.cycles_hybrid += 1
            eng.last_cycle_mode = "hybrid"
            spans.begin("host_tail")
            heads = []
            for ci in host_cqs:
                pcq = eng.queues.cluster_queues.get(w.cq_names[ci])
                if pcq is None:
                    continue
                h = pcq.pop(now)
                if h is not None:
                    heads.append(h)
            if heads:
                host_result = eng._sequential_cycle(heads,
                                                    count_cycle=False)
                result.entries.extend(host_result.entries)
                result.inadmissible.extend(host_result.inadmissible)
                st, hst = result.stats, host_result.stats
                st.admitted += hst.admitted
                st.preempting += hst.preempting
                st.skipped += hst.skipped
                st.inadmissible += hst.inadmissible
                for k, v in hst.preemption_skips.items():
                    st.preemption_skips[k] = \
                        st.preemption_skips.get(k, 0) + v
            spans.end()
        self._count("oracle_cycles_total", (eng.last_cycle_mode,))
        return result

    def _apply(self, w, wls, pending_infos, wl_admitted, parked,
               slot_position, flavor_of_res, apply_rows=None,
               slot_mask=None, slot_preempting=None,
               head_idx=None, preempt_targets=None, tas_attach=None):
        """Apply verdicts through the engine's assume path. Rows outside
        ``apply_rows`` / slots outside ``slot_mask`` belong to host roots
        and are left untouched (the sequential tail owns them).

        Returns ``(CycleResult, finalize)``: the caller MUST invoke
        ``finalize()`` (status conditions + metric/journal flush — the
        async-PATCH analog) after stopping the apply-phase clock."""
        from kueue_tpu.scheduler.preemption import Target

        eng = self.engine
        result = CycleResult()
        W = len(pending_infos)
        if apply_rows is None:
            apply_rows = np.ones(W, bool)
        if slot_mask is None:
            slot_mask = np.ones(w.num_cqs, bool)
        if slot_preempting is None:
            slot_preempting = np.zeros(w.num_cqs, bool)

        # Group verdict rows per slot (vectorized: verdict rows are
        # sparse relative to the row space).
        admit_of_slot: dict[int, int] = {}
        parked_of_slot: dict[int, list[int]] = {}
        adm_rows = np.nonzero(wl_admitted[:W] & apply_rows)[0]
        for ci, i in zip(wls.cq[adm_rows].tolist(), adm_rows.tolist()):
            admit_of_slot[ci] = i
        park_rows = np.nonzero(parked[:W] & apply_rows)[0]
        for ci, i in zip(wls.cq[park_rows].tolist(), park_rows.tolist()):
            parked_of_slot.setdefault(ci, []).append(i)

        # Apply per slot in the host's nominate order (the queue manager's
        # ClusterQueue iteration order). Cohort-inadmissible requeues
        # triggered by evictions are DEFERRED to one bulk pass after the
        # loop — every NoFit-parked head in an evicting cohort
        # re-activates at cycle end, exactly like the sequential path
        # (engine._sequential_cycle defers identically; in the reference
        # these requeues ride watch events that land after schedule()).
        cq_idx = {n: i for i, n in enumerate(w.cq_names)}
        nominate_order = [cq_idx[n] for n in eng.queues.cluster_queues
                          if n in cq_idx]
        bulk = eng.begin_bulk_admit()
        requeue_cohorts: set = set()
        eng._deferred_cohort_requeue = requeue_cohorts
        try:
            pairs = self._apply_slots(
                nominate_order, slot_mask, admit_of_slot,
                parked_of_slot, pending_infos, w, wls,
                flavor_of_res, slot_position,
                slot_preempting, head_idx, preempt_targets,
                eng, bulk, result, tas_attach=tas_attach)
        finally:
            eng._deferred_cohort_requeue = None

        def finalize() -> None:
            """The async-status-PATCH analog (scheduler.go:870): runs
            after the apply span's clock stops, timed as its own
            phase."""
            eng.bulk_finalize_batch(pairs, bulk)
            eng._requeue_cohorts_bulk(requeue_cohorts)
            eng.flush_bulk_admit(bulk)

        return result, finalize

    def _apply_slots(self, nominate_order, slot_mask, admit_of_slot,
                     parked_of_slot, pending_infos, w, wls, flavor_of_res,
                     slot_position, slot_preempting, head_idx,
                     preempt_targets, eng, bulk, result, tas_attach=None):
        from kueue_tpu.scheduler.preemption import Target

        admits = []
        _pt = _obs_perf.begin()
        # Columnar diff build: the per-slot loop reads its verdict
        # columns through plain Python lists — one bulk tolist() per
        # column instead of a numpy scalar index (about a microsecond
        # each) per slot —
        # and the assignment-flyweight key bytes for ALL slots come from
        # one contiguous tobytes() sliced per slot.
        slot_mask_l = slot_mask.tolist()
        slot_preempting_l = slot_preempting.tolist()
        slot_position_l = slot_position.tolist()
        head_idx_l = head_idx.tolist() if head_idx is not None else None
        fob = np.ascontiguousarray(flavor_of_res)
        fb_stride = fob.shape[1] * fob.shape[2] * fob.itemsize
        fb_all = fob.tobytes()
        for ci in nominate_order:
            if not slot_mask_l[ci]:
                continue
            i = admit_of_slot.get(ci)
            if i is not None:
                info = pending_infos[i]
                entry = self._make_entry(
                    info, w, wls, flavor_of_res, i,
                    topo=None if tas_attach is None
                    else tas_attach.get(i),
                    fbytes=fb_all[ci * fb_stride:(ci + 1) * fb_stride],
                    ci=ci)
                entry.status = EntryStatus.ASSUMED
                entry.commit_position = slot_position_l[ci]
                admits.append(entry)
                result.entries.append(entry)
                result.stats.admitted += 1
            if slot_preempting_l[ci]:
                wid = head_idx_l[ci]
                info = pending_infos[wid]
                entry = self._make_entry(info, w, wls, flavor_of_res, wid)
                entry.status = EntryStatus.PREEMPTING
                entry.preemption_targets = [
                    Target(victim, reason)
                    for victim, reason in preempt_targets.get(int(ci), [])]
                entry.inadmissible_msg = (
                    f"Preempting {len(entry.preemption_targets)} "
                    "workload(s)")
                eng._issue_preemptions(entry, bulk=bulk)
                result.entries.append(entry)
                result.stats.preempting += 1
            head_row = head_idx_l[ci] if head_idx_l is not None else -1
            for i in parked_of_slot.get(ci, ()):
                info = pending_infos[i]
                pcq = eng.queues.cluster_queues.get(info.cluster_queue)
                if pcq is not None:
                    pcq.park(info.key)
                # Entries surface only for parked HEADS (the sequential
                # path parks scheduling-equivalence siblings silently
                # inside requeue_if_not_present) — a mass bulk-park
                # cycle must not allocate one Entry per sibling row.
                if i == head_row:
                    entry = Entry(info=info,
                                  requeue_reason=RequeueReason.NO_FIT)
                    entry.inadmissible_msg = "NoFit (batched oracle)"
                    result.entries.append(entry)
        _obs_perf.end("apply.diff_build", _pt)
        # Preempt-mode slots whose victim set was selected but whose
        # commit lost (capacity claimed by an earlier entry this cycle)
        # are the reference's skipped preemptions
        # (admission_cycle_preemption_skips, scheduler.go:432 overlap /
        # failed re-fit): count them like _sequential_cycle does.
        if preempt_targets:
            for ci, targets in preempt_targets.items():
                if targets and not slot_preempting[ci]:
                    name = w.cq_names[ci]
                    result.stats.preemption_skips[name] = \
                        result.stats.preemption_skips.get(name, 0) + 1
            for cq_name, skips in result.stats.preemption_skips.items():
                m = eng.metrics.admission_cycle_preemption_skips
                m[cq_name] = m.get(cq_name, 0) + skips
                eng.registry.counter(
                    "admission_cycle_preemption_skips").inc(
                    (cq_name,), skips)
        # The whole cycle's admissions assumed in one flat engine pass
        # (admissions never interact with the preemption/park verdicts
        # applied above — victims are admitted rows, parks are other
        # pending rows). Status finalization is deferred to the
        # finalize phase (bulk_finalize_batch).
        _pt = _obs_perf.begin()
        pairs = eng.bulk_assume_batch(admits, bulk)
        _obs_perf.end("apply.rowcache_writeback", _pt)
        return pairs

    def _make_entry(self, info, w, wls, flavor_of_res, i,
                    topo=None, fbytes=None, ci=None) -> Entry:
        """Entry for an admitted verdict row. Assignments are FLYWEIGHTS:
        rows with equal scheduling-equivalence hash and equal slot flavor
        picks produce identical Assignment structures, and the bulk-admit
        path never mutates them — one immutable instance serves every
        equivalent admission (the per-entry construction was the largest
        single apply-phase cost at 1k admissions/cycle).

        ``flavor_of_res[ci]`` is [P, S]: one PodSetAssignment per real
        pod set (flavorassigner.go:707 builds one per podset)."""
        if ci is None:
            ci = int(wls.cq[i])
        # Content-addressed key: the scheduling-equivalence hash TUPLE
        # (dense hash ids are recycled and must not key a cache) plus the
        # slot's flavor picks, guarded by the spec version that defines
        # the flavor-id space. TAS admits carry a per-admission topology
        # assignment and BYPASS the flyweight both ways (a cached plain
        # assignment must not serve a placed admission, and a placed one
        # must not be reused — bulk_assume_batch flyweights by object
        # identity, so fresh Assignment objects are required).
        ver = self.engine.cache.spec_version
        cache = getattr(self, "_assignment_cache", None)
        if cache is None or cache[0] != ver:
            cache = (ver, {})
            self._assignment_cache = cache
        rows = self.engine.queues.rows
        if fbytes is None:
            fbytes = flavor_of_res[ci].tobytes()
        # The scheduling-equivalence hash's FIRST element is the cluster
        # queue (cache/queues.scheduling_hash) — but Assignment content
        # is CQ-independent (pod-set shapes plus the slot's flavor
        # picks, which fbytes covers), so the flyweight key drops it:
        # equivalent admissions across the whole CQ axis share one
        # Assignment instead of one per queue.
        h = rows._hash_tuple[i]
        key = (h[1:] if h is not None else None, fbytes)
        if topo is None and h is not None:
            cached = cache[1].get(key)
            if cached is not None:
                return Entry(info=info, assignment=cached)
        pod_sets = []
        usage: dict[FlavorResource, int] = {}
        for p, psr in enumerate(info.total_requests):
            flavors = {}
            for s_i, res in enumerate(w.resource_names):
                fl = flavor_of_res[ci, p, s_i]
                if fl < 0 or wls.requests[i, p, s_i] <= 0:
                    continue
                name = w.flavor_names[fl]
                flavors[res] = FlavorAssignment(name=name, mode=Mode.FIT)
                fr = FlavorResource(name, res)
                usage[fr] = usage.get(fr, 0) + int(wls.requests[i, p, s_i])
            pod_sets.append(PodSetAssignment(
                name=psr.name, flavors=flavors,
                requests=dict(psr.requests), count=psr.count,
                topology_assignment=None if topo is None
                else topo.get(psr.name)))
        assignment = Assignment(pod_sets=pod_sets, usage=usage)
        if topo is None and h is not None:
            cache[1][key] = assignment
        return Entry(info=info, assignment=assignment)
