"""The batched scheduling oracle: whole scheduling cycles as one compiled
device program, driven to quiescence by a small host loop.

This is the north-star component (BASELINE.json): the reference's
per-workload admission loop — heads → snapshot → nominate → order → commit
(scheduler.go:286) — lifted into Workloads x ClusterQueues x
FlavorResources array programs:

  cycle_step (jit):
    1. derive quota state from current usage        [ops/quota.derive_world]
    2. pick per-CQ heads (priority/ts ranks)        [segment-min]
    3. nominate ALL heads at once                   [ops/assign.assign_flavors]
    4. order entries (classical iterator key)       [argsort of composite key]
    5. sequential-equivalent commit                 [ops/commit.commit_scan]
    6. park NoFit heads (BestEffortFIFO inadmissible semantics)

Fast-path scope: classical ordering AND fair sharing over arbitrary
cohort forests (the hierarchical device DRS tournament,
ops/commit.commit_grouped_fair via fair_mode);
no-preemption-policy ClusterQueues decided entirely on device; workloads
flagged `needs_oracle` (preemption candidates required) are returned for
the host's sequential preemptor. Multi-podset workloads are pre-filtered
by the encoder (schema.encode_workloads eligible mask).

Decision parity with the sequential engine is enforced by
tests/test_drain_parity.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kueue_tpu.ops import assign as aops
from kueue_tpu.ops import commit as cops
from kueue_tpu.ops import pallas_kernels as pk
from kueue_tpu.ops import quota as qops
from kueue_tpu.tensor.schema import (
    WorkloadTensors,
    WorldTensors,
    encode_snapshot,
    encode_workloads,
)

BIG_RANK = np.int64(1) << 40


@dataclass
class DrainDecision:
    key: str
    cluster_queue: str
    cycle: int
    position: int  # commit position within the cycle
    flavors: dict  # resource -> flavor name (first pod set)
    # Per-podset flavor dicts (multi-podset workloads; [flavors] for
    # single-podset ones).
    podset_flavors: list = None


def _cycle_core(
    pending,  # bool[W]
    inadmissible,  # bool[W]
    usage,  # int64[N, R] (full node usage, invariant-consistent)
    rank,  # int64[W] global head-order rank (priority desc, ts asc)
    commit_rank,  # int64[W] FIFO tiebreak rank for the commit order
    wl_cq,  # int32[W]
    wl_req,  # int64[W, S]
    wl_priority,  # int64[W]
    wl_has_qr,  # bool[W]
    wl_hash,  # int32[W] scheduling-equivalence hash id
    nominal, lend_limit, borrow_limit, parent, ancestors, height,
    group_of_res, group_flavors, no_preemption, can_pwb, can_always_reclaim,
    best_effort, fung_borrow_try_next, fung_pref_preempt_first,
    root_members, root_nodes, local_chain,
    wl_ts=None,  # float64[W] creation time (fair mode ordering)
    fair_weight=None,  # float64[N]
    child_rank=None,  # int64[N] fair-tournament child-order tiebreak
    local_depth=None,  # int32[Rn, K] fair-tournament level structure
    slot_kind_override=None,  # int32[C] ENTRY_* (-1 = use computed kind),
    #   from the bridge's sim-augmented nomination (multi-flavor groups
    #   on preemption-enabled CQs): ENTRY_FIT where the fungibility fold
    #   chose a flavor that fits; ENTRY_PREEMPT where the chosen flavor's
    #   mode is Preempt — the victims are then selected HERE, by the
    #   fused preemptor below, on the overridden flavor (preemption.go:129
    #   GetTargets runs for any Preempt-mode nomination)
    slot_borrows_override=None,  # int32[C] the assignment's borrow level
    #   (-1 = keep): the worst over the head's resources of what each
    #   cell said — a simulated cell's is the borrow WITH its own victims
    #   removed (preemption_oracle.go:41) — which the commit iterator
    #   orders by (scheduler.go:971); it stands whatever the final
    #   target selection frees
    slot_flavor_override=None,  # int32[C, S] flavor per resource (-1 =
    #   keep computed): set by the bridge's sim-augmented nomination when
    #   the fungibility lattice needed preemption simulations to pick the
    #   flavor (multi-flavor groups, flavorassigner.go:1127)
    root_parent_local=None,  # int32[Rn, K] (victim-removal bubbling)
    # --- fused classical preemption (round 2): when the admitted
    # tensors + policy config are provided, preempt-flagged slots get
    # their victim sets selected INSIDE this program
    # (ops/preempt.classical_targets_impl against the cycle-start
    # usage — identical semantics to the former second launch, minus
    # two host round-trips per preempting cycle) ---
    adm_cq=None,  # int32[A]
    adm_pri=None,  # int64[A]
    adm_ts=None,  # float64[A]
    adm_qrt=None,  # float64[A]
    adm_uid=None,  # int64[A]
    adm_evicted=None,  # bool[A]
    adm_usage=None,  # int64[A, R]
    pc_wcq_policy=None,  # int32[C]
    pc_reclaim_policy=None,  # int32[C]
    pc_bwc_forbidden=None,  # bool[C]
    pc_bwc_threshold=None,  # int64[C]
    pc_cq_has_parent=None,  # bool[C]
    root_of_cq=None,  # int32[C]
    adm_rank=None,  # int64[A] precomputed candidate-ordering rank
    #   (ops/preempt.classical_targets_impl adm_rank)
    adm_by_root=None,  # int32[Rn, A_l] admitted ids grouped by root
    wl_flavor_ok=None,  # bool[W, NF] per-workload flavor eligibility
    #   masks (taints/selectors/affinity — ops/assign.assign_flavors
    #   flavor_ok); None = every flavor eligible for every row
    slot_maybe=None,  # bool[C] host precheck: this slot's head COULD
    #   have preemption candidates (exact-conservative: False only when
    #   provably none exist — candidate_generator.go's policy tests
    #   evaluated against the admitted set). Slots masked off resolve to
    #   the kernel's found=False outcome without running the preemptor;
    #   a cycle with no maybe-slots skips target selection entirely
    #   (lax.cond), which is most cycles in converged worlds.
    *,
    depth: int, num_resources: int, num_cqs: int,
    fair_mode: bool = False, num_flavors: int = 1, v_cap: int = 32,
):
    W = pending.shape[0]
    C = num_cqs
    S = num_resources

    # 1. Derive quota state from CQ usage rows.
    is_cq_row = (jnp.arange(usage.shape[0]) < C)[:, None]
    cq_usage = jnp.where(is_cq_row, usage, 0)
    derived = qops.derive_world(nominal, lend_limit, borrow_limit, cq_usage,
                                parent, depth=depth)

    # 2. Heads: per CQ, lowest rank among active pending workloads
    # (manager.go:872 Heads / cluster_queue.go:715 Pop).
    with jax.named_scope("kueue.heads"):
        active = pending & ~inadmissible
        eff_rank = jnp.where(active, rank, BIG_RANK)
        head_rank = pk.select_heads(eff_rank, wl_cq, C, BIG_RANK)
        w_ids = jnp.arange(W, dtype=jnp.int32)
        is_head = active & (eff_rank == head_rank[wl_cq]) \
            & (eff_rank < BIG_RANK)
        # Map CQ -> head workload index (-1 none). Heads are unique per CQ
        # because rank embeds the workload index; non-heads scatter out of
        # bounds and are dropped.
        head_idx = jnp.full((C,), -1, jnp.int32).at[
            jnp.where(is_head, wl_cq, C)].max(w_ids, mode="drop")

    slot_valid = head_idx >= 0
    h_safe = jnp.maximum(head_idx, 0)
    h_cq = jnp.where(slot_valid, wl_cq[h_safe], 0).astype(jnp.int32)
    # [C, P, S]: per-podset head requests.
    h_req = jnp.where(slot_valid[:, None, None], wl_req[h_safe], 0)
    P = h_req.shape[1]

    # 3. Nominate all heads at once (per-podset flavor choices with
    # within-workload usage accumulation, flavorassigner.go:707).
    with jax.named_scope("kueue.assign"):
        h_ok = None
        if wl_flavor_ok is not None:
            h_ok = jnp.where(slot_valid[:, None], wl_flavor_ok[h_safe], True)
        flavor_of_res, pmode, borrows, needs_oracle, usage_fr = \
            aops.assign_flavors(
                h_cq, h_req, derived, nominal, ancestors, height, group_of_res,
                group_flavors, no_preemption, can_pwb, fung_borrow_try_next,
                fung_pref_preempt_first, flavor_ok=h_ok,
                depth=depth, num_resources=S)
    if slot_flavor_override is not None:
        # Sim-nomination overrides are single-podset by construction
        # (the bridge demotes multi-podset sim heads): apply at podset 0
        # and clear the rest.
        has_fo = jnp.any(slot_flavor_override >= 0, axis=1)
        fo0 = jnp.where(has_fo[:, None], slot_flavor_override,
                        flavor_of_res[:, 0])
        flavor_of_res = flavor_of_res.at[:, 0].set(fo0)
        if P > 1:
            tail_clear = has_fo[:, None, None] \
                & (jnp.arange(P)[None, :, None] > 0)
            flavor_of_res = jnp.where(tail_clear, -1, flavor_of_res)
        usage_fr = jnp.where(
            flavor_of_res >= 0,
            flavor_of_res * S + jnp.arange(S)[None, None, :], -1)

    entry_fr_d, req_fr = entry_columns(usage_fr, h_req, nominal.shape[1])

    # 5. Commit. Entry kinds: FIT commits; preempt-mode-no-candidates
    # reserves capacity unless the CQ can always reclaim
    # (scheduler.go:499); everything else skips.
    kind = jnp.where(
        ~slot_valid | needs_oracle, cops.ENTRY_SKIP,
        jnp.where(pmode == aops.P_FIT, cops.ENTRY_FIT,
                  jnp.where((pmode == aops.P_NO_CANDIDATES)
                            & ~can_always_reclaim[h_cq],
                            cops.ENTRY_RESERVE, cops.ENTRY_SKIP)))
    # Bridge-provided verdict overrides (device preemption): a slot with
    # an override is no longer an oracle fallback.
    overridden = jnp.zeros((C,), bool)
    if slot_kind_override is not None:
        overridden = slot_valid & (slot_kind_override >= 0)
        # A Preempt-mode override is an oracle slot on the overridden
        # flavor; its nomination mode is the bridge's, not the pre-sim
        # pass's (parking below reads it).
        want_targets = overridden & (slot_kind_override
                                     == cops.ENTRY_PREEMPT)
        kind = jnp.where(overridden & ~want_targets, slot_kind_override,
                         jnp.where(want_targets, cops.ENTRY_SKIP, kind))
        needs_oracle = (needs_oracle & ~overridden) | want_targets
        pmode = jnp.where(
            want_targets, aops.P_NO_CANDIDATES,
            jnp.where(overridden & (slot_kind_override == cops.ENTRY_FIT),
                      aops.P_FIT, pmode))
    slot_oracle = needs_oracle & slot_valid
    # Commit against the freshly-aggregated full usage (cohort rows are
    # derived from CQ rows; the raw carry may predate aggregation).
    # Root-grouped: subtrees commit independently (ops/commit.py).
    full_usage = derived["usage"]

    # --- fused classical preemption target selection ---
    # The victims stay packed, [C, v_cap], from the selection to the
    # host: ids (-1 where a column holds no target) and each one's
    # candidate variant. [C, 0] where this program has no preemptor.
    slot_overflow = jnp.zeros((C,), bool)
    victim_ids = jnp.zeros((C, 0), jnp.int32)
    victim_variant = jnp.zeros((C, 0), jnp.int32)
    preempt_counts = jnp.zeros((2,), jnp.int32)
    fused_preempt = jnp.zeros((C,), bool)
    slot_victim_row = slot_victim_vals = slot_victim_ids = claimed0 = None
    if adm_cq is not None and not fair_mode:
        from kueue_tpu.ops import preempt as pops

        h_pri = jnp.where(slot_valid, wl_priority[h_safe], 0)
        h_ts = jnp.where(slot_valid, wl_ts[h_safe], 0.0)
        oracle_eff = (slot_oracle if slot_maybe is None
                      else slot_oracle & slot_maybe)
        A_l_ = (adm_by_root.shape[1] if adm_by_root is not None
                else adm_cq.shape[0])
        V = min(v_cap, A_l_)  # must match the kernel's victim width

        def _run_targets(_):
            with jax.named_scope("kueue.preempt"):
                p_fr, p_req = preempt_columns(usage_fr, h_req, entry_fr_d,
                                              req_fr)
                (found, overflow, _n, borrow, v_ids, taken,
                 v_variant, skipped) = pops.classical_targets_impl(
                    oracle_eff, h_pri, h_ts, p_fr, p_req,
                    pc_wcq_policy, pc_reclaim_policy, pc_bwc_forbidden,
                    pc_bwc_threshold, pc_cq_has_parent,
                    adm_cq, adm_pri, adm_ts, adm_qrt, adm_uid, adm_evicted,
                    adm_usage, full_usage, derived["subtree_quota"],
                    lend_limit, borrow_limit, nominal, ancestors, height,
                    local_chain, root_nodes, root_of_cq,
                    adm_rank=adm_rank, adm_by_root=adm_by_root,
                    depth=depth, v_cap=v_cap)
                # Canonical dtypes: both cond branches must match
                # exactly.
                return (found, overflow, borrow.astype(jnp.int32),
                        v_ids.astype(jnp.int32), taken,
                        v_variant.astype(jnp.int32),
                        jnp.sum(jnp.where(oracle_eff, skipped, 0),
                                dtype=jnp.int32))

        def _skip_targets(_):
            return (jnp.zeros((C,), bool), jnp.zeros((C,), bool),
                    jnp.zeros((C,), jnp.int32),
                    jnp.zeros((C, V), jnp.int32),
                    jnp.zeros((C, V), bool),
                    jnp.zeros((C, V), jnp.int32), jnp.int32(0))

        # The slots the preemptor is run for (the launch takes its
        # branch where there is one), and the ordered candidates its
        # scans passed over as invalid.
        n_slots = jnp.sum(oracle_eff, dtype=jnp.int32)
        (pfound, poverflow, pborrow, pv_ids, ptaken,
         pvariant, n_skipped) = jax.lax.cond(
            n_slots > 0, _run_targets, _skip_targets, None)
        preempt_counts = jnp.stack([n_slots, n_skipped])
        pfound = pfound & oracle_eff
        fused_preempt = pfound
        slot_overflow = poverflow & oracle_eff
        # Precheck-masked slots land here too: no candidates == the
        # kernel's found=False outcome.
        no_cand = slot_oracle & ~pfound & ~slot_overflow
        kind = jnp.where(
            pfound, cops.ENTRY_PREEMPT,
            jnp.where(slot_overflow, cops.ENTRY_SKIP,
                      jnp.where(no_cand,
                                jnp.where(can_always_reclaim[h_cq],
                                          cops.ENTRY_SKIP,
                                          cops.ENTRY_RESERVE),
                                kind)))
        borrows = jnp.where(pfound, pborrow, borrows)
        # The commit kernel and the host read the same packed columns.
        R = adm_usage.shape[1]
        is_target = ptaken & pfound[:, None]
        pv_safe = jnp.maximum(pv_ids, 0)
        f_row = jnp.where(
            is_target, local_chain[jnp.maximum(adm_cq[pv_safe], 0), 0], -1)
        f_vals = jnp.where(is_target[:, :, None], adm_usage[pv_safe], 0)
        victim_ids = jnp.where(is_target, pv_safe, -1)
        victim_variant = jnp.where(is_target, pvariant, 0)
        if V < v_cap:
            pad = v_cap - V
            f_row = jnp.concatenate(
                [f_row, jnp.full((C, pad), -1, f_row.dtype)], axis=1)
            f_vals = jnp.concatenate(
                [f_vals, jnp.zeros((C, pad, R), f_vals.dtype)], axis=1)
            victim_ids = jnp.concatenate(
                [victim_ids, jnp.full((C, pad), -1, jnp.int32)], axis=1)
            victim_variant = jnp.concatenate(
                [victim_variant, jnp.zeros((C, pad), jnp.int32)], axis=1)
        slot_victim_row, slot_victim_vals, slot_victim_ids = \
            f_row, f_vals, victim_ids
        claimed0 = jnp.zeros((adm_cq.shape[0],), bool)
        # Every flagged slot is decided in-program; overflow slots are
        # reported separately for host-root demotion.
        needs_oracle = needs_oracle & jnp.zeros((C,), bool)
        slot_oracle = slot_oracle & jnp.zeros((C,), bool)
    if slot_borrows_override is not None:
        # The bridge's borrow stands over the nomination pass's and the
        # fused preemptor's alike.
        borrows = jnp.where(slot_borrows_override >= 0,
                            slot_borrows_override, borrows)
    with jax.named_scope("kueue.commit"):
        if fair_mode:
            # 4f/5f. Fair-sharing tournament ordering fused with the commit
            # (fair_sharing_iterator.go:47): per-root DRS recomputation after
            # every winner, on device.
            slot_admitted, slot_round, _ = cops.commit_grouped_fair(
                slot_valid, entry_fr_d, req_fr, kind, borrows,
                jnp.where(slot_valid, wl_priority[h_safe], 0),
                jnp.where(slot_valid, wl_ts[h_safe], 0.0),
                full_usage, derived["subtree_quota"], lend_limit, borrow_limit,
                nominal, ancestors, derived["potential"], fair_weight, parent,
                root_members, root_nodes, local_chain, child_rank, local_depth,
                root_parent_local, depth=depth, num_flavors=num_flavors)
            # Overrides are classical only.
            slot_preempting = jnp.zeros((C,), bool)
            # Positions: tournament round within the root (rounds are the
            # reference's pop order; roots are independent).
            slot_position = jnp.maximum(slot_round, 0)
        else:
            # 4. Commit order (scheduler.go:971).
            key = cops.make_commit_order_key(
                wl_has_qr[h_safe] & slot_valid, borrows,
                jnp.where(slot_valid, wl_priority[h_safe], 0),
                jnp.where(slot_valid, commit_rank[h_safe], (1 << 24) - 1))
            order = jnp.argsort(key).astype(jnp.int32)
            slot_committed, _ = cops.commit_grouped(
                key, slot_valid, entry_fr_d, req_fr, kind, borrows, full_usage,
                derived["subtree_quota"], lend_limit, borrow_limit, nominal,
                ancestors, root_members, root_nodes, local_chain,
                root_parent_local, slot_victim_row, slot_victim_vals,
                slot_victim_ids, claimed0, depth=depth)
            slot_admitted = slot_committed & (kind != cops.ENTRY_PREEMPT)
            slot_preempting = slot_committed & (kind == cops.ENTRY_PREEMPT)
            # Positions report the global commit order (scheduler.go:971).
            slot_position = jnp.zeros((C,), jnp.int32).at[order].set(
                jnp.arange(C, dtype=jnp.int32))
    adm_target = jnp.where(slot_valid & slot_admitted, h_safe, W)
    wl_admitted = jnp.zeros((W,), bool).at[adm_target].set(True, mode="drop")

    # 6. Park NoFit / no-candidate heads on BestEffortFIFO CQs
    # (cluster_queue.go requeueIfNotPresent + inadmissible map).
    # PREEMPT-overridden slots never park: with targets they are
    # PREEMPTING (plain requeue awaiting evictions); a failed commit fit
    # is a SKIPPED entry (plain requeue) in the reference.
    # PREEMPT verdicts — host-override or fused in-program selection —
    # never park: with targets the entry is PREEMPTING (plain requeue
    # awaiting evictions), and its scheduling-equivalence siblings must
    # not be swept into the inadmissible map with it.
    preempt_override = (overridden | fused_preempt) \
        & (kind == cops.ENTRY_PREEMPT)
    parked_slot = slot_valid & ~slot_admitted & best_effort[h_cq] & (
        (pmode == aops.P_NO_FIT) | (pmode == aops.P_NO_CANDIDATES)) \
        & ~preempt_override
    wl_parked = jnp.zeros((W,), bool).at[
        jnp.where(parked_slot, h_safe, W)].set(True, mode="drop")
    # Scheduling-equivalence bulk parking (cluster_queue.go:615): pending
    # workloads identical in shape to a parked head share its verdict.
    parked_hash_mask = jnp.zeros((W + 1,), bool).at[
        jnp.where(parked_slot, wl_hash[h_safe], W)].set(True, mode="drop")
    wl_parked = wl_parked | (active
                             & parked_hash_mask[jnp.minimum(wl_hash, W)])

    new_pending = pending & ~wl_admitted
    new_inadmissible = inadmissible | (wl_parked & new_pending)

    # Reservations are cycle-local (snapshot-local in the reference):
    # post-cycle usage holds admissions only. Bubbling consumes headroom
    # monotonically, so the order they were added in does not matter:
    # the bottom-up aggregation of step 1, over the CQ rows with each
    # admitted slot's request added, is what replaying them gives.
    with jax.named_scope("kueue.commit"):
        admitted_req = jnp.where(slot_admitted[:, None], req_fr, 0)
        usage_clean = qops.compute_node_usage(
            cq_usage.at[:C].add(admitted_req), derived["subtree_quota"],
            lend_limit, parent, derived["level"], depth=depth)

    any_needs_oracle = jnp.any(slot_oracle)
    return (new_pending, new_inadmissible, usage_clean, wl_admitted,
            slot_admitted, slot_position, flavor_of_res, any_needs_oracle,
            slot_oracle, slot_preempting, head_idx, slot_overflow,
            victim_ids, victim_variant, preempt_counts)


def entry_columns(usage_fr, h_req, num_fr: int):
    """Dense per-flavor-resource entry form for the commit kernels (and
    the preemptor, where it is no wider than the head's own columns:
    preempt_columns): ``entry_fr_d`` int32[C, R] (the column's id, -1
    where it asks nothing) and ``req_fr`` [C, R], the requests of
    ``h_req`` [C, P, S] aggregated over podsets per fr column of
    ``usage_fr`` [C, P, S], so columns are UNIQUE by construction (two
    podsets sharing a flavor must be fit-checked against their combined
    usage; per-column checks would double-book headroom)."""
    C = usage_fr.shape[0]
    flat_fr = usage_fr.reshape(C, -1)
    flat_req = h_req.reshape(C, -1)
    req_fr = jnp.zeros((C, num_fr), h_req.dtype).at[
        jnp.arange(C)[:, None], jnp.where(flat_fr >= 0, flat_fr, 0)
    ].add(jnp.where(flat_fr >= 0, flat_req, 0))
    entry_fr_d = jnp.where(req_fr > 0,
                           jnp.arange(num_fr, dtype=jnp.int32)[None, :], -1)
    return entry_fr_d, req_fr


def preempt_width(num_podsets: int, num_resources: int, num_fr: int) -> int:
    """The columns the cycle program's fused preemptor runs at: a head
    holds one flavor a (pod set, resource), so P · S columns where that
    is narrower than the flavor-resource grid's R, else the grid's."""
    return min(num_podsets * num_resources, num_fr)


def pack_columns(usage_fr, h_req):
    """Each head's own columns, W = P · S of them: its chosen flavor-
    resource ids int32[C, W] (-1 where the column asks nothing or has no
    flavor) and their requests [C, W]. A column that repeats an earlier
    one of its row (two pod sets on one flavor) is added into it and
    dropped, so columns stay unique as entry_columns' are."""
    C, P, S = usage_fr.shape
    W = P * S
    fr = usage_fr.reshape(C, W)
    req = h_req.reshape(C, W)
    live = (fr >= 0) & (req > 0)
    fr = jnp.where(live, fr, -1).astype(jnp.int32)
    req = jnp.where(live, req, 0)
    if P > 1:
        same = (fr[:, :, None] == fr[:, None, :]) & live[:, :, None] \
            & live[:, None, :]  # [C, W, W]
        repeat = jnp.any(same & jnp.tri(W, k=-1, dtype=bool), axis=2)
        req = jnp.where(repeat, 0, jnp.sum(
            jnp.where(same, req[:, None, :], 0), axis=2))
        fr = jnp.where(repeat, -1, fr)
    return fr, req


def preempt_columns(usage_fr, h_req, entry_fr_d, req_fr):
    """The fused preemptor's entry form: the head's own columns
    (pack_columns) where they are narrower than the dense [C, R] form
    (``entry_fr_d``, ``req_fr``), which is handed back as it is
    otherwise, with no op added. The preemptor reads a column by its id
    and reduces over the columns with the inactive ones masked, so what
    it decides does not depend on the form."""
    _C, P, S = usage_fr.shape
    if preempt_width(P, S, req_fr.shape[1]) == req_fr.shape[1]:
        return entry_fr_d, req_fr
    with jax.named_scope("kueue.preempt_columns"):
        return pack_columns(usage_fr, h_req)


cycle_step = partial(jax.jit,
                     static_argnames=("depth", "num_resources", "num_cqs",
                                      "fair_mode",
                                      "num_flavors"))(_cycle_core)


@partial(jax.jit, static_argnames=("depth", "num_resources", "num_cqs",
                                   "fair_mode", "num_flavors"))
def drain_loop(
    pending, inadmissible, usage, rank, commit_rank, wl_cq, wl_req,
    wl_priority, wl_has_qr, wl_hash, nominal, lend_limit, borrow_limit,
    parent, ancestors, height, group_of_res, group_flavors, no_preemption,
    can_pwb, can_always_reclaim, best_effort, fung_borrow_try_next,
    fung_pref_preempt_first, root_members, root_nodes, local_chain,
    max_cycles, wl_ts=None, fair_weight=None, child_rank=None,
    local_depth=None, root_parent_local=None,
    *,
    depth: int, num_resources: int, num_cqs: int,
    fair_mode: bool = False, num_flavors: int = 1,
):
    """Whole drain as ONE device program: run scheduling cycles until a
    cycle admits nothing (or max_cycles), recording per-workload verdicts.

    This removes the per-cycle host round-trip of the naive driver — on a
    remote-attached TPU each cycle's host sync costs orders of magnitude
    more than the cycle itself. Returns:
      admit_cycle int32[W]  (-1 = not admitted)
      admit_pos   int32[W]  commit position within its cycle
      wl_flavor   int32[W, P, S] chosen flavor per (podset, resource)
      usage       final usage tensor
      cycles      int32 number of cycles executed (incl. the empty one)
      oracle_flag bool  any workload flagged for the host preemptor
    """
    W = pending.shape[0]
    S = num_resources

    def step(pending, inadmissible, usage):
        return _cycle_core(
            pending, inadmissible, usage, rank, commit_rank, wl_cq, wl_req,
            wl_priority, wl_has_qr, wl_hash, nominal, lend_limit,
            borrow_limit, parent, ancestors, height, group_of_res,
            group_flavors, no_preemption, can_pwb, can_always_reclaim,
            best_effort, fung_borrow_try_next, fung_pref_preempt_first,
            root_members, root_nodes, local_chain, wl_ts, fair_weight,
            child_rank, local_depth,
            root_parent_local=root_parent_local,
            depth=depth, num_resources=num_resources, num_cqs=num_cqs,
            fair_mode=fair_mode, num_flavors=num_flavors)

    max_cycles = jnp.asarray(max_cycles, jnp.int32)

    def cond(state):
        (_, _, _, cycle, progress, _, _, _, _) = state
        return progress & (cycle < max_cycles)

    def body(state):
        (pending, inadmissible, usage, cycle, _, admit_cycle, admit_pos,
         wl_flavor, oracle_flag) = state
        (pending, inadmissible, usage, wl_admitted, _slot_admitted,
         slot_position, flavor_of_res, any_oracle, _slot_oracle,
         _slot_preempting, _head_idx, _slot_overflow, _victim_ids,
         _victim_variant, _preempt_counts) = step(pending, inadmissible,
                                                  usage)
        admit_cycle = jnp.where(wl_admitted, cycle, admit_cycle)
        admit_pos = jnp.where(wl_admitted, slot_position[wl_cq], admit_pos)
        wl_flavor = jnp.where(wl_admitted[:, None, None],
                              flavor_of_res[wl_cq], wl_flavor)
        progress = jnp.any(wl_admitted)
        return (pending, inadmissible, usage, cycle + 1, progress,
                admit_cycle, admit_pos, wl_flavor, oracle_flag | any_oracle)

    P = wl_req.shape[1]
    init = (pending, inadmissible, usage, jnp.int32(0), jnp.asarray(True),
            jnp.full((W,), -1, jnp.int32), jnp.zeros((W,), jnp.int32),
            jnp.full((W, P, S), -1, jnp.int32), jnp.asarray(False))
    (pending, inadmissible, usage, cycles, _, admit_cycle, admit_pos,
     wl_flavor, oracle_flag) = jax.lax.while_loop(cond, body, init)
    return admit_cycle, admit_pos, wl_flavor, usage, cycles, oracle_flag


class BatchedDrainSolver:
    """Drive cycle_step to quiescence over a pending set.

    Used by the perf harness and by differential tests; the serving-path
    integration (engine oracle mode) wraps the same step.
    """

    def __init__(self, snapshot, pending_infos, max_depth: int = 4,
                 fair: bool = False):
        self.world = encode_snapshot(snapshot, max_depth=max_depth)
        self.wls = encode_workloads(self.world, pending_infos)
        self.infos = pending_infos
        self.fair = fair

    def head_ranks(self) -> np.ndarray:
        """Heap order: priority desc, timestamp asc, stable by index
        (cluster_queue.go heap less)."""
        W = self.wls.num_workloads
        order = np.lexsort((np.arange(W), self.wls.timestamp,
                            -self.wls.priority))
        rank = np.empty(W, np.int64)
        rank[order] = np.arange(W)
        return rank

    def commit_ranks(self) -> np.ndarray:
        """FIFO tiebreak for the commit ordering: creation/queue-order
        timestamp ascending (scheduler.go:1001)."""
        W = self.wls.num_workloads
        order = np.lexsort((np.arange(W), self.wls.timestamp))
        rank = np.empty(W, np.int64)
        rank[order] = np.arange(W)
        return rank

    def _host_args(self):
        """The cycle-step argument set as numpy arrays (pre-transfer)."""
        w, wl = self.world, self.wls
        return dict(
            rank=self.head_ranks(), commit_rank=self.commit_ranks(),
            wl_cq=wl.cq, wl_req=wl.requests, wl_priority=wl.priority,
            wl_has_qr=wl.has_quota_reservation, wl_hash=wl.hash_id,
            nominal=w.nominal, lend_limit=w.lend_limit,
            borrow_limit=w.borrow_limit, parent=w.parent,
            ancestors=w.ancestors, height=w.height,
            group_of_res=w.group_of_res, group_flavors=w.group_flavors,
            no_preemption=w.no_preemption,
            can_pwb=w.can_preempt_while_borrowing,
            can_always_reclaim=w.can_always_reclaim,
            best_effort=w.best_effort,
            fung_borrow_try_next=w.fung_borrow_try_next,
            fung_pref_preempt_first=w.fung_pref_preempt_first,
            root_members=w.root_members, root_nodes=w.root_nodes,
            local_chain=w.local_chain, wl_ts=wl.timestamp,
            fair_weight=w.fair_weight, child_rank=w.child_rank,
            local_depth=w.local_depth,
            root_parent_local=w.root_parent_local,
        )

    def _device_args(self):
        return {k: jnp.asarray(v) for k, v in self._host_args().items()}

    def solve_one_cycle(self, usage=None):
        """Run exactly one scheduling cycle (the serving-path unit:
        encode happened at construction; this is transfer + solve +
        decode). Returns (admitted_row_ids np.int64[], usage np[N, R])
        so a caller can carry usage across re-encoded cycles.

        The workload axis is bucket-padded to a power of two so repeated
        cycles over a shrinking pending set reuse one compiled program
        per bucket (the engine bridge does the same); padding happens on
        the numpy side, before the single host->device transfer."""
        from kueue_tpu.tensor.schema import (
            WL_PAD_FILLS,
            pad_axis0,
            pow2_bucket,
        )

        w, wl = self.world, self.wls
        W = wl.num_workloads
        Wp = pow2_bucket(W, 64)
        args = self._host_args()
        if Wp != W:
            for key, fill in WL_PAD_FILLS.items():
                args[key] = pad_axis0(args[key], Wp, fill)
        args = {k: jnp.asarray(v) for k, v in args.items()}
        active = np.zeros(Wp, bool)
        active[:W] = wl.eligible & (wl.cq >= 0)
        pending = jnp.asarray(active)
        inadmissible = jnp.zeros(Wp, bool)
        if usage is None:
            usage = w.usage
        out = cycle_step(pending, inadmissible, jnp.asarray(usage),
                         **args,
                         depth=w.depth, num_resources=w.num_resources,
                         num_cqs=w.num_cqs, fair_mode=self.fair,
                         num_flavors=max(w.num_flavors, 1))
        wl_admitted = np.asarray(out[3])[:W]
        new_usage = np.asarray(out[2])
        return np.nonzero(wl_admitted)[0], new_usage

    def solve(self, max_cycles: int = 10_000):
        """Drain until no cycle admits anything. Returns
        (decisions, stats)."""
        w, wl = self.world, self.wls
        W = wl.num_workloads
        pending = jnp.asarray(wl.eligible & (wl.cq >= 0))
        inadmissible = jnp.zeros(W, bool)
        usage = jnp.asarray(np.broadcast_to(
            w.usage, (w.num_nodes, w.nominal.shape[1])).copy())
        args = self._device_args()

        # ONE device program for the whole drain (no per-cycle host sync).
        admit_cycle, admit_pos, wl_flavor, usage, cycles, oracle_flag = \
            drain_loop(pending, inadmissible, usage, **args,
                       max_cycles=max_cycles,
                       depth=w.depth, num_resources=w.num_resources,
                       num_cqs=w.num_cqs, fair_mode=self.fair,
                       num_flavors=max(w.num_flavors, 1))
        admit_cycle = np.asarray(admit_cycle)
        admit_pos = np.asarray(admit_pos)
        wl_flavor = np.asarray(wl_flavor)

        decisions: list[DrainDecision] = []
        admitted_ids = np.nonzero(admit_cycle >= 0)[0]
        order = admitted_ids[np.lexsort((admit_pos[admitted_ids],
                                         admit_cycle[admitted_ids]))]
        for wid in order:
            ci = self.wls.cq[wid]
            podset_flavors = []
            # Real pod sets only (the tensor axis is pow2-padded).
            n_real = len(self.infos[wid].total_requests)
            for p in range(min(n_real, self.wls.requests.shape[1])):
                flavors = {}
                for s_i, res in enumerate(w.resource_names):
                    fl = wl_flavor[wid, p, s_i]
                    if fl >= 0 and self.wls.requests[wid, p, s_i] > 0:
                        flavors[res] = w.flavor_names[fl]
                podset_flavors.append(flavors)
            decisions.append(DrainDecision(
                key=self.wls.keys[wid],
                cluster_queue=w.cq_names[ci],
                cycle=int(admit_cycle[wid]), position=int(admit_pos[wid]),
                flavors=podset_flavors[0],
                podset_flavors=podset_flavors))
        return decisions, {
            "cycles": int(cycles),
            "needs_oracle": bool(oracle_flag),
            "admitted": len(decisions),
            "final_usage": np.asarray(usage),
            # Per-workload decision vectors (dryrun/multichip parity
            # asserts these element-wise, not just aggregates).
            "admit_cycle": admit_cycle,
            "admit_pos": admit_pos,
            "wl_flavor": wl_flavor,
        }
