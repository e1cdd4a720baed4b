"""Batched within-ClusterQueue preemption: target selection on device.

The reference's classical preemptor (preemption.go:277) is, for the
within-CQ case (reclaimWithinCohort=Never — the candidate set is the
preemptor's own CQ), a pure function of the cycle-start snapshot:

  1. candidates = admitted workloads in the CQ that use any resource
     needing preemption and satisfy withinClusterQueue policy
     (common/preemption_policy.go:32);
  2. sort by CandidatesOrdering (common/ordering.go:42 — evicted first,
     priority asc, quota-reservation recency desc, uid);
  3. greedily remove until the preemptor fits (prefix property: the set
     removed after k steps is the first k candidates, so all prefixes
     can be checked at once);
  4. fill back (preemption.go:334): walk targets in reverse (skipping
     the last), re-adding any whose re-addition keeps the fit.

Here all C heads are solved together: candidate classification and
ordering are masked sorts over the admitted-workload tensors, prefix
fits is one [C, V] availability evaluation with exact usage-removal
bubbling along the cohort chain, and fill-back is a short reverse scan
bounded by V_MAX targets.

Differential parity vs scheduler.preemption.Preemptor is enforced by
tests/test_preempt_device.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from kueue_tpu.ops.quota import (
    available_along_chain,
    local_quota,
    sat_sub,
)

# withinClusterQueue policy codes (api.types.PreemptionPolicy).
POLICY_NEVER = 0
POLICY_LOWER = 1
POLICY_LOWER_OR_NEWER_EQ = 2
POLICY_ANY = 3

# Candidate variants (classical/hierarchical_preemption.go:31), matching
# scheduler/preemption.{WITHIN_CQ,...}.
V_NEVER = 0
V_WITHIN_CQ = 1
V_HIERARCHICAL_RECLAIM = 2
V_RECLAIM_WITHOUT_BORROWING = 3
V_RECLAIM_WHILE_BORROWING = 4

# bwc_threshold sentinel: "no maxPriorityThreshold".
NO_THRESHOLD = (1 << 62)


def _policy_ok(policy, p_pri, p_ts, c_pri, c_ts):
    """common/preemption_policy.go:32."""
    lower = p_pri > c_pri
    newer_eq = (p_pri == c_pri) & (p_ts < c_ts)
    return jnp.where(
        policy == POLICY_LOWER, lower,
        jnp.where(policy == POLICY_LOWER_OR_NEWER_EQ, lower | newer_eq,
                  policy == POLICY_ANY))


# The availability walk is shared with the commit fit check so the
# kernel's "this victim set makes the entry fit" decision and the
# commit's re-check can never drift apart.
_avail_with_removal = available_along_chain


def _adjust_chain_usage(g_usage, g_lq, removed, *, depth):
    """Usage rows along the chain after removing `removed` [S] from the
    CQ (row 0): the CQ row drops by `removed`; each ancestor drops by the
    change in the child's above-local-quota overflow (the exact inverse
    of the addUsage bubbling, resource_node.go:144)."""
    rows = []
    cq_old = g_usage[0]
    cq_new = jnp.maximum(0, cq_old - removed)
    rows.append(cq_new)
    # Overflow contribution delta bubbles upward.
    over_old = jnp.maximum(0, sat_sub(cq_old, g_lq[0]))
    over_new = jnp.maximum(0, sat_sub(cq_new, g_lq[0]))
    delta = over_old - over_new
    for d in range(1, depth + 1):
        a_old = g_usage[d]
        a_new = jnp.maximum(0, a_old - delta)
        rows.append(a_new)
        over_old = jnp.maximum(0, sat_sub(a_old, g_lq[d]))
        over_new = jnp.maximum(0, sat_sub(a_new, g_lq[d]))
        delta = over_old - over_new
    return jnp.stack(rows)


@partial(jax.jit, static_argnames=("depth", "v_max"))
def within_cq_targets(
    slot_need,  # bool[C] head needs within-CQ preemption on this slot
    slot_pri,  # int64[C] preemptor effective priority
    slot_ts,  # float64[C] preemptor creation time
    slot_fr,  # int32[C, S] chosen flavor-resource per resource (-1 none)
    slot_req,  # int64[C, S] requested amount per resource
    wcq_policy,  # int32[C] POLICY_* code per CQ
    adm_cq,  # int32[A] admitted workload's CQ
    adm_pri,  # int64[A]
    adm_ts,  # float64[A] creation time
    adm_qrt,  # float64[A] quota-reservation timestamp (recent = larger)
    adm_uid,  # int64[A] uid rank (ascending tie-break)
    adm_evicted,  # bool[A]
    adm_usage,  # int64[A, R] usage on the fr grid
    usage,  # int64[N, R] cycle-start usage (aggregated)
    subtree_quota, lend_limit, borrow_limit, ancestors,
    *,
    depth: int,
    v_max: int,
):
    """Returns per slot:
      found bool[C] — a fitting target set exists within v_max victims
      overflow bool[C] — needed more than v_max victims (host fallback)
      target_mask bool[C, A] — admitted workloads to preempt
      n_targets int32[C]
    """
    C, S = slot_req.shape
    A = adm_cq.shape[0]
    V = min(v_max, A)  # cannot take more victims than admitted rows
    lq = local_quota(subtree_quota, lend_limit)

    def per_slot(c, need, p_pri, p_ts, frs, req, policy):
        frs_safe = jnp.maximum(frs, 0)
        active = (frs >= 0) & (req > 0)

        chain = jnp.concatenate(
            [jnp.asarray([c], jnp.int32), ancestors[c]])
        chain_ok = chain >= 0
        chain_safe = jnp.maximum(chain, 0)
        g_sq = subtree_quota[chain_safe[:, None], frs_safe[None, :]]
        g_lq = lq[chain_safe[:, None], frs_safe[None, :]]
        g_bl = borrow_limit[chain_safe[:, None], frs_safe[None, :]]
        g_usage = usage[chain_safe[:, None], frs_safe[None, :]]

        # Resources needing preemption: request exceeds current available.
        avail0 = _avail_with_removal(chain_ok, g_sq, g_lq, g_bl, g_usage,
                                     depth=depth)
        need_fr = active & (req > avail0)

        # Candidate classification (classifyPreemptionVariant, within-CQ).
        cand_usage_s = adm_usage[:, frs_safe] * active[None, :]  # [A, S]
        uses_any = jnp.any(jnp.where(need_fr[None, :], cand_usage_s > 0,
                                     False), axis=1)
        is_cand = need & (adm_cq == c) & uses_any & _policy_ok(
            policy, p_pri, p_ts, adm_pri, adm_ts)

        # CandidatesOrdering (common/ordering.go:42): evicted first,
        # priority asc, admitted more recently first (reservation
        # timestamp desc), uid asc; non-candidates last. lexsort's last
        # key is the primary.
        order = jnp.lexsort((
            adm_uid,
            -adm_qrt,
            adm_pri,
            jnp.where(adm_evicted, 0, 1),
            jnp.where(is_cand, 0, 1),
        )).astype(jnp.int32)

        n_cand = jnp.sum(is_cand.astype(jnp.int32))
        # Prefix removal sums over the first V candidates in order.
        v_ids = order[:V]  # [V]
        v_valid = is_cand[v_ids] & (jnp.arange(V) < n_cand)
        v_usage = jnp.where(v_valid[:, None], cand_usage_s[v_ids], 0)
        prefix = jnp.cumsum(v_usage, axis=0)  # [V, S] removed after k+1

        def fits_with(removed):
            adj = _adjust_chain_usage(g_usage, g_lq, removed, depth=depth)
            avail = _avail_with_removal(chain_ok, g_sq, g_lq, g_bl, adj,
                                        depth=depth)
            return jnp.all(jnp.where(active, req <= avail, True))

        fits_k = jax.vmap(fits_with)(prefix)  # [V] fits after k+1 removals
        fits_k = fits_k & v_valid  # only meaningful where a victim exists
        any_fit = jnp.any(fits_k)
        kstar = jnp.argmax(fits_k)  # first k with fit (0-based)
        overflow = need & ~any_fit & (n_cand > V)
        found = need & any_fit

        # Fill-back (preemption.go:334): reverse over targets 0..kstar-1
        # (the last target, kstar, never fills back), re-adding any whose
        # re-addition preserves the fit.
        kept0 = (jnp.arange(V) <= kstar) & v_valid & found

        def fb_step(kept, i):
            idx = kstar - 1 - i  # reverse order, skipping the last
            in_range = (idx >= 0) & found
            idx_safe = jnp.maximum(idx, 0)
            trial = kept & ~(jnp.arange(V) == idx_safe)
            removed = jnp.sum(
                jnp.where(trial[:, None], v_usage, 0), axis=0)
            ok = in_range & kept[idx_safe] & fits_with(removed)
            return jnp.where(ok, trial, kept), None

        kept, _ = jax.lax.scan(fb_step, kept0, jnp.arange(V))

        target_mask = jnp.zeros((A,), bool).at[
            jnp.where(kept, v_ids, A)].set(True, mode="drop")
        return found, overflow, target_mask, jnp.sum(
            kept.astype(jnp.int32))

    return jax.vmap(per_slot)(
        jnp.arange(C, dtype=jnp.int32), slot_need, slot_pri, slot_ts,
        slot_fr, slot_req, wcq_policy)


def classical_targets_impl(
    slot_need,  # bool[C] head needs preemption on this slot
    slot_pri,  # int64[C] preemptor effective priority
    slot_ts,  # float64[C] preemptor creation time
    slot_fr,  # int32[C, S] chosen flavor-resource per resource (-1 none)
    slot_req,  # int64[C, S] requested amount per resource
    wcq_policy,  # int32[C] withinClusterQueue POLICY_* code
    reclaim_policy,  # int32[C] reclaimWithinCohort POLICY_* code
    bwc_forbidden,  # bool[C] borrowWithinCohort is Never/absent
    bwc_threshold,  # int64[C] maxPriorityThreshold (NO_THRESHOLD = none)
    cq_has_parent,  # bool[C]
    adm_cq,  # int32[A] admitted workload's CQ
    adm_pri,  # int64[A]
    adm_ts,  # float64[A] creation time
    adm_qrt,  # float64[A] quota-reservation timestamp (recent = larger)
    adm_uid,  # int64[A] uid rank (ascending tie-break)
    adm_evicted,  # bool[A]
    adm_usage,  # int64[A, R] usage on the fr grid
    usage,  # int64[N, R] cycle-start usage (aggregated)
    subtree_quota, lend_limit, borrow_limit, nominal,  # int64[N, R]
    ancestors,  # int32[N, D]
    height,  # int32[N] subtree height per node
    local_chain,  # int32[C, D+1] positions into the CQ root's node row
    root_nodes,  # int32[Rn, K]
    root_of_cq,  # int32[C]
    slot_cq=None,  # int32[C'] CQ id per row (default: row index == CQ).
    #   Decouples rows from CQ ids so callers can batch arbitrary
    #   (CQ, flavor-cell) simulation rows into ONE launch (the bridge's
    #   sim-augmented nomination paid one launch per cell before).
    adm_rank=None,  # int64[A] OPTIONAL precomputed rank of the slot-
    #   independent ordering tail (priority asc, reservation recency
    #   desc, uid asc — common/ordering.go:42). When provided, candidate
    #   ordering is ONE composite-key argsort per slot instead of a
    #   6-key lexsort (the dominant kernel cost at large admitted sets).
    adm_by_root=None,  # int32[Rn, A_l] OPTIONAL admitted ids grouped by
    #   cohort root (-1 pad): per-slot candidate work shrinks from O(A)
    #   to O(max admitted per root). Victim ids in the outputs stay
    #   GLOBAL.
    *,
    depth: int,
    v_cap: int,
):
    """The full classical preemptor (preemption.go:277 classicalPreemptions
    + classical/hierarchical_preemption.go + candidate_generator.go) for
    ALL ClusterQueue heads at once.

    Per slot: classify every admitted workload of the slot's cohort root
    (WithinCQ / HierarchicalReclaim / ReclaimWithoutBorrowing /
    ReclaimWhileBorrowing), order candidates (evicted first, then
    hierarchy < priority < same-queue buckets, then priority asc /
    reservation recency desc / uid), sequence the borrowing attempts
    (preemption.go:287-311), greedily remove candidates until the
    preemptor fits (dynamic within-nominal validity per
    candidate_generator.go:136), then fill back spared victims
    (preemption.go:334).

    The greedy scan is bounded at v_cap ordered candidates; slots that
    fail to fit with more candidates available report overflow=True and
    must fall back to the host preemptor.

    Returns per slot, the victims packed to V = min(v_cap, A_l) columns
    (the scanned candidates, in candidate order):
      found bool[C], overflow bool[C], n_targets int32[C],
      borrow_after int32[C] — the assignment borrow level with the
        victims removed (preemption_oracle.go:41 SimulatePreemption →
        FindHeightOfLowestSubtreeThatFits), which is what the commit
        iterator orders preempting entries by (scheduler.go:971),
      v_ids int32[C, V] — GLOBAL admitted ids of the scanned candidates
        (-1 on an ``adm_by_root`` pad row),
      taken bool[C, V] — which of them are the targets,
      v_variant int32[C, V] — each one's candidate variant (V_*, for
        the preemption reason; 0 on a pad row).
    """
    C, S = slot_req.shape
    A = adm_cq.shape[0]
    A_l = A if adm_by_root is None else adm_by_root.shape[1]
    V = min(v_cap, A_l)
    K = root_nodes.shape[1]
    lq_all = local_quota(subtree_quota, lend_limit)

    adm_chain = jnp.concatenate(
        [adm_cq[:, None], ancestors[jnp.maximum(adm_cq, 0)]],
        axis=1)  # [A, D+1] global node ids
    adm_loc = local_chain[jnp.maximum(adm_cq, 0)]  # [A, D+1]

    def per_slot(c, need, p_pri, p_ts, frs, req):
        frs_safe = jnp.maximum(frs, 0)
        active = (frs >= 0) & (req > 0)

        # Candidate scope: with adm_by_root, gather ONLY the slot's
        # root's admitted rows (candidates never cross cohort roots —
        # candidate_generator.go walks the preemptor's hierarchy) so all
        # per-candidate work is O(max admitted per root), not O(A).
        if adm_by_root is None:
            l_ok = jnp.ones((A,), bool)
            l_cq, l_pri, l_ts = adm_cq, adm_pri, adm_ts
            l_qrt, l_uid, l_ev = adm_qrt, adm_uid, adm_evicted
            l_usage = adm_usage
            l_chain, l_loc = adm_chain, adm_loc
            l_rank = adm_rank
            g_rows = None
        else:
            g_rows = adm_by_root[root_of_cq[c]]  # [A_l] global ids
            l_ok = g_rows >= 0
            rsafe = jnp.maximum(g_rows, 0)
            l_cq = jnp.where(l_ok, adm_cq[rsafe], -1)
            l_pri = adm_pri[rsafe]
            l_ts = adm_ts[rsafe]
            l_qrt = adm_qrt[rsafe]
            l_uid = adm_uid[rsafe]
            l_ev = adm_evicted[rsafe] & l_ok
            l_usage = jnp.where(l_ok[:, None], adm_usage[rsafe], 0)
            l_chain = jnp.where(l_ok[:, None], adm_chain[rsafe], -1)
            l_loc = jnp.where(l_ok[:, None], adm_loc[rsafe], -1)
            # Pad rows sort last; ties among pads are irrelevant (they
            # can never be candidates).
            l_rank = (None if adm_rank is None
                      else jnp.where(l_ok, adm_rank[rsafe], A))

        # Root-local state over the slot's root, columns = the slot's
        # chosen flavor-resources.
        nodes = root_nodes[root_of_cq[c]]  # [K]
        nodes_safe = jnp.maximum(nodes, 0)
        node_ok = nodes >= 0

        def gather_l(arr):
            g = arr[nodes_safe[:, None], frs_safe[None, :]]
            return jnp.where(node_ok[:, None], g, 0)

        usage_l0 = gather_l(usage)
        sq_l = gather_l(subtree_quota)
        lq_l = gather_l(lq_all)
        height_l = jnp.where(node_ok, height[nodes_safe], 0)
        bl_l = jnp.where(node_ok[:, None],
                         borrow_limit[nodes_safe[:, None],
                                      frs_safe[None, :]], 0)
        nom_l = gather_l(nominal)

        loc_c = local_chain[c]  # [D+1] positions into K
        chain_ok_c = loc_c >= 0
        loc_c_safe = jnp.maximum(loc_c, 0)

        def fits_with(usage_l, allow_borrow):
            g_usage = usage_l[loc_c_safe]
            avail = available_along_chain(
                chain_ok_c, sq_l[loc_c_safe], lq_l[loc_c_safe],
                bl_l[loc_c_safe], g_usage, depth=depth)
            ok = jnp.all(jnp.where(active, req <= avail, True))
            # workloadFits without borrowing: usage + req must stay within
            # the CQ's guaranteed quota (preemption.go:624 borrowingWith).
            cq_row = loc_c_safe[0]
            nb_ok = jnp.all(jnp.where(
                active, usage_l[cq_row] + req <= sq_l[cq_row], True))
            return ok & (allow_borrow | nb_ok)

        avail0 = available_along_chain(
            chain_ok_c, sq_l[loc_c_safe], lq_l[loc_c_safe],
            bl_l[loc_c_safe], usage_l0[loc_c_safe], depth=depth)
        need_fr = active & (req > avail0)
        any_need = need & jnp.any(need_fr)

        # Hierarchical-advantage walk (hierarchical_preemption.go:149):
        # adv_before[d] = whether any strict subtree below level d already
        # fits the (remaining) request within quota.
        def lavail_row(r):
            return jnp.maximum(0, lq_l[r] - usage_l0[r])

        cq_row = loc_c_safe[0]
        fits_cq = jnp.all(jnp.where(
            active, sq_l[cq_row] >= usage_l0[cq_row] + req, True))
        rem = jnp.where(active, jnp.maximum(0, req - lavail_row(cq_row)), 0)
        adv = fits_cq
        adv_before_list = [jnp.asarray(False)]  # level 0 unused
        for d in range(1, depth + 1):
            adv_before_list.append(adv)
            r = loc_c_safe[d]
            okd = chain_ok_c[d]
            fits_d = jnp.all(jnp.where(
                active, sq_l[r] >= usage_l0[r] + rem, True))
            adv = adv | (fits_d & okd)
            rem = jnp.where(active, jnp.maximum(0, rem - lavail_row(r)), 0)
        adv_before = jnp.stack(adv_before_list)  # [D+1]

        # --- candidate classification over all admitted workloads ---
        c_chain = jnp.concatenate(
            [jnp.asarray([c], jnp.int32), ancestors[c]])  # [D+1]
        same_cq = l_cq == c
        same_root = (l_ok if g_rows is not None else
                     root_of_cq[jnp.maximum(l_cq, 0)]
                     == root_of_cq[c])
        # LCA level: lowest d >= 1 with c_chain[d] on the candidate's
        # chain. Loops over the (short) depth axes to keep peak memory at
        # O(A) per slot.
        NO_LCA = depth + 9
        lca_level = jnp.full((A_l,), NO_LCA, jnp.int32)
        for d in range(depth, 0, -1):
            on_chain = jnp.zeros((A_l,), bool)
            for e in range(depth + 1):
                on_chain = on_chain | (l_chain[:, e] == c_chain[d])
            on_chain = on_chain & (c_chain[d] >= 0)
            lca_level = jnp.where(on_chain, d, lca_level)
        has_lca = lca_level <= depth
        lca_node = c_chain[jnp.clip(lca_level, 0, depth)]  # [A]
        # Candidate-chain position of the LCA.
        lca_pos = jnp.full((A_l,), NO_LCA, jnp.int32)
        for e in range(depth, -1, -1):
            lca_pos = jnp.where(l_chain[:, e] == lca_node, e, lca_pos)

        uses_any = jnp.any(
            (l_usage[:, frs_safe] > 0) & need_fr[None, :], axis=1)
        pol = jnp.where(same_cq, wcq_policy[c], reclaim_policy[c])
        pol_gate = jnp.where(
            same_cq, wcq_policy[c] != POLICY_NEVER,
            (reclaim_policy[c] != POLICY_NEVER) & cq_has_parent[c])
        pol_ok = _policy_ok(pol, p_pri, p_ts, l_pri, l_ts)

        adv_at_lca = adv_before[jnp.clip(lca_level, 0, depth)]
        rwob = (bwc_forbidden[c] | (l_pri >= p_pri)
                | (l_pri > bwc_threshold[c]))
        variant = jnp.where(
            same_cq, jnp.int32(V_WITHIN_CQ),
            jnp.where(adv_at_lca, jnp.int32(V_HIERARCHICAL_RECLAIM),
                      jnp.where(rwob,
                                jnp.int32(V_RECLAIM_WITHOUT_BORROWING),
                                jnp.int32(V_RECLAIM_WHILE_BORROWING))))

        # Static within-nominal pruning (collectCandidatesInSubtree +
        # candidateIsValid at cycle start): every node on the candidate's
        # chain strictly below the LCA must be above nominal in some
        # needed resource. Level-wise loop keeps peak memory at O(A * S).
        wn_rownominal = jnp.all(jnp.where(
            need_fr[None, :], sq_l >= usage_l0, True), axis=1)  # [K]
        static_bad = jnp.zeros((A_l,), bool)
        for e in range(depth + 1):
            loc_e = l_loc[:, e]
            below = (e < lca_pos) & (loc_e >= 0)
            static_bad = static_bad | (
                below & wn_rownominal[jnp.maximum(loc_e, 0)])
        static_path_ok = ~static_bad

        is_cand = (any_need & uses_any & pol_gate & pol_ok
                   & (same_cq | (same_root & has_lca & static_path_ok)))
        bucket = jnp.where(same_cq, 2, jnp.where(adv_at_lca, 0, 1))

        no_other = ~jnp.any(is_cand & ~same_cq)
        no_hier = ~jnp.any(is_cand & (bucket == 0))
        under_nominal = jnp.all(jnp.where(
            need_fr, nom_l[cq_row] > usage_l0[cq_row], True))

        # Attempt sequencing (preemption.go:287-311).
        case1 = no_other | (bwc_forbidden[c] & ~under_nominal)
        case2 = ~case1 & bwc_forbidden[c] & no_hier
        b1 = jnp.where(case2, False, True)
        b2 = jnp.where(case2, True, False)
        en2 = ~case1

        # Ordering: evicted first, bucket, priority asc, reservation
        # recency desc, uid asc; non-candidates last (lexsort: last key
        # is primary).
        if l_rank is None:
            order = jnp.lexsort((
                l_uid,
                -l_qrt,
                l_pri,
                bucket,
                jnp.where(l_ev, 0, 1),
                jnp.where(is_cand, 0, 1),
            )).astype(jnp.int32)
        else:
            # Composite key: only is_cand and bucket vary per slot; the
            # rest is the precomputed rank. Rank uniqueness makes the
            # order total — one argsort, no ties.
            lvl = (jnp.where(is_cand, 0, 2)
                   + jnp.where(l_ev, 0, 1)) * 4 + bucket
            order = jnp.argsort(
                lvl.astype(jnp.int64) * (A + 1) + l_rank
            ).astype(jnp.int32)
        v_ids = order[:V]  # [V]
        v_cand = is_cand[v_ids]
        v_variant = variant[v_ids]
        v_same = same_cq[v_ids]
        v_loc = l_loc[v_ids]  # [V, D+1]
        v_lca_pos = lca_pos[v_ids]
        v_usage = l_usage[v_ids][:, frs_safe]  # [V, S]
        n_cand = jnp.sum(is_cand.astype(jnp.int32))

        def remove_chain(usage_l, loc, val):
            """resource_node.go:156 removeUsage along one chain."""
            for e in range(depth + 1):
                row_ok = loc[e] >= 0
                r = jnp.maximum(loc[e], 0)
                ssp = usage_l[r] - lq_l[r]
                usage_l = usage_l.at[r].add(jnp.where(row_ok, -val, 0))
                val = jnp.where(row_ok & (ssp > 0),
                                jnp.minimum(val, ssp), 0)
            return usage_l

        def add_chain(usage_l, loc, val):
            """resource_node.go:144 addUsage along one chain."""
            for e in range(depth + 1):
                row_ok = loc[e] >= 0
                r = jnp.maximum(loc[e], 0)
                la = jnp.maximum(0, lq_l[r] - usage_l[r])
                usage_l = usage_l.at[r].add(jnp.where(row_ok, val, 0))
                val = jnp.where(row_ok, jnp.maximum(0, val - la), 0)
            return usage_l

        def run_attempt(allow_borrow):
            def step(carry, i):
                usage_l, taken, found = carry
                ok = v_cand[i] & ~found
                # candidateIsValid (candidate_generator.go:136), dynamic.
                bad_borrow = (allow_borrow
                              & (v_variant[i]
                                 == V_RECLAIM_WITHOUT_BORROWING)
                              & ~v_same[i])
                wn_bad = jnp.asarray(False)
                for e in range(depth + 1):
                    below = (e < v_lca_pos[i]) & (v_loc[i, e] >= 0)
                    r = jnp.maximum(v_loc[i, e], 0)
                    wn = jnp.all(jnp.where(need_fr,
                                           sq_l[r] >= usage_l[r], True))
                    wn_bad = wn_bad | (below & wn)
                valid = ok & ~bad_borrow & (v_same[i] | ~wn_bad)
                removed = remove_chain(usage_l, v_loc[i], v_usage[i])
                usage_l = jnp.where(valid, removed, usage_l)
                taken = taken.at[i].set(valid)
                fit = fits_with(usage_l, allow_borrow)
                found = found | (valid & fit)
                return (usage_l, taken, found), None

            init = (usage_l0, jnp.zeros((V,), bool), jnp.asarray(False))
            (usage_f, taken, found), _ = jax.lax.scan(
                step, init, jnp.arange(V))

            # Fill-back (preemption.go:334): reverse over targets except
            # the last, re-adding any whose re-addition keeps the fit.
            last_idx = jnp.max(jnp.where(taken, jnp.arange(V), -1))

            def fb(carry, j):
                usage_l, taken = carry
                i = V - 1 - j
                consider = found & taken[i] & (i != last_idx)
                trial = add_chain(usage_l, v_loc[i], v_usage[i])
                spared = consider & fits_with(trial, allow_borrow)
                usage_l = jnp.where(spared, trial, usage_l)
                taken = taken.at[i].set(taken[i] & ~spared)
                return (usage_l, taken), None

            (usage_fb, taken_fb), _ = jax.lax.scan(fb, (usage_f, taken),
                                                   jnp.arange(V))
            return found, taken_fb, usage_fb

        def borrow_after_height(usage_l):
            """FindHeightOfLowestSubtreeThatFits
            (classical/hierarchical_preemption.go:221) against a
            root-local usage state; max over the slot's resources."""
            lavail = jnp.maximum(0, lq_l - usage_l)  # [K, S]
            borrowing_cq = nom_l[cq_row] < usage_l[cq_row] + req  # [S]
            has_par = chain_ok_c[1] if depth >= 1 else jnp.asarray(False)
            remaining = jnp.maximum(0, req - lavail[cq_row])
            found_b = jnp.zeros((req.shape[0],), bool)
            found_h = jnp.zeros((req.shape[0],), jnp.int32)
            for d in range(1, depth + 1):
                r = loc_c_safe[d]
                okd = chain_ok_c[d]
                borrowing = sq_l[r] < usage_l[r] + remaining
                fits_here = okd & ~borrowing & ~found_b
                found_h = jnp.where(fits_here, height_l[r], found_h)
                found_b = found_b | fits_here
                remaining = jnp.where(okd & ~found_b,
                                      jnp.maximum(0, remaining - lavail[r]),
                                      remaining)
            root_h = jnp.int32(0)
            for d in range(depth + 1):
                root_h = jnp.where(chain_ok_c[d], height_l[loc_c_safe[d]],
                                   root_h)
            h = jnp.where(~borrowing_cq | ~has_par, 0,
                          jnp.where(found_b, found_h, root_h))
            return jnp.max(jnp.where(active, h, 0))

        f1, t1, u1 = run_attempt(b1)
        f2, t2, u2 = run_attempt(b2)
        use2 = ~f1 & en2 & f2
        found = (f1 | use2) & any_need
        taken = jnp.where(f1, t1, jnp.where(use2, t2,
                                            jnp.zeros((V,), bool)))
        overflow = need & any_need & ~found & (n_cand > V)
        borrow_after = jnp.where(
            f1, borrow_after_height(u1),
            jnp.where(use2, borrow_after_height(u2), 0)).astype(jnp.int32)

        if g_rows is None:
            g_v_ids, g_variant = v_ids, v_variant
        else:
            # Map local victim positions back to GLOBAL ids.
            g_v_ids = jnp.where(l_ok[v_ids], g_rows[v_ids], -1)
            g_variant = jnp.where(l_ok[v_ids], v_variant, 0)
        return (found, overflow, jnp.sum(taken.astype(jnp.int32)),
                borrow_after, g_v_ids, taken, g_variant)

    if slot_cq is None:
        slot_cq = jnp.arange(C, dtype=jnp.int32)
    return jax.vmap(per_slot)(
        slot_cq, slot_need, slot_pri, slot_ts, slot_fr, slot_req)


@partial(jax.jit, static_argnames=("depth", "v_cap"))
def sim_targets(*args, slot_cq, adm_rank, adm_by_root, depth: int,
                v_cap: int):
    """The sim program (preemption_oracle.go:41 SimulatePreemption, one
    row per (head, flavor, resource) cell): the classical preemptor over
    a block of rows, returning only what the fungibility fold reads —
    found bool[B], overflow bool[B], borrow_after int32[B], and whether
    any victim sits in the row's own ClusterQueue (Preempt, else
    Reclaim). The victim sets stay on the device: the fold does not
    need them, and the final target selection is the cycle program's."""
    adm_cq = args[10]
    out = classical_targets_impl(*args, slot_cq=slot_cq,
                                 adm_rank=adm_rank,
                                 adm_by_root=adm_by_root, depth=depth,
                                 v_cap=v_cap)
    found, overflow, _n, borrow_after, v_ids, taken, _variant = out
    same = jnp.any(taken & (v_ids >= 0)
                   & (adm_cq[jnp.maximum(v_ids, 0)] == slot_cq[:, None]),
                   axis=1)
    return found, overflow, borrow_after, same
