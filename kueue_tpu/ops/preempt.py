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
from typing import NamedTuple

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


class _Window(NamedTuple):
    """What the greedy scan reads of V ordered candidates of one slot."""
    cand: jax.Array  # bool[V] a candidate sits here
    variant: jax.Array  # int32[V] V_*
    same: jax.Array  # bool[V] of the preemptor's own ClusterQueue
    loc: jax.Array  # int32[V, D+1] its chain, root-local rows
    lca_pos: jax.Array  # int32[V] where its chain meets the preemptor's
    usage: jax.Array  # int64[V, S] what it holds of the slot's columns


def next_window(key, open_, cursor, width: int):
    """The next ``width`` candidates of one slot's order: the local ids
    (int32[width], -1 once none is left) of the lowest ``key``s above
    ``cursor`` among the rows of ``open_``. ``key`` int64[A_l] is the
    slot's candidate order (unique among candidates), ``open_`` bool[A_l]
    the candidates an attempt may still take. ``width`` masked argmins:
    no sort, no gather."""
    big = jnp.iinfo(key.dtype).max

    def pick(cur, _):
        k = jnp.where(open_ & (key > cur), key, big)
        a = jnp.argmin(k).astype(jnp.int32)
        has = k[a] < big
        return jnp.where(has, k[a], cur), jnp.where(has, a, -1)

    _, ids = jax.lax.scan(pick, cursor, None, length=width)
    return ids


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
    #   desc, uid asc — common/ordering.go:42); worked out here where it
    #   is not given. Candidate ordering is ONE composite-key argsort
    #   per slot over it (a 6-key lexsort was the dominant kernel cost
    #   at large admitted sets).
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

    The greedy scan finds its targets wherever they lie in the order:
    the first V = min(v_cap, A_l) ordered candidates are walked as one
    window; a slot that has not fit by then while candidates are left
    walks on, window by window (next_window: the next V that the attempt
    may take and that are still valid), holding its targets as a mask
    over the candidates, then gives back in reverse order and packs what
    is left. Where no slot has more than V candidates no launch enters
    that region. `overflow` means one thing: the targets that remain
    after fill-back are more than V, which the packed columns cannot
    hold; such a slot is reported and must fall back to the host
    preemptor.

    A slot is _preemptor_rows' classify and then its scan, under one
    vmap over the slots.

    Returns per slot, the victims packed to V columns (the targets in
    candidate order; for a slot decided in its first window, that
    window's candidates with `taken` marking the targets):
      found bool[C], overflow bool[C], n_targets int32[C],
      borrow_after int32[C] — the assignment borrow level with the
        victims removed (preemption_oracle.go:41 SimulatePreemption →
        FindHeightOfLowestSubtreeThatFits), which is what the commit
        iterator orders preempting entries by (scheduler.go:971),
      v_ids int32[C, V] — GLOBAL admitted ids of the scanned candidates
        (-1 on an ``adm_by_root`` pad row),
      taken bool[C, V] — which of them are the targets,
      v_variant int32[C, V] — each one's candidate variant (V_*, for
        the preemption reason; 0 on a pad row),
      skipped int32[C] — the ordered candidates the scans passed over
        as invalid before the slot fit or ran out (both attempts).
    """
    classify, scan, _fill = _preemptor_rows(
        slot_req.shape[1], wcq_policy, reclaim_policy, bwc_forbidden,
        bwc_threshold, cq_has_parent, adm_cq, adm_pri, adm_ts, adm_qrt,
        adm_uid, adm_evicted, adm_usage, usage, subtree_quota, lend_limit,
        borrow_limit, nominal, ancestors, height, local_chain, root_nodes,
        root_of_cq, adm_rank, adm_by_root, depth=depth, v_cap=v_cap)

    def per_slot(c, need, p_pri, p_ts, frs, req):
        return scan(c, need, frs, req,
                    *classify(c, need, p_pri, p_ts, frs, req))

    if slot_cq is None:
        slot_cq = jnp.arange(slot_req.shape[0], dtype=jnp.int32)
    return jax.vmap(per_slot)(
        slot_cq, slot_need, slot_pri, slot_ts, slot_fr, slot_req)


class _SlotTables(NamedTuple):
    """A slot's view of its root's quota tables (_preemptor_rows): what
    both parts of the preemptor read of them, a few hundred values a
    slot. Root-local tables lie on ONE flat axis [S * K], resource s of
    node r at s * K + r, the slot's chosen flavor-resources as the
    columns."""
    frs_safe: jax.Array  # int32[S] the slot's columns (0 where none)
    active: jax.Array  # bool[S] a column the slot requests
    usage_l0: jax.Array  # int64[S * K] cycle-start usage
    sq_l: jax.Array  # int64[S * K] subtree quota
    lq_l: jax.Array  # int64[S * K] local quota
    height_l: jax.Array  # int32[K]
    chain_ok_c: jax.Array  # bool[D+1] the slot's own chain
    loc_c_safe: jax.Array  # int32[D+1] its rows (0 where none)
    sq_c: jax.Array  # int64[D+1, S] along it
    lq_c: jax.Array
    bl_c: jax.Array
    nom_cq: jax.Array  # int64[S] its ClusterQueue's nominal
    usage_c0: jax.Array  # int64[D+1, S] cycle-start usage along it
    need_fr: jax.Array  # bool[S] the columns the request does not fit


class _Classified(NamedTuple):
    """What a slot's scans read of its classify (_preemptor_rows): per
    candidate of the slot's root (A_l of them), and per slot. The chain
    is held a level a row, so that a block of these (the sim program's)
    has no short minor axis."""
    key: jax.Array  # int64[A_l] the candidate order, unique; others last
    cand: jax.Array  # bool[A_l] a candidate of the slot
    variant: jax.Array  # int32[A_l] V_*
    same: jax.Array  # bool[A_l] of the slot's own ClusterQueue
    lca_pos: jax.Array  # int32[A_l] where its chain meets the slot's
    loc: jax.Array  # int32[D+1, A_l] its chain, root-local rows
    v_ids: jax.Array  # int32[V] the first V of the order (-1: none)
    allow: jax.Array  # bool[2] each attempt's allow_borrow
    en2: jax.Array  # bool: a second attempt where the first fails


class _Gathered(NamedTuple):
    """The slot's root's admitted rows as its classify gathered them,
    which the scans read where both run under one vmap."""
    ok: jax.Array  # bool[A_l] a row, not a pad
    loc: jax.Array  # int32[A_l, D+1] its chain, root-local rows
    usage: jax.Array  # int64[A_l, S] what it holds of the slot's columns


def _preemptor_rows(
    S, wcq_policy, reclaim_policy, bwc_forbidden, bwc_threshold,
    cq_has_parent, adm_cq, adm_pri, adm_ts, adm_qrt, adm_uid, adm_evicted,
    adm_usage, usage, subtree_quota, lend_limit, borrow_limit, nominal,
    ancestors, height, local_chain, root_nodes, root_of_cq, adm_rank,
    adm_by_root, *, depth: int, v_cap: int,
):
    """One slot of the classical preemptor (classical_targets_impl, whose
    arguments these are, less the slots' own; ``S`` the slot's columns)
    in two parts:

      classify(c, need, p_pri, p_ts, frs, req) -> (_Classified,
        _Gathered): the work that scales with the candidates — gather
        the root's admitted rows, LCA level and position, variant, the
        within-nominal check, which are candidates, the attempt
        sequencing and the order;
      scan(c, need, frs, req, classified, gathered=None) -> the slot's
        outputs: the attempts' greedy scans, the walk beyond the first
        window and fill-back. Without ``gathered`` it reads what it
        needs of the root's rows from the world.

    and ``fill``, the _Classified of a slot with no candidate, whose
    scans find nothing. The cycle program runs both parts under one
    vmap; the sim program classifies its live rows only
    (sim_targets)."""
    A = adm_cq.shape[0]
    A_l = A if adm_by_root is None else adm_by_root.shape[1]
    V = min(v_cap, A_l)
    K = root_nodes.shape[1]
    NO_LCA = depth + 9
    lq_all = local_quota(subtree_quota, lend_limit)
    if adm_rank is None:
        adm_rank = jnp.zeros((A,), jnp.int64).at[
            jnp.lexsort((adm_uid, -adm_qrt, adm_pri))].set(
            jnp.arange(A, dtype=jnp.int64))

    adm_chain = jnp.concatenate(
        [adm_cq[:, None], ancestors[jnp.maximum(adm_cq, 0)]],
        axis=1)  # [A, D+1] global node ids
    adm_loc = local_chain[jnp.maximum(adm_cq, 0)]  # [A, D+1]
    # Level e of a candidate's chain can lie strictly below its LCA with
    # the preemptor only where some chain of the world has a level e + 1.
    levels_below_an_lca = jnp.any(adm_loc[:, 1:] >= 0, axis=0)  # [D]
    node_at = jnp.tile(jnp.arange(K, dtype=jnp.int32), S)
    # Column-major, so that a scan's loop that reads it does not hold it
    # padded to 128 lanes a row.
    usage_by_col = adm_usage.T  # [R, A]

    def root_rows(c):
        """The global ids of the slot's root's admitted rows (-1 pad),
        or None where every admitted row is in scope."""
        return None if adm_by_root is None else adm_by_root[root_of_cq[c]]

    def rows(tbl, r):
        """Row(s) ``r`` of a root-local table: [..., S]."""
        return jnp.stack([tbl[r + s * K] for s in range(S)], axis=-1)

    def add_row(tbl, r, val):
        """``val`` [S] added to row ``r`` of a root-local table, as
        one elementwise pass (a scatter is a pass a cell there)."""
        return tbl + jnp.where(node_at == r, jnp.repeat(val, K), 0)

    def slot_tables(c, frs, req):
        # No axis of size S is there to be laid out: the chip's compiler
        # makes the window axis of a row's scatter the minor one and
        # pads it to 128 lanes, whatever its place in the source — with
        # two resources a [K, S] table held 64 times its data, and the
        # scans carried, copied and scattered into that (PERF.md, PR 35;
        # tools/tpu_layouts.py shows what a program was compiled to).
        frs_safe = jnp.maximum(frs, 0)
        active = (frs >= 0) & (req > 0)
        nodes = root_nodes[root_of_cq[c]]  # [K]
        nodes_safe = jnp.maximum(nodes, 0)
        node_ok = nodes >= 0
        flat_node = jnp.tile(nodes_safe, S)  # [S * K]
        flat_ok = jnp.tile(node_ok, S)
        flat_fr = jnp.repeat(frs_safe, K)

        def gather_l(arr):
            return jnp.where(flat_ok, arr[flat_node, flat_fr], 0)

        usage_l0 = gather_l(usage)
        sq_l = gather_l(subtree_quota)
        lq_l = gather_l(lq_all)
        loc_c = local_chain[c]  # [D+1] positions into K
        chain_ok_c = loc_c >= 0
        loc_c_safe = jnp.maximum(loc_c, 0)
        # The slot's own chain, [D+1, S]; row 0 is its ClusterQueue's.
        sq_c, lq_c, bl_c = (rows(t, loc_c_safe)
                            for t in (sq_l, lq_l, gather_l(borrow_limit)))
        usage_c0 = rows(usage_l0, loc_c_safe)
        avail0 = available_along_chain(
            chain_ok_c, sq_c, lq_c, bl_c, usage_c0, depth=depth)
        return _SlotTables(
            frs_safe, active, usage_l0, sq_l, lq_l,
            jnp.where(node_ok, height[nodes_safe], 0), chain_ok_c,
            loc_c_safe, sq_c, lq_c, bl_c,
            rows(gather_l(nominal), loc_c_safe[0]), usage_c0,
            active & (req > avail0))

    # Within-nominal pruning (collectCandidatesInSubtree +
    # candidateIsValid): every node on the candidate's chain strictly
    # below the LCA must be above nominal in some needed resource.
    # Level-wise loop keeps peak memory at O(A * S).
    def path_within_nominal(t, loc_level, lca_pos, usage_l):
        """Which candidates have a node below the LCA within nominal;
        ``loc_level(e)`` is level e of the candidates' chains, int32[A_l].
        A level that no chain of the world reaches above is not
        looked at: the predicate is the launch's, not the slot's,
        so the branch is a real one under the vmap."""
        wn_cell = (t.sq_l >= usage_l) | ~jnp.repeat(t.need_fr, K)
        wn_row = wn_cell[:K]
        for s in range(1, S):
            wn_row = wn_row & wn_cell[s * K:(s + 1) * K]
        bad = jnp.zeros((A_l,), bool)
        for e in range(depth):  # level `depth` is never below an LCA
            def at_level(bad, e=e):
                loc_e = loc_level(e)
                below = (e < lca_pos) & (loc_e >= 0)
                return bad | (below & wn_row[jnp.maximum(loc_e, 0)])

            bad = jax.lax.cond(levels_below_an_lca[e], at_level,
                               lambda bad: bad, bad)
        return bad

    def classify(c, need, p_pri, p_ts, frs, req):
        t = slot_tables(c, frs, req)
        # Candidate scope: with adm_by_root, gather ONLY the slot's
        # root's admitted rows (candidates never cross cohort roots —
        # candidate_generator.go walks the preemptor's hierarchy) so all
        # per-candidate work is O(max admitted per root), not O(A).
        g_rows = root_rows(c)
        if g_rows is None:
            l_ok = jnp.ones((A,), bool)
            l_cq, l_pri, l_ts = adm_cq, adm_pri, adm_ts
            l_ev = adm_evicted
            l_usage = adm_usage
            l_chain, l_loc = adm_chain, adm_loc
            l_rank = adm_rank
        else:
            l_ok = g_rows >= 0
            rsafe = jnp.maximum(g_rows, 0)
            l_cq = jnp.where(l_ok, adm_cq[rsafe], -1)
            l_pri = adm_pri[rsafe]
            l_ts = adm_ts[rsafe]
            l_ev = adm_evicted[rsafe] & l_ok
            l_usage = jnp.where(l_ok[:, None], adm_usage[rsafe], 0)
            l_chain = jnp.where(l_ok[:, None], adm_chain[rsafe], -1)
            l_loc = jnp.where(l_ok[:, None], adm_loc[rsafe], -1)
            # Pad rows sort last; ties among pads are irrelevant (they
            # can never be candidates).
            l_rank = jnp.where(l_ok, adm_rank[rsafe], A)
        any_need = need & jnp.any(t.need_fr)

        # Hierarchical-advantage walk (hierarchical_preemption.go:149):
        # adv_before[d] = whether any strict subtree below level d already
        # fits the (remaining) request within quota.
        lavail_c0 = jnp.maximum(0, t.lq_c - t.usage_c0)  # [D+1, S]
        fits_cq = jnp.all(jnp.where(
            t.active, t.sq_c[0] >= t.usage_c0[0] + req, True))
        rem = jnp.where(t.active, jnp.maximum(0, req - lavail_c0[0]), 0)
        adv = fits_cq
        adv_before_list = [jnp.asarray(False)]  # level 0 unused
        for d in range(1, depth + 1):
            adv_before_list.append(adv)
            okd = t.chain_ok_c[d]
            fits_d = jnp.all(jnp.where(
                t.active, t.sq_c[d] >= t.usage_c0[d] + rem, True))
            adv = adv | (fits_d & okd)
            rem = jnp.where(t.active, jnp.maximum(0, rem - lavail_c0[d]), 0)
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
            (l_usage[:, t.frs_safe] > 0) & t.need_fr[None, :], axis=1)
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

        static_path_ok = ~path_within_nominal(
            t, lambda e: l_loc[:, e], lca_pos, t.usage_l0)

        is_cand = (any_need & uses_any & pol_gate & pol_ok
                   & (same_cq | (same_root & has_lca & static_path_ok)))
        bucket = jnp.where(same_cq, 2, jnp.where(adv_at_lca, 0, 1))

        no_other = ~jnp.any(is_cand & ~same_cq)
        no_hier = ~jnp.any(is_cand & (bucket == 0))
        under_nominal = jnp.all(jnp.where(
            t.need_fr, t.nom_cq > t.usage_c0[0], True))

        # Attempt sequencing (preemption.go:287-311).
        case1 = no_other | (bwc_forbidden[c] & ~under_nominal)
        case2 = ~case1 & bwc_forbidden[c] & no_hier

        # Ordering: evicted first, bucket, priority asc, reservation
        # recency desc, uid asc; non-candidates last. Only is_cand and
        # bucket vary per slot; the rest is the precomputed rank, whose
        # uniqueness makes the composite key a total order — one
        # argsort, no ties — and lets a later window be found by key
        # alone (next_window).
        lvl = (jnp.where(is_cand, 0, 2)
               + jnp.where(l_ev, 0, 1)) * 4 + bucket
        key = lvl.astype(jnp.int64) * (A + 1) + l_rank
        order = jnp.argsort(key).astype(jnp.int32)
        return (_Classified(key, is_cand, variant, same_cq, lca_pos,
                            l_loc.T,
                            order[:V], jnp.stack([~case2, case2]), ~case1),
                _Gathered(l_ok, l_loc, l_usage[:, t.frs_safe]))

    fill = _Classified(
        key=jnp.full((A_l,), jnp.iinfo(jnp.int64).max, jnp.int64),
        cand=jnp.zeros((A_l,), bool),
        variant=jnp.zeros((A_l,), jnp.int32),
        same=jnp.zeros((A_l,), bool),
        lca_pos=jnp.full((A_l,), NO_LCA, jnp.int32),
        loc=jnp.full((depth + 1, A_l), -1, jnp.int32),
        v_ids=jnp.full((V,), -1, jnp.int32),
        allow=jnp.asarray([True, False]),
        en2=jnp.asarray(False))

    def scan(c, need, frs, req, cl, gathered=None):
        t = slot_tables(c, frs, req)
        g_rows = root_rows(c)
        if gathered is not None:  # classify's, under the same vmap
            l_ok = gathered.ok

            def loc_level(e):
                return gathered.loc[:, e]

            def rows_at(at):
                return gathered.loc[at], gathered.usage[at]
        else:
            # No copy of the root's rows is kept (the sim program's
            # block): a window reads its V rows from the world, the walk
            # the candidates' chains a level at a time.
            l_ok = jnp.ones((A_l,), bool) if g_rows is None else g_rows >= 0
            loc_level = cl.loc.__getitem__

            def rows_at(at):
                g = at if g_rows is None else g_rows[at]
                held = usage_by_col[t.frs_safe[:, None],
                                    jnp.maximum(g, 0)[None, :]]  # [S, V]
                return cl.loc[:, at].T, jnp.where(g >= 0, held, 0).T

        any_need = need & jnp.any(t.need_fr)
        key, is_cand, variant, same_cq = cl.key, cl.cand, cl.variant, cl.same
        n_cand = jnp.sum(is_cand.astype(jnp.int32))

        def fits_with(usage_l, allow_borrow):
            g_usage = rows(usage_l, t.loc_c_safe)
            avail = available_along_chain(
                t.chain_ok_c, t.sq_c, t.lq_c, t.bl_c, g_usage, depth=depth)
            ok = jnp.all(jnp.where(t.active, req <= avail, True))
            # workloadFits without borrowing: usage + req must stay within
            # the CQ's guaranteed quota (preemption.go:624 borrowingWith).
            nb_ok = jnp.all(jnp.where(
                t.active, g_usage[0] + req <= t.sq_c[0], True))
            return ok & (allow_borrow | nb_ok)

        def window(ids):
            """V candidates by local id (-1: none)."""
            at = jnp.maximum(ids, 0)
            loc, held = rows_at(at)
            return _Window(is_cand[at] & (ids >= 0), variant[at],
                           same_cq[at], loc, cl.lca_pos[at], held)

        v_ids = cl.v_ids  # [V]
        first = window(v_ids)

        # A chain's rows are distinct, so what a level reads of its row
        # is what the row held before the chain was touched: every
        # level's row is read at once, one gather a column and not one
        # a level — the program's kernels are counted in its set-up.
        def remove_chain(usage_l, loc, val):
            """resource_node.go:156 removeUsage along one chain."""
            row_ok = loc >= 0
            r = jnp.maximum(loc, 0)
            ssp = rows(usage_l, r) - rows(t.lq_l, r)  # [D+1, S]
            for e in range(depth + 1):
                usage_l = add_row(usage_l, r[e],
                                  jnp.where(row_ok[e], -val, 0))
                val = jnp.where(row_ok[e] & (ssp[e] > 0),
                                jnp.minimum(val, ssp[e]), 0)
            return usage_l

        def add_chain(usage_l, loc, val):
            """resource_node.go:144 addUsage along one chain."""
            row_ok = loc >= 0
            r = jnp.maximum(loc, 0)
            la = jnp.maximum(0, rows(t.lq_l, r) - rows(usage_l, r))
            for e in range(depth + 1):
                usage_l = add_row(usage_l, r[e],
                                  jnp.where(row_ok[e], val, 0))
                val = jnp.where(row_ok[e], jnp.maximum(0, val - la[e]), 0)
            return usage_l

        def scan_window(win, usage_l, found, allow_borrow):
            """The greedy over one window of V ordered candidates: every
            one still valid is taken until the preemptor fits. Returns
            the usage, which were taken, found, how many candidates
            were passed over as invalid, and the last position looked at
            (-1: none)."""
            def step(carry, i):
                usage_l, taken, found, skipped, last = carry
                ok = win.cand[i] & ~found
                # candidateIsValid (candidate_generator.go:136), dynamic.
                bad_borrow = (allow_borrow
                              & (win.variant[i]
                                 == V_RECLAIM_WITHOUT_BORROWING)
                              & ~win.same[i])
                loc = win.loc[i]  # [D+1]
                r = jnp.maximum(loc, 0)
                below = (jnp.arange(depth + 1) < win.lca_pos[i]) & (loc >= 0)
                wn = jnp.all(jnp.where(
                    t.need_fr, rows(t.sq_l, r) >= rows(usage_l, r), True),
                    axis=1)
                wn_bad = jnp.any(below & wn)
                valid = ok & ~bad_borrow & (win.same[i] | ~wn_bad)
                removed = remove_chain(usage_l, loc, win.usage[i])
                usage_l = jnp.where(valid, removed, usage_l)
                taken = taken.at[i].set(valid)
                fit = fits_with(usage_l, allow_borrow)
                found = found | (valid & fit)
                return (usage_l, taken, found,
                        skipped + (ok & ~valid).astype(jnp.int32),
                        jnp.where(ok, i.astype(jnp.int32), last)), None

            init = (usage_l, jnp.zeros((V,), bool), found,
                    jnp.int32(0), jnp.int32(-1))
            return jax.lax.scan(step, init, jnp.arange(V))[0]

        def fill_back(usage_l, taken, i, loc, val, consider, allow_borrow):
            """preemption.go:334, one target: given back where the
            preemptor still fits with it."""
            trial = add_chain(usage_l, loc, val)
            spared = consider & taken[i] & fits_with(trial, allow_borrow)
            return (jnp.where(spared, trial, usage_l),
                    taken.at[i].set(taken[i] & ~spared))

        def first_window(allow_borrow):
            """An attempt over the first V of the order: (usage, ids,
            variants, taken, found, skipped, targets held)."""
            usage_f, taken, found, skipped, _ = scan_window(
                first, t.usage_l0, jnp.asarray(False), allow_borrow)

            # Fill-back (preemption.go:334): reverse over targets except
            # the last, re-adding any whose re-addition keeps the fit.
            last_idx = jnp.max(jnp.where(taken, jnp.arange(V), -1))

            def fb(carry, j):
                i = V - 1 - j
                return fill_back(*carry, i, first.loc[i], first.usage[i],
                                 found & (i != last_idx),
                                 allow_borrow), None

            (usage_fb, taken_fb), _ = jax.lax.scan(fb, (usage_f, taken),
                                                   jnp.arange(V))
            return (usage_fb, v_ids, first.variant, taken_fb, found,
                    skipped, jnp.int32(0))

        def beyond_first_window(state, allow_borrow, walks_on):
            """The same greedy and fill-back over the rest of the order,
            for a slot whose first V ordered candidates did not make it
            fit while more are left (``walks_on``). The targets are held
            as a mask over the candidates (a walk may take thousands
            before it fits and give nearly all back), and packed again
            at the end."""
            usage_l, ids, _var, taken, found, skipped, _n = state
            att_cand = is_cand & ~(allow_borrow & (
                variant == V_RECLAIM_WITHOUT_BORROWING) & ~same_cq)
            held = jnp.zeros((A_l,), bool).at[
                jnp.where(taken, ids, A_l)].set(True, mode="drop")

            def forward(c):
                # The next V candidates this attempt may take that are
                # valid at this usage: validity only ever goes, so none
                # is missed, and the scan checks each again.
                usage_l, held, found, cursor, skipped, _ = c
                open_ = att_cand & (same_cq | ~path_within_nominal(
                    t, loc_level, cl.lca_pos, usage_l))
                w_ids = next_window(key, open_, cursor, V)
                usage_l, w_taken, found, _, last = scan_window(
                    window(w_ids), usage_l, found, allow_borrow)
                held = held.at[jnp.where(w_taken, w_ids, A_l)].set(
                    True, mode="drop")
                upto = jnp.where(
                    last >= 0,
                    key[jnp.maximum(w_ids[jnp.maximum(last, 0)], 0)],
                    cursor)
                passed = jnp.sum(
                    is_cand & (key > cursor) & (key <= upto),
                    dtype=jnp.int32) - jnp.sum(w_taken, dtype=jnp.int32)
                return (usage_l, held, found, upto, skipped + passed,
                        (w_ids[V - 1] >= 0) & ~found)

            usage_l, held, found, _, skipped, _ = jax.lax.while_loop(
                lambda c: c[-1], forward,
                (usage_l, held, found, key[ids[V - 1]], skipped,
                 walks_on))

            def backward(c):
                # Fill-back, V targets a page from the last taken down;
                # the very last one stays.
                usage_l, held, below, first_page, _ = c
                w_ids = next_window(-key, held, -below, V)
                win = window(w_ids)

                def one(carry, i):
                    return fill_back(*carry, i, win.loc[i], win.usage[i],
                                     ~(first_page & (i == 0)),
                                     allow_borrow), None

                (usage_l, kept), _ = jax.lax.scan(
                    one, (usage_l, w_ids >= 0), jnp.arange(V))
                held = held.at[jnp.where((w_ids >= 0) & ~kept, w_ids,
                                         A_l)].set(False, mode="drop")
                return (usage_l, held,
                        jnp.min(jnp.where(
                            w_ids >= 0, key[jnp.maximum(w_ids, 0)],
                            below)),
                        jnp.asarray(False), w_ids[V - 1] >= 0)

            usage_l, held, _, _, _ = jax.lax.while_loop(
                lambda c: c[-1], backward,
                (usage_l, held, jnp.iinfo(key.dtype).max,
                 jnp.asarray(True), walks_on & found))

            ids = next_window(key, held, jnp.int64(-1), V)
            return (usage_l, ids,
                    jnp.where(ids >= 0, variant[jnp.maximum(ids, 0)], 0),
                    ids >= 0, found, skipped,
                    jnp.sum(held, dtype=jnp.int32))

        # One copy of an attempt in the program — its first window and
        # the walk beyond it — run for the two attempts in turn: what a
        # program costs when it is first loaded
        # follows its size. The region is entered only where some slot
        # walks on: with few candidates (n_cand <= V in every slot) a
        # launch pays for the first windows alone.
        def attempt(k, attempts):
            state = first_window(cl.allow[k])
            enabled = (k == 0) | (cl.en2 & ~attempts[4][0])
            walks_on = enabled & any_need & ~state[4] & (n_cand > V)
            _, state = jax.lax.while_loop(
                lambda c: c[0],
                lambda c: (jnp.asarray(False), beyond_first_window(
                    c[1], cl.allow[k], walks_on)),
                (walks_on, state))
            return jax.tree.map(lambda x, new: x.at[k].set(new),
                                attempts, state)

        (u, ids, var, tk, f, s, n_held) = jax.lax.fori_loop(
            0, 2, attempt, jax.tree.map(
                lambda x: jnp.zeros((2,) + x.shape, x.dtype),
                jax.eval_shape(first_window, cl.allow[0])))

        def borrow_after_height(usage_l):
            """FindHeightOfLowestSubtreeThatFits
            (classical/hierarchical_preemption.go:221) against a
            root-local usage state; max over the slot's resources."""
            usage_c = rows(usage_l, t.loc_c_safe)  # [D+1, S]
            lavail = jnp.maximum(0, t.lq_c - usage_c)
            borrowing_cq = t.nom_cq < usage_c[0] + req  # [S]
            has_par = (t.chain_ok_c[1] if depth >= 1
                       else jnp.asarray(False))
            remaining = jnp.maximum(0, req - lavail[0])
            found_b = jnp.zeros((req.shape[0],), bool)
            found_h = jnp.zeros((req.shape[0],), jnp.int32)
            for d in range(1, depth + 1):
                okd = t.chain_ok_c[d]
                borrowing = t.sq_c[d] < usage_c[d] + remaining
                fits_here = okd & ~borrowing & ~found_b
                found_h = jnp.where(fits_here,
                                    t.height_l[t.loc_c_safe[d]], found_h)
                found_b = found_b | fits_here
                remaining = jnp.where(okd & ~found_b,
                                      jnp.maximum(0, remaining - lavail[d]),
                                      remaining)
            root_h = jnp.int32(0)
            for d in range(depth + 1):
                root_h = jnp.where(t.chain_ok_c[d],
                                   t.height_l[t.loc_c_safe[d]], root_h)
            h = jnp.where(~borrowing_cq | ~has_par, 0,
                          jnp.where(found_b, found_h, root_h))
            return jnp.max(jnp.where(t.active, h, 0))

        f1, f2 = f[0], f[1]
        # More than V targets is the one thing the packed columns cannot
        # hold.
        big1, big2 = f1 & (n_held[0] > V), f2 & (n_held[1] > V)
        ids1, ids2, var1, var2 = ids[0], ids[1], var[0], var[1]
        t1, t2, u1, u2, s1, s2 = tk[0], tk[1], u[0], u[1], s[0], s[1]
        use2 = ~f1 & cl.en2 & f2
        overflow = need & any_need & (big1 | (use2 & big2))
        found = (f1 | use2) & any_need & ~overflow
        taken = jnp.where(found, jnp.where(f1, t1, t2),
                          jnp.zeros((V,), bool))
        ids = jnp.where(f1, ids1, ids2)
        borrow_after = jnp.where(
            f1, borrow_after_height(u1),
            jnp.where(use2, borrow_after_height(u2), 0)).astype(jnp.int32)
        skipped = s1 + jnp.where(cl.en2 & ~f1, s2, 0)

        ids_ok = ids >= 0
        ids_safe = jnp.maximum(ids, 0)
        if g_rows is None:
            g_v_ids = jnp.where(ids_ok, ids, -1)
        else:
            # Map local victim positions back to GLOBAL ids.
            ids_ok = ids_ok & l_ok[ids_safe]
            g_v_ids = jnp.where(ids_ok, g_rows[ids_safe], -1)
        g_variant = jnp.where(ids_ok, jnp.where(f1, var1, var2), 0)
        return (found, overflow, jnp.sum(taken.astype(jnp.int32)),
                borrow_after, g_v_ids, taken, g_variant, skipped)

    return classify, scan, fill


# Rows of the sim program's block classified at a time (sim_targets).
# The classify scales with the rows it is given, the scans hardly do
# (a step is bound by its ops, not by the rows' bytes), and a block's
# live rows are a prefix of it: a launch classifies its live prefix in
# chunks of this many rows and scans the whole block once. On a v5e a
# chunk costs its rows and next to nothing more, and in the selective
# benchmark cell a third of the launches have under 64 live rows:
# PERF.md's findings have the launch times it was chosen from.
SIM_CHUNK = 64


@partial(jax.jit, static_argnames=("depth", "v_cap", "chunk"))
def sim_targets(*args, slot_cq, adm_rank, adm_by_root, depth: int,
                v_cap: int, chunk: int = SIM_CHUNK):
    """The sim program (preemption_oracle.go:41 SimulatePreemption, one
    row per (head, flavor, resource) cell): the classical preemptor over
    a block of rows, returning only what the fungibility fold reads —
    found bool[B], overflow bool[B], borrow_after int32[B], and whether
    any victim sits in the row's own ClusterQueue (Preempt, else
    Reclaim). The victim sets stay on the device: the fold does not
    need them, and the final target selection is the cycle program's.
    A row whose targets are more than the packed columns hold reports
    overflow, which the bridge hands to the host (`sim-overflow`).

    The bridge packs the rows that need a simulation to the front of the
    block (``slot_need`` False pads it): only the rows up to the last
    that needs one are classified, ``chunk`` (at most the block) at a
    time in one loop, and a row past them keeps the fill of a row with
    no candidate. The scans then run once over the whole block. The
    answers are classical_targets_impl's, row for row."""
    slot_need, slot_pri, slot_ts, slot_fr, slot_req = args[:5]
    adm_cq = args[10]
    classify, scan, fill = _preemptor_rows(
        slot_req.shape[1], *args[5:], adm_rank=adm_rank,
        adm_by_root=adm_by_root, depth=depth, v_cap=v_cap)
    B = slot_need.shape[0]
    chunk = min(chunk, B)
    row_args = (slot_cq, slot_need, slot_pri, slot_ts, slot_fr, slot_req)
    live = jnp.max(jnp.where(slot_need, jnp.arange(1, B + 1), 0))

    def classify_chunk(i, classified):
        # Where ``chunk`` does not divide the block, the last chunk
        # starts early and classifies some rows again.
        lo = jnp.minimum(i * chunk, B - chunk)
        part, _gathered = jax.vmap(classify)(*(
            jax.lax.dynamic_slice_in_dim(x, lo, chunk) for x in row_args))
        return jax.tree.map(
            lambda buf, new: jax.lax.dynamic_update_slice_in_dim(
                buf, new, lo, 0), classified, part)

    with jax.named_scope("kueue.sim_classify_chunk"):
        classified = jax.lax.fori_loop(
            0, -(-live // chunk), classify_chunk,
            jax.tree.map(lambda x: jnp.broadcast_to(x, (B,) + x.shape),
                         fill))
    found, overflow, _n, borrow_after, v_ids, taken, _variant, _skip = \
        jax.vmap(scan)(slot_cq, slot_need, slot_fr, slot_req, classified)
    same = jnp.any(taken & (v_ids >= 0)
                   & (adm_cq[jnp.maximum(v_ids, 0)] == slot_cq[:, None]),
                   axis=1)
    return found, overflow, borrow_after, same
