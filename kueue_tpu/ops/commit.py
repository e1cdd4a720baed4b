"""Sequential-equivalent commit as a lax.scan: the cycle's steps 4-5
(scheduler.go:945 makeIterator ordering + :371 processEntry usage
accumulation) as one compiled scan over the ordered entries.

The subtle part of the batched design (SURVEY.md §7.4): nomination is
embarrassingly parallel, but the reference commits entries one at a time
against evolving usage. We reproduce that exactly with a scan whose carry
is the [N, R] usage matrix: each step re-checks fit along the entry's
ancestor chain from current carry (scheduler.go:680 fits) and, on success,
adds usage with the localQuota bubbling of resource_node.go:144.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from kueue_tpu.api.types import INF
from kueue_tpu.ops.quota import (
    available_along_chain,
    local_quota,
    sat_add,
    sat_sub,
)


ENTRY_SKIP = 0  # never commits (NoFit / ineligible slot)
ENTRY_FIT = 1  # commits if it still fits against evolving usage
ENTRY_RESERVE = 2  # preempt-mode w/o candidates: reserve capacity
#   (scheduler.go:499 reserveCapacityForUnreclaimablePreempt)
ENTRY_PREEMPT = 3  # preempt-mode with device-selected targets: fit is
#   checked with the entry's victims removed (scheduler.go:680 fits with
#   preemption targets); on success the removal persists in the carry
#   (victim usage is gone for later entries, like preempted_workloads)
#   and the entry's usage is added, but the entry is PREEMPTING, not
#   admitted (the job restarts only after evictions complete).


def _entry_verdict(g_sq, g_lq, g_bl, g_usage, chain_ok, frs, req, kind,
                   borrows_k, cq_nom, cq_bl, cq_usage_now, *, depth):
    """The per-entry fit check + usage-bubbling amounts, shared by the
    global scan and the root-grouped commit.

    g_* are [D+1, S] gathers along the entry's ancestor chain (index 0 =
    the CQ itself). Returns (fits bool, adds int64[D+1, S] — the usage to
    add at each chain level, already masked)."""
    active = (frs >= 0) & (req > 0)
    g_local_avail = jnp.maximum(0, sat_sub(g_lq, g_usage))
    avail = available_along_chain(chain_ok, g_sq, g_lq, g_bl, g_usage,
                                  depth=depth)

    fits = ((kind == ENTRY_FIT) | (kind == ENTRY_PREEMPT)) & jnp.all(
        jnp.where(active, req <= avail, True))

    # Reservation amount (scheduler.go:708 quotaResourcesToReserve):
    # when borrowing, cap at nominal+borrowingLimit-usage (or full
    # usage if no limit); else clamp into remaining nominal headroom.
    borrowing_amt = jnp.where(
        cq_bl >= INF, req,
        jnp.minimum(req, sat_sub(sat_add(cq_nom, cq_bl), cq_usage_now)))
    nominal_amt = jnp.maximum(
        0, jnp.minimum(req, sat_sub(cq_nom, cq_usage_now)))
    reserve_req = jnp.where(borrows_k > 0, borrowing_amt, nominal_amt)

    do_add = fits | (kind == ENTRY_RESERVE)
    v = jnp.where(kind == ENTRY_RESERVE, reserve_req, req)
    v = jnp.where(active & do_add, v, 0)  # [S]

    # Usage bubbling (resource_node.go:144): node gets v, parent gets
    # max(0, v - localAvailable(node)).
    adds = []
    for d in range(depth + 1):
        adds.append(jnp.where(chain_ok[d] & active, v, 0))
        v = jnp.maximum(0, v - g_local_avail[d])
    return fits, jnp.stack(adds)


@partial(jax.jit, static_argnames=("depth",))
def commit_scan(
    order,  # int32[K] entry indices in commit order
    entry_cq,  # int32[K] CQ node per entry
    entry_fr,  # int32[K, S] flavor-resource index per resource (-1 none)
    entry_req,  # int64[K, S] request per resource
    entry_kind,  # int32[K] ENTRY_SKIP / ENTRY_FIT / ENTRY_RESERVE
    entry_borrows,  # int32[K] assignment borrowing level
    usage0,  # int64[N, R] usage at cycle start
    subtree_quota,  # int64[N, R] (static within the cycle)
    lend_limit,  # int64[N, R]
    borrow_limit,  # int64[N, R]
    nominal,  # int64[N, R]
    ancestors,  # int32[N, D]
    *,
    depth: int,
):
    """Returns (admitted bool[K] aligned with `order`, final usage)."""
    lq = local_quota(subtree_quota, lend_limit)

    def step(usage, k):
        cq = entry_cq[k]
        frs = entry_fr[k]  # [S]
        req = entry_req[k]  # [S]
        frs_safe = jnp.maximum(frs, 0)

        # Chain cq -> root as [D+1] node indices (-1 padded).
        chain = jnp.concatenate(
            [jnp.asarray([cq], jnp.int32), ancestors[cq]])  # [D+1]
        chain_ok = chain >= 0
        chain_safe = jnp.maximum(chain, 0)

        # Gather per-(chain-node, fr) scalars: [D+1, S].
        g_sq = subtree_quota[chain_safe[:, None], frs_safe[None, :]]
        g_lq = lq[chain_safe[:, None], frs_safe[None, :]]
        g_bl = borrow_limit[chain_safe[:, None], frs_safe[None, :]]
        g_usage = usage[chain_safe[:, None], frs_safe[None, :]]

        fits, adds = _entry_verdict(
            g_sq, g_lq, g_bl, g_usage, chain_ok, frs, req, entry_kind[k],
            entry_borrows[k], nominal[cq, frs_safe],
            borrow_limit[cq, frs_safe], usage[cq, frs_safe], depth=depth)

        new_usage = usage
        for d in range(depth + 1):
            new_usage = new_usage.at[chain_safe[d], frs_safe].add(adds[d])
        return new_usage, fits

    usage_final, admitted = jax.lax.scan(step, usage0, order)
    return admitted, usage_final


def _apply_victims(usage_l, lq_l, parent_local, rows, vals, *, depth):
    """Aggregated victim-usage removal (resource_node.go:156 removeUsage)
    over a root-local node set: take the victims' usage off their CQ
    rows, then propagate each row's above-local-quota share to its
    parent, level by level. Exact vs sequential per-victim removal:
    headroom consumption is monotone, so min-sum aggregation per row
    equals the per-victim walk.

    Only the victims' own chains are touched: V rows a level, gathered
    and scattered, victims that meet in a row summed there and carried
    on by the first of them.

    usage_l, lq_l: int64[K, R]; parent_local: int32[K]; rows: int32[V]
    victim CQ positions (-1 = none); vals: int64[V, R]."""
    K = usage_l.shape[0]
    first_of = jnp.arange(rows.shape[0])
    row = jnp.where(rows >= 0, rows, K)  # K = nothing left to remove
    rem = jnp.where((rows >= 0)[:, None], vals, 0)
    for _ in range(depth + 1):
        same = row[:, None] == row[None, :]  # [V, V]
        total = jnp.sum(jnp.where(same[:, :, None], rem[None], 0), axis=1)
        r_safe = jnp.minimum(row, K - 1)
        prop = jnp.minimum(total,
                           jnp.maximum(0, usage_l[r_safe] - lq_l[r_safe]))
        prop = jnp.maximum(prop, 0)
        usage_l = usage_l.at[row].add(-rem, mode="drop")
        parent = parent_local[r_safe]
        carries = (row < K) & (parent >= 0) \
            & (jnp.argmax(same, axis=1) == first_of)
        row = jnp.where(carries, parent, K)
        rem = jnp.where(carries[:, None], prop, 0)
    return usage_l


def _commit_one_local(usage_l, c, entry_fr, entry_req, entry_kind,
                      entry_borrows, subtree_quota, lq, borrow_limit,
                      nominal, ancestors, local_chain, *, depth,
                      victims=None, claimed=None):
    """Commit one entry (slot id c, -1 = none) against a root-local usage
    carry [K, R]: gather along the chain, run _entry_verdict, bubble the
    adds. Shared by the grouped classical and fair commits.

    victims (optional): (row int32[C, V], vals int64[C, V, R],
    ids int32[C, V], lq_l [K, R], parent_local [K]) — per-entry victim
    sets for ENTRY_PREEMPT slots. The fit check runs with the victims'
    usage removed (exact removeUsage bubbling along the victims' own
    chains), the removal persists on success, and an entry whose victim
    ids intersect `claimed` (workloads already preempted by an earlier
    entry this cycle) is skipped — the one-admission-per-cohort overlap
    rule (scheduler.go:432). Returns (new_usage_l, new_claimed, fits)."""
    ok = c >= 0
    c_safe = jnp.maximum(c, 0)
    frs = entry_fr[c_safe]
    req = jnp.where(ok, entry_req[c_safe], 0)
    frs_safe = jnp.maximum(frs, 0)

    chain = jnp.concatenate(
        [jnp.asarray([c_safe], jnp.int32), ancestors[c_safe]])
    chain_ok = (chain >= 0) & ok
    chain_safe = jnp.maximum(chain, 0)
    loc = local_chain[c_safe]  # [D+1] positions into K
    loc_safe = jnp.maximum(loc, 0)

    g_sq = subtree_quota[chain_safe[:, None], frs_safe[None, :]]
    g_lq = lq[chain_safe[:, None], frs_safe[None, :]]
    g_bl = borrow_limit[chain_safe[:, None], frs_safe[None, :]]

    kind = jnp.where(ok, entry_kind[c_safe], ENTRY_SKIP)
    is_pre = ok & (kind == ENTRY_PREEMPT)

    overlap = jnp.asarray(False)
    if victims is not None:
        v_row, v_vals, v_ids, lq_l, parent_local = victims
        rows = jnp.where(is_pre, v_row[c_safe], -1)  # [V]
        vals = v_vals[c_safe]  # [V, R]
        trial = _apply_victims(usage_l, lq_l, parent_local, rows, vals,
                               depth=depth)
        ids = v_ids[c_safe]
        A = claimed.shape[0]
        overlap = is_pre & jnp.any(
            (ids >= 0) & claimed[jnp.clip(ids, 0, A - 1)])
        # An entry whose targets overlap an earlier entry's is skipped
        # before its fit is looked at (scheduler.go:432): it adds no
        # usage either.
        kind = jnp.where(overlap, ENTRY_SKIP, kind)
    else:
        trial = usage_l

    g_usage = trial[loc_safe[:, None], frs_safe[None, :]]
    fits, adds = _entry_verdict(
        g_sq, g_lq, g_bl, g_usage, chain_ok, frs, req, kind,
        entry_borrows[c_safe], nominal[c_safe, frs_safe],
        borrow_limit[c_safe, frs_safe], g_usage[0], depth=depth)

    # ENTRY_PREEMPT: the victim removal persists only when the entry
    # commits; otherwise the carry is untouched. `adds` is already masked
    # to zero for non-committing kinds inside _entry_verdict.
    new_usage = usage_l if victims is None else jnp.where(
        fits & is_pre, trial, usage_l)
    for d in range(depth + 1):
        new_usage = new_usage.at[loc_safe[d], frs_safe].add(adds[d])
    new_claimed = claimed
    if victims is not None:
        commit_pre = fits & is_pre
        new_claimed = claimed.at[
            jnp.where(commit_pre & (ids >= 0), ids,
                      claimed.shape[0])].set(True, mode="drop")
    return new_usage, new_claimed, fits & ok


@partial(jax.jit, static_argnames=("depth",))
def commit_grouped(
    entry_key,  # int64[C] commit-order sort key (lower = earlier)
    entry_valid,  # bool[C] slot participates this cycle
    entry_fr,  # int32[C, S]
    entry_req,  # int64[C, S]
    entry_kind,  # int32[C]
    entry_borrows,  # int32[C]
    usage0,  # int64[N, R]
    subtree_quota, lend_limit, borrow_limit, nominal, ancestors,
    root_members,  # int32[Rn, M] CQ/slot ids per root, -1 pad
    root_nodes,  # int32[Rn, K] subtree node ids per root, -1 pad
    local_chain,  # int32[C, D+1] chain positions into the root's node row
    root_parent_local=None,  # int32[Rn, K] parent positions (victims)
    slot_victim_row=None,  # int32[C, V] victim CQ local positions
    slot_victim_vals=None,  # int64[C, V, R] victim usage rows
    slot_victim_ids=None,  # int32[C, V] admitted-workload ids (overlap)
    claimed0=None,  # bool[A] initially-claimed victims (usually zeros)
    *,
    depth: int,
):
    """Sequential-equivalent commit, parallel across root subtrees.

    Admissions never interact across roots (all quota math — borrowing,
    lending, usage bubbling — stays under the entry's root cohort), so the
    reference's one-at-a-time commit order is reproduced exactly by one
    scan over the positions of the roots' entries in global key order,
    each step committing that position of every root at once. Scan length
    drops from C (all slots) to max-CQs-per-root — the difference between
    a 1000-step and an ~8-step sequential section per cycle on TPU.

    slot_victim_* carry device-selected preemption victims for
    ENTRY_PREEMPT slots (ops/preempt.classical_targets_impl output): the
    fit check runs with the victims removed along their own chains, removals
    persist on success, and victim overlap between entries applies the
    one-admission-per-cohort rule (scheduler.go:432).

    Returns (admitted bool[C] by slot, final usage int64[N, R]).
    """
    N, R = usage0.shape
    Rn, M = root_members.shape
    K = root_nodes.shape[1]
    BIGKEY = jnp.int64((1 << 62))
    lq = local_quota(subtree_quota, lend_limit)
    # Invalid slots must never commit regardless of their kind value (the
    # BIGKEY demotion alone is not a guarantee: valid non-quota-reserved
    # keys also carry bit 62).
    entry_kind = jnp.where(entry_valid, entry_kind, ENTRY_SKIP)

    member_ok = root_members >= 0
    members_safe = jnp.maximum(root_members, 0)
    mkey = jnp.where(member_ok & entry_valid[members_safe],
                     entry_key[members_safe], BIGKEY)
    morder = jnp.argsort(mkey, axis=1)
    sorted_members = jnp.take_along_axis(root_members, morder, axis=1)

    nodes_safe = jnp.maximum(root_nodes, 0)
    node_ok = root_nodes >= 0
    init_local = jnp.where(node_ok[:, :, None],
                           usage0[nodes_safe], 0)  # [Rn, K, R]
    has_victims = slot_victim_row is not None
    if has_victims:
        lq_locals = jnp.where(node_ok[:, :, None], lq[nodes_safe], 0)
    else:
        claimed0 = jnp.zeros((1,), bool)
        lq_locals = jnp.zeros((Rn, 1, 1), lq.dtype)
        root_parent_local = jnp.full((Rn, K), -1, jnp.int32)

    def commit_all(usage_ls, claimeds, cs, *, with_victims):
        """One position of every root at once: cs int32[Rn]."""
        def one_root(usage_l, claimed, c, lq_l, parent_local):
            victims = ((slot_victim_row, slot_victim_vals,
                        slot_victim_ids, lq_l, parent_local)
                       if with_victims else None)
            return _commit_one_local(
                usage_l, c, entry_fr, entry_req, entry_kind, entry_borrows,
                subtree_quota, lq, borrow_limit, nominal, ancestors,
                local_chain, depth=depth, victims=victims, claimed=claimed)

        return jax.vmap(one_root)(usage_ls, claimeds, cs, lq_locals,
                                  root_parent_local)

    # The sequential section does only what depends on the carry: a
    # position at which no root's entry preempts takes the branch without
    # the victim removal, the claimed-victims lookup and its update (for
    # every other kind they are the identity) — a real branch, the scan
    # being over positions with the roots vmapped inside the step.
    preempts_at = jnp.any(
        (sorted_members >= 0)
        & (entry_kind[jnp.maximum(sorted_members, 0)] == ENTRY_PREEMPT),
        axis=0)  # [M]

    def step(carry, position):  # usage_ls [Rn, K, R], claimeds [Rn, A]
        cs, some_root_preempts = position
        if not has_victims:
            usage_ls, claimeds, fits = commit_all(*carry, cs,
                                                  with_victims=False)
        else:
            usage_ls, claimeds, fits = jax.lax.cond(
                some_root_preempts,
                partial(commit_all, with_victims=True),
                partial(commit_all, with_victims=False), *carry, cs)
        return (usage_ls, claimeds), fits

    claimeds0 = jnp.broadcast_to(claimed0, (Rn,) + claimed0.shape)
    (final_local, _), admitted_seq = jax.lax.scan(
        step, (init_local, claimeds0), (sorted_members.T, preempts_at))
    admitted_seq = admitted_seq.T  # [Rn, M], as sorted_members

    # Scatter per-root verdicts back to slot order.
    flat_members = sorted_members.reshape(-1)
    flat_adm = admitted_seq.reshape(-1)
    C = entry_key.shape[0]
    admitted = jnp.zeros((C,), bool).at[
        jnp.where(flat_members >= 0, flat_members, C)].max(
        flat_adm, mode="drop")

    # Scatter local usage back into the global node matrix (subtrees are
    # disjoint and cover every node).
    flat_nodes = root_nodes.reshape(-1)
    flat_usage = final_local.reshape(-1, R)
    usage_final = usage0.at[
        jnp.where(flat_nodes >= 0, flat_nodes, N)].set(
        flat_usage, mode="drop")
    return admitted, usage_final


@partial(jax.jit, static_argnames=("depth", "num_flavors"))
def commit_grouped_fair(
    entry_valid,  # bool[C]
    entry_fr,  # int32[C, S]
    entry_req,  # int64[C, S]
    entry_kind,  # int32[C]
    entry_borrows,  # int32[C]
    entry_priority,  # int64[C]
    entry_ts,  # float64[C] creation time (ascending tiebreak)
    usage0,  # int64[N, R]
    subtree_quota, lend_limit, borrow_limit, nominal, ancestors,
    potential,  # int64[N, R] from quota.derive_world
    fair_weight,  # float64[N]
    parent,  # int32[N]
    root_members, root_nodes, local_chain,
    child_rank,  # int64[N] position in the parent's ordered child list
    local_depth,  # int32[Rn, K] chain distance from the root row
    root_parent_local,  # int32[Rn, K]
    *,
    depth: int,
    num_flavors: int,
):
    """Fair-sharing commit order (KEP 1714): the admission-side
    hierarchical DRS tournament (fair_sharing_iterator.go:47,125 +
    computeDRS :220) fused with the grouped commit. Per root subtree,
    repeat: simulate each candidate head's nominated usage bubbled along
    its ancestor chain (resource_node.go:144), compute the
    DominantResourceShare of every chain node (fair_sharing.go:140 — max
    over borrowed resources of borrowed*1000/lendable(parent), weighted
    by fairSharing.weight, zero-weight borrowers last), then run the
    bottom-up tournament over the cohort tree: at each cohort the
    surviving candidate per child subtree competes on the DRS of ITS
    child-of-this-cohort node (the LCA semantics of
    preemption/fairsharing/least_common_ancestor.go), with priority
    desc / timestamp asc / child-list order tiebreaks
    (fair_sharing_iterator.go:176). The root winner commits against
    evolving usage and the loop re-runs — the reference's
    pop-one-recompute loop, vmapped across roots on device.

    Returns (admitted bool[C], round int32[C] commit round within the
    root (-1 = not admitted), usage int64[N, R]).
    """
    N, R = usage0.shape
    Rn, M = root_members.shape
    K = root_nodes.shape[1]
    NF = num_flavors
    # Resources per flavor for the flavor-summed reshapes below; the
    # entry_fr/entry_req column count is independent (the cycle core
    # passes a dense per-flavor-resource layout).
    S = R // NF
    D = depth
    lq = local_quota(subtree_quota, lend_limit)
    entry_kind = jnp.where(entry_valid, entry_kind, ENTRY_SKIP)
    INF_F = jnp.float64(jnp.inf)

    member_ok = root_members >= 0

    # lendable seen by node n = calculateLendable(parent(n))
    # (fair_sharing.go:177): the parent's potentialAvailable summed over
    # flavors, per resource.
    lendable_node = jnp.sum(
        jnp.minimum(potential, INF).reshape(N, NF, S), axis=1)  # [N, S]

    def per_root(members, m_ok, local_usage, nodes, p_local, ld):
        c = jnp.maximum(members, 0)  # [M] member CQ node ids
        frs = entry_fr[c]  # [M, S]
        req = entry_req[c]
        frs_safe = jnp.maximum(frs, 0)
        active_fr = (frs >= 0) & (req > 0)
        chain = jnp.concatenate(
            [c[:, None].astype(jnp.int32), ancestors[c]], axis=1)
        chain_ok = chain >= 0  # [M, D+1]
        chain_safe = jnp.maximum(chain, 0)
        rows = local_chain[c]  # [M, D+1] rows into the local carry
        rows_safe = jnp.maximum(rows, 0)
        g_lq_fr = lq[chain_safe[:, :, None],
                     frs_safe[:, None, :]]  # [M, D+1, S]
        sq_full = subtree_quota[chain_safe]  # [M, D+1, R]
        par_of_chain = parent[chain_safe]  # [M, D+1]
        lend = lendable_node[jnp.maximum(par_of_chain, 0)]  # [M, D+1, S]
        wgt = fair_weight[chain_safe]  # [M, D+1]
        has_par = chain_ok & (par_of_chain >= 0)
        pri_f = entry_priority[c].astype(jnp.float64)
        ts = entry_ts[c]
        crank_row = child_rank[jnp.maximum(nodes, 0)].astype(jnp.float64)
        row0 = rows_safe[:, 0]
        kidx = jnp.arange(K)

        def drs_keys(usage_l):
            """(zwb, key) per member per chain position: the DRS of chain
            node j after the member's simulated usage addition — the
            value the tournament reads when the member competes at chain
            node j+1 (computeDRS stores the child's DRS at the parent).
            """
            g_u_fr = usage_l[rows_safe[:, :, None],
                             frs_safe[:, None, :]]  # [M, D+1, S]
            local_avail = jnp.maximum(0, g_lq_fr - g_u_fr)
            v = jnp.where(active_fr, req, 0)  # [M, S]
            adds = []
            for d in range(D + 1):
                adds.append(jnp.where(chain_ok[:, d:d + 1] & active_fr,
                                      v, 0))
                v = jnp.maximum(0, v - local_avail[:, d, :])
            adds = jnp.stack(adds, axis=1)  # [M, D+1, S]
            u_full = usage_l[rows_safe]  # [M, D+1, R]
            u_full = u_full.at[
                jnp.arange(M)[:, None, None],
                jnp.arange(D + 1)[None, :, None],
                jnp.where(frs >= 0, frs_safe, R - 1)[:, None, :]].add(
                jnp.where(frs[:, None, :] >= 0, adds, 0), mode="drop")
            borrowed = jnp.maximum(0, u_full - sq_full)
            by_res = borrowed.reshape(M, D + 1, NF, S).sum(axis=2)
            ratio_rs = jnp.where(
                (by_res > 0) & (lend > 0),
                by_res.astype(jnp.float64) * 1000.0
                / jnp.maximum(lend, 1).astype(jnp.float64), 0.0)
            ratio = jnp.where(has_par, jnp.max(ratio_rs, axis=2), 0.0)
            zwb = (wgt == 0) & (ratio > 0)
            keyv = jnp.where(
                zwb, ratio,
                jnp.where(wgt > 0, ratio / jnp.maximum(wgt, 1e-300), 0.0))
            return zwb.astype(jnp.float64), keyv

        def tournament(zwb, keyv, alive):
            """runTournament :125 bottom-up: rows at depth d promote
            their surviving candidate to the parent row, competing on
            the candidate's DRS at its current chain position."""
            cand = jnp.full((K,), -1, jnp.int32).at[
                jnp.where(alive, row0, K)].set(
                jnp.arange(M, dtype=jnp.int32), mode="drop")
            candj = jnp.zeros((K,), jnp.int32)
            for d in range(D, 0, -1):
                at_d = (ld == d) & (cand >= 0)
                m = jnp.maximum(cand, 0)
                kz = jnp.where(at_d, zwb[m, candj], INF_F)
                ks = jnp.where(at_d, keyv[m, candj], INF_F)
                kp = jnp.where(at_d, -pri_f[m], INF_F)
                kt = jnp.where(at_d, ts[m], INF_F)
                kr = jnp.where(at_d, crank_row, INF_F)
                seg = jnp.where(at_d & (p_local >= 0), p_local, K)
                mask = at_d
                for kk in (kz, ks, kp, kt, kr):
                    kk = jnp.where(mask, kk, INF_F)
                    mn = jax.ops.segment_min(kk, seg, num_segments=K + 1)
                    mask = mask & (kk == mn[seg])
                wrow = jax.ops.segment_min(
                    jnp.where(mask, kidx, K), seg,
                    num_segments=K + 1)[:K]
                got = wrow < K
                wsafe = jnp.minimum(wrow, K - 1)
                cand = jnp.where(got, cand[wsafe], cand)
                candj = jnp.where(got, candj[wsafe] + 1, candj)
            root_row = jnp.argmax((ld == 0) & (nodes >= 0))
            return cand[root_row]

        def round_step(carry, r):
            usage_l, remaining = carry
            alive = remaining & m_ok & entry_valid[c]
            zwb, keyv = drs_keys(usage_l)
            win = tournament(zwb, keyv, alive)
            win_safe = jnp.maximum(win, 0)
            cw = jnp.where(win >= 0, members[win_safe], -1)
            new_usage, _, fits = _commit_one_local(
                usage_l, cw, entry_fr, entry_req, entry_kind,
                entry_borrows, subtree_quota, lq, borrow_limit, nominal,
                ancestors, local_chain, depth=depth)
            remaining = remaining & ~(
                (jnp.arange(M) == win_safe) & (win >= 0))
            return (new_usage, remaining), (cw, fits)

        init = (local_usage, jnp.ones((M,), bool))
        (final_usage, _), (win_seq, fit_seq) = jax.lax.scan(
            round_step, init, jnp.arange(M))
        return final_usage, win_seq, fit_seq

    nodes_safe = jnp.maximum(root_nodes, 0)
    init_local = jnp.where((root_nodes >= 0)[:, :, None],
                           usage0[nodes_safe], 0)
    final_local, win_seq, fit_seq = jax.vmap(per_root)(
        root_members, member_ok, init_local, root_nodes,
        root_parent_local, local_depth)

    C = entry_valid.shape[0]
    flat_win = win_seq.reshape(-1)
    flat_fit = fit_seq.reshape(-1)
    rounds = jnp.broadcast_to(jnp.arange(M)[None, :], (Rn, M)).reshape(-1)
    target = jnp.where(flat_win >= 0, flat_win, C)
    admitted = jnp.zeros((C,), bool).at[target].max(flat_fit, mode="drop")
    entry_round = jnp.full((C,), -1, jnp.int32).at[
        jnp.where(flat_fit, target, C)].max(
        rounds.astype(jnp.int32), mode="drop")

    flat_nodes = root_nodes.reshape(-1)
    flat_usage = final_local.reshape(-1, R)
    usage_final = usage0.at[
        jnp.where(flat_nodes >= 0, flat_nodes, N)].set(
        flat_usage, mode="drop")
    return admitted, entry_round, usage_final


def make_commit_order_key(has_qr, borrows, priority, ts_rank):
    """Classical iterator sort key (scheduler.go:971): quota-reserved
    first, fewer borrows, higher priority, FIFO. Composite int64 for a
    single argsort."""
    hq = jnp.where(has_qr, 0, 1).astype(jnp.int64)
    b = jnp.clip(borrows, 0, 31).astype(jnp.int64)
    # Invert priority into a non-negative ascending component.
    p_inv = (jnp.int64(1 << 31) - 1 - priority.astype(jnp.int64))
    r = ts_rank.astype(jnp.int64)
    return (hq << 62) | (b << 56) | (p_inv << 24) | jnp.clip(r, 0,
                                                             (1 << 24) - 1)
