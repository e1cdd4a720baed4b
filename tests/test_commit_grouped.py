"""commit_grouped must reproduce commit_scan exactly: the root-grouped
parallel commit is only a reformulation (admissions never interact across
root subtrees), so admitted sets and final usage must be bit-identical on
random worlds."""

import jax.numpy as jnp
import numpy as np
import pytest

from kueue_tpu.ops import commit as cops


def random_world(rng, n_roots, cqs_per_root, depth_extra, R, spread=False):
    """Build parent/ancestors plus grouping arrays for a random forest:
    each root cohort heads a chain of `depth_extra` interior cohorts. The
    ClusterQueues hang from the chain's last cohort, or with `spread`
    from any cohort of it, so that chains of every length occur."""
    C = n_roots * cqs_per_root
    nodes = []  # cohort ids come after CQs
    parent = []
    for _ in range(C):
        parent.append(-1)
    cohort_base = C
    n_cohorts = n_roots * (1 + depth_extra)
    parent += [-1] * n_cohorts
    # Wire: root r cohort = cohort_base + r; interior (if any) chains up.
    for r in range(n_roots):
        chain = [cohort_base + r]
        for d in range(depth_extra):
            inner = cohort_base + n_roots + r * depth_extra + d
            parent[inner] = chain[-1]
            chain.append(inner)
        for i in range(cqs_per_root):
            cq = r * cqs_per_root + i
            under = chain[rng.integers(len(chain))] if spread else chain[-1]
            parent[cq] = under if rng.random() < 0.9 else -1
    N = C + n_cohorts
    parent = np.asarray(parent, np.int32)
    D = depth_extra + 2
    ancestors = np.full((N, D), -1, np.int32)
    for i in range(N):
        a, d = parent[i], 0
        while a >= 0 and d < D:
            ancestors[i, d] = a
            a = parent[a]
            d += 1
    from kueue_tpu.tensor.schema import build_root_grouping
    (_, root_members, root_nodes, local_chain, root_parent_local,
     root_of_cq, _local_depth) = build_root_grouping(parent, ancestors,
                                                     C, D)

    from kueue_tpu.api.types import INF
    nominal = rng.integers(0, 50, (N, R)).astype(np.int64)
    borrow_limit = np.where(rng.random((N, R)) < 0.5, INF,
                            rng.integers(0, 30, (N, R))).astype(np.int64)
    lend_limit = np.where(rng.random((N, R)) < 0.5, INF,
                          rng.integers(0, 30, (N, R))).astype(np.int64)
    usage0 = rng.integers(0, 20, (N, R)).astype(np.int64)
    return dict(C=C, N=N, D=D, parent=parent, ancestors=ancestors,
                root_members=root_members, root_nodes=root_nodes,
                local_chain=local_chain, root_of_cq=root_of_cq,
                root_parent_local=root_parent_local, nominal=nominal,
                borrow_limit=borrow_limit, lend_limit=lend_limit,
                usage0=usage0)


def _subtree_quota(w):
    from kueue_tpu.ops.quota import compute_level, compute_subtree_quota
    level = compute_level(jnp.asarray(w["parent"]), w["D"])
    sq = compute_subtree_quota(jnp.asarray(w["nominal"]),
                               jnp.asarray(w["lend_limit"]),
                               jnp.asarray(w["parent"]), level, depth=w["D"])
    return level, sq


@pytest.mark.parametrize("seed", range(6))
def test_grouped_matches_scan(seed):
    rng = np.random.default_rng(seed)
    R = int(rng.integers(1, 4))
    S = R  # one flavor; fr index == resource index
    w = random_world(rng, n_roots=int(rng.integers(2, 5)),
                     cqs_per_root=int(rng.integers(1, 5)),
                     depth_extra=int(rng.integers(0, 2)), R=R)
    C, D = w["C"], w["D"]

    _, sq = _subtree_quota(w)

    entry_fr = np.tile(np.arange(S, dtype=np.int32), (C, 1))
    entry_fr[rng.random((C, S)) < 0.2] = -1
    entry_req = rng.integers(0, 40, (C, S)).astype(np.int64)
    entry_kind = rng.choice(
        [cops.ENTRY_SKIP, cops.ENTRY_FIT, cops.ENTRY_RESERVE,
         cops.ENTRY_PREEMPT], C).astype(np.int32)
    entry_borrows = rng.integers(0, 3, C).astype(np.int32)
    entry_key = rng.permutation(C).astype(np.int64)
    entry_valid = np.ones(C, bool)

    order = np.argsort(entry_key).astype(np.int32)
    adm_scan, usage_scan = cops.commit_scan(
        jnp.asarray(order), jnp.arange(C, dtype=jnp.int32),
        jnp.asarray(entry_fr), jnp.asarray(entry_req),
        jnp.asarray(entry_kind), jnp.asarray(entry_borrows),
        jnp.asarray(w["usage0"]), sq, jnp.asarray(w["lend_limit"]),
        jnp.asarray(w["borrow_limit"]), jnp.asarray(w["nominal"]),
        jnp.asarray(w["ancestors"]), depth=D)
    # Scatter scan verdicts (aligned with `order`) back to slots.
    slot_adm_scan = np.zeros(C, bool)
    slot_adm_scan[order] = np.asarray(adm_scan)

    adm_grp, usage_grp = cops.commit_grouped(
        jnp.asarray(entry_key), jnp.asarray(entry_valid),
        jnp.asarray(entry_fr), jnp.asarray(entry_req),
        jnp.asarray(entry_kind), jnp.asarray(entry_borrows),
        jnp.asarray(w["usage0"]), sq, jnp.asarray(w["lend_limit"]),
        jnp.asarray(w["borrow_limit"]), jnp.asarray(w["nominal"]),
        jnp.asarray(w["ancestors"]), jnp.asarray(w["root_members"]),
        jnp.asarray(w["root_nodes"]), jnp.asarray(w["local_chain"]),
        depth=D)

    np.testing.assert_array_equal(slot_adm_scan, np.asarray(adm_grp))
    np.testing.assert_array_equal(np.asarray(usage_scan),
                                  np.asarray(usage_grp))


def test_invalid_slots_never_commit():
    """entry_valid=False must force SKIP even when the caller leaves a
    committing kind on the slot."""
    rng = np.random.default_rng(42)
    w = random_world(rng, n_roots=2, cqs_per_root=2, depth_extra=0, R=1)
    C, D = w["C"], w["D"]
    _, sq = _subtree_quota(w)
    entry_fr = np.zeros((C, 1), np.int32)
    entry_req = np.ones((C, 1), np.int64)
    entry_kind = np.full(C, cops.ENTRY_RESERVE, np.int32)
    entry_valid = np.zeros(C, bool)  # nothing participates
    adm, usage = cops.commit_grouped(
        jnp.asarray(np.arange(C, dtype=np.int64)), jnp.asarray(entry_valid),
        jnp.asarray(entry_fr), jnp.asarray(entry_req),
        jnp.asarray(entry_kind), jnp.zeros(C, jnp.int32),
        jnp.asarray(w["usage0"]), sq, jnp.asarray(w["lend_limit"]),
        jnp.asarray(w["borrow_limit"]), jnp.asarray(w["nominal"]),
        jnp.asarray(w["ancestors"]), jnp.asarray(w["root_members"]),
        jnp.asarray(w["root_nodes"]), jnp.asarray(w["local_chain"]),
        depth=D)
    assert not np.asarray(adm).any()
    np.testing.assert_array_equal(np.asarray(usage), w["usage0"])


@pytest.mark.parametrize("height", [1, 2, 3, 4])
@pytest.mark.parametrize("seed", range(3))
def test_usage_after_a_cycle_is_the_replay_of_its_admissions(seed, height):
    """The cycle's post-commit usage (oracle/batched._cycle_core,
    usage_clean) is one bottom-up aggregation over the CQ rows with each
    admitted slot's request added. The oracle is the replay it replaced:
    one admission at a time, each bubbling what its node's local quota
    does not hold to the parent (resource_node.go:144), in an order drawn
    here, since none is owed."""
    from kueue_tpu.ops.quota import compute_node_usage, local_quota

    rng = np.random.default_rng(1000 * height + seed)
    R = int(rng.integers(1, 4))
    w = random_world(rng, n_roots=int(rng.integers(1, 4)),
                     cqs_per_root=int(rng.integers(2, 7)),
                     depth_extra=height - 1, R=R, spread=True)
    C, N, D, parent = w["C"], w["N"], w["D"], w["parent"]
    level, sq = _subtree_quota(w)
    lend = jnp.asarray(w["lend_limit"])
    cq_usage = np.where((np.arange(N) < C)[:, None], w["usage0"], 0)
    usage0 = np.asarray(compute_node_usage(
        jnp.asarray(cq_usage), sq, lend, jnp.asarray(parent), level,
        depth=D))
    admitted = rng.random(C) < 0.6
    req = rng.integers(0, 40, (C, R)).astype(np.int64)
    req[rng.random((C, R)) < 0.2] = 0

    lq = np.asarray(local_quota(sq, lend))
    want = usage0.copy()
    for c in rng.permutation(C):
        for r in range(R):
            node, v = int(c), int(req[c, r]) * bool(admitted[c])
            while node >= 0 and v > 0:
                local_avail = max(0, int(lq[node, r]) - int(want[node, r]))
                want[node, r] += v
                v = max(0, v - local_avail)
                node = int(parent[node])

    cq_after = cq_usage.copy()
    cq_after[:C] += np.where(admitted[:, None], req, 0)
    got = compute_node_usage(jnp.asarray(cq_after), sq, lend,
                             jnp.asarray(parent), level, depth=D)
    np.testing.assert_array_equal(np.asarray(got), want)


def _remove_victims_over_all_rows(usage_l, lq_l, parent_local, rows, vals,
                                  *, depth):
    """_apply_victims as it stood before PR 33: the victims' usage
    scattered over all K rows of the root, and a K-row scatter-add to the
    parents a level."""
    K = usage_l.shape[0]
    rem = jnp.zeros_like(usage_l).at[
        jnp.where(rows >= 0, rows, K)].add(vals, mode="drop")
    p_safe = jnp.where(parent_local >= 0, parent_local, K)
    for _ in range(depth + 1):
        prop = jnp.minimum(rem, jnp.maximum(0, usage_l - lq_l))
        prop = jnp.maximum(prop, 0)
        usage_l = usage_l - rem
        rem = jnp.zeros_like(rem).at[p_safe].add(
            jnp.where((parent_local >= 0)[:, None], prop, 0), mode="drop")
    return usage_l


@pytest.mark.parametrize("height", [1, 2, 3, 4])
@pytest.mark.parametrize("seed", range(3))
def test_removal_along_the_victims_chains_is_the_removal_over_all_rows(
        seed, height):
    """Victims that share a ClusterQueue, victims whose chains meet at a
    cohort from different depths, empty columns: the V rows a level give
    what the K-row scatters gave, on every row."""
    from kueue_tpu.ops.quota import compute_node_usage, local_quota

    rng = np.random.default_rng(2000 * height + seed)
    R = int(rng.integers(1, 3))
    w = random_world(rng, n_roots=1, cqs_per_root=int(rng.integers(3, 9)),
                     depth_extra=height - 1, R=R, spread=True)
    C, N, D, parent = w["C"], w["N"], w["D"], w["parent"]
    level, sq = _subtree_quota(w)
    lend = jnp.asarray(w["lend_limit"])
    # Usage that holds the victims': each row of `vals` runs in its CQ.
    V = 8
    cq_of = rng.integers(0, C, V)
    vals = rng.integers(0, 9, (V, R)).astype(np.int64)
    cq_usage = np.zeros((N, R), np.int64)
    np.add.at(cq_usage, cq_of, vals)
    cq_usage[:C] += rng.integers(0, 6, (C, R))
    usage = compute_node_usage(jnp.asarray(cq_usage), sq, lend,
                               jnp.asarray(parent), level, depth=D)
    # Every node is its own local row here: one root's node set in node
    # order (a CQ with no parent is a root of its own, and is left out of
    # the victims).
    in_tree = parent[cq_of] >= 0
    rows = np.where(in_tree & (rng.random(V) < 0.8), cq_of, -1)
    rows = rows.astype(np.int32)
    args = (usage, local_quota(sq, lend), jnp.asarray(parent),
            jnp.asarray(rows), jnp.asarray(vals))
    np.testing.assert_array_equal(
        np.asarray(cops._apply_victims(*args, depth=D)),
        np.asarray(_remove_victims_over_all_rows(*args, depth=D)))


def _commit_one_local_removing_over_all_rows(
        usage_l, c, entry_fr, entry_req, entry_kind, entry_borrows,
        subtree_quota, lq, borrow_limit, nominal, ancestors, local_chain,
        victims, claimed, *, depth):
    """ops/commit._commit_one_local's victims path as it stood before
    PR 33, with PR 34's repair: an entry whose targets overlap an
    earlier entry's is skipped outright and adds no usage."""
    ok = c >= 0
    c_safe = jnp.maximum(c, 0)
    frs = entry_fr[c_safe]
    req = jnp.where(ok, entry_req[c_safe], 0)
    frs_safe = jnp.maximum(frs, 0)
    chain = jnp.concatenate(
        [jnp.asarray([c_safe], jnp.int32), ancestors[c_safe]])
    chain_ok = (chain >= 0) & ok
    chain_safe = jnp.maximum(chain, 0)
    loc_safe = jnp.maximum(local_chain[c_safe], 0)
    g_sq = subtree_quota[chain_safe[:, None], frs_safe[None, :]]
    g_lq = lq[chain_safe[:, None], frs_safe[None, :]]
    g_bl = borrow_limit[chain_safe[:, None], frs_safe[None, :]]
    kind = jnp.where(ok, entry_kind[c_safe], cops.ENTRY_SKIP)
    is_pre = ok & (kind == cops.ENTRY_PREEMPT)

    v_row, v_vals, v_ids, lq_l, parent_local = victims
    rows = jnp.where(is_pre, v_row[c_safe], -1)
    trial = _remove_victims_over_all_rows(
        usage_l, lq_l, parent_local, rows, v_vals[c_safe], depth=depth)
    ids = v_ids[c_safe]
    overlap = is_pre & jnp.any(
        (ids >= 0) & claimed[jnp.clip(ids, 0, claimed.shape[0] - 1)])
    kind = jnp.where(overlap, cops.ENTRY_SKIP, kind)

    g_usage = trial[loc_safe[:, None], frs_safe[None, :]]
    fits, adds = cops._entry_verdict(
        g_sq, g_lq, g_bl, g_usage, chain_ok, frs, req, kind,
        entry_borrows[c_safe], nominal[c_safe, frs_safe],
        borrow_limit[c_safe, frs_safe], g_usage[0], depth=depth)
    new_usage = jnp.where(fits & is_pre, trial, usage_l)
    for d in range(depth + 1):
        new_usage = new_usage.at[loc_safe[d], frs_safe].add(adds[d])
    new_claimed = claimed.at[
        jnp.where(fits & is_pre & (ids >= 0), ids,
                  claimed.shape[0])].set(True, mode="drop")
    return new_usage, new_claimed, fits & ok


def _commit_every_step_through_the_victims(
        entry_key, entry_valid, entry_fr, entry_req, entry_kind,
        entry_borrows, usage0, subtree_quota, lend_limit, borrow_limit,
        nominal, ancestors, root_members, root_nodes, local_chain,
        root_parent_local, slot_victim_row, slot_victim_vals,
        slot_victim_ids, claimed0, *, depth):
    """commit_grouped's loop as it stood before PR 33: a scan over a
    root's entries inside a vmap over the roots, every step removing its
    (mostly empty) victim set over all K rows."""
    import jax
    from kueue_tpu.ops.quota import local_quota

    N, R = usage0.shape
    C = entry_key.shape[0]
    lq = local_quota(subtree_quota, lend_limit)
    entry_kind = jnp.where(entry_valid, entry_kind, cops.ENTRY_SKIP)
    members_safe = jnp.maximum(root_members, 0)
    mkey = jnp.where((root_members >= 0) & entry_valid[members_safe],
                     entry_key[members_safe], jnp.int64(1 << 62))
    sorted_members = jnp.take_along_axis(
        root_members, jnp.argsort(mkey, axis=1), axis=1)
    nodes_safe = jnp.maximum(root_nodes, 0)
    node_ok = (root_nodes >= 0)[:, :, None]
    init_local = jnp.where(node_ok, usage0[nodes_safe], 0)
    lq_locals = jnp.where(node_ok, lq[nodes_safe], 0)

    def per_root(members, local_usage, lq_l, parent_local):
        def step(carry, c):
            usage_l, claimed, fits = _commit_one_local_removing_over_all_rows(
                carry[0], c, entry_fr, entry_req, entry_kind, entry_borrows,
                subtree_quota, lq, borrow_limit, nominal, ancestors,
                local_chain, (slot_victim_row, slot_victim_vals,
                              slot_victim_ids, lq_l, parent_local),
                carry[1], depth=depth)
            return (usage_l, claimed), fits

        (usage_f, _), fits_seq = jax.lax.scan(
            step, (local_usage, claimed0), members)
        return usage_f, fits_seq

    final_local, admitted_seq = jax.vmap(per_root)(
        sorted_members, init_local, lq_locals, root_parent_local)
    flat_members = sorted_members.reshape(-1)
    admitted = jnp.zeros((C,), bool).at[
        jnp.where(flat_members >= 0, flat_members, C)].max(
        admitted_seq.reshape(-1), mode="drop")
    flat_nodes = root_nodes.reshape(-1)
    usage_final = usage0.at[
        jnp.where(flat_nodes >= 0, flat_nodes, N)].set(
        final_local.reshape(-1, R), mode="drop")
    return admitted, usage_final


V_CAP = 3


def full_cohorts(n_roots, cqs_per_root):
    """Cohorts one level high and full: every ClusterQueue lends all of
    its nominal 10 and runs two workloads of 5, so nothing fits without
    an eviction, and an entry of 5 fits with one victim removed."""
    from kueue_tpu.api.types import INF
    from kueue_tpu.tensor.schema import build_root_grouping

    C, D, R = n_roots * cqs_per_root, 2, 1
    N = C + n_roots
    parent = np.full(N, -1, np.int32)
    parent[:C] = C + np.arange(C) // cqs_per_root
    ancestors = np.full((N, D), -1, np.int32)
    ancestors[:C, 0] = parent[:C]
    (_, root_members, root_nodes, local_chain, root_parent_local,
     root_of_cq, _) = build_root_grouping(parent, ancestors, C, D)
    nominal = np.zeros((N, R), np.int64)
    nominal[:C] = 10
    adm_cq = np.repeat(np.arange(C, dtype=np.int32), 2)  # two a queue
    adm_usage = np.full((2 * C, R), 5, np.int64)
    usage0 = np.zeros((N, R), np.int64)
    usage0[:C] = 10
    usage0[C:] = 10 * cqs_per_root
    return dict(C=C, N=N, D=D, parent=parent, ancestors=ancestors,
                root_members=root_members, root_nodes=root_nodes,
                local_chain=local_chain, root_of_cq=root_of_cq,
                root_parent_local=root_parent_local, nominal=nominal,
                borrow_limit=np.full((N, R), INF, np.int64),
                lend_limit=np.full((N, R), INF, np.int64), usage0=usage0,
                adm_cq=adm_cq, adm_usage=adm_usage)


def preempting(w, entries):
    """Entry tensors for a world with a running set (adm_cq, adm_usage):
    `entries` maps a slot to (kind, request, key, victim ids)."""
    C, R = w["C"], w["usage0"].shape[1]
    e = dict(entry_key=np.arange(C, dtype=np.int64) + 1000,
             entry_valid=np.ones(C, bool),
             entry_fr=np.tile(np.arange(R, dtype=np.int32), (C, 1)),
             entry_req=np.zeros((C, R), np.int64),
             entry_kind=np.full(C, cops.ENTRY_SKIP, np.int32),
             entry_borrows=np.zeros(C, np.int32),
             slot_victim_row=np.full((C, V_CAP), -1, np.int32),
             slot_victim_vals=np.zeros((C, V_CAP, R), np.int64),
             slot_victim_ids=np.full((C, V_CAP), -1, np.int32),
             claimed0=np.zeros(len(w["adm_cq"]), bool))
    for c, (kind, req, key, victims) in entries.items():
        e["entry_kind"][c], e["entry_req"][c], e["entry_key"][c] = (
            kind, req, key)
        for j, v in enumerate(victims):
            e["slot_victim_row"][c, j] = w["local_chain"][w["adm_cq"][v], 0]
            e["slot_victim_vals"][c, j] = w["adm_usage"][v]
            e["slot_victim_ids"][c, j] = v
    return e


def both_commits(w, e):
    """(admitted, usage) of commit_grouped and of the loop it replaced."""
    _, sq = _subtree_quota(w)
    args = [jnp.asarray(a) for a in (
        e["entry_key"], e["entry_valid"], e["entry_fr"], e["entry_req"],
        e["entry_kind"], e["entry_borrows"], w["usage0"], sq,
        w["lend_limit"], w["borrow_limit"], w["nominal"], w["ancestors"],
        w["root_members"], w["root_nodes"], w["local_chain"],
        w["root_parent_local"], e["slot_victim_row"], e["slot_victim_vals"],
        e["slot_victim_ids"], e["claimed0"])]
    got = cops.commit_grouped(*args, depth=w["D"])
    want = _commit_every_step_through_the_victims(*args, depth=w["D"])
    return [np.asarray(x) for x in got], [np.asarray(x) for x in want]


PRE, FIT = cops.ENTRY_PREEMPT, cops.ENTRY_FIT
# Slots 0-2, 3-5, 6-8 are the three cohorts' queues; workloads 2c, 2c+1
# run in queue c. Each case: slot -> (kind, request, key, victims), and
# the slots that commit.
VICTIM_CASES = {
    "no_root_preempts": (
        {0: (FIT, 5, 1, []), 4: (cops.ENTRY_RESERVE, 5, 1, [])}, []),
    "one_root_preempts": (
        {0: (FIT, 5, 1, []), 4: (PRE, 5, 1, [8]), 5: (FIT, 5, 2, [])}, [4]),
    "several_roots_preempt_at_one_position": (
        {0: (PRE, 5, 1, [0]), 3: (PRE, 10, 1, [6, 9]), 6: (PRE, 5, 1, [14]),
         7: (FIT, 5, 2, [])}, [0, 3, 6]),
    "roots_preempt_at_different_positions": (
        {0: (PRE, 5, 1, [2]), 1: (FIT, 5, 2, []), 3: (FIT, 5, 1, []),
         4: (PRE, 5, 2, [6]), 8: (PRE, 5, 3, [16, 17])}, [0, 4, 8]),
    "victims_already_claimed": (
        {0: (PRE, 5, 1, [2]), 1: (PRE, 5, 2, [2, 3]), 2: (PRE, 5, 3, [3])},
        [0, 2]),
    # Cycle 70 of the 8 x 6 world under reclaimWithinCohort Any (ISSUE
    # 34): the second preemptor of a full cohort fits only if the entry
    # skipped between the two, for its overlapping target, added nothing.
    "second_preemptor_of_a_root_after_a_skipped_overlap": (
        {0: (PRE, 5, 1, [2]), 1: (PRE, 5, 2, [2]), 2: (PRE, 5, 3, [4])},
        [0, 2]),
    "a_preemption_that_fails_its_fit_claims_nothing": (
        {0: (PRE, 11, 1, [2, 3]), 1: (PRE, 10, 2, [2, 3]),
         2: (FIT, 5, 3, [])}, [1]),
    "the_space_of_a_removal_stays_for_later_entries": (
        {3: (PRE, 5, 1, [6, 7]), 4: (FIT, 5, 2, []), 5: (FIT, 5, 3, [])},
        [3, 4]),
}


@pytest.mark.parametrize("case", sorted(VICTIM_CASES))
def test_victims_path_only_where_an_entry_preempts(case):
    entries, commits = VICTIM_CASES[case]
    w = full_cohorts(n_roots=3, cqs_per_root=3)
    (adm, usage), (adm_ref, usage_ref) = both_commits(
        w, preempting(w, entries))
    np.testing.assert_array_equal(adm, adm_ref)
    np.testing.assert_array_equal(usage, usage_ref)
    assert sorted(np.nonzero(adm)[0]) == commits


@pytest.mark.parametrize("seed", range(6))
def test_random_victims_match_the_loop_that_removed_at_every_step(seed):
    rng = np.random.default_rng(seed)
    R = int(rng.integers(1, 3))
    w = random_world(rng, n_roots=int(rng.integers(1, 4)),
                     cqs_per_root=int(rng.integers(2, 6)),
                     depth_extra=int(rng.integers(0, 3)), R=R, spread=True)
    C = w["C"]
    A = 3 * C
    w["adm_cq"] = rng.integers(0, C, A).astype(np.int32)
    w["adm_usage"] = rng.integers(0, 8, (A, R)).astype(np.int64)
    entries = {}
    for c in range(C):
        kind = rng.choice([cops.ENTRY_SKIP, FIT, cops.ENTRY_RESERVE, PRE, PRE])
        same_root = np.nonzero(
            w["root_of_cq"][w["adm_cq"]] == w["root_of_cq"][c])[0]
        n = min(len(same_root), int(rng.integers(0, V_CAP + 1)))
        victims = rng.choice(same_root, n, replace=False) \
            if kind == PRE else []
        entries[c] = (kind, rng.integers(0, 30, R), int(rng.integers(4)),
                      victims)
    e = preempting(w, entries)
    e["entry_valid"] = rng.random(C) < 0.9
    (adm, usage), (adm_ref, usage_ref) = both_commits(w, e)
    np.testing.assert_array_equal(adm, adm_ref)
    np.testing.assert_array_equal(usage, usage_ref)


def _eqns(jaxpr, into_cond=True):
    """The equations of a jaxpr and of the jaxprs nested in it."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, into_cond)


def _k_row_scatter_adds(jaxpr, K):
    """scatter-adds whose updates span a root's K node rows."""
    return sum(e.primitive.name == "scatter-add"
               and K in e.invars[2].aval.shape for e in _eqns(jaxpr))


@pytest.mark.parametrize("with_victims", [True, False])
def test_scan_body_branches_around_the_victim_removal(with_victims):
    """No step scatters a root's K rows: the removal runs along the
    victims' chains, and only in the branch a step takes when some
    root's entry preempts."""
    import jax

    w = full_cohorts(n_roots=2, cqs_per_root=6)  # K = 7: no other axis
    e = preempting(w, {})
    _, sq = _subtree_quota(w)
    args = [e["entry_key"], e["entry_valid"], e["entry_fr"], e["entry_req"],
            e["entry_kind"], e["entry_borrows"], w["usage0"], sq,
            w["lend_limit"], w["borrow_limit"], w["nominal"],
            w["ancestors"], w["root_members"], w["root_nodes"],
            w["local_chain"]]
    if with_victims:
        args += [w["root_parent_local"], e["slot_victim_row"],
                 e["slot_victim_vals"], e["slot_victim_ids"], e["claimed0"]]
    K = w["root_nodes"].shape[1]
    assert K == 7

    def find(jaxpr, name, **kw):
        return [e for e in _eqns(jaxpr, **kw) if e.primitive.name == name]

    closed = jax.make_jaxpr(
        lambda *a: cops.commit_grouped(*a, depth=w["D"]))(*args)
    (scan,) = find(closed.jaxpr, "scan")
    body = scan.params["jaxpr"].jaxpr
    conds = find(body, "cond")
    assert find(body, "scatter-add")
    if not with_victims:
        assert not conds and not _k_row_scatter_adds(body, K)
        return
    (cond,) = conds
    assert not find(body, "scatter-add", into_cond=False)
    plain, removing = (b.jaxpr for b in cond.params["branches"])
    assert not _k_row_scatter_adds(body, K)
    assert len(find(removing, "scatter-add")) \
        == len(find(plain, "scatter-add")) + w["D"] + 1
