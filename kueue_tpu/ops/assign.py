"""Batched flavor assignment: the vmapped nomination kernel.

Replaces the reference's per-workload flavor loop
(flavorassigner.go:932 findFlavorForPodSets, :1198 fitsResourceQuota) with
one vectorized pass over ALL pending workloads at once: for each workload,
scan its ClusterQueue's flavor order per resource group, classify each
flavor as Fit / NoCandidates / NoFit with a borrowing level, and fold with
the FlavorFungibility preference lattice (flavorassigner.go:483
isPreferred, :1127 shouldTryNextFlavor).

Scope: multi-podset workloads are first-class — requests are
``int64[W, P, S]`` and the flavor scan accumulates assumed usage across
a workload's pod sets exactly like the sequential walk
(flavorassigner.go:1015,1213; see ``wl_req`` below). Taint, selector
and affinity filtering is the caller's per-workload mask (``flavor_ok``,
evaluated on the host at row encode), in the nomination kernel and the
sim-grid alike. Not here: the preemption candidate SEARCH — workloads
whose CQ has a non-Never preemption policy and that need preemption are
flagged ``needs_oracle`` for the device preemptor (ops/preempt.py) or
the sequential fallback.
For CQs with all-Never policies the kernel computes the exact
NoCandidates outcome the sequential path produces
(preemption_oracle.go:58).

Mode encoding matches scheduler/flavorassigner.PMode:
  0=NO_FIT, 1=NO_CANDIDATES, 2/3=preempt/reclaim (host only), 4=FIT.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from kueue_tpu.api.types import INF
from kueue_tpu.ops.quota import borrow_height, sat_add

P_NO_FIT = 0
P_NO_CANDIDATES = 1
P_FIT = 4
# Representative-mode key: big multiplier so pmode dominates borrow.
_BIG = 1 << 20


def _mode_key(pmode, borrow, pref_preempt_first):
    """Total order matching isPreferred: larger key = more preferred.
    Default (BorrowingOverPreemption): pmode major, -borrow minor.
    PreemptionOverBorrowing: -borrow major, pmode minor.
    NO_FIT is always least preferred (pmode 0 dominates either way because
    borrow <= depth << _BIG)."""
    pmode = pmode.astype(jnp.int64)
    borrow = borrow.astype(jnp.int64)
    default_key = pmode * _BIG - borrow
    pref_key = -borrow * _BIG + pmode
    # Keep NO_FIT at the absolute bottom under either preference.
    pref_key = jnp.where(pmode == P_NO_FIT, -_BIG * _BIG, pref_key)
    default_key = jnp.where(pmode == P_NO_FIT, -_BIG * _BIG, default_key)
    return jnp.where(pref_preempt_first, pref_key, default_key)


def _classify_flavor(c, req, fl, avail, potential, nominal, derived,
                     ancestors, height, no_preemption, can_pwb, *, depth,
                     acc=None):
    """fitsResourceQuota before the oracle consult
    (flavorassigner.go:1198): classify one flavor for every resource of
    one workload. Shared by the nomination kernel and the sim-grid so
    the two folds can never diverge. ``acc`` (int64[R], optional) is the
    within-workload usage already assigned to earlier pod sets — the
    reference's assumedUsage: every check runs against
    val = acc[fr] + req (flavorassigner.go:1213). Returns (pmode[S],
    borrow[S], oracle[S] — gate open and the CQ can actually
    preempt)."""
    S = req.shape[0]
    fl_safe = jnp.maximum(fl, 0)
    fr = fl_safe * S + jnp.arange(S)
    if acc is not None:
        req = req + jnp.where(req > 0, acc[fr], 0)
    a = avail[c, fr]
    p = potential[c, fr]
    nom = nominal[c, fr]
    no_fit = req > p
    fit = req <= a
    bh, may_reclaim = borrow_height(
        jnp.full((S,), c, jnp.int32), fr, req, derived, ancestors,
        height, nominal, depth=depth)
    preempt_gate = (nom >= req) | may_reclaim | can_pwb[c]
    pmode = jnp.where(
        no_fit, P_NO_FIT,
        jnp.where(fit, P_FIT,
                  jnp.where(preempt_gate, P_NO_CANDIDATES, P_NO_FIT)))
    oracle = (~no_fit) & (~fit) & preempt_gate & ~no_preemption[c]
    return pmode, bh, oracle


@partial(jax.jit, static_argnames=("depth", "num_resources"))
def flavor_grid(
    wl_cq,  # int32[C] head CQ per slot
    wl_req,  # int64[C, S]
    flavor_ok,  # bool[C, NF] the heads' flavor eligibility (assign_flavors'
    #   ``flavor_ok``, one row a slot)
    derived, nominal, ancestors, height, group_of_res, group_flavors,
    no_preemption, can_pwb,
    *,
    depth: int,
    num_resources: int,
):
    """Per-(slot, group, flavor, resource) granular classification — the
    pre-oracle part of fitsResourceQuota (flavorassigner.go:1198) exposed
    for the sim-augmented nomination: cells flagged ``sim`` need a
    preemption simulation (preemption_oracle.go:41) before the
    fungibility lattice can pick the flavor; the bridge runs those sims
    with the sim program (ops/preempt.sim_targets) and folds the lattice
    as array code (engine_bridge._fold_fungibility). A flavor the
    slot's mask excludes is not in its walk (checkFlavorForPodSets
    skips it: neither tried nor simulated): ``in_walk`` is False there
    and none of its cells is flagged ``sim``.

    Returns (pmode int32[C, G, F, S] in {NO_FIT, NO_CANDIDATES, FIT},
    borrow int32[C, G, F, S] pre-sim, sim bool[C, G, F, S],
    in_group bool[C, G, S], in_walk bool[C, G, F])."""
    S = num_resources
    avail = jnp.maximum(0, derived["available"])
    potential = derived["potential"]
    G = group_flavors.shape[1]

    def per_slot(c, req, ok):
        g_of_res = group_of_res[c]
        active = req > 0

        def eval_fl(fl):
            pmode, bh, oracle = _classify_flavor(
                c, req, fl, avail, potential, nominal, derived, ancestors,
                height, no_preemption, can_pwb, depth=depth)
            walked = (fl >= 0) & ok[jnp.maximum(fl, 0)]
            return pmode, bh, oracle & active & walked, walked

        pmode, borrow, sim, in_walk = jax.vmap(jax.vmap(eval_fl))(
            group_flavors[c])
        in_group = (g_of_res[None, :] == jnp.arange(G)[:, None]) \
            & active[None, :]  # [G, S]
        return pmode, borrow, sim & in_group[:, None, :], in_group, in_walk

    return jax.vmap(per_slot)(wl_cq, wl_req, flavor_ok)


@partial(jax.jit, static_argnames=("depth", "num_resources"))
def assign_flavors(
    wl_cq,  # int32[W]
    wl_req,  # int64[W, P, S] per-podset count-scaled requests
    derived,  # dict from quota.derive_world (usage-current)
    nominal,  # int64[N, R]
    ancestors,  # int32[N, D]
    height,  # int32[N]
    group_of_res,  # int32[C, S]
    group_flavors,  # int32[C, G, F]
    no_preemption,  # bool[C]
    can_pwb,  # bool[C]
    fung_borrow_try_next,  # bool[C]
    fung_pref_preempt_first,  # bool[C]
    flavor_ok=None,  # bool[W, NF] per-workload flavor eligibility
    #   (taints/selectors/affinity vs the flavor's nodeLabels —
    #   flavorassigner.flavor_matches_podset evaluated on host at row
    #   encode; None = all flavors eligible). A masked flavor is
    #   skipped exactly like the reference's checkFlavorForPodSets
    #   taint/affinity rejection: try the next flavor in order.
    *,
    depth: int,
    num_resources: int,
):
    """Returns per-workload:
      flavor_of_res: int32[W, P, S] chosen flavor id per (podset,
          resource) (-1 none)
      pmode: int32[W] representative preemption-mode (worst over podsets)
      borrows: int32[W] assignment borrowing level (max over podsets)
      needs_oracle: bool[W]
      usage_fr: int32[W, P, S] flavor-resource index (-1 none)

    Pod sets are scanned in order with within-workload usage
    accumulation — the reference walks podsets sequentially
    (flavorassigner.go:707 grouped loop) and every later podset's
    fitsResourceQuota sees the earlier podsets' assigned usage as
    assumedUsage (:1015, :1213). Zero-request (padding) podsets
    classify as all-fitting and choose no flavors.
    """
    S = num_resources
    R = nominal.shape[1]
    avail = jnp.maximum(0, derived["available"])  # CQ available clipped
    potential = derived["potential"]

    G, F = group_flavors.shape[1], group_flavors.shape[2]

    def per_workload(c, req_ps, ok):
        g_of_res = group_of_res[c]  # [S]

        def podset_step(acc, req):
            active = req > 0  # [S]

            def eval_flavor(fl):
                """Classify flavor fl for every resource: (pmode[S],
                borrow[S], needs_oracle[S])."""
                return _classify_flavor(
                    c, req, fl, avail, potential, nominal, derived,
                    ancestors, height, no_preemption, can_pwb,
                    depth=depth, acc=acc)

            def eval_group(g):
                in_group = (g_of_res == g) & active  # [S]
                flavors = group_flavors[c, g]  # [F]

                def scan_step(carry, fl):
                    (best_key, best_fl, best_pmode_s, best_borrow_s,
                     best_oracle, stopped) = carry
                    valid = fl >= 0
                    if ok is not None:
                        valid = valid & ok[jnp.maximum(fl, 0)]
                    pmode_s, borrow_s, oracle_s = eval_flavor(
                        jnp.maximum(fl, 0))
                    # Mask resources outside the group as
                    # perfectly-fitting.
                    pmode_s = jnp.where(in_group, pmode_s, P_FIT)
                    borrow_s = jnp.where(in_group, borrow_s, 0)
                    oracle_s = jnp.where(in_group, oracle_s, False)
                    # Representative = worst (min key) over group
                    # resources.
                    keys = _mode_key(pmode_s, borrow_s,
                                     fung_pref_preempt_first[c])
                    rep_key = jnp.min(jnp.where(in_group, keys,
                                                keys.max()))
                    rep_pmode = pmode_s[jnp.argmin(
                        jnp.where(in_group, keys, keys.max()))]
                    rep_borrow = jnp.max(jnp.where(in_group, borrow_s, 0))
                    # shouldTryNextFlavor (kernel modes only).
                    try_next = (rep_pmode <= P_NO_CANDIDATES) | (
                        (rep_borrow > 0) & fung_borrow_try_next[c])
                    consider = valid & ~stopped
                    better = consider & (rep_key > best_key)
                    stop_here = consider & ~try_next
                    new = (
                        jnp.where(better | stop_here, rep_key, best_key),
                        jnp.where(better | stop_here, fl, best_fl),
                        jnp.where(better | stop_here, pmode_s,
                                  best_pmode_s),
                        jnp.where(better | stop_here, borrow_s,
                                  best_borrow_s),
                        jnp.where(better | stop_here, jnp.any(oracle_s),
                                  best_oracle),
                        stopped | stop_here,
                    )
                    return new, None

                init = (
                    jnp.asarray(-(_BIG * _BIG) - 1),
                    jnp.asarray(-1, jnp.int32),
                    jnp.full((S,), P_NO_FIT, jnp.int32),
                    jnp.zeros((S,), jnp.int32),
                    jnp.asarray(False),
                    jnp.asarray(False),
                )
                (key, fl, pmode_s, borrow_s, oracle, _), _ = jax.lax.scan(
                    scan_step, init, flavors)
                group_active = jnp.any(in_group)
                # representative pmode of the chosen flavor over group
                # resources
                keys = _mode_key(pmode_s, borrow_s,
                                 fung_pref_preempt_first[c])
                rep_pmode = jnp.where(
                    group_active,
                    pmode_s[jnp.argmin(jnp.where(in_group, keys,
                                                 keys.max()))],
                    P_FIT)
                rep_pmode = jnp.where(
                    fl < 0, jnp.where(group_active, P_NO_FIT, P_FIT),
                    rep_pmode)
                group_borrow = jnp.where(
                    group_active & (fl >= 0),
                    jnp.max(jnp.where(in_group, borrow_s, 0)), 0)
                return fl, rep_pmode, group_borrow, oracle & group_active

            g_ids = jnp.arange(G)
            g_fl, g_pmode, g_borrow, g_oracle = jax.vmap(eval_group)(g_ids)

            # Podset-level aggregation.
            pmode = jnp.min(g_pmode)
            borrows = jnp.max(g_borrow)
            needs_oracle = jnp.any(g_oracle)
            # Resources not covered by any group with a positive request
            # make the whole assignment NoFit (flavorassigner.go:939-941).
            uncovered = jnp.any(active & (g_of_res < 0))
            pmode = jnp.where(uncovered, P_NO_FIT, pmode)
            flavor_of_res = jnp.where(
                active & (g_of_res >= 0),
                g_fl[jnp.maximum(g_of_res, 0)], -1)
            flavor_of_res = jnp.where(pmode == P_NO_FIT, -1,
                                      flavor_of_res)
            usage_fr = jnp.where(flavor_of_res >= 0,
                                 flavor_of_res * S + jnp.arange(S), -1)
            # Accumulate this podset's assigned usage for the next one
            # (assignment.append, flavorassigner.go:765).
            acc = acc.at[jnp.where(usage_fr >= 0, usage_fr, R)].add(
                jnp.where(usage_fr >= 0, req, 0), mode="drop")
            return acc, (flavor_of_res, pmode, borrows, needs_oracle,
                         usage_fr)

        acc0 = jnp.zeros((R,), wl_req.dtype)
        _, (flavor_ps, pmode_ps, borrow_ps, oracle_ps, usage_fr_ps) = \
            jax.lax.scan(podset_step, acc0, req_ps)
        return (flavor_ps, jnp.min(pmode_ps), jnp.max(borrow_ps),
                jnp.any(oracle_ps), usage_fr_ps)

    if flavor_ok is None:
        return jax.vmap(lambda c, r: per_workload(c, r, None))(
            wl_cq, wl_req)
    return jax.vmap(per_workload)(wl_cq, wl_req, flavor_ok)
