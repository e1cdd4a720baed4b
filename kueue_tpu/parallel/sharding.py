"""Multi-chip sharding of the batched oracle over a jax.sharding.Mesh.

The scaling story (SURVEY.md §2.7/§5): the problem's big axis is Workloads
(50k+ pending), the small one is the node set (~1k CQs + cohorts). So:

  * workload-axis arrays ([W], [W, S]) are sharded over the mesh's "wl"
    axis — this is the framework's analog of data/sequence parallelism;
  * world/node arrays ([N, R], [C, ...]) are replicated (they're KBs);
  * heads selection (segment-min by CQ over all workloads) becomes a
    sharded reduction — XLA inserts the psum-style collectives over
    ICI when the workload axis spans chips;
  * nomination + commit operate on the [C]-sized head set, which is
    replicated — the commit scan is sequential by semantics and tiny.

Both the single cycle (sharded_cycle_step) and the WHOLE drain
(sharded_drain_loop — the jax.lax.while_loop over cycles runs entirely
on the mesh, no per-cycle host sync) are exposed. Decision parity of the
sharded programs against the single-device ones is enforced by
tests/test_multichip_parity.py.

On multi-host TPU (jax.distributed), the same jit works unchanged: the
mesh spans hosts and the workload shards ride ICI/DCN. No hand-written
collectives — the sharding annotations are the whole communication layer.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kueue_tpu.oracle.batched import cycle_step, drain_loop

WL_AXIS = "wl"


def make_mesh(devices=None, axis: str = WL_AXIS) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def _shardings(mesh: Mesh):
    return dict(
        wl=NamedSharding(mesh, P(WL_AXIS)),
        wl2=NamedSharding(mesh, P(WL_AXIS, None)),
        wl3=NamedSharding(mesh, P(WL_AXIS, None, None)),
        r=NamedSharding(mesh, P()),
        r2=NamedSharding(mesh, P(None, None)),
        r3=NamedSharding(mesh, P(None, None, None)),
    )


# (workload-sharded?, rank) of the common positional prefix:
# pending, inadmissible, usage, rank, commit_rank, wl_cq, wl_req,
# wl_priority, wl_has_qr, wl_hash, nominal, lend_limit, borrow_limit,
# parent, ancestors, height, group_of_res, group_flavors, no_preemption,
# can_pwb, can_always_reclaim, best_effort, fung_borrow_try_next,
# fung_pref_preempt_first, root_members, root_nodes, local_chain
_PREFIX = ("wl", "wl", "r2", "wl", "wl", "wl", "wl3", "wl", "wl", "wl",
           "r2", "r2", "r2", "r", "r2", "r", "r2", "r3", "r", "r", "r",
           "r", "r", "r", "r2", "r2", "r2")
# wl_ts, fair_weight, child_rank, local_depth, root_parent_local
_TAIL = ("wl", "r", "r", "r2", "r2")


def sharded_cycle_step(mesh: Mesh, depth: int, num_resources: int,
                       num_cqs: int, fair_mode: bool = False,
                       num_flavors: int = 1):
    """One scheduling cycle with the workload axis sharded over the mesh.
    Takes the _PREFIX args, then wl_ts, fair_weight, child_rank,
    local_depth, root_parent_local."""
    sh = _shardings(mesh)
    in_shardings = tuple(sh[n] for n in list(_PREFIX) + list(_TAIL))
    # 15 outputs (batched._cycle_core): ... plus slot_overflow [C],
    # victim_ids [C, 0], victim_variant [C, 0] (the packed victims:
    # empty when the fused preemption tensors are not provided, as
    # here) and the preemptor's two counts, int32[2].
    out_shardings = (
        sh["wl"], sh["wl"], sh["r2"], sh["wl"], sh["r"], sh["r"],
        sh["r3"], sh["r"], sh["r"], sh["r"], sh["r"], sh["r"],
        sh["r2"], sh["r2"], sh["r"])

    def fn(pending, inadmissible, usage, rank, commit_rank, wl_cq,
           wl_req, wl_priority, wl_has_qr, wl_hash, nominal,
           lend_limit, borrow_limit, parent, ancestors, height,
           group_of_res, group_flavors, no_preemption, can_pwb,
           can_always_reclaim, best_effort, fung_borrow_try_next,
           fung_pref_preempt_first, root_members, root_nodes,
           local_chain, wl_ts, fair_weight, child_rank, local_depth,
           root_parent_local):
        return cycle_step.__wrapped__(
            pending, inadmissible, usage, rank, commit_rank, wl_cq,
            wl_req, wl_priority, wl_has_qr, wl_hash, nominal,
            lend_limit, borrow_limit, parent, ancestors, height,
            group_of_res, group_flavors, no_preemption, can_pwb,
            can_always_reclaim, best_effort, fung_borrow_try_next,
            fung_pref_preempt_first, root_members, root_nodes,
            local_chain, wl_ts, fair_weight, child_rank, local_depth,
            root_parent_local=root_parent_local,
            depth=depth, num_resources=num_resources,
            num_cqs=num_cqs, fair_mode=fair_mode,
            num_flavors=num_flavors)

    return jax.jit(fn, in_shardings=in_shardings,
                   out_shardings=out_shardings)


def sharded_drain_loop(mesh: Mesh, depth: int, num_resources: int,
                       num_cqs: int, fair_mode: bool = False,
                       num_flavors: int = 1):
    """The WHOLE drain (oracle.batched.drain_loop) on the mesh: the
    while-loop over cycles compiles into one sharded program; per-cycle
    heads selection reduces across workload shards via mesh collectives.
    Takes the _PREFIX args, then max_cycles (int), wl_ts, fair_weight,
    child_rank, local_depth, root_parent_local."""
    sh = _shardings(mesh)
    names = list(_PREFIX) + ["r"] + list(_TAIL)
    in_shardings = tuple(sh[n] for n in names)
    out_shardings = (sh["wl"], sh["wl"], sh["wl3"], sh["r2"], sh["r"],
                     sh["r"])

    def fn(pending, inadmissible, usage, rank, commit_rank, wl_cq,
           wl_req, wl_priority, wl_has_qr, wl_hash, nominal, lend_limit,
           borrow_limit, parent, ancestors, height, group_of_res,
           group_flavors, no_preemption, can_pwb, can_always_reclaim,
           best_effort, fung_borrow_try_next, fung_pref_preempt_first,
           root_members, root_nodes, local_chain, max_cycles, wl_ts,
           fair_weight, child_rank, local_depth, root_parent_local):
        return drain_loop.__wrapped__(
            pending, inadmissible, usage, rank, commit_rank, wl_cq,
            wl_req, wl_priority, wl_has_qr, wl_hash, nominal, lend_limit,
            borrow_limit, parent, ancestors, height, group_of_res,
            group_flavors, no_preemption, can_pwb, can_always_reclaim,
            best_effort, fung_borrow_try_next, fung_pref_preempt_first,
            root_members, root_nodes, local_chain, max_cycles, wl_ts,
            fair_weight, child_rank, local_depth, root_parent_local,
            depth=depth, num_resources=num_resources, num_cqs=num_cqs,
            fair_mode=fair_mode, num_flavors=num_flavors)

    return jax.jit(fn, in_shardings=in_shardings,
                   out_shardings=out_shardings)


def solver_mesh_args(solver, mesh: Mesh):
    """Assemble a BatchedDrainSolver's arrays in the positional order the
    sharded programs take (_PREFIX then tail), device_put with the right
    shardings. Workload counts must be divisible by the mesh size (pad
    upstream). Returns (prefix_list, tail_list)."""
    w, wl = solver.world, solver.wls
    W = wl.num_workloads
    sh = _shardings(mesh)
    prefix_vals = [
        wl.eligible & (wl.cq >= 0),                     # pending
        np.zeros(W, bool),                              # inadmissible
        np.broadcast_to(w.usage,
                        (w.num_nodes, w.nominal.shape[1])).copy(),
        solver.head_ranks(), solver.commit_ranks(),
        wl.cq, wl.requests, wl.priority, wl.has_quota_reservation,
        wl.hash_id,
        w.nominal, w.lend_limit, w.borrow_limit, w.parent, w.ancestors,
        w.height, w.group_of_res, w.group_flavors, w.no_preemption,
        w.can_preempt_while_borrowing, w.can_always_reclaim,
        w.best_effort, w.fung_borrow_try_next, w.fung_pref_preempt_first,
        w.root_members, w.root_nodes, w.local_chain,
    ]
    tail_vals = [wl.timestamp, w.fair_weight, w.child_rank, w.local_depth,
                 w.root_parent_local]
    prefix = [jax.device_put(v, sh[n])
              for v, n in zip(prefix_vals, _PREFIX)]
    tail = [jax.device_put(v, sh[n]) for v, n in zip(tail_vals, _TAIL)]
    return prefix, tail
