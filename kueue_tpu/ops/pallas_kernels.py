"""Pallas TPU kernels for the hot per-cycle array ops.

Two ops dominate a batched scheduling cycle's memory traffic, and both are
bandwidth-bound reductions over the "big" axis:

  * heads selection — per-ClusterQueue min of the workload rank vector
    (cluster_queue.go:715 Pop / manager.go:891 heads, lifted to one
    reduction over all W pending workloads into C bins, W >> C);
  * TAS leaf fit-counting — min over resources of floor(free / per-pod)
    for every topology leaf (tas_flavor_snapshot.go:1748 fillInCounts'
    inner loop, O(leaves x resources)).

Each kernel keeps the whole problem resident in VMEM (a 50k-workload rank
vector is ~200 KB — the scheduler's "model" is tiny by TPU standards) and
folds the big axis tile-by-tile with an in-kernel fori_loop, producing the
whole reduction in one fused kernel with no HBM round-trips for the
accumulator. The kernels are gridless, with int32 loop bounds and selects
written as mask multiplies. Asked of the installed compiler (JAX 0.9.0,
libtpu 0.0.34, v5e, under jax_enable_x64 — PR 22): both kernels compile
as written; a grid-partitioned pallas_call compiles too, provided its
index maps return int32 (a Python ``0`` there becomes an i64 result,
which Mosaic refuses to legalize in ``func.return`` — the failure once
put down to grids as such); Python-int loop bounds compile; bool selects
compile, and what recurses in lowering is a bare Python scalar as an
operand of ``jnp.where`` inside the kernel (64-bit under x64), not the
select. So the gridless mask-multiply form is a choice that still
compiles, not a necessity; changing it is a performance question.

Which implementation serves is never silent on the chip: chip_smoke.py
fails there unless `pallas_enabled()` is true and `_interpret()` false,
checks the compiled cycle program for its `tpu_custom_call`, and prints
which leaf-count path ran. tests/test_tpu_compile.py keeps both kernels
compiling for the chip; numerical parity with the jnp references is
tests/test_pallas_kernels.py (interpret mode, CPU).

Dispatch: `pallas_enabled()` — on when the default backend is a TPU,
forced on/off with KUEUE_TPU_PALLAS=1/0; off it, the kernels run in
interpret mode (tests) or give way to the jnp reference implementations.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INT32_BIG = np.int32(2**31 - 1)

# Tile of the big (workload / leaf) axis folded per loop iteration.
_TILE_W = 256


def pallas_enabled() -> bool:
    env = os.environ.get("KUEUE_TPU_PALLAS")
    if env is not None:
        return env not in ("0", "false", "")
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Heads selection: segment-min of rank over the workload axis into CQ bins.
# ---------------------------------------------------------------------------


def _make_heads_kernel(c_pad: int):
    def kernel(cq_ref, rank_ref, out_ref):
        """cq/rank: int32[n_tiles, TILE_W]; out: int32[1, c_pad]."""
        n_tiles = cq_ref.shape[0]

        def body(i, acc):
            cq = cq_ref[i, :]
            rank = rank_ref[i, :]
            col = jax.lax.broadcasted_iota(jnp.int32, (_TILE_W, c_pad), 1)
            vals = jnp.where(cq[:, None] == col, rank[:, None], INT32_BIG)
            return jnp.minimum(acc, jnp.min(vals, axis=0))

        init = jnp.full((c_pad,), INT32_BIG, jnp.int32)
        # int32 loop bounds, spelled out: under jax_enable_x64 a Python
        # int would be 64-bit. (The installed compiler takes either.)
        out_ref[0, :] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_tiles),
                                          body, init)

    return kernel


@partial(jax.jit, static_argnames=("num_cqs",))
def _heads_pallas(eff_rank, wl_cq, *, num_cqs: int):
    from jax.experimental import pallas as pl

    W = eff_rank.shape[0]
    c_pad = max(128, -(-num_cqs // 128) * 128)
    w_pad = -(-W // _TILE_W) * _TILE_W
    rank32 = jnp.minimum(eff_rank, INT32_BIG).astype(jnp.int32)
    rank32 = jnp.pad(rank32, (0, w_pad - W), constant_values=INT32_BIG)
    cq32 = jnp.pad(wl_cq.astype(jnp.int32), (0, w_pad - W),
                   constant_values=-1)
    n_tiles = w_pad // _TILE_W

    out = pl.pallas_call(
        _make_heads_kernel(c_pad),
        out_shape=jax.ShapeDtypeStruct((1, c_pad), jnp.int32),
        interpret=_interpret(),
    )(cq32.reshape(n_tiles, _TILE_W), rank32.reshape(n_tiles, _TILE_W))
    return out[0, :num_cqs].astype(eff_rank.dtype)


def select_heads(eff_rank, wl_cq, num_cqs: int, big_rank):
    """Per-CQ minimum effective rank.

    Equivalent to jax.ops.segment_min(eff_rank, wl_cq, num_segments=C)
    with inactive entries carrying `big_rank`; the Pallas path clamps the
    sentinel to INT32_BIG, so callers must treat >= min(big_rank,
    INT32_BIG) as "no head".
    """
    if pallas_enabled():
        out = _heads_pallas(eff_rank, wl_cq, num_cqs=num_cqs)
        return jnp.where(out >= INT32_BIG, big_rank, out)
    return jax.ops.segment_min(eff_rank, wl_cq, num_segments=num_cqs)


# ---------------------------------------------------------------------------
# TAS leaf fit counts: min over resources of floor(free / per-pod).
# ---------------------------------------------------------------------------


def _leaf_kernel(free_ref, used_ref, req_ref, div_ref, anyreq_ref, mask_ref,
                 out_ref):
    """free/used: int32[L_pad, S_pad]; req (0/1), div: int32[1, S_pad];
    anyreq: int32[1, 1]; mask (0/1) / out: int32[n_tiles, TILE_W].

    Pure int32 arithmetic, selects expressed as mask multiplies. (With
    the installed compiler ``jnp.where`` lowers here as well, as long as
    every operand is typed int32 — see the module docstring.)
    """
    from jax.experimental import pallas as pl

    n_tiles = out_ref.shape[0]
    req = req_ref[0, :]
    div = div_ref[0, :]
    anyreq = anyreq_ref[0, 0]

    def body(i, carry):
        rows = pl.ds(i * _TILE_W, _TILE_W)
        free = jnp.maximum(0, free_ref[rows, :] - used_ref[rows, :])
        # requested -> floor(free/div); not requested -> INT32_BIG.
        counts = (free // div[None, :]) * req[None, :] + \
            (1 - req[None, :]) * INT32_BIG
        state = jnp.min(counts, axis=1) * anyreq
        out_ref[i, :] = state * mask_ref[i, :]
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_tiles), body, jnp.int32(0))


@jax.jit
def _leaf_pallas(free, used, per_pod, leaf_mask):
    from jax.experimental import pallas as pl

    L, S = free.shape
    s_pad = max(128, -(-S // 128) * 128)
    l_pad = -(-max(L, 1) // _TILE_W) * _TILE_W
    n_tiles = l_pad // _TILE_W

    def pad2(x):
        x = jnp.minimum(x, INT32_BIG).astype(jnp.int32)
        return jnp.pad(x, ((0, l_pad - L), (0, s_pad - S)))

    pp32 = jnp.pad(jnp.minimum(per_pod, INT32_BIG).astype(jnp.int32),
                   (0, s_pad - S)).reshape(1, s_pad)
    req32 = (pp32 > 0).astype(jnp.int32)
    div32 = jnp.maximum(pp32, 1)
    anyreq = jnp.max(req32).reshape(1, 1)
    mask32 = jnp.pad(leaf_mask.astype(jnp.int32),
                     (0, l_pad - L)).reshape(n_tiles, _TILE_W)

    out = pl.pallas_call(
        _leaf_kernel,
        out_shape=jax.ShapeDtypeStruct((n_tiles, _TILE_W), jnp.int32),
        interpret=_interpret(),
    )(pad2(free), pad2(used), req32, div32, anyreq, mask32)
    return out.reshape(l_pad)[:L]


def leaf_fit_counts_in_range(free_capacity, tas_usage, assumed_usage,
                             per_pod) -> bool:
    """Whether the Pallas leaf kernel's int32 arithmetic is exact for
    these CONCRETE inputs. The kernel clamps operands to int32
    independently, which corrupts floor(free/per_pod) once any quantity
    reaches 2^31 (memory-in-bytes easily does); callers must route such
    worlds through the int64 jnp path. Traced (in-jit) inputs return
    False — the dispatch is host-side only."""
    import jax.core

    arrs = (free_capacity, tas_usage, assumed_usage, per_pod)
    if any(isinstance(a, jax.core.Tracer) for a in arrs):
        return False
    return all(int(np.max(np.asarray(a), initial=0)) < int(INT32_BIG)
               for a in arrs)


def leaf_fit_counts(free_capacity, tas_usage, assumed_usage, per_pod,
                    leaf_mask):
    """Pods that fit per topology leaf; Pallas path when enabled and the
    quantities fit int32, else the jnp reference (ops.tas.leaf_states)."""
    if pallas_enabled() and leaf_fit_counts_in_range(
            free_capacity, tas_usage, assumed_usage, per_pod):
        used = tas_usage + assumed_usage
        return _leaf_pallas(free_capacity, used, per_pod, leaf_mask)
    from kueue_tpu.ops.tas import _leaf_states_jnp
    return _leaf_states_jnp(free_capacity, tas_usage, assumed_usage,
                            per_pod, leaf_mask)
