"""Gumbel top-k selection as a Pallas kernel — E3CS sampling at large K.

``A_t ~ multinomialNR(p/k, k)`` == top-k of ``log p + Gumbel`` (Yellott 1977).
At cross-device-FL scale (K ~ 10^5..10^6 clients) the selection itself becomes
a bandwidth-bound scan over the weight vector; this kernel streams the
perturbed scores through VMEM in tiles and maintains the running top-k in a
scratch buffer via k iterative max-extractions per tile (k << tile, so the
cost is one VPU max-reduction per candidate).

Layout: grid ``(n_tiles,)``; scratch holds the k [value, index] slots merged
across tiles.  Output: (k,) values and int32 indices, descending by score.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "gumbel_topk_kernel_call",
    "sort_topk",
    "streaming_topk_body",
    "topk_buffers",
    "topk_tile",
]

NEG_INF = -1e30
LANE_TILE = 1024  # score tiles are whole multiples of this (XLA's 1-D f32 tiling)


def topk_tile(tile: int, K: int) -> int:
    """Scores per grid step: at most ``tile`` (and K), rounded up to a whole
    ``LANE_TILE``; the caller pads K up to a multiple of it."""
    return -(-min(tile, max(K, 1)) // LANE_TILE) * LANE_TILE


def topk_buffers(k: int):
    """``(out_specs, out_shape, scratch_shapes)`` of the running top-k: the
    k [value, index] slots live in ``(ceil(k/128), 128)`` arrays, slot ``j``
    at flat position ``j`` (Mosaic carries no 1-D vector through a loop)."""
    rows = -(-k // 128)
    spec = pl.BlockSpec((rows, 128), lambda t: (0, 0))
    shape = [jax.ShapeDtypeStruct((rows, 128), jnp.float32), jax.ShapeDtypeStruct((rows, 128), jnp.int32)]
    scratch = [pltpu.VMEM((rows, 128), jnp.float32), pltpu.VMEM((rows, 128), jnp.int32)]
    return [spec, spec], shape, scratch


def sort_topk(vals: jax.Array, idx: jax.Array, k: int):
    """A kernel's slot buffers -> the (k,) [value, index] pairs descending by
    value (stable: equal values keep slot order)."""
    vals = vals.reshape(-1)[:k]
    idx = idx.reshape(-1)[:k]
    order = jnp.argsort(-vals)
    return vals[order], idx[order]


def _flat_iota(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1] + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1
    )


def streaming_topk_body(s, val_ref, idx_ref, best_v, best_i, *, k, tile, n_tiles):
    """Shared streaming top-k merge used by every selection kernel.

    Takes this tile's already-masked scores ``s`` (tile,), merges them into
    the running k-slot [value, index] VMEM scratch (``topk_buffers``) by
    extracting the tile max k times (each accepted only if it beats the
    current k-th best), and on the last tile emits the slots *unsorted*;
    callers order them with ``sort_topk`` outside the kernel.  Callers
    provide the score prelude (masking, perturbation fusion); everything
    below the scores is identical across kernels so it lives here once.

    Mosaic lowers no dynamic indexing, so positions are found as the first
    flat index attaining the extremum, ``min(where(x == m, iota, big))`` —
    ``argmax`` / ``argmin``'s tie order — and written back with
    ``where(iota == pos, new, old)``.  Slots past k hold +inf, so they are
    never the minimum and never replaced.
    """
    ti = pl.program_id(0)
    rows = best_v.shape[0]
    slot_id = _flat_iota((rows, 128))

    @pl.when(ti == 0)
    def _init():
        best_v[...] = jnp.where(slot_id < k, NEG_INF, jnp.inf)
        best_i[...] = jnp.zeros_like(best_i)

    base = ti * tile
    s = s.reshape(tile // 128, 128)
    lane_id = _flat_iota((tile // 128, 128))

    def body(j, carry):
        s, bv, bi = carry
        m = jnp.max(s)
        am = jnp.min(jnp.where(s == m, lane_id, tile))
        # current minimum of the top-k slots and its first position
        kmin = jnp.min(bv)
        kmin_pos = jnp.min(jnp.where(bv == kmin, slot_id, rows * 128))
        hit = (slot_id == kmin_pos) & (m > kmin)
        bv = jnp.where(hit, m, bv)
        bi = jnp.where(hit, base + am, bi)
        s = jnp.where(lane_id == am, NEG_INF, s)
        return s, bv, bi

    s, bv, bi = jax.lax.fori_loop(0, k, body, (s, best_v[...], best_i[...]))
    best_v[...] = bv
    best_i[...] = bi

    @pl.when(ti == n_tiles - 1)
    def _finish():
        val_ref[...] = best_v[...]
        idx_ref[...] = best_i[...]


def _kernel(s_ref, val_ref, idx_ref, best_v, best_i, *, k, tile, n_tiles, K):
    ti = pl.program_id(0)
    s = s_ref[...].astype(jnp.float32)  # (tile,)
    pos = ti * tile + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
    s = jnp.where(pos < K, s, NEG_INF)
    streaming_topk_body(s, val_ref, idx_ref, best_v, best_i, k=k, tile=tile, n_tiles=n_tiles)


def gumbel_topk_kernel_call(scores: jax.Array, k: int, tile: int = 8192, interpret: bool = False):
    """scores: (K,) perturbed log-probabilities. Returns (values, indices)."""
    K = scores.shape[0]
    tile = topk_tile(tile, K)
    K_p = math.ceil(K / tile) * tile
    if K_p != K:
        scores = jnp.pad(scores, (0, K_p - K), constant_values=NEG_INF)
    n_tiles = K_p // tile
    kernel = functools.partial(_kernel, k=k, tile=tile, n_tiles=n_tiles, K=K)
    out_specs, out_shape, scratch = topk_buffers(k)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile,), lambda t: (t,))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(scores)
    return sort_topk(vals, idx, k)
