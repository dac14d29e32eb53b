"""E3CS hot-path Pallas kernels: fused Gumbel-perturb + top-k, and the tiled
exponential-weight update.

At fleet scale (K ~ 10^6 clients) the selection step is bandwidth-bound: every
extra pass over the (K,) probability/weight vectors costs a full HBM
round-trip.  The two kernels here each make exactly one pass:

* ``fused_gumbel_topk_kernel_call`` — fuses the Plackett-Luce perturbation
  ``score_i = log p_i + Gumbel(u_i)`` (with ``Gumbel(u) = -log(-log u)``) into
  the streaming top-k merge of ``gumbel_topk.py``, so perturbed scores are
  never materialised in HBM.  Uniform variates are generated outside the
  kernel with the host PRNG (keeps the draw bit-reproducible across backends)
  and consumed tile-by-tile.

* ``e3cs_update_kernel_call`` — fuses Eq. (16)'s importance-weighted
  estimator, the proof-regime clamp (step <= 1), the overflow-set freeze
  (Eq. 17) and the log-weight add into one elementwise pass.  The global
  re-centering max is returned per-tile so the caller can finish the shift
  with a tiny (n_tiles,) reduction instead of re-reading all of ``logw``.

Layout follows the house idiom of ``gumbel_topk.py``: 1-D grid over weight
tiles, running top-k state in VMEM scratch, trailing-tile finalisation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gumbel_topk import NEG_INF, sort_topk, streaming_topk_body, topk_buffers, topk_tile

__all__ = ["fused_gumbel_topk_kernel_call", "e3cs_update_kernel_call"]

_EPS = 1e-20


def _fused_kernel(p_ref, u_ref, val_ref, idx_ref, best_v, best_i, *, k, tile, n_tiles, K):
    ti = pl.program_id(0)
    p = p_ref[...].astype(jnp.float32)  # (tile,)
    u = u_ref[...].astype(jnp.float32)
    # Gumbel perturbation fused in-register: log p - log(-log u)
    g = -jnp.log(-jnp.log(jnp.clip(u, _EPS, 1.0 - 1e-7)))
    s = jnp.log(jnp.maximum(p, _EPS)) + g
    pos = ti * tile + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
    s = jnp.where((pos < K) & (p > 0.0), s, NEG_INF)
    streaming_topk_body(s, val_ref, idx_ref, best_v, best_i, k=k, tile=tile, n_tiles=n_tiles)


def fused_gumbel_topk_kernel_call(p: jax.Array, u: jax.Array, k: int, tile: int = 8192, interpret: bool = False):
    """One-pass Plackett-Luce draw: perturb ``p`` with ``Gumbel(u)`` and keep
    the running top-k, without writing scores back to HBM.

    Args:
      p: (K,) selection probabilities.
      u: (K,) iid Uniform(0,1) variates.
      k: cohort size (static).

    Returns (values, indices): top-k perturbed scores, descending.
    """
    K = p.shape[0]
    tile = topk_tile(tile, K)
    K_p = math.ceil(K / tile) * tile
    if K_p != K:
        p = jnp.pad(p, (0, K_p - K))
        u = jnp.pad(u, (0, K_p - K), constant_values=0.5)
    n_tiles = K_p // tile
    kernel = functools.partial(_fused_kernel, k=k, tile=tile, n_tiles=n_tiles, K=K)
    out_specs, out_shape, scratch = topk_buffers(k)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((tile,), lambda t: (t,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(p, u)
    return sort_topk(vals, idx, k)


def _update_kernel(logw_ref, p_ref, mask_ref, x_ref, frozen_ref, scale_ref, out_ref, tmax_ref, *, tile, K):
    ti = pl.program_id(0)
    logw = logw_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    mask = mask_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    frozen = frozen_ref[...].astype(jnp.float32)
    scale = scale_ref[0]

    xhat = mask * x / jnp.maximum(p, 1e-12)  # Eq. (16)
    step = jnp.minimum(scale * xhat, 1.0)  # Eq. (17) exponent, proof clamp
    new = logw + jnp.where(frozen > 0, 0.0, step)
    out_ref[...] = new

    pos = ti * tile + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
    tmax_ref[0] = jnp.max(jnp.where(pos < K, new, NEG_INF))


def e3cs_update_kernel_call(
    logw: jax.Array,
    p: jax.Array,
    sel_mask: jax.Array,
    x: jax.Array,
    frozen: jax.Array,
    scale: jax.Array,
    tile: int = 8192,
    interpret: bool = False,
):
    """Fused E3CS weight update (Eqs. 16-17) over (K,) vectors.

    ``scale`` is the scalar exponent coefficient ``(k - K sigma) * eta / K``.
    Returns ``(new_logw, tile_max)``; the caller re-centers with
    ``new_logw - tile_max.max()`` (ProbAlloc is shift-invariant).
    """
    K = logw.shape[0]
    tile = min(tile, max(K, 8))
    K_p = math.ceil(K / tile) * tile
    if K_p != K:
        pad = K_p - K
        logw = jnp.pad(logw, (0, pad))
        p = jnp.pad(p, (0, pad), constant_values=1.0)
        sel_mask = jnp.pad(sel_mask, (0, pad))
        x = jnp.pad(x, (0, pad))
        frozen = jnp.pad(frozen.astype(jnp.float32), (0, pad))
    n_tiles = K_p // tile
    kernel = functools.partial(_update_kernel, tile=tile, K=K)
    new_logw, tmax = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((1,), lambda t: (t,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K_p,), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles,), jnp.float32),
        ],
        scratch_shapes=[],
        interpret=interpret,
    )(logw, p, sel_mask, x, frozen.astype(jnp.float32), jnp.reshape(scale, (1,)).astype(jnp.float32))
    return new_logw[:K], tmax
