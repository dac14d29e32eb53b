"""Fused round kernels: the E3CS round's per-client work in one VMEM pass.

The staged ``RoundProgram`` pipeline makes 4-5 full passes over the (K,)
client axis per round — allocation epilogue, Gumbel perturbation, top-k,
trace unpack, weight update, credit-ring shift — each a separate kernel (or
XLA op) that round-trips the vectors through HBM.  At K ~ 1e7 the round is
launch- and bandwidth-bound, not compute-bound.  This module collapses the
round into two tiled Pallas kernels, each reading every input tile exactly
once:

* **select** (``fused_alloc_select`` / ``fused_perturb_select``) — rebuild
  the allocation ``p`` from the four scalars of
  ``engine.sharded.masked_prob_alloc_scalars`` (bisection stays outside: it
  is a scalar fixed-point, not a vector pass), add the pre-drawn Gumbel
  noise, and stream the running top-k in VMEM scratch
  (``gumbel_topk.streaming_topk_body``).  The Gumbel vector is drawn
  *outside* with the staged engine's exact ``jax.random.gumbel`` call so
  selections stay bit-reproducible.

* **tail** (``fused_round_tail``) — per tile: unpack the packed 1-bit /
  2-bit trace row (or pass dense outcomes through), derive the on-time bits,
  apply Eq. 16/17's clamped importance-weighted log-weight step with the
  overflow/activity freeze, emit the per-tile re-centering max (one
  lane-dense 1024-wide block per tile), refresh the pow-d loss cache, and
  pop/shift/push both staleness rings (late credit + late feedback) —
  everything downstream of the outcome row except the global re-centering,
  which needs a cross-tile (and cross-shard) max and is finished by the
  caller from the partial maxes.

Bit-identity contract: with dispatch on the jnp references (``ref.py``) the
fused engine path is staged-op-for-staged-op identical by construction; in
interpret mode the Pallas kernels are pinned bit-identical to the committed
round goldens across {sync, async} x {D=1, D=8} x {dense, 1-bit, 2-bit}
(``tests/test_round_fused.py``).  Dispatch honours ``REPRO_INTERPRET``
(see ``dispatch.py``); ``tile=None`` consults the autotune cache.

Known (measure-zero) divergence: exactly tied perturbed scores may resolve
in a different order than ``lax.top_k``, and a shard with fewer than ``k``
active clients pads its candidate list with ``(NEG_INF, 0)`` instead of
``(-inf, <index>)`` — with continuous Gumbel scores and ``K_active >= k``
per shard (the supported regime) neither is reachable.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.volatility import DEAD_LAG

from .autotune import best_config
from .dispatch import kernel_route
from .gumbel_topk import LANE_TILE, NEG_INF, sort_topk, streaming_topk_body, topk_buffers, topk_tile
from .ref import fused_alloc_select_ref, fused_perturb_select_ref, round_tail_ref
from .unpack_bits import BYTE_TILE, expand_packed

__all__ = [
    "fused_alloc_select",
    "fused_perturb_select",
    "fused_round_tail",
    "fused_select_kernel_call",
    "round_tail_kernel_call",
]

_LAG_DEAD_CODE = 3  # 2-bit crumb sentinel (mirrors engine.round_program)


# ---------------------------------------------------------------------------
# Select: allocation epilogue + perturb + streaming top-k
# ---------------------------------------------------------------------------


def _select_kernel(scal_ref, *refs, k, tile, n_tiles, K, has_active, from_w):
    refs = list(refs)
    w = refs.pop(0)[...]  # weights (from_w) or staged probabilities (from_p)
    g = refs.pop(0)[...]
    act = refs.pop(0)[...] if has_active else None
    if from_w:
        p_ref = refs.pop(0)
        c_ref = refs.pop(0)
    val_ref, idx_ref, best_v, best_i = refs

    ti = pl.program_id(0)
    if from_w:
        sigma, residual, cap, denom = scal_ref[0], scal_ref[1], scal_ref[2], scal_ref[3]
        use_cap = scal_ref[4] > 0
        p = sigma + residual * jnp.minimum(w, cap) / denom
        cp = (p >= 1.0 - 1e-6) & use_cap
        p = jnp.clip(p, sigma, 1.0)
        if act is not None:
            p = p * act
            cp = cp & (act > 0)
        p_ref[...] = p
        c_ref[...] = cp.astype(jnp.float32)
    else:
        p = w
    s = jnp.log(jnp.maximum(p, 1e-20)) + g
    pos = ti * tile + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
    valid = pos < K
    if act is not None:
        valid = valid & (act > 0)
    s = jnp.where(valid, s, NEG_INF)
    streaming_topk_body(s, val_ref, idx_ref, best_v, best_i, k=k, tile=tile, n_tiles=n_tiles)


def fused_select_kernel_call(
    w: jax.Array,
    g: jax.Array,
    k: int,
    *,
    scalars: Optional[Tuple] = None,
    sigma=None,
    active: Optional[jax.Array] = None,
    tile: int = 8192,
    interpret: bool = False,
):
    """One-pass select.  With ``scalars`` (from_w mode) ``w`` is the masked
    weight vector and the kernel rebuilds ``(p, capped)`` before perturbing;
    without, ``w`` *is* the staged ``p`` and only perturb+top-k run.
    Returns ``(p, capped_f32, vals, idx)`` or ``(vals, idx)``."""
    from_w = scalars is not None
    K = w.shape[0]
    tile = topk_tile(tile, K)
    K_p = math.ceil(K / tile) * tile
    pad = K_p - K
    if pad:
        w = jnp.pad(w, (0, pad))
        g = jnp.pad(g, (0, pad))
        if active is not None:
            active = jnp.pad(active, (0, pad))
    n_tiles = K_p // tile
    has_active = active is not None

    if from_w:
        residual, cap, denom, use_cap = scalars
        scal = jnp.stack([
            jnp.asarray(sigma, jnp.float32),
            jnp.asarray(residual, jnp.float32),
            jnp.asarray(cap, jnp.float32),
            jnp.asarray(denom, jnp.float32),
            use_cap.astype(jnp.float32),
        ])
    else:
        scal = jnp.zeros((1,), jnp.float32)  # unused; keeps one kernel signature

    vec = pl.BlockSpec((tile,), lambda t: (t,))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), vec, vec]
    args = [scal, w, g]
    if has_active:
        in_specs.append(vec)
        args.append(active)
    out_specs = []
    out_shape = []
    if from_w:
        out_specs += [vec, vec]
        out_shape += [
            jax.ShapeDtypeStruct((K_p,), jnp.float32),
            jax.ShapeDtypeStruct((K_p,), jnp.float32),
        ]
    topk_specs, topk_shape, scratch = topk_buffers(k)
    out_specs += topk_specs
    out_shape += topk_shape
    kernel = functools.partial(
        _select_kernel, k=k, tile=tile, n_tiles=n_tiles, K=K, has_active=has_active, from_w=from_w
    )
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    if from_w:
        p, c, vals, idx = out
        return (p[:K], c[:K], *sort_topk(vals, idx, k))
    return sort_topk(*out, k)


def fused_alloc_select(
    w: jax.Array,
    g: jax.Array,
    k: int,
    *,
    sigma,
    scalars: Tuple,
    active: Optional[jax.Array] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Dispatching from_w select: ``(p, capped, vals, idx)``, ``idx`` local
    (the sharded caller adds its shard offset, exactly like
    ``local_topk_candidates``)."""
    use_kernel, interp = _route(interpret)
    if not use_kernel:
        return fused_alloc_select_ref(w, g, k, sigma=sigma, scalars=scalars, active=active)
    tile = tile or best_config("round_fused", w.shape[0])["tile"]
    p, c, vals, idx = fused_select_kernel_call(
        w, g, k, scalars=scalars, sigma=sigma, active=active, tile=tile, interpret=interp
    )
    return p, c > 0, vals, idx


def fused_perturb_select(
    p: jax.Array,
    g: jax.Array,
    k: int,
    *,
    active: Optional[jax.Array] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Dispatching from_p select (sorted-allocator path): ``(vals, idx)``."""
    use_kernel, interp = _route(interpret)
    if not use_kernel:
        return fused_perturb_select_ref(p, g, k, active=active)
    tile = tile or best_config("round_fused", p.shape[0])["tile"]
    return fused_select_kernel_call(p, g, k, active=active, tile=tile, interpret=interp)


def _route(interpret: Optional[bool]):
    """Per-call dispatch: explicit ``interpret`` forces the kernel; otherwise
    ``REPRO_INTERPRET`` / backend decide (jnp reference is the CPU default —
    the interpreter would dominate a scanned horizon)."""
    if interpret is not None:
        return True, interpret
    return kernel_route(cpu_kernel_default=False)


# ---------------------------------------------------------------------------
# Tail: unpack + update + credit rings
# ---------------------------------------------------------------------------


def _make_tail_kernel(*, kind, S, late_fb, has_active, eta, K_glob, K, tile, decay):
    is_async = kind in ("crumbs", "lag")

    def kernel(res_ref, *refs):
        refs = list(refs)
        obs = refs.pop(0)[...]
        mask = refs.pop(0)[...]
        p = refs.pop(0)[...]
        cp = refs.pop(0)[...] > 0
        logw = refs.pop(0)[...]
        loss = refs.pop(0)[...]
        act = refs.pop(0)[...] if has_active else None
        credit = refs.pop(0) if S > 0 else None
        fbr = refs.pop(0) if late_fb else None
        x_ref = refs.pop(0)
        lag_ref = refs.pop(0) if is_async else None
        logw_ref = refs.pop(0)
        tmax_ref = refs.pop(0)
        loss_ref = refs.pop(0)
        if S > 0:
            arr_ref = refs.pop(0)
            cr_out = refs.pop(0)
        if late_fb:
            afb_ref = refs.pop(0)
            fb_out = refs.pop(0)

        ti = pl.program_id(0)
        # -- decode the outcome row (the unpack_bits / unpack_crumbs expansion)
        lag = None
        if kind == "bits":
            x = expand_packed(obs.astype(jnp.int32), 8).astype(jnp.float32)
        elif kind == "crumbs":
            codes = expand_packed(obs.astype(jnp.int32), 4)
            lag = jnp.where(codes == _LAG_DEAD_CODE, DEAD_LAG, codes)
        elif kind == "x":
            x = obs
        else:  # "lag"
            lag = obs
        if lag is not None:
            x = (lag == 0).astype(jnp.float32)  # deadline-based selector feedback
            lag_ref[...] = lag
        x_ref[...] = x

        # -- Eq. 16/17 elementwise (staged op order; recenter is the caller's)
        residual = res_ref[0]
        xhat = mask * x / jnp.maximum(p, 1e-12)
        step = residual * eta * xhat / K_glob
        step = jnp.minimum(step, 1.0)
        frozen = cp if act is None else cp | (act == 0)
        logw_pre = logw + jnp.where(frozen, 0.0, step)
        logw_ref[...] = logw_pre
        pos = ti * tile + jax.lax.broadcasted_iota(jnp.int32, (tile,), 0)
        valid = pos < K
        if act is not None:
            valid = valid & (act > 0)
        # per-tile max, broadcast over one lane-dense block
        tmax_ref[...] = jnp.full((LANE_TILE,), jnp.max(jnp.where(valid, logw_pre, -jnp.inf)))
        loss_ref[...] = jnp.where(mask > 0, 1.0 - x, loss)

        # -- staleness rings: pop slot 0, shift, push this round's schedule
        if S > 0:
            sched = [mask * (lag == s + 1) * decay[s] for s in range(S)]
            arr_ref[...] = credit[0, :]
            for s in range(S):
                nxt = credit[s + 1, :] if s + 1 < S else jnp.zeros((tile,), jnp.float32)
                cr_out[s, :] = nxt + sched[s]
            if late_fb:
                afb_ref[...] = fbr[0, :]
                for s in range(S):
                    row = jnp.minimum(residual * eta * (sched[s] / jnp.maximum(p, 1e-12)) / K_glob, 1.0)
                    row = jnp.where(frozen, 0.0, row)
                    nxt = fbr[s + 1, :] if s + 1 < S else jnp.zeros((tile,), jnp.float32)
                    fb_out[s, :] = nxt + row

    return kernel


def round_tail_kernel_call(
    obs,
    mask,
    p,
    capped,
    logw,
    loss_cache,
    credit=None,
    fb=None,
    *,
    kind: str,
    residual,
    eta: float,
    K_glob: int,
    decay=(),
    active=None,
    tile: int = 8192,
    interpret: bool = False,
):
    """Tiled tail pass; see ``ref.round_tail_ref`` for the exact contract.
    Returns the same dict (``m`` reduced from the per-tile maxes)."""
    K = mask.shape[0]
    is_async = kind in ("crumbs", "lag")
    S = len(decay) if credit is not None else 0
    late_fb = fb is not None
    # whole byte tiles for the packed decode, whole 128-lane rows otherwise
    granule = {"bits": 8 * BYTE_TILE, "crumbs": 4 * BYTE_TILE}.get(kind, LANE_TILE)
    tile = -(-min(tile, K) // granule) * granule
    K_p = math.ceil(K / tile) * tile
    pad = K_p - K
    has_active = active is not None

    vec = pl.BlockSpec((tile,), lambda t: (t,))
    if kind == "bits":
        obs = jnp.pad(obs, (0, K_p // 8 - obs.shape[0]))
        obs_spec = pl.BlockSpec((tile // 8,), lambda t: (t,))
    elif kind == "crumbs":
        obs = jnp.pad(obs, (0, K_p // 4 - obs.shape[0]))
        obs_spec = pl.BlockSpec((tile // 4,), lambda t: (t,))
    else:
        if pad:
            obs = jnp.pad(obs, (0, pad))
        obs_spec = vec
    if pad:
        mask = jnp.pad(mask, (0, pad))
        p = jnp.pad(p, (0, pad), constant_values=1.0)
        capped = jnp.pad(capped.astype(jnp.float32), (0, pad))
        logw = jnp.pad(logw, (0, pad))
        loss_cache = jnp.pad(loss_cache, (0, pad))
        if has_active:
            active = jnp.pad(active, (0, pad))
        if credit is not None:
            credit = jnp.pad(credit, ((0, 0), (0, pad)))
        if fb is not None:
            fb = jnp.pad(fb, ((0, 0), (0, pad)))
    n_tiles = K_p // tile

    ring = pl.BlockSpec((max(S, 1), tile), lambda t: (0, t))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), obs_spec, vec, vec, vec, vec, vec]
    args = [
        jnp.reshape(residual, (1,)).astype(jnp.float32),
        obs, mask, p, capped.astype(jnp.float32), logw, loss_cache,
    ]
    if has_active:
        in_specs.append(vec)
        args.append(active)
    if S > 0:
        in_specs.append(ring)
        args.append(credit)
        if late_fb:
            in_specs.append(ring)
            args.append(fb)

    out_specs = [vec]
    out_shape = [jax.ShapeDtypeStruct((K_p,), jnp.float32)]  # x
    if is_async:
        out_specs.append(vec)
        out_shape.append(jax.ShapeDtypeStruct((K_p,), jnp.int32))  # lag
    out_specs += [vec, pl.BlockSpec((LANE_TILE,), lambda t: (t,)), vec]
    out_shape += [
        jax.ShapeDtypeStruct((K_p,), jnp.float32),  # logw_pre
        jax.ShapeDtypeStruct((n_tiles * LANE_TILE,), jnp.float32),  # per-tile masked max
        jax.ShapeDtypeStruct((K_p,), jnp.float32),  # loss_cache
    ]
    if S > 0:
        out_specs += [vec, ring]
        out_shape += [
            jax.ShapeDtypeStruct((K_p,), jnp.float32),  # arriving credit
            jax.ShapeDtypeStruct((S, K_p), jnp.float32),  # shifted credit ring
        ]
    if late_fb:
        out_specs += [vec, ring]
        out_shape += [
            jax.ShapeDtypeStruct((K_p,), jnp.float32),  # arriving feedback
            jax.ShapeDtypeStruct((S, K_p), jnp.float32),  # shifted feedback ring
        ]

    kernel = _make_tail_kernel(
        kind=kind, S=S, late_fb=late_fb, has_active=has_active, eta=eta,
        K_glob=K_glob, K=K, tile=tile, decay=tuple(decay),
    )
    res = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)

    res = list(res)
    out = {"x": res.pop(0)[:K]}
    if is_async:
        out["lag"] = res.pop(0)[:K]
    out["logw_pre"] = res.pop(0)[:K]
    out["m"] = jnp.max(res.pop(0))  # max of per-tile maxes == global max, exactly
    out["loss_cache"] = res.pop(0)[:K]
    if S > 0:
        out["arriving"] = res.pop(0)[:K]
        out["credit"] = res.pop(0)[:, :K]
    if late_fb:
        out["arr_fb"] = res.pop(0)[:K]
        out["fb"] = res.pop(0)[:, :K]
    return out


def fused_round_tail(
    obs,
    mask,
    p,
    capped,
    logw,
    loss_cache,
    credit=None,
    fb=None,
    *,
    kind: str,
    residual,
    eta: float,
    K_glob: int,
    decay=(),
    active=None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Dispatching tail pass (kernel vs ``ref.round_tail_ref``)."""
    use_kernel, interp = _route(interpret)
    if not use_kernel:
        return round_tail_ref(
            obs, mask, p, capped, logw, loss_cache, credit, fb,
            kind=kind, residual=residual, eta=eta, K_glob=K_glob, decay=decay, active=active,
        )
    tile = tile or best_config("round_fused", mask.shape[0])["tile"]
    return round_tail_kernel_call(
        obs, mask, p, capped, logw, loss_cache, credit, fb,
        kind=kind, residual=residual, eta=eta, K_glob=K_glob, decay=decay,
        active=active, tile=tile, interpret=interp,
    )
