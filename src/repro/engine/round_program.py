"""One RoundProgram: the paper's round pipeline, assembled from stages.

The paper's round is one fixed pipeline —

    allocate (ProbAlloc) -> select (Plackett-Luce) -> observe (volatile
    outcomes) -> credit (staleness ring) -> update (E3CS / selector state)

— yet the repo grew four independent copies of it: the legacy ``core/sim``
loop, ``engine/scan_sim``'s scan bodies, ``engine/sharded``'s shard_map
horizon, and the ``fl/server`` training loop.  Every follow-on (sharded
async rounds, selector credit for late arrivals, real-transport serving)
was blocked on re-implementing it a fifth time.  Client-selection surveys
(Fu et al. 2022; Németh et al. 2022) frame selection policy, participation
model and system scale as *orthogonal axes*; this module makes the
architecture agree:

* **placement** — ``mesh=None`` runs the round dense on one device;
  ``mesh=<1-D device mesh>`` runs the same stages data-parallel over the
  K-sharded mesh (``prob_alloc`` -> ``masked_prob_alloc(axis_name=...)``
  with one scalar ``psum`` per bisection step, Plackett-Luce -> per-shard
  top-k + exact ``(D, k)`` candidate merge, per-shard PRNG via
  ``fold_in(key, shard_index)``).  A 1-device mesh is bit-identical to the
  dense engine (the fold_in is skipped).
* **staleness** — ``staleness=None`` is the synchronous deadline-drop
  round; ``staleness=S`` generalises outcomes to completion lags and rides
  a bounded ``(S, K)`` pending-credit ring in the scan carry, crediting a
  client that completes ``l <= S`` rounds late with ``alpha**l``.  ``S=0``
  reproduces the sync drop semantics exactly.  Under a mesh the ring is
  sharded ``(S, K/D)`` — sharded async rounds are a *composition*, not a
  fifth implementation.
* **observe source** — ``override`` picks where outcomes come from:
  ``"none"`` (a stateful ``(init_state, sample)`` model carried through the
  scan), ``"dense"`` (a ``(T, K)`` trace streamed through the scan xs:
  float32 success bits, or int32 lags when async), ``"packed"`` (1-bit
  success rows, 8 clients/byte, expanded in-scan by ``unpack_bits``), or
  ``"packed_lags"`` (2-bit lag rows, 4 clients/byte, expanded by
  ``unpack_crumbs`` — the async twin of ``"packed"``).  Under a mesh the
  packed rows shard along the byte axis, so replay memory divides by D.
* **feedback policy** — ``"deadline"`` keeps the paper's selector
  feedback: E3CS observes the on-time bits ``1{lag == 0}`` only.
  ``"late_credit"`` additionally buffers the *selection-round* allocation
  next to the credit ring: when a late-but-alive client's update lands at
  ``t + l``, the estimator receives the decayed reward ``alpha**l`` at the
  buffered importance weight ``1/p_t`` (same Eq. 16/17 math, same
  proof-regime clamp), so persistence is rewarded instead of ignored.
  ``repro.scenarios.harness`` scores the two policies side by side.

``RoundProgram.build_runner`` compiles any combination over a whole
``lax.scan`` horizon with the ``build_scan_runner`` output contracts;
``RoundProgram.from_config`` is the single resolution path from an
``FLConfig`` to a program (the training server and the serving drivers both
construct through it, so staleness / allocator / volatility knobs cannot
drift between entry points).

Bit-identity contract (pinned in ``tests/test_round_program.py`` against
goldens captured from the pre-refactor engines): (S=None, D=1) matches the
old ``scan_sim`` sync engine for all five schemes and every observe source;
(S=2, D=1) matches the old async engine; mesh=1 matches the dense
``allocator="bisect"`` engine.  The PRNG discipline is the one every
engine shared: carry the key, ``split(key, 3)`` per round, ``k1`` to
selection, ``k2`` to the outcome draw.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import FLConfig
from repro.core.selection import (
    E3CSState,
    e3cs_init,
    e3cs_probs,
    e3cs_update,
    fedcs_select,
    make_quota_schedule,
    pow_d_select,
    random_select,
    selection_mask,
    ucb_init,
    ucb_select,
    ucb_update,
)
from repro.core.selection.sampling import perturbed_scores
from repro.core.volatility import DEAD_LAG
from repro.engine.sharded import (
    _axis_size,
    _exchange_topk,
    _pad0,
    _shard_topk_merge,
    _shmap,
    masked_prob_alloc,
    masked_prob_alloc_scalars,
)
from repro.fl.round import ServerState, init_server_state, make_alloc_fn, make_select_fn
from repro.kernels.radix_select import threshold_select_route, topk_mask
from repro.kernels.round_fused import fused_alloc_select, fused_perturb_select, fused_round_tail
from repro.kernels.unpack_bits import unpack_bits, unpack_crumbs
from repro.obs.sketches import SKETCH_FIELDS, SketchSpec, lag_bins, region_ids, sketch_carry0, sketch_step
from repro.obs.taps import ROUND_TAPS
from repro.obs.trace import stage

__all__ = [
    "RoundProgram",
    "ring_pop_push",
    "lag_credit_schedule",
    "staleness_ring_step",
    "OBSERVE_MODES",
    "FEEDBACK_MODES",
]

OBSERVE_MODES = ("none", "dense", "packed", "packed_lags")
FEEDBACK_MODES = ("deadline", "late_credit")
_LAG_DEAD_CODE = 3  # 2-bit crumb sentinel (see repro.scenarios.replay)
_NO_TIES = False  # the topk_ties gauge of select paths without a threshold


# ---------------------------------------------------------------------------
# The staleness ring — single source for every engine and the serving loop
# ---------------------------------------------------------------------------


def ring_pop_push(pending, sched):
    """One generic bounded-ring update: pop slot 0 (due now), shift, add the
    newly scheduled rows.

    ``pending`` is ``(..., S, K)`` — slot s holds the value arriving s rounds
    from now; ``sched`` is the ``(..., S, K)`` rows to schedule (slot s lands
    ``s + 1`` rounds from now).  Returns ``(arriving, new_pending)``.  Both
    the late-*credit* ring (CEP accounting, aggregation weights) and the
    late-*feedback* ring (buffered E3CS updates) are instances of this.
    """
    arriving = pending[..., 0, :]
    shifted = jnp.concatenate(
        [pending[..., 1:, :], jnp.zeros_like(pending[..., :1, :])], axis=-2
    )
    return arriving, shifted + sched


def lag_credit_schedule(mask, lag, S: int, alpha: float):
    """Decayed-credit rows for this round's selections: row s is
    ``mask * 1{lag == s+1} * alpha**(s+1)`` — the ``(..., S, K)`` schedule a
    lag draw pushes into a ring.  ``mask`` / ``lag`` are ``(..., K)`` (any
    leading batch axes, e.g. the multi-job J axis)."""
    decay = jnp.asarray([alpha ** (s + 1) for s in range(S)], jnp.float32)
    lag_rows = jnp.arange(1, S + 1, dtype=jnp.int32)
    return mask[..., None, :] * (lag[..., None, :] == lag_rows[:, None]) * decay[:, None]


def staleness_ring_step(pending, mask, lag, S: int, alpha: float):
    """One update of the bounded staleness-credit ring; returns
    ``(arriving, new_pending)``.  ``S=0`` is the synchronous no-ring case
    (nothing arrives, pending unchanged)."""
    if S == 0:
        return jnp.zeros_like(mask), pending
    return ring_pop_push(pending, lag_credit_schedule(mask, lag, S, alpha))


# ---------------------------------------------------------------------------
# Placement contexts: what differs between dense and K-sharded execution
# ---------------------------------------------------------------------------
#
# ``ctx.select(state, key)`` returns ``(idx, p, capped, sigma, mask, ties)``.
# The E3CS Plackett-Luce round on one placement (``_LocalCtx`` unfused, and
# ``_ShardCtx`` at D=1) writes the mask by a threshold select
# (``repro.kernels.radix_select``) where its kernel runs
# (``threshold_select_route``: a TPU) and returns ``idx=None``: nothing
# downstream reads ids there.  ``ties`` feeds the ``topk_ties`` gauge.


class _LocalCtx:
    """Dense single-placement stage context (the D=1 reference)."""

    def __init__(self, program: "RoundProgram"):
        fl = program.fl
        self.K_loc = fl.K
        self.active = None
        self.e3cs_kwargs = {}
        K, k = fl.K, fl.k

        if program.fused:
            # fused allocate-epilogue + perturb + top-k: the Gumbel field is
            # drawn with the staged sampler's exact call so the staged and
            # fused engines consume identical noise (bit-identity contract)
            allocator = getattr(fl, "allocator", "sort")
            quota_fn = program.quota_fn
            tile = program.tile

            def select(state, rng):
                sigma = quota_fn(state.t)
                g = jax.random.gumbel(rng, (K,), jnp.float32)
                if allocator == "bisect":
                    with stage("round.allocate"):
                        w = jnp.exp(state.e3cs.logw - jnp.max(state.e3cs.logw))
                        scalars = masked_prob_alloc_scalars(w, k, sigma)
                    with stage("round.sample"):
                        p, capped, _, idx = fused_alloc_select(
                            w, g, k, sigma=sigma, scalars=scalars, tile=tile
                        )
                else:
                    with stage("round.allocate"):
                        p, capped = e3cs_probs(state.e3cs, k, sigma)
                    with stage("round.sample"):
                        _, idx = fused_perturb_select(p, g, k, tile=tile)
                return idx, p, capped, sigma, selection_mask(idx, K), _NO_TIES

        elif fl.scheme == "e3cs" and fl.sampler == "plackett_luce" and threshold_select_route():
            # the cohort mask straight from the score field: a threshold
            # select in place of lax.top_k's sort (nothing here reads ids)
            alloc = make_alloc_fn(fl)
            quota_fn = program.quota_fn

            def select(state, rng):
                sigma = quota_fn(state.t)
                p, capped = alloc(state, sigma)
                with stage("round.sample"):
                    mask, ties = topk_mask(perturbed_scores(rng, p), k)
                return None, p, capped, sigma, mask, ties

        else:
            base = make_select_fn(fl, program.quota_fn, program.rho)

            def select(state, rng):
                idx, p, capped, sigma = base(state, rng)
                return idx, p, capped, sigma, selection_mask(idx, K), _NO_TIES

        self.select = select
        self.observe = _make_observe(program, K_loc=K, fold=lambda key: key)

    @staticmethod
    def psum(v):
        return v

    @staticmethod
    def pmax(v):
        return v

    @staticmethod
    def gather(x):
        return x


class _ShardCtx:
    """Per-shard stage context, built *inside* the ``shard_map`` body (it
    closes over the traced shard index)."""

    def __init__(self, program: "RoundProgram", vol_loc, rho_full, active_loc, Ks: int, D: int):
        fl = program.fl
        axis_name = program.axis_name
        d = jax.lax.axis_index(axis_name)
        K, k, scheme = fl.K, fl.k, fl.scheme
        self.K_loc = Ks
        self.active = active_loc
        self.e3cs_kwargs = dict(K=K, axis_name=axis_name, active=active_loc)
        quota_fn = program.quota_fn
        threshold = D == 1 and threshold_select_route()

        def select(state, k1):
            sigma = quota_fn(state.t)
            capped = jnp.zeros((Ks,), bool)
            if scheme == "e3cs":
                logw = state.e3cs.logw
                gmax = jax.lax.pmax(
                    jnp.max(jnp.where(active_loc > 0, logw, -jnp.inf)), axis_name
                )
                w = jnp.exp(logw - gmax) * active_loc
                if program.fused:
                    # one VMEM pass: allocation epilogue + perturb + local
                    # top-k; only the bisection scalars and the (D, k)
                    # candidate merge cross shards
                    k_sel = jax.random.fold_in(k1, d) if D > 1 else k1
                    g = jax.random.gumbel(k_sel, (Ks,), jnp.float32)
                    with stage("round.allocate"):
                        scalars = masked_prob_alloc_scalars(
                            w, k, sigma, active=active_loc, n_iters=program.n_iters,
                            tile=program.tile, axis_name=axis_name, block=program.block,
                        )
                    with stage("round.sample"):
                        p, capped, vals, loc = fused_alloc_select(
                            w, g, k, sigma=sigma, scalars=scalars, active=active_loc,
                            tile=program.tile,
                        )
                        idx = _exchange_topk(vals, loc + jnp.asarray(d * Ks, jnp.int32), k, axis_name)
                else:
                    with stage("round.allocate"):
                        p, capped = masked_prob_alloc(
                            w, k, sigma, active=active_loc, n_iters=program.n_iters,
                            tile=program.tile, axis_name=axis_name, block=program.block,
                        )
                    if threshold:
                        # one shard holds every client: the threshold
                        # select writes the mask (no candidate merge)
                        with stage("round.sample"):
                            scores = jnp.where(active_loc > 0, perturbed_scores(k1, p), -jnp.inf)
                            mask, ties = topk_mask(scores, k)
                        return None, p, capped, sigma, mask, ties
                    k_sel = jax.random.fold_in(k1, d) if D > 1 else k1
                    with stage("round.sample"):
                        scores = jnp.where(active_loc > 0, perturbed_scores(k_sel, p), -jnp.inf)
                        idx = _shard_topk_merge(scores, k, axis_name)
            elif scheme == "random":
                idx = random_select(k1, K, k)
            elif scheme == "fedcs":
                idx = fedcs_select(rho_full, k, k1)
            elif scheme == "ucb":
                idx = ucb_select(state.ucb, k)
            elif scheme == "pow_d":
                loss_full = jax.lax.all_gather(state.loss_cache, axis_name, tiled=True)[:K]
                idx = pow_d_select(k1, loss_full, k, fl.pow_d)
            else:
                raise ValueError(fl.scheme)
            loc = idx - d * Ks
            valid = (loc >= 0) & (loc < Ks)
            mask = jnp.zeros((Ks,), jnp.float32).at[jnp.clip(loc, 0, Ks - 1)].max(
                valid.astype(jnp.float32)
            )
            if scheme == "random":
                p = jnp.full((Ks,), k / K)
            elif scheme != "e3cs":
                p = mask
            return idx, p, capped, sigma, mask, _NO_TIES

        self.select = select
        fold = (lambda key: jax.random.fold_in(key, d)) if D > 1 else (lambda key: key)
        self.observe = _make_observe(program, K_loc=Ks, fold=fold, vol=vol_loc)
        self.psum = lambda v: jax.lax.psum(v, axis_name)
        self.pmax = lambda v: jax.lax.pmax(v, axis_name)
        self.gather = lambda x: jax.lax.all_gather(x, axis_name, tiled=True)[:K]


def _make_observe(program: "RoundProgram", K_loc: int, fold, vol=None):
    """The observe stage: success bits (sync) or completion lags (async)
    from the program's configured source.  ``k2`` follows the shared PRNG
    discipline even when the source is a trace (the split still happens, the
    key is simply unused) so replayed runs stay bit-identical to generated
    ones given identical outcomes."""
    mode = program.override
    vol = program.vol if vol is None else vol
    is_async = program.staleness is not None

    if mode == "none":

        def observe(x_over, k2, vs):
            return vol.sample(fold(k2), vs)

    elif mode == "dense":
        cast = (lambda x: jnp.asarray(x, jnp.int32)) if is_async else (lambda x: x)

        def observe(x_over, k2, vs):
            return cast(x_over), vs

    elif mode == "packed":

        def observe(x_over, k2, vs):
            return unpack_bits(x_over, K_loc), vs

    else:  # packed_lags

        def observe(x_over, k2, vs):
            codes = unpack_crumbs(x_over, K_loc)
            return jnp.where(codes == _LAG_DEAD_CODE, DEAD_LAG, codes), vs

    return observe


# ---------------------------------------------------------------------------
# The one round body
# ---------------------------------------------------------------------------


def _make_step(program: "RoundProgram", ctx, lean: bool, taps: bool = False,
               sketch: Optional[SketchSpec] = None, region=None):
    """Assemble the scan body from the program's stages and a placement
    context.  This is the single copy of the round pipeline; every engine
    entry point scans (or host-steps) exactly this function.

    Sync carry is ``(state, key)``; async carry is ``(state, key, rings)``
    where ``rings`` is ``(credit,)`` or ``(credit, feedback)`` — see
    ``RoundProgram.init_rings``.  With ``taps=True`` the carry additionally
    threads the ``ROUND_TAPS`` counter pytree as a trailing element and each
    round emits its gauge row as a trailing scan output.  Taps observe
    values the round already computes (psum-reduced under a mesh, so every
    placement emits the identical replicated scalars) and never touch the
    PRNG stream or the state math — taps-on runs are bit-identical to the
    goldens (pinned in ``tests/test_obs.py``).

    With ``sketch=<SketchSpec>`` (requires taps) the carry further threads
    the per-shard sketch accumulators and each round emits a trailing
    *local* sketch row — zeros except every ``sketch.window``-th round,
    gated on the global ``state.t`` (``repro.obs.sketches``).  The runner
    merges shards with one post-scan psum and windows the stream; like
    taps, sketches never touch the round's math or PRNG stream.  ``region``
    is the (K_loc,) int32 region-id slab (defaults to the spec's global
    layout — the sharded runner passes the shard slice).
    """
    fl = program.fl
    k, scheme, eta, K_glob = fl.k, fl.scheme, fl.eta, fl.K
    sync = program.staleness is None
    S = 0 if sync else int(program.staleness)
    alpha = program.alpha
    late_fb = (not sync) and program.feedback == "late_credit" and scheme == "e3cs" and S > 0
    fused = program.fused
    if fused:
        # static per-slot credit schedule + in-kernel observe decode kind
        decay = tuple(alpha ** (s + 1) for s in range(S))
        if program.override == "packed":
            kind = "bits"
        elif program.override == "packed_lags":
            kind = "crumbs"
        else:
            kind = "x" if sync else "lag"
    if sketch is not None:
        L = lag_bins(program.staleness)
        if region is None:
            region = jnp.asarray(region_ids(sketch, ctx.K_loc))

    def tap_row(mask, x, sigma, capped, ties, arriving=None):
        stale = jnp.zeros((), jnp.float32) if arriving is None else ctx.psum(jnp.sum(arriving))
        return {
            "selected": ctx.psum(jnp.sum(mask)),
            "on_time": ctx.psum(jnp.vdot(mask, x)),
            "stale": stale,
            "sigma": jnp.asarray(sigma, jnp.float32),
            "capped_frac": ctx.psum(jnp.sum(capped.astype(jnp.float32))) / K_glob,
            "topk_ties": jnp.asarray(ties, jnp.float32),  # global already (one shard)
        }

    def step(carry, x_over):
        tapc = skc = None
        if sync:
            if sketch is not None:
                (state, key, tapc, skc) = carry
            elif taps:
                (state, key, tapc) = carry
            else:
                (state, key) = carry
        else:
            if sketch is not None:
                (state, key, rings, tapc, skc) = carry
            elif taps:
                (state, key, rings, tapc) = carry
            else:
                (state, key, rings) = carry
        key, k1, k2 = jax.random.split(key, 3)
        # allocate + select
        with stage("round.select"):
            idx, p, capped, sigma, mask, ties = ctx.select(state, k1)
        if fused:
            # observe-decode + Eq. 16/17 elementwise + credit rings in ONE
            # fused pass (repro.kernels.round_fused); only the recenter —
            # which needs a cross-tile / cross-shard max — stays out here
            with stage("round.observe"):
                if kind in ("bits", "crumbs"):
                    obs, vs = x_over, state.vol_state  # raw bytes decode in-kernel
                else:
                    obs, vs = ctx.observe(x_over, k2, state.vol_state)
            with stage("round.update"):
                residual = jnp.asarray(k, p.dtype) - K_glob * sigma
                tail = fused_round_tail(
                    obs, mask, p, capped, state.e3cs.logw, state.loss_cache,
                    rings[0] if (not sync and S > 0) else None,
                    rings[1] if late_fb else None,
                    kind=kind, residual=residual, eta=eta, K_glob=K_glob,
                    decay=decay, active=ctx.active, tile=program.tile,
                )
                x = tail["x"]
                logw = tail["logw_pre"] - ctx.pmax(tail["m"])
                if ctx.active is not None:
                    logw = logw * ctx.active
                e3cs = E3CSState(logw=logw, t=state.e3cs.t + 1)
                loss_cache = tail["loss_cache"]
                ucb = state.ucb
            if not sync:
                lag = tail["lag"]
                with stage("round.credit"):
                    if S == 0:
                        arriving, new_rings = jnp.zeros_like(mask), (rings[0],)
                    else:
                        arriving, new_rings = tail["arriving"], (tail["credit"],)
                    if late_fb:
                        logw = e3cs.logw + tail["arr_fb"]
                        m = jnp.max(logw) if ctx.active is None else jnp.max(
                            jnp.where(ctx.active > 0, logw, -jnp.inf)
                        )
                        logw = logw - ctx.pmax(m)
                        if ctx.active is not None:
                            logw = logw * ctx.active
                        e3cs = e3cs._replace(logw=logw)
                        new_rings = new_rings + (tail["fb"],)
        else:
            # observe
            with stage("round.observe"):
                obs, vs = ctx.observe(x_over, k2, state.vol_state)
            if sync:
                x = obs
            else:
                lag = obs
                x = (lag == 0).astype(jnp.float32)  # deadline-based selector feedback
            # update (selector state; Eq. 16/17 lives in e3cs_update)
            with stage("round.update"):
                e3cs = state.e3cs
                if scheme == "e3cs":
                    e3cs = e3cs_update(state.e3cs, p, capped, mask, x, k, sigma, eta, **ctx.e3cs_kwargs)
                loss_cache = jnp.where(mask > 0, 1.0 - x, state.loss_cache)  # pow-d loss proxy
                ucb = state.ucb
                if scheme == "ucb":
                    ucb = ucb_update(state.ucb, idx, ctx.gather(x))
        if sync:
            state = state._replace(
                e3cs=e3cs, ucb=ucb, vol_state=vs, t=state.t + 1,
                sel_counts=state.sel_counts + mask, loss_cache=loss_cache,
            )
            out = (ctx.psum(jnp.vdot(mask, x)), sigma) if lean else (mask, x, p, sigma)
            if taps:
                row = tap_row(mask, x, sigma, capped, ties)
                new_tapc = ROUND_TAPS.accumulate(tapc, row)
                if sketch is not None:
                    skc2, sk_row = sketch_step(
                        sketch, skc, mask, x, None, p, state.sel_counts, state.t,
                        region, ctx.active, L,
                    )
                    return (state, key, new_tapc, skc2), out + (row, sk_row)
                return (state, key, new_tapc), out + (row,)
            return (state, key), out
        # credit: pop this round's arrivals, push the new late completions
        # (the fused path already did this inside the tail kernel)
        if not fused:
            with stage("round.credit"):
                if S == 0:
                    arriving, pending = jnp.zeros_like(mask), rings[0]
                else:
                    sched = lag_credit_schedule(mask, lag, S, alpha)
                    arriving, pending = ring_pop_push(rings[0], sched)
                new_rings = (pending,)
                if late_fb:
                    # buffer the selection-round importance weight next to the credit
                    # ring: the arriving slot is a ready-to-apply log-weight step
                    # (same residual/clamp as e3cs_update, decayed reward alpha**lag;
                    # the schedule rows are shared with the credit ring above)
                    xhat_rows = sched / jnp.maximum(p, 1e-12)
                    residual = jnp.asarray(k, p.dtype) - K_glob * sigma
                    rows = jnp.minimum(residual * eta * xhat_rows / K_glob, 1.0)
                    frozen = capped if ctx.active is None else capped | (ctx.active == 0)
                    rows = jnp.where(frozen, 0.0, rows)
                    arriving_fb, fb = ring_pop_push(rings[1], rows)
                    logw = e3cs.logw + arriving_fb
                    m = jnp.max(logw) if ctx.active is None else jnp.max(
                        jnp.where(ctx.active > 0, logw, -jnp.inf)
                    )
                    logw = logw - ctx.pmax(m)
                    if ctx.active is not None:
                        logw = logw * ctx.active
                    e3cs = e3cs._replace(logw=logw)
                    new_rings = (pending, fb)
        on_time = ctx.psum(jnp.vdot(mask, x))
        stale = ctx.psum(jnp.sum(arriving))
        state = state._replace(
            e3cs=e3cs, ucb=ucb, vol_state=vs, t=state.t + 1,
            sel_counts=state.sel_counts + mask, loss_cache=loss_cache,
            cep=state.cep + on_time + stale, succ_hist=state.succ_hist + on_time,
        )
        out = (on_time, stale, sigma) if lean else (mask, lag, p, sigma, arriving)
        if taps:
            row = tap_row(mask, x, sigma, capped, ties, arriving)
            new_tapc = ROUND_TAPS.accumulate(tapc, row)
            if sketch is not None:
                skc2, sk_row = sketch_step(
                    sketch, skc, mask, x, lag, p, state.sel_counts, state.t,
                    region, ctx.active, L,
                )
                return (state, key, new_rings, new_tapc, skc2), out + (row, sk_row)
            return (state, key, new_rings, new_tapc), out + (row,)
        return (state, key, new_rings), out

    return step


# ---------------------------------------------------------------------------
# Sharded volatility-model plumbing (nested dataclasses, e.g. CompletionLag)
# ---------------------------------------------------------------------------


def _collect_k_fields(vol, K: int, prefix: str = "") -> dict:
    """Dotted names of the model's per-client ``(K, ...)`` array fields,
    recursing into nested dataclass fields (``CompletionLag.base.rho``)."""
    if not dataclasses.is_dataclass(vol):
        raise TypeError(
            f"sharded rounds need a dataclass volatility model with (K,)-indexed "
            f"array fields (bernoulli / markov / deadline, or a lag wrapper over "
            f"one), got {type(vol).__name__}; replay traces through "
            f"override='packed' / 'packed_lags' instead"
        )
    out = {}
    for f in dataclasses.fields(vol):
        v = getattr(vol, f.name)
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            out.update(_collect_k_fields(v, K, prefix + f.name + "."))
        elif hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1 and v.shape[0] == K:
            out[prefix + f.name] = jnp.asarray(v)
    return out


def _rebuild_vol(vol, arrs: dict):
    """Replace the (possibly nested) fields named by ``_collect_k_fields``
    with their per-shard slabs."""
    if not arrs:
        return vol
    groups: dict = {}
    for name, a in arrs.items():
        head, _, rest = name.partition(".")
        if rest:
            groups.setdefault(head, {})[rest] = a
        else:
            groups[head] = a
    kw = {
        head: _rebuild_vol(getattr(vol, head), v) if isinstance(v, dict) else v
        for head, v in groups.items()
    }
    return dataclasses.replace(vol, **kw)


# ---------------------------------------------------------------------------
# RoundProgram
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RoundProgram:
    """A composed round pipeline; see the module docstring for the axes.

    ``vol`` is the observe model: a success-bit ``(init_state, sample)``
    implementer when synchronous, a *lag* model when ``staleness`` is set
    (its pytree state rides in the scan carry either way).  For trace
    overrides it only seeds ``vol_state`` (bits come from the trace).
    ``base_vol`` optionally records the underlying success-bit model a lag
    model wraps (``from_config`` fills it) — the training server evaluates
    against it.
    """

    fl: FLConfig
    vol: object
    rho: object
    override: str = "none"
    staleness: Optional[int] = None
    alpha: float = 0.5
    feedback: str = "deadline"
    mesh: Optional[object] = None
    axis_name: str = "shards"
    n_iters: int = 48
    tile: int = 8192
    block: int = 1
    fused: bool = False
    base_vol: object = None
    quota_fn: object = None  # override; default derives the schedule from fl

    def __post_init__(self):
        if self.fused:
            if self.fl.scheme != "e3cs":
                raise ValueError(
                    "fused=True fuses the E3CS allocate/perturb/update stages; "
                    f"scheme {self.fl.scheme!r} has nothing to fuse"
                )
            if self.fl.sampler != "plackett_luce":
                raise ValueError(
                    "fused=True implements the plackett_luce (Gumbel top-k) sampler only"
                )
        if self.override not in OBSERVE_MODES:
            raise ValueError(f"unknown override mode {self.override!r} (want one of {OBSERVE_MODES})")
        if self.feedback not in FEEDBACK_MODES:
            raise ValueError(f"unknown feedback policy {self.feedback!r} (want one of {FEEDBACK_MODES})")
        if self.staleness is None and self.override == "packed_lags":
            raise ValueError("override='packed_lags' replays completion lags; it needs staleness=S (async rounds)")
        if self.staleness is not None and self.override == "packed":
            raise ValueError("async rounds replay 2-bit lag traces: use override='packed_lags', not 'packed'")
        if self.feedback == "late_credit" and self.staleness is None:
            raise ValueError(
                "feedback='late_credit' buffers selection-round allocations in the staleness "
                "ring; it needs staleness=S (S=0 degenerates to deadline feedback)"
            )
        self.rho = jnp.asarray(self.rho, jnp.float32) if self.rho is not None else None
        # materialise the quota schedule OUTSIDE any jit trace: the sharded
        # runner builds its stage context inside the shard_map body, and a
        # schedule first constructed under a trace would cache tracer-backed
        # constants on the program (leaking into later compilations)
        if self.quota_fn is None:
            fl = self.fl
            self.quota_fn = make_quota_schedule(fl.quota, fl.k, fl.K, fl.rounds, fl.quota_frac)

    # -- single knob-resolution path -------------------------------------

    @classmethod
    def from_config(
        cls,
        fl_cfg: FLConfig,
        volatility=None,
        mesh=None,
        feedback: str = "deadline",
        override: str = "none",
        **engine_opts,
    ) -> "RoundProgram":
        """Resolve an ``FLConfig`` (plus an optional volatility override and
        mesh) into a program — the ONE place staleness / allocator /
        volatility knobs are interpreted.  ``repro.fl.FLServer`` and the
        ``repro.launch.select_serve`` drivers both construct through here,
        with a regression test pinning that they cannot drift.

        * volatility: ``fl_cfg.volatility`` (or the ``volatility`` argument)
          resolved by ``repro.fl.server.build_volatility`` — builtin name,
          scenario name, or model object.
        * staleness: ``fl_cfg.staleness_rounds > 0`` wraps the model in
          ``CompletionLag(late_prob, lag_decay, max_lag=S)`` and selects the
          async round body; 0 is the synchronous program.
        * allocator/mesh: a mesh forces the sort-free ``"bisect"`` allocator
          (the sharded round has no sorted path), so the D=1 dense reference
          of a sharded program is ``allocator="bisect"`` by construction.
        """
        from repro.core.volatility import CompletionLag
        from repro.fl.server import build_volatility  # deferred: fl.server imports this module

        vol, rho = build_volatility(fl_cfg, fl_cfg.K, volatility=volatility)
        if mesh is not None and fl_cfg.allocator != "bisect":
            fl_cfg = dataclasses.replace(fl_cfg, allocator="bisect")
        S = int(fl_cfg.staleness_rounds)
        base_vol = vol
        staleness: Optional[int] = None
        if S > 0:
            staleness = S
            vol = CompletionLag(
                vol, p_late=fl_cfg.late_prob, lag_decay=fl_cfg.lag_decay, max_lag=S
            )
        return cls(
            fl=fl_cfg, vol=vol, rho=rho, override=override, staleness=staleness,
            alpha=float(fl_cfg.staleness_alpha), feedback=feedback, mesh=mesh,
            base_vol=base_vol, **engine_opts,
        )

    # -- derived pieces ---------------------------------------------------

    @property
    def lag_model(self):
        """The lag model driving async rounds (None when synchronous)."""
        return self.vol if self.staleness is not None else None

    def select_fn(self):
        """The dense per-round ``select(state, rng) -> (idx, p, capped,
        sigma)`` — the allocate+select stages for host-driven loops (the FL
        training server gathers cohort data between select and train)."""
        return make_select_fn(self.fl, self.quota_fn, self.rho)

    def init_rings(self, K_loc: Optional[int] = None):
        """Zeroed async carry rings: ``(credit,)``, plus the buffered
        feedback ring under ``feedback='late_credit'``.  The per-client
        width defaults to what the program's placement needs — ``fl.K``
        dense, the shard-padded ``K_pad`` under a mesh — so the rings drop
        straight into a ``carry_key`` runner; ``K_loc`` overrides it."""
        S = 0 if self.staleness is None else int(self.staleness)
        if K_loc is None:
            K = self.fl.K if self.mesh is None else self._sharded_geometry()[0]
        else:
            K = int(K_loc)
        rings = (jnp.zeros((S, K), jnp.float32),)
        if self.feedback == "late_credit" and self.fl.scheme == "e3cs" and S > 0:
            rings = rings + (jnp.zeros((S, K), jnp.float32),)
        return rings

    def build_step(self, lean: bool = False, taps: bool = False):
        """The dense scan body ``step(carry, x_over)`` plus its initial
        state — what ``core.sim.selection_sim_loop`` host-steps per round and
        ``build_runner`` scans over the horizon.  With ``taps=True`` the
        carry gains a trailing ``ROUND_TAPS.init_counters()`` pytree and the
        per-round output a trailing gauge row (see ``_make_step``); taps off
        leaves the carry contract exactly as before."""
        if self.mesh is not None:
            raise ValueError("build_step is the dense body; sharded programs compile via build_runner")
        step = _make_step(self, _LocalCtx(self), lean, taps)
        state0 = init_server_state({}, self.fl.K, self.vol.init_state())
        return step, state0

    # -- compiled whole-horizon runners ----------------------------------

    def build_runner(
        self,
        outputs: str = "full",
        carry_key: bool = False,
        scan_length: Optional[int] = None,
        taps: bool = False,
        sketch: Optional[SketchSpec] = None,
    ):
        """Compile the program over a whole horizon; returns ``(run, state0)``.

        Output contracts (the historical ``build_scan_runner`` ones):

        * sync  full — ``run(state, key, xs_in) -> (state, masks, xs, ps, sigmas)``
        * sync  lean — ``... -> (state, successes, sigmas)``
        * async full — ``... -> (state, masks, lags, ps, sigmas, arrived)``
        * async lean — ``... -> (state, on_time, stale, sigmas)``

        ``carry_key=True`` threads the PRNG key (and, async, the rings)
        through the signature so chunked/streamed horizons resume
        bit-identically: sync becomes ``run(state, key, xs_in) -> (state,
        key, *outs)``; async becomes ``run(state, key, rings, xs_in) ->
        (state, key, rings, *outs)`` (seed rings with ``init_rings``).
        ``scan_length`` scans that many rounds instead of ``fl.rounds`` (the
        quota schedule always spans ``fl.rounds``).

        Under a mesh, per-client state, trace rows and outputs are padded to
        ``K_pad`` (a multiple of D, of 8·D for ``"packed"``, of 4·D for
        ``"packed_lags"``); slice ``[:K]``.

        ``taps=True`` appends one trailing payload to every contract above:
        ``{"series": {gauge: (T,)}, "counters": {counter: scalar}}`` — the
        ``ROUND_TAPS`` schema, identical for every placement.  With
        ``carry_key=True`` the taps counters thread through the streamed
        carry instead: seed them with ``ROUND_TAPS.init_counters()`` and the
        signature becomes sync ``run(state, key, tapc, xs_in) -> (state,
        key, tapc, *outs, series)`` / async ``run(state, key, rings, tapc,
        xs_in) -> (state, key, rings, tapc, *outs, series)``, where
        ``series`` is the per-chunk ``{gauge: (T,)}`` row dict — concatenate
        chunks host-side (``repro.scenarios.replay.replay_packed_stream``
        does); chunked and one-shot streams are bit-identical.

        ``sketch=<SketchSpec>`` (requires ``taps=True``, one-shot only)
        additionally runs the client-axis sketch stage
        (``repro.obs.sketches``): the taps payload gains a ``"sketches"``
        key mapping ``SKETCH_FIELDS`` to ``(T // window, ...)`` streams —
        psum-merged under a mesh, so every placement emits the identical
        stream, and bit-identical to sketches-off runs on every other
        output.
        """
        if outputs not in ("full", "lean"):
            raise ValueError(f"unknown outputs mode {outputs!r} (want 'full' or 'lean')")
        if sketch is not None and not taps:
            raise ValueError("sketch streams ride the taps stage; pass taps=True")
        if sketch is not None and carry_key:
            raise ValueError(
                "sketch streams are one-shot (the windowed emission is sliced in-jit); "
                "chunked carry_key horizons stream taps counters instead"
            )
        lean = outputs == "lean"
        T = self.fl.rounds if scan_length is None else int(scan_length)
        if self.mesh is None:
            return self._build_local_runner(lean, carry_key, T, taps, sketch)
        return self._build_sharded_runner(lean, carry_key, T, taps, sketch)

    def _build_local_runner(self, lean: bool, carry_key: bool, T: int, taps: bool,
                            sketch: Optional[SketchSpec] = None):
        step = _make_step(self, _LocalCtx(self), lean, taps, sketch)
        state0 = init_server_state({}, self.fl.K, self.vol.init_state())
        sync = self.staleness is None
        tap0 = ROUND_TAPS.init_counters() if taps else None
        if sketch is not None:
            W = sketch.window
            sk0 = sketch_carry0(self.fl.K, lag_bins(self.staleness))

        if sync:
            if sketch is not None:

                @jax.jit
                def run_sketch(state, key, xs_in):
                    (state, key, tapc, _), out = jax.lax.scan(
                        step, (state, key, tap0, sk0), xs_in, length=T
                    )
                    *outs, row, sk = out
                    stream = jax.tree.map(lambda a: a[W - 1 :: W], sk)
                    return (state, *outs, {"series": row, "counters": tapc, "sketches": stream})

                return run_sketch, state0

            if taps and carry_key:

                @jax.jit
                def run_stream(state, key, tapc, xs_in):
                    (state, key, tapc), out = jax.lax.scan(step, (state, key, tapc), xs_in, length=T)
                    *outs, row = out
                    return (state, key, tapc, *outs, row)

                return run_stream, state0

            if taps:

                @jax.jit
                def run_taps(state, key, xs_in):
                    (state, key, tapc), out = jax.lax.scan(step, (state, key, tap0), xs_in, length=T)
                    *outs, row = out
                    return (state, *outs, {"series": row, "counters": tapc})

                return run_taps, state0

            @jax.jit
            def run(state, key, xs_in):
                (state, key), out = jax.lax.scan(step, (state, key), xs_in, length=T)
                head = (state, key) if carry_key else (state,)
                return (*head, *out)

            return run, state0

        init_rings = self.init_rings

        if sketch is not None:

            @jax.jit
            def run_async(state, key, xs_in):
                (state, key, _, tapc, _), out = jax.lax.scan(
                    step, (state, key, init_rings(), tap0, sk0), xs_in, length=T
                )
                *outs, row, sk = out
                stream = jax.tree.map(lambda a: a[W - 1 :: W], sk)
                return (state, *outs, {"series": row, "counters": tapc, "sketches": stream})

        elif taps and carry_key:

            @jax.jit
            def run_async(state, key, rings, tapc, xs_in):
                (state, key, rings, tapc), out = jax.lax.scan(
                    step, (state, key, rings, tapc), xs_in, length=T
                )
                *outs, row = out
                return (state, key, rings, tapc, *outs, row)

        elif carry_key:

            @jax.jit
            def run_async(state, key, rings, xs_in):
                (state, key, rings), out = jax.lax.scan(step, (state, key, rings), xs_in, length=T)
                return (state, key, rings, *out)

        elif taps:

            @jax.jit
            def run_async(state, key, xs_in):
                (state, key, _, tapc), out = jax.lax.scan(
                    step, (state, key, init_rings(), tap0), xs_in, length=T
                )
                *outs, row = out
                return (state, *outs, {"series": row, "counters": tapc})

        else:

            @jax.jit
            def run_async(state, key, xs_in):
                (state, key, _), out = jax.lax.scan(step, (state, key, init_rings()), xs_in, length=T)
                return (state, *out)

        return run_async, state0

    def _sharded_geometry(self):
        """(K_pad, Ks, width, D): padded population, per-shard width, xs row
        width, mesh size — the byte-packed modes pad K to whole shard bytes."""
        fl, D = self.fl, _axis_size(self.mesh, self.axis_name)
        K = fl.K
        if self.override in ("packed", "packed_lags"):
            cpb = 8 if self.override == "packed" else 4  # clients per byte
            B_loc = -(-((K + cpb - 1) // cpb) // D)
            return cpb * B_loc * D, cpb * B_loc, B_loc * D, D
        K_pad = D * (-(-K // D))
        width = K_pad if self.override == "dense" else D
        return K_pad, K_pad // D, width, D

    def _build_sharded_runner(self, lean: bool, carry_key: bool, T: int, taps: bool,
                              sketch: Optional[SketchSpec] = None):
        fl, axis_name = self.fl, self.axis_name
        K, k, scheme = fl.K, fl.k, fl.scheme
        sync = self.staleness is None
        S = 0 if sync else int(self.staleness)
        if scheme == "e3cs" and fl.sampler != "plackett_luce":
            raise ValueError("the sharded engine only implements the plackett_luce sampler")
        K_pad, Ks, width, D = self._sharded_geometry()
        if scheme == "e3cs" and k > Ks:
            raise ValueError(f"k={k} exceeds the shard width {Ks}; need k <= K_pad/D for per-shard top-k")
        active = (jnp.arange(K_pad) < K).astype(jnp.float32)
        vol_arrays = (
            {n: _pad0(a, K_pad) for n, a in _collect_k_fields(self.vol, K).items()}
            if self.override == "none"
            else {}
        )
        vs0 = jax.tree.map(
            lambda a: _pad0(a, K_pad) if getattr(a, "ndim", 0) >= 1 and a.shape[0] == K else a,
            self.vol.init_state(),
        )
        vs_spec = jax.tree.map(
            lambda a: P(axis_name) if getattr(a, "ndim", 0) >= 1 and a.shape[0] == K_pad else P(), vs0
        )
        rho_rep = self.rho if scheme == "fedcs" else jnp.zeros((1,), jnp.float32)

        state0 = ServerState(
            params={},
            e3cs=e3cs_init(K_pad),
            ucb=ucb_init(K),  # replicated (small selector state)
            loss_cache=jnp.full((K_pad,), 1e9, jnp.float32),
            vol_state=vs0,
            t=jnp.zeros((), jnp.int32),
            sel_counts=jnp.zeros((K_pad,), jnp.float32),
            cep=jnp.zeros((), jnp.float32),
            succ_hist=jnp.zeros((), jnp.float32),
        )
        state_spec = ServerState(
            params={},
            e3cs=E3CSState(logw=P(axis_name), t=P()),
            ucb=jax.tree.map(lambda _: P(), state0.ucb),
            loss_cache=P(axis_name),
            vol_state=vs_spec,
            t=P(),
            sel_counts=P(axis_name),
            cep=P(),
            succ_hist=P(),
        )
        rings0 = self.init_rings() if not sync else ()  # sized (S, K_pad) via the mesh geometry
        rings_spec = tuple(P(None, axis_name) for _ in rings0)
        # tap rows/counters are psum-reduced inside the body -> replicated P()
        tap0 = ROUND_TAPS.init_counters() if taps else {}
        tap_spec = {n: P() for n in tap0}
        row_spec = {n: P() for n in ROUND_TAPS.gauge_names()}
        # the sketch stream is psum-merged after the scan -> replicated P();
        # the per-shard sketch carry never crosses the shard_map boundary
        if sketch is not None:
            W = sketch.window
            L = lag_bins(self.staleness)
            region_pad = jnp.asarray(_pad0(jnp.asarray(region_ids(sketch, K)), K_pad), jnp.int32)
            sk_spec = {n: P() for n in SKETCH_FIELDS}
        else:
            region_pad = jnp.zeros((K_pad,), jnp.int32)
        program = self

        def horizon(state, key, rings, tapc, xs, vol_arr, rho_full, active_loc, region_loc):
            vol_loc = _rebuild_vol(program.vol, vol_arr)
            ctx = _ShardCtx(program, vol_loc, rho_full, active_loc, Ks, D)
            step = _make_step(program, ctx, lean, taps, sketch,
                              region_loc if sketch is not None else None)
            tail = (tapc,) if taps else ()
            if sketch is not None:
                tail = tail + (sketch_carry0(Ks, L),)
            carry0 = ((state, key) if sync else (state, key, rings)) + tail
            carry, out = jax.lax.scan(step, carry0, xs, length=T)
            new_rings = () if sync else carry[2]
            new_tapc = (carry[2] if sync else carry[3]) if taps else {}
            if sketch is not None:
                *rest, sk = out
                sk = jax.tree.map(lambda a: jax.lax.psum(a, axis_name), sk)
                sk = jax.tree.map(lambda a: a[W - 1 :: W], sk)
                out = tuple(rest) + (sk,)
            return (carry[0], carry[1], new_rings, new_tapc) + out

        if sync:
            out_specs = (P(), P()) if lean else (P(None, axis_name),) * 3 + (P(),)
        else:
            out_specs = (P(), P(), P()) if lean else (
                P(None, axis_name), P(None, axis_name), P(None, axis_name), P(), P(None, axis_name)
            )
        if taps:
            out_specs = out_specs + (row_spec,)
        if sketch is not None:
            out_specs = out_specs + (sk_spec,)
        shm = _shmap(
            horizon,
            self.mesh,
            in_specs=(
                state_spec, P(), rings_spec, tap_spec, P(None, axis_name),
                {n: P(axis_name) for n in vol_arrays}, P(), P(axis_name), P(axis_name),
            ),
            out_specs=(state_spec, P(), rings_spec, tap_spec) + out_specs,
        )
        pad_dtype = {"dense": jnp.int32 if not sync else jnp.float32}.get(self.override, jnp.uint8)

        def _pad_xs(xs_in):
            if self.override == "none":
                return jnp.zeros((T, D), jnp.float32)  # ignored; keeps one scan signature
            xs = jnp.asarray(xs_in, pad_dtype)
            return jnp.pad(xs, ((0, 0), (0, width - xs.shape[1])))

        def _finish(state, tapc, out):
            if not taps:
                return (state, *out)
            if sketch is not None:
                *outs, row, sk = out
                return (state, *outs, {"series": row, "counters": tapc, "sketches": sk})
            *outs, row = out
            return (state, *outs, {"series": row, "counters": tapc})

        if carry_key and sync and taps:

            @jax.jit
            def run(state, key, tapc, xs_in):
                state, key, _, tapc, *out = shm(
                    state, key, (), tapc, _pad_xs(xs_in), vol_arrays, rho_rep, active, region_pad
                )
                *outs, row = out
                return (state, key, tapc, *outs, row)

        elif carry_key and sync:

            @jax.jit
            def run(state, key, xs_in):
                state, key, _, _, *out = shm(
                    state, key, (), tap0, _pad_xs(xs_in), vol_arrays, rho_rep, active, region_pad
                )
                return (state, key, *out)

        elif carry_key and taps:

            @jax.jit
            def run(state, key, rings, tapc, xs_in):
                state, key, rings, tapc, *out = shm(
                    state, key, rings, tapc, _pad_xs(xs_in), vol_arrays, rho_rep, active, region_pad
                )
                *outs, row = out
                return (state, key, rings, tapc, *outs, row)

        elif carry_key:

            @jax.jit
            def run(state, key, rings, xs_in):
                state, key, rings, _, *out = shm(
                    state, key, rings, tap0, _pad_xs(xs_in), vol_arrays, rho_rep, active, region_pad
                )
                return (state, key, rings, *out)

        elif sync:

            @jax.jit
            def run(state, key, xs_in):
                state, _, _, tapc, *out = shm(
                    state, key, (), tap0, _pad_xs(xs_in), vol_arrays, rho_rep, active, region_pad
                )
                return _finish(state, tapc, out)

        else:

            @jax.jit
            def run(state, key, xs_in):
                state, _, _, tapc, *out = shm(
                    state, key, rings0, tap0, _pad_xs(xs_in), vol_arrays, rho_rep, active, region_pad
                )
                return _finish(state, tapc, out)

        return run, state0
