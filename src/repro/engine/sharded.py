"""Million-client ProbAlloc and K-sharded selection rounds.

``repro.core.selection.prob_alloc`` vectorises the paper's case analysis via a
full ``O(K log K)`` sort plus cumulative sums — fine at K=100, hostile at
K=10^6 (a global sort is the one primitive that does not shard).  This module
solves the same fixed point by **fixed-iteration bisection** on the monotone
scalar function

    g(alpha) = alpha / sum_j min(w_j, (1 - sigma) * alpha)         (Eq. 24)

g is non-decreasing in alpha (numerator linear, denominator concave and
saturating), and the capped allocation is exact when ``g(alpha) = 1/(k - K
sigma)``.  Each bisection step only needs ``sum_j min(w_j, cap)`` — an
embarrassingly shardable masked reduction that we evaluate tile-by-tile
(two-level summation), so the whole search is O(n_iters * K) flops, O(K)
memory traffic, and never materialises an ordering of the weights.

``n_iters=48`` halvings shrink the bracket below float32 resolution, so the
result matches the sort-based solver (and the paper's literal case
enumeration, ``prob_alloc_reference``) to ~1e-6 in p.  Weights keep their
dtype end to end: float64 inputs (x64 mode) solve in float64 with a
dtype-scaled epsilon instead of silently downcasting.

Three levels of parallelism, all the same reduction:

* **tiles** — ``masked_prob_alloc`` sums per-tile partials (more accurate
  than a flat fp32 reduction at K ~ 10^6, and the shape a ``psum`` needs);
* **bracket blocks** — with ``block=b > 1`` each pass evaluates the capped
  sum at the ``2**b - 1`` dyadic candidate caps of the next ``b`` halvings in
  ONE sweep of the weights (``repro.kernels.bisect_tiles``: the slab stays in
  VMEM across the block), collapsing 48 sweeps to ``ceil(48/b)``;
* **devices** — with ``axis_name`` set, every reduction finishes with one
  scalar (or, in block mode, one ``(2**b - 1,)``-vector) ``psum`` per step;
  nothing else crosses shards.  ``prob_alloc_shmap`` stands this up on a real
  device mesh via ``shard_map``, and ``build_sharded_scan_runner`` threads the
  fully sharded round — allocator, distributed Plackett-Luce top-k, per-shard
  volatility draw (``jax.random.fold_in(key, shard_index)``, bit-reproducible
  for a fixed shard count) and E3CS update — through a whole compiled
  ``lax.scan`` horizon.

All entry points take an optional ``active`` mask and traced ``k`` /
``sigma`` scalars, which is what lets the multi-job engine vmap one compiled
allocator over heterogeneous (K, k, sigma) jobs via padding.
"""
from __future__ import annotations


from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.selection.sampling import local_topk_candidates, merge_topk_candidates, perturbed_scores
from repro.kernels.bisect_tiles import bisect_block_sums
from repro.obs.trace import stage

__all__ = [
    "prob_alloc_sharded",
    "masked_prob_alloc",
    "masked_prob_alloc_scalars",
    "prob_alloc_shmap",
    "distributed_topk",
    "plackett_luce_shmap",
    "build_sharded_scan_runner",
    "sharded_selection_sim",
]


def _exchange_topk(vals: jax.Array, gidx: jax.Array, k: int, axis_name: str):
    """The candidate exchange: an all-gather of every shard's ``(k,)``
    candidate scores and global indices into ``(D*k,)`` pairs, and the exact
    merge (``repro.core.selection.sampling.merge_topk_candidates``), under
    the ``round.exchange`` stage.  Returns the replicated ``(k,)`` global
    top-k indices."""
    with stage("round.exchange"):
        cv = jax.lax.all_gather(vals, axis_name, tiled=True)
        ci = jax.lax.all_gather(gidx, axis_name, tiled=True)
        return merge_topk_candidates(cv, ci, k)


def _shard_topk_merge(scores_loc: jax.Array, k: int, axis_name: str):
    """The one distributed top-k step every sharded selection shares: this
    shard's ``lax.top_k(k)`` candidates (global indices via the shard
    offset), then the candidate exchange (``_exchange_topk``)."""
    Ks = scores_loc.shape[0]
    v, gi = local_topk_candidates(scores_loc, k, jax.lax.axis_index(axis_name) * Ks)
    return _exchange_topk(v, gi, k, axis_name)


def _shmap(f, mesh, in_specs, out_specs):
    """`shard_map` with replication checking off: the bisection's `fori_loop`
    carry trips the static replication checker (jax#21296); the specs here are
    explicit so the check adds nothing."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)


def _axis_size(mesh, axis_name: str) -> int:
    return mesh.shape[axis_name]


def _tiny(dt) -> jnp.ndarray:
    """Dtype-scaled division guard (float32: ~1e-38, float64: ~1e-308) —
    a flat 1e-30 floor is wider than float64's usable range and was the one
    constant that broke x64-mode allocations."""
    return jnp.asarray(jnp.finfo(dt).tiny, dt)


def _tiled_sum(x: jax.Array, tile: int) -> jax.Array:
    """Two-level (per-tile, then cross-tile) sum: shard-shaped and more
    accurate than a flat fp32 reduction at K ~ 10^6."""
    n = x.shape[0]
    pad = (-n) % tile
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return jnp.sum(jnp.sum(x.reshape(-1, tile), axis=1))


def _reduce_sum(x: jax.Array, tile: int, axis_name: Optional[str]) -> jax.Array:
    s = _tiled_sum(x, tile)
    return jax.lax.psum(s, axis_name) if axis_name else s


def masked_prob_alloc(
    w: jax.Array,
    k: jax.Array,
    sigma: jax.Array,
    active: jax.Array | None = None,
    n_iters: int = 48,
    tile: int = 8192,
    axis_name: Optional[str] = None,
    block: int = 1,
):
    """Sort-free ProbAlloc (paper Algorithm 2) over an optionally-masked
    population.

    Args:
      w: ``(K_pad,)`` non-negative weights (entries with ``active == 0`` are
         ignored and receive ``p = 0``).  Any float dtype; the search runs and
         returns in ``w.dtype``.
      k: cohort size — python int or traced scalar.
      sigma: fairness floor in ``[0, k/K_active]`` — python float or traced.
      active: ``(K_pad,)`` 0/1 validity mask (default: all active).
      n_iters: total bisection halvings (static).
      tile: reduction tile width (static).
      axis_name: when set, ``w``/``active`` are this device's shard of a
         K-sharded population and every reduction finishes with a ``psum``
         over the named mesh axis — ``k``/``sigma`` stay global, and the
         returned ``(p, capped)`` are the local shard.  One scalar ``psum``
         per bisection step; nothing else crosses shards.
      block: halvings resolved per weight sweep (static).  ``block=1`` is
         plain bisection; ``block=b`` probes the ``2**b - 1`` dyadic interior
         caps of the bracket in one fused pass (``repro.kernels.bisect_tiles``)
         and binary-searches the precomputed sums — same final bracket up to
         float roundoff in the midpoint arithmetic, ``ceil(n_iters/b)`` sweeps
         (and cross-device syncs) instead of ``n_iters``.

    Returns:
      ``(p, capped)``: allocation with ``sum(p) = k``, ``sigma <= p_i <= 1``
      on active arms and ``p_i = 0`` off them; ``capped`` is the overflow set.
    """
    w, active, k, sigma = _alloc_prelude(w, k, sigma, active)
    residual, cap, denom, use_cap = _alloc_scalars(
        w, k, sigma, active, n_iters=n_iters, tile=tile, axis_name=axis_name, block=block
    )
    # the unified elementwise epilogue: with cap=+inf / denom=max(w_sum,eps)
    # in the plain branch, min(w, cap) == w bitwise, so one expression
    # reproduces both branches of the historical lax.cond exactly.
    p = sigma + residual * jnp.minimum(w, cap) / denom
    capped = (p >= 1.0 - 1e-6) & use_cap
    p = jnp.clip(p, sigma, 1.0) * active
    return p, capped & (active > 0)


def _alloc_prelude(w, k, sigma, active):
    """Shared input normalisation: cast to the weight dtype and fold the
    activity mask into the weights (exactly once)."""
    w = jnp.asarray(w)
    dt = w.dtype
    if active is None:
        active = jnp.ones(w.shape, dt)
    else:
        active = jnp.asarray(active, dt)
    return w * active, active, jnp.asarray(k, dt), jnp.asarray(sigma, dt)


def _alloc_scalars(w, k, sigma, active, *, n_iters, tile, axis_name, block):
    """The scalar half of ``masked_prob_alloc``: bracket the cap by
    bisection and return ``(residual, cap, denom, use_cap)`` such that

        p_raw  = sigma + residual * min(w, cap) / denom
        capped = (p_raw >= 1 - 1e-6) & use_cap
        p      = clip(p_raw, sigma, 1) * active

    reproduces the full allocation bitwise.  ``w`` must already be masked
    (``_alloc_prelude``).  This is the piece the fused round kernel hoists
    out: everything downstream of these four scalars is elementwise."""
    dt = w.dtype
    eps = _tiny(dt)
    K_act = _reduce_sum(active, tile, axis_name)
    residual = k - K_act * sigma  # >= 0 by the feasibility constraint
    one_ms = 1.0 - sigma

    w_sum = _reduce_sum(w, tile, axis_name)
    w_max = jnp.max(jnp.where(active > 0, w, -jnp.inf))
    if axis_name:
        w_max = jax.lax.pmax(w_max, axis_name)
    # overflow iff the plain (uncapped) allocation exceeds 1 somewhere
    overflow = sigma + residual * w_max / jnp.maximum(w_sum, eps) > 1.0 + 1e-9

    def capped_branch(_):
        # bracket: g(0+) = 1/(K_act*(1-sigma)) <= 1/residual (since k <= K)
        # and g(w_sum/residual) >= 1/residual, so the root is in (0, hi].
        hi0 = w_sum / jnp.maximum(residual, eps)

        if block == 1:

            def body(_, lohi):
                lo, hi = lohi
                mid = 0.5 * (lo + hi)
                s = _reduce_sum(jnp.minimum(w, one_ms * mid), tile, axis_name)
                go_up = mid * residual < s  # g(mid) < 1/residual -> alpha too small
                return jnp.where(go_up, mid, lo), jnp.where(go_up, hi, mid)

            n_pass = n_iters
        else:
            npts = (1 << block) - 1
            frac = jnp.arange(1, npts + 1, dtype=dt) / (npts + 1)

            def body(_, lohi):
                lo, hi = lohi
                mids = lo + (hi - lo) * frac  # the block's dyadic candidates
                s = bisect_block_sums(w, one_ms * mids, tile=tile).astype(dt)
                if axis_name:
                    s = jax.lax.psum(s, axis_name)
                n_up = jnp.sum((mids * residual < s).astype(jnp.int32))
                grid = jnp.concatenate([lo[None], mids, hi[None]])
                return grid[n_up], grid[n_up + 1]

            n_pass = -(-n_iters // block)

        lo, hi = jax.lax.fori_loop(0, n_pass, body, (jnp.zeros((), dt), hi0))
        alpha = 0.5 * (lo + hi)
        cap = one_ms * alpha
        denom = jnp.maximum(_reduce_sum(jnp.minimum(w, cap), tile, axis_name), eps)
        return residual, cap, denom, jnp.ones((), bool)

    def plain_branch(_):
        return residual, jnp.asarray(jnp.inf, dt), jnp.maximum(w_sum, eps), jnp.zeros((), bool)

    return jax.lax.cond(overflow, capped_branch, plain_branch, None)


def masked_prob_alloc_scalars(
    w: jax.Array,
    k: jax.Array,
    sigma: jax.Array,
    active: jax.Array | None = None,
    n_iters: int = 48,
    tile: int = 8192,
    axis_name: Optional[str] = None,
    block: int = 1,
):
    """``masked_prob_alloc`` minus its elementwise epilogue: run the same
    bisection (identical scalars, identical cross-shard reductions) and
    return ``(residual, cap, denom, use_cap)``.  The fused round kernel
    (``repro.kernels.round_fused``) consumes these to rebuild ``p`` /
    ``capped`` inside one VMEM-resident pass, bit-identical to the staged
    allocator."""
    w, active, k, sigma = _alloc_prelude(w, k, sigma, active)
    return _alloc_scalars(w, k, sigma, active, n_iters=n_iters, tile=tile, axis_name=axis_name, block=block)


@partial(jax.jit, static_argnames=("k", "n_iters", "tile", "block"))
def prob_alloc_sharded(w: jax.Array, k: int, sigma, n_iters: int = 48, tile: int = 8192, block: int = 1):
    """Drop-in for ``repro.core.selection.prob_alloc`` at fleet scale:
    identical (p, capped) contract, no global sort, O(n_iters * K) work."""
    return masked_prob_alloc(w, k, sigma, active=None, n_iters=n_iters, tile=tile, block=block)


def _pad0(a: jax.Array, n: int) -> jax.Array:
    """Pad axis 0 with zeros up to length ``n``."""
    if a.shape[0] == n:
        return a
    return jnp.pad(a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def prob_alloc_shmap(
    w: jax.Array,
    k,
    sigma,
    mesh,
    active: jax.Array | None = None,
    axis_name: str = "shards",
    n_iters: int = 48,
    tile: int = 8192,
    block: int = 1,
):
    """``masked_prob_alloc`` data-parallel over a K-sharded device mesh.

    The weights are padded to a multiple of the mesh axis size, sharded via
    ``shard_map``, and each device evaluates its slab's capped partial sum —
    per bisection step, one scalar ``psum`` combines them and everything else
    is shard-local (the compiled program contains no gather, no sort, and
    exactly one all-reduce inside the refinement loop; asserted on the HLO in
    ``tests/test_sharded.py``).  Ragged populations are handled by the pad
    mask.  Returns global ``(p, capped)`` of the original length.
    """
    K = w.shape[0]
    D = _axis_size(mesh, axis_name)
    K_pad = D * (-(-K // D))
    if active is None:
        active = jnp.ones((K,), w.dtype)
    wp = _pad0(jnp.asarray(w), K_pad)
    ap = _pad0(jnp.asarray(active, wp.dtype), K_pad)
    body = partial(masked_prob_alloc, n_iters=n_iters, tile=tile, axis_name=axis_name, block=block)
    f = _shmap(
        body,
        mesh,
        in_specs=(P(axis_name), P(), P(), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)),
    )
    p, capped = f(wp, jnp.asarray(k, wp.dtype), jnp.asarray(sigma, wp.dtype), ap)
    return p[:K], capped[:K]


# ---------------------------------------------------------------------------
# Distributed Plackett-Luce top-k
# ---------------------------------------------------------------------------


def distributed_topk(scores: jax.Array, k: int, mesh, axis_name: str = "shards") -> jax.Array:
    """Global top-k indices of ``scores`` without a global sort or gather of
    the full vector: per-shard ``lax.top_k(k)``, an all-gather of the
    ``(D, k)`` candidate (value, index) pairs, and one final k-way merge —
    O(K/D) work per device plus O(D·k) replicated, versus O(K log K) for a
    global sort.

    Exactly equal to ``lax.top_k(scores, k)[1]`` — including tie order — by
    the containment argument in
    ``repro.core.selection.sampling.merge_topk_candidates``.
    """
    K = scores.shape[0]
    D = _axis_size(mesh, axis_name)
    K_pad = D * (-(-K // D))
    if k > K_pad // D:
        raise ValueError(f"k={k} exceeds the shard width {K_pad // D} (= ceil(K/D)); need k <= K/D")
    neg_inf = jnp.asarray(-jnp.inf, scores.dtype)
    sp = jnp.concatenate([jnp.asarray(scores), jnp.full((K_pad - K,), neg_inf)]) if K_pad != K else scores
    body = partial(_shard_topk_merge, k=k, axis_name=axis_name)
    return _shmap(body, mesh, in_specs=P(axis_name), out_specs=P())(sp)


def plackett_luce_shmap(rng: jax.Array, p: jax.Array, k: int, mesh, axis_name: str = "shards") -> jax.Array:
    """K-sharded Plackett-Luce draw: each shard perturbs its slab of
    ``log p`` with Gumbel noise from its own fold_in stream
    (``fold_in(rng, shard_index)``; bit-reproducible for a fixed shard count)
    and the cohort is the distributed top-k of the perturbed scores.

    Same distribution as ``plackett_luce_sample`` (iid Gumbel perturbations
    followed by an exact global top-k); not the same bits for D > 1, since the
    per-shard streams differ from one (K,) draw.
    """
    K = p.shape[0]
    D = _axis_size(mesh, axis_name)
    K_pad = D * (-(-K // D))
    Ks = K_pad // D
    if k > Ks:
        raise ValueError(f"k={k} exceeds the shard width {Ks} (= ceil(K/D)); need k <= K/D")
    pp = _pad0(jnp.asarray(p), K_pad)

    def body(p_loc):
        d = jax.lax.axis_index(axis_name)
        key = jax.random.fold_in(rng, d) if D > 1 else rng
        pos = d * Ks + jnp.arange(Ks, dtype=jnp.int32)
        scores = jnp.where(pos < K, perturbed_scores(key, p_loc), -jnp.inf)
        return _shard_topk_merge(scores, k, axis_name)

    return _shmap(body, mesh, in_specs=P(axis_name), out_specs=P())(pp)


# ---------------------------------------------------------------------------
# The K-sharded selection round, compiled over a whole scan horizon
# ---------------------------------------------------------------------------


def build_sharded_scan_runner(
    fl,
    vol,
    rho,
    mesh,
    override: str = "none",
    outputs: str = "full",
    axis_name: str = "shards",
    n_iters: int = 48,
    tile: int = 8192,
    block: int = 1,
    staleness: Optional[int] = None,
    alpha: float = 0.5,
    feedback: str = "deadline",
    carry_key: bool = False,
    scan_length: Optional[int] = None,
    taps: bool = False,
    fused: bool = False,
):
    """Compile the whole T-round horizon with the K axis sharded over a mesh.

    The mesh placement of the ONE round body in
    ``repro.engine.round_program`` (same round semantics, same per-round
    ``split(key, 3)`` PRNG discipline as the dense engine) with every
    per-client array — E3CS log-weights, allocation, volatility parameters and
    state, selection counts, loss cache, the per-round trace rows and (async)
    the ``(S, K/D)`` staleness rings — living as shards on a ``shard_map``
    mesh.  Per round the only cross-shard traffic is: one scalar ``psum`` per
    bisection step (the allocator), one ``(D·k,)`` candidate all-gather (the
    distributed Plackett-Luce top-k), one ``pmax`` pair for weight
    re-centering, and — in lean mode — one scalar ``psum`` per round metric.

    PRNG: the carried key is replicated and split exactly like the unsharded
    engine; shard-local draws (Gumbel perturbations, volatility bits) use
    ``fold_in(round_key, shard_index)`` so runs are bit-reproducible for a
    fixed shard count.  On a 1-device mesh the fold_in is skipped, which makes
    the sharded engine **bit-identical** to
    ``build_scan_runner(fl(allocator="bisect"), ...)`` for every scheme
    (pinned in ``tests/test_sharded.py``).  Caveat: with
    ``override="packed"`` the contract additionally needs ``K % 8 == 0`` —
    byte sharding pads the population to whole bytes, and a padded draw shape
    changes every threefry stream, so non-aligned K is distributionally
    equivalent but not bit-equal.

    Schemes: ``e3cs`` is fully sharded (the hot path).  ``random`` / ``fedcs``
    / ``ucb`` / ``pow_d`` keep their small selector state replicated and run
    the selection itself replicated (gathering the (K,) vector they score, for
    ucb/pow_d) — correctness-grade at scale, bit-identical at D=1.

    ``override="packed"`` shards the ``(T, ceil(K/8))`` uint8 trace rows along
    the byte axis, so replay memory divides by D as well (``"packed_lags"``
    does the same for 2-bit async lag traces at 4 clients/byte); ``"dense"``
    shards the trace columns; ``"none"`` draws from ``vol`` with per-shard
    parameters (any — possibly nested — dataclass model whose per-client
    arrays are K-indexed: the builtins, or ``CompletionLag`` over one).

    With ``staleness=S`` the runner compiles the *async* round body: ``vol``
    is a lag model and the ``(S, K/D)``-sharded pending-credit ring rides in
    the scan carry — the "sharded async rounds" composition.  Returns
    ``(run, state0)`` with the ``build_scan_runner`` signatures; K-arrays in
    ``state0`` and the outputs are padded to ``K_pad`` (a multiple of D·8
    for packed, D·4 for packed_lags); slice ``[:K]``.

    ``taps=True`` appends the ``repro.obs.ROUND_TAPS`` telemetry payload
    (``{"series", "counters"}``, psum-reduced so replicated across shards)
    as the runner's trailing output — same schema as the dense engine.
    """
    from repro.engine.round_program import RoundProgram  # deferred: round_program imports this module

    program = RoundProgram(
        fl=fl, vol=vol, rho=rho, override=override, staleness=staleness, alpha=alpha,
        feedback=feedback, mesh=mesh, axis_name=axis_name, n_iters=n_iters, tile=tile,
        block=block, fused=fused,
    )
    return program.build_runner(outputs=outputs, carry_key=carry_key, scan_length=scan_length, taps=taps)


def sharded_selection_sim(
    scheme: str,
    mesh,
    K: int = 100,
    k: int = 20,
    T: int = 2500,
    quota: str = "const",
    frac: float = 0.0,
    eta: float = 0.5,
    volatility: str = "bernoulli",
    stickiness: float = 0.8,
    seed: int = 0,
    xs_override: Optional[np.ndarray] = None,
    packed_override: Optional[np.ndarray] = None,
    outputs: str = "full",
    block: int = 1,
    vol=None,
    rho=None,
    taps: bool = False,
    fused: bool = False,
):
    """Sharded counterpart of ``engine.scan_sim.scan_selection_sim``: same
    keyword surface plus a ``mesh``, same output dict (K-wide arrays sliced
    back to the true population; ``taps=True`` adds the ``"taps"`` entry)."""
    from repro.configs.base import FLConfig
    from repro.core.volatility import make_volatility, paper_success_rates

    if xs_override is not None and packed_override is not None:
        raise ValueError("pass at most one of xs_override / packed_override")
    override = "dense" if xs_override is not None else ("packed" if packed_override is not None else "none")
    fl = FLConfig(K=K, k=k, rounds=T, scheme=scheme, quota=quota, quota_frac=frac, eta=eta, allocator="bisect")
    if rho is None:
        rho = getattr(vol, "rho", None)
    if rho is None:
        rho = paper_success_rates(K)
    if vol is None:
        vol = make_volatility(volatility, jnp.asarray(rho), stickiness=stickiness, seed=seed)
    run, state = build_sharded_scan_runner(
        fl, vol, rho, mesh, override=override, outputs=outputs, block=block, taps=taps, fused=fused
    )
    key = jax.random.PRNGKey(seed)
    if override == "dense":
        xs_in = jnp.asarray(xs_override, jnp.float32)
    elif override == "packed":
        xs_in = jnp.asarray(packed_override, jnp.uint8)
    else:
        xs_in = jnp.zeros((T, 0), jnp.float32)

    def _taps_out(rest):
        payload = rest[-1]
        return {
            "series": {n: np.asarray(v) for n, v in payload["series"].items()},
            "counters": {n: float(v) for n, v in payload["counters"].items()},
        }

    if outputs == "lean":
        state, successes, sigmas, *rest = run(state, key, xs_in)
        out = {
            "successes": np.asarray(successes),
            "sigmas": np.asarray(sigmas),
            "counts": np.asarray(state.sel_counts)[:K],
        }
        if taps:
            out["taps"] = _taps_out(rest)
        return out
    state, masks, xs, ps, sigmas, *rest = run(state, key, xs_in)
    masks = np.asarray(masks)[:, :K]
    out = {
        "masks": masks,
        "xs": np.asarray(xs)[:, :K],
        "ps": np.asarray(ps)[:, :K],
        "sigmas": np.asarray(sigmas),
        "counts": masks.sum(0),
    }
    if taps:
        out["taps"] = _taps_out(rest)
    return out
