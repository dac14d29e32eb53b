"""Plain reference of one E3CS selection round, as the round program and the
K-sharded serving engine define it.  Imports nothing of the program.

One round at population K, cohort k, constant quota ``sigma = f * k / K``
and learning rate ``eta`` (E3CS, arXiv:2011.08756, Algorithms 1 and 2):

1. ``key, k1, k2 = split(key, 3)``; ``k1`` draws the selection noise
   (``gumbel(k1, (K,))``; on D shards, shard ``d`` draws
   ``gumbel(fold_in(k1, d), (K/D,))``), ``k2`` is unused with replayed
   feedback.
2. Allocate (ProbAlloc): ``w = exp(logw - max logw)``;
   ``p = sigma + (k - K sigma) w / sum w``, and where that exceeds 1 the
   capped fixed point of Eqs. 21-24 (here by water-filling: cap, re-spread,
   repeat); ``p`` clipped to ``[sigma, 1]``; capped clients are those at
   ``p >= 1-1e-6`` when capping is active.
3. Select (Plackett-Luce by the Gumbel top-k): the k largest of
   ``log max(p, 1e-20) + g``, ties to the lower index.  The k-th largest is
   found by bisection on the scores' bit patterns, not by a sort.
4. Update (Eqs. 16-17): ``logw += min((k - K sigma) eta x / (K p), 1)`` on
   selected, successful, uncapped clients; then ``logw -= max logw``;
   selection counts add the cohort.

``dtype`` is the arithmetic precision: float32 is the reference, bfloat16 the
control (the selection noise is always drawn in float32, then cast).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["RoundSpec", "init_state", "unpack_row", "kth_largest", "free_run", "replay_job"]

_P_FLOOR = 1e-20
_IW_FLOOR = 1e-12


class RoundSpec:
    """The static numbers of one configuration's round."""

    def __init__(self, K: int, k: int, sigma_frac: float, eta: float, shards: int = 1, dtype=jnp.float32):
        if K % shards:
            raise ValueError(f"K={K} does not split into {shards} equal shards")
        self.K, self.k, self.shards = int(K), int(k), int(shards)
        self.sigma_frac, self.eta = float(sigma_frac), float(eta)
        self.dtype = dtype


def init_state(spec: RoundSpec) -> dict:
    return {
        "logw": jnp.zeros((spec.K,), spec.dtype),
        "sel_counts": jnp.zeros((spec.K,), jnp.float32),
        "t": jnp.zeros((), jnp.int32),
    }


def unpack_row(packed, K: int):
    """``(K/8,)`` uint8 -> ``(K,)`` float32: bit ``j`` of byte ``b`` is client ``8b+j``."""
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
    return bits.reshape(-1)[:K].astype(jnp.float32)


def _ordered(scores):
    """Scores as uint32 keys in the same order as the float values."""
    i = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    i = jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest(scores, k: int):
    """``(tau, mask)``: the k-th largest score and the mask of the k largest
    (ties at ``tau`` to the lowest indices), by bisection on the keys."""
    u = _ordered(scores)

    def body(_, lohi):
        lo, hi = lohi
        d = hi - lo
        mid = lo + d // jnp.uint32(2) + (d & jnp.uint32(1))  # ceil((lo + hi) / 2), no overflow
        ok = jnp.sum((u >= mid).astype(jnp.int32)) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - jnp.uint32(1))

    lo, _ = jax.lax.fori_loop(0, 32, body, (jnp.uint32(0), jnp.uint32(0xFFFFFFFF)))
    above = u > lo
    tie = u == lo
    take = k - jnp.sum(above.astype(jnp.int32))
    mask = above | (tie & (jnp.cumsum(tie.astype(jnp.int32)) <= take))
    tau = jnp.max(jnp.where(tie, scores.astype(jnp.float32), -jnp.inf))
    return tau, mask.astype(jnp.float32)


def _allocate(spec: RoundSpec, logw):
    dt = spec.dtype
    K, k = spec.K, spec.k
    sigma = jnp.asarray(spec.sigma_frac * k / K, dt)
    residual = jnp.asarray(k, dt) - jnp.asarray(K, dt) * sigma
    w = jnp.exp(logw - jnp.max(logw))
    p_plain = sigma + residual * w / jnp.sum(w)

    def capped_case(_):
        # the capped fixed point of Eqs. 21-24 by water-filling: cap every
        # client whose share would pass 1, re-spread the rest of the mass over
        # the others, repeat until none passes (each pass caps at least one)
        def spread(capped):
            free = jnp.where(capped, jnp.zeros((), dt), w)
            rest = residual - jnp.sum(capped.astype(jnp.float32)).astype(dt) * (1 - sigma)
            return jnp.where(capped, jnp.ones((), dt), sigma + rest * free / jnp.sum(free))

        def more(c):
            capped, n = c
            return (n < 1000) & jnp.any(~capped & (spread(capped) > 1 + 1e-9))

        def cap(c):
            capped, n = c
            return capped | (spread(capped) > 1 + 1e-9), n + 1

        capped, _ = jax.lax.while_loop(more, cap, (p_plain > 1 + 1e-9, jnp.zeros((), jnp.int32)))
        p = spread(capped)
        return p, p >= 1 - 1e-6

    def plain_case(_):
        return p_plain, jnp.zeros((K,), bool)

    p, capped = jax.lax.cond(jnp.max(p_plain) > 1 + 1e-9, capped_case, plain_case, None)
    return jnp.clip(p, sigma, 1.0), capped, sigma, residual


def _noise(spec: RoundSpec, k1):
    if spec.shards == 1:
        return jax.random.gumbel(k1, (spec.K,), jnp.float32)
    Ks = spec.K // spec.shards
    return jnp.concatenate(
        [jax.random.gumbel(jax.random.fold_in(k1, d), (Ks,), jnp.float32) for d in range(spec.shards)]
    )


def _update(spec: RoundSpec, logw, p, capped, mask, x, residual):
    dt = spec.dtype
    xhat = mask.astype(dt) * x.astype(dt) / jnp.maximum(p, jnp.asarray(_IW_FLOOR, dt))
    step = jnp.minimum(residual * jnp.asarray(spec.eta, dt) * xhat / jnp.asarray(spec.K, dt), 1.0)
    logw = logw + jnp.where(capped, jnp.zeros((), dt), step)
    return logw - jnp.max(logw)


def _round(spec: RoundSpec, state, key, x, forced_mask=None):
    """One round; returns ``(state, key, successes, capped_share, gap)``.
    With ``forced_mask`` the cohort is the one given (teacher forcing) and
    ``gap`` is how far its lowest member lies below the reference's k-th
    largest score (0 where the reference picks the same k)."""
    dt = spec.dtype
    key, k1, _k2 = jax.random.split(key, 3)
    p, capped, _sigma, residual = _allocate(spec, state["logw"])
    g = _noise(spec, k1).astype(dt)
    scores = jnp.log(jnp.maximum(p, jnp.asarray(_P_FLOOR, dt))) + g
    tau, mask = kth_largest(scores, spec.k)
    gap = jnp.zeros((), jnp.float32)
    if forced_mask is not None:
        gap = jnp.max(jnp.where(forced_mask > 0, tau - scores.astype(jnp.float32), -jnp.inf))
        gap = jnp.maximum(gap, 0.0)
        mask = forced_mask
    logw = _update(spec, state["logw"], p, capped, mask, x, residual)
    state = {"logw": logw, "sel_counts": state["sel_counts"] + mask, "t": state["t"] + 1}
    successes = jnp.sum(mask * x)
    return state, key, successes, jnp.mean(capped.astype(jnp.float32)), gap


def free_run(spec: RoundSpec, state, key, pool, n_rounds: int, chunk: int):
    """``n_rounds`` rounds from ``state``; round ``t`` replays packed row
    ``t % len(pool)``.  Returns ``(state, key, successes (n,), capped (n,))``.
    Runs ``chunk`` rounds per compiled call."""
    rows = pool.shape[0]

    @jax.jit
    def run(state, key, pool, t0):
        def body(carry, i):
            st, ky = carry
            x = unpack_row(pool[(t0 + i) % rows], spec.K)
            st, ky, s, c, _ = _round(spec, st, ky, x)
            return (st, ky), (s, c)

        (state, key), (s, c) = jax.lax.scan(body, (state, key), jnp.arange(chunk, dtype=jnp.int32))
        return state, key, s, c

    succ, capd = [], []
    done = 0
    while done < n_rounds:
        state, key, s, c = run(state, key, pool, jnp.int32(done))
        succ.append(s)
        capd.append(c)
        done += chunk
    succ = jnp.concatenate(succ)[:n_rounds]
    capd = jnp.concatenate(capd)[:n_rounds]
    return state, key, succ, capd


def replay_job(job: dict, pool_bits, rows, cohorts=None, shards: int = 1, dtype=jnp.float32, chunk: int = 32):
    """One served job's ticks, from a fresh state and the key ``PRNGKey(seed)``.

    ``pool_bits``: ``(P, K)`` 0/1 feedback rows; tick ``i`` posts row
    ``rows[i]``.  Teacher-forced when ``cohorts`` (``(n, k)`` client ids) is
    given: returns ``(gaps (n,), logw (K,))``.  Free-running otherwise (the
    control's way of standing in for the program): returns ``(cohorts (n, k),
    logw (K,))``."""
    import numpy as np

    K, k = int(job["K"]), int(job["k"])
    spec = RoundSpec(K, k, job["sigma_frac"], job["eta"], shards=shards, dtype=dtype)
    packed = jnp.asarray(np.packbits(np.asarray(pool_bits, bool), axis=1, bitorder="little"))
    n = len(rows)
    m = -(-n // chunk) * chunk
    forced = cohorts is not None
    idx = np.zeros((m, k), np.int32)
    if forced:
        idx[:n] = np.asarray(cohorts, np.int32)
    row_of = np.zeros(m, np.int32)
    row_of[:n] = rows
    valid = np.arange(m) < n

    @jax.jit
    def run(state, key, packed, idx, row_of, valid):
        def body(carry, xs):
            st, ky = carry
            i, r, ok = xs
            x = unpack_row(packed[r], K)
            mask = jnp.zeros((K,), jnp.float32).at[i].set(1.0) if forced else None
            st2, ky2, _s, _c, gap = _round(spec, st, ky, x, mask)
            st = jax.tree.map(lambda a, b: jnp.where(ok, a, b), st2, st)
            ky = jnp.where(ok, ky2, ky)
            if forced:
                return (st, ky), gap
            # the cohort this round picked: the clients whose count rose
            return (st, ky), jnp.nonzero(st2["sel_counts"] - carry[0]["sel_counts"], size=k)[0].astype(jnp.int32)

        return jax.lax.scan(body, (state, key), (idx, row_of, valid))

    state, key = init_state(spec), jax.random.PRNGKey(int(job["seed"]))
    outs = []
    for s in range(0, m, chunk):
        (state, key), o = run(state, key, packed, idx[s:s + chunk], row_of[s:s + chunk], valid[s:s + chunk])
        outs.append(np.asarray(o))
    return np.concatenate(outs)[:n], np.asarray(state["logw"], np.float32)
