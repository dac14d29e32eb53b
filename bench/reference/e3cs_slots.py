"""Plain reference of a multi-tenant slot-engine job, one tick at a time.
Imports nothing of the program.

Each tenant job (population K, cohort k, constant quota
``sigma = f * k / K``, learning rate ``eta``, seed) lives in a slot of width
``K_max``.  Tick ``t`` of the job (its t-th answered round) with success bits
``x`` (the feedback posted with the tick):

1. noise: ``g = gumbel(fold_in(PRNGKey(seed), t), (K_max,))``, float32; the
   job's clients are the first K entries;
2. allocate (ProbAlloc, arXiv:2011.08756 Algorithm 2) over the K clients, the
   capped fixed point of Eqs. 21-24 by water-filling (cap, re-spread, repeat);
3. the cohort: the k largest ``log max(p, 1e-20) + g``;
4. update (Eqs. 16-17): ``logw += min((k - K sigma) eta x / (K p), 1)`` on
   selected, successful, uncapped clients, then ``logw -= max logw``.

The reference runs teacher-forced: at each tick it takes the cohort the
server answered, reads how far that cohort's lowest member lies below the
reference's own k-th largest score (``gap``, 0 when the reference picks the
same k), and updates with the served cohort.  Arithmetic is float64; the
control rounds every intermediate to bfloat16.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["noise", "replay_job"]


def noise(seed: int, ticks: int, K_max: int) -> np.ndarray:
    """``(ticks, K_max)`` float32 selection noise of one job."""
    import jax

    n = -(-max(ticks, 1) // 256) * 256  # a few shapes only: each compiles once
    return np.asarray(_drawer(int(K_max))(jax.random.PRNGKey(int(seed)), np.arange(n, dtype=np.uint32)))[:ticks]


@functools.lru_cache(maxsize=None)
def _drawer(K_max: int):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda base, ts: jax.vmap(
        lambda t: jax.random.gumbel(jax.random.fold_in(base, t), (K_max,), jnp.float32))(ts))


def _quantizer(dtype):
    if dtype in (None, "float64"):
        return lambda a: np.asarray(a, np.float64)
    import ml_dtypes

    bf = ml_dtypes.bfloat16 if dtype == "bfloat16" else np.dtype(dtype)
    return lambda a: np.asarray(a, np.float64).astype(bf).astype(np.float64)


def _allocate(w, k, K, sigma, q):
    residual = q(k - K * sigma)

    def spread(capped):
        free = np.where(capped, 0.0, w)
        rest = q(residual - capped.sum() * (1 - sigma))
        return np.where(capped, 1.0, q(sigma + q(rest * q(free / q(free.sum())))))

    capped = np.zeros(K, bool)
    p = spread(capped)
    if p.max() <= 1 + 1e-9:
        return np.clip(p, sigma, 1.0), capped
    # the capped fixed point of Eqs. 21-24 by water-filling: cap every client
    # whose share passes 1, re-spread the rest, repeat until none passes
    while True:
        over = ~capped & (p > 1 + 1e-9)
        if not over.any():
            break
        capped |= over
        p = spread(capped)
    return np.clip(p, sigma, 1.0), p >= 1 - 1e-6


def replay_job(job: dict, pool_bits, rows, cohorts=None, K_max: int = 4096, dtype=None):
    """One job's ticks from a fresh state.  Tick ``i`` posts feedback row
    ``rows[i]`` of ``pool_bits`` (``(P, K)`` 0/1).  Teacher-forced when
    ``cohorts`` (the served client ids per tick) is given: returns
    ``(gaps (n,), logw (K,))``.  Free-running otherwise (the control's way of
    standing in for the program): returns ``(cohorts (n, k), logw (K,))``."""
    n = len(rows)
    g = noise(job["seed"], n, K_max)
    bits = np.asarray(pool_bits)[np.asarray(rows, np.int64)]
    q = _quantizer(dtype)
    K, k = int(job["K"]), int(job["k"])
    sigma = float(q(job["sigma_frac"] * k / K))
    eta = float(job["eta"])
    logw = np.zeros(K, np.float64)
    out = np.zeros(n) if cohorts is not None else np.zeros((n, k), np.int64)
    for i in range(n):
        w = q(np.exp(q(logw - logw.max())))
        p, capped = _allocate(w, k, K, sigma, q)
        scores = q(np.log(np.maximum(p, 1e-20)) + q(g[i, :K]))
        if cohorts is not None:
            tau = np.partition(scores, K - k)[K - k]
            idx = np.asarray(cohorts[i], np.int64)
            out[i] = max(0.0, float(tau - scores[idx].min()))
        else:
            idx = np.sort(np.argpartition(-scores, k - 1)[:k])
            out[i] = idx
        mask = np.zeros(K)
        mask[idx] = 1.0
        x = bits[i].astype(np.float64)
        step = np.minimum(q(q(q(k - K * sigma) * eta) * q(mask * x / np.maximum(p, 1e-12)) / K), 1.0)
        logw = q(logw + np.where(capped, 0.0, step))
        logw = q(logw - logw.max())
    return out, logw
