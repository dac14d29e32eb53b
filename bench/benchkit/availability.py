"""The benchmark's own feedback generators: which clients would complete
each round, drawn from the seed.

* ``diurnal_pool`` — one period of diurnal availability (the paper's four
  Bernoulli classes, each client's rate swung by a sinusoid at its own
  timezone phase), packed 8 clients a byte in the program's replay format:
  bit ``j`` of byte ``b`` is client ``8*b + j``.  Drawn on the device in one
  compiled call, plane by plane (``(8, K/8)``), so packing needs no reshape.
* ``diurnal_dense`` — the same draws as a ``(rows, K)`` 0/1 array, for the
  tests and for checks at small K.
* ``class_bits`` — i.i.d. Bernoulli rows over the paper's classes (contiguous
  client blocks), for the served cells' feedback, drawn with NumPy.
"""
from __future__ import annotations

import numpy as np

__all__ = ["diurnal_pool", "diurnal_dense", "class_rates", "class_bits"]


def class_rates(K: int, classes) -> np.ndarray:
    """Per-client base rates: ``len(classes)`` contiguous blocks of clients."""
    idx = (np.arange(K, dtype=np.int64) * len(classes)) // K
    return np.asarray(classes, np.float32)[idx]


def _diurnal_planes(key, K: int, rows: int, mix: dict):
    """``(rows, 8, K//8)`` availability planes; plane ``[r, j, b]`` is client
    ``8*b + j`` in round ``r`` of the period."""
    import jax
    import jax.numpy as jnp

    if K % 8:
        raise ValueError(f"packed rows need K divisible by 8, got K={K}")
    B = K // 8
    k_phase, k_bits = jax.random.split(key)
    classes = jnp.asarray(mix["classes"], jnp.float32)
    n = len(mix["classes"])
    client = 8 * jax.lax.broadcasted_iota(jnp.int32, (8, B), 1) + jax.lax.broadcasted_iota(jnp.int32, (8, B), 0)
    # class = client * n // K without int32 overflow at K ~ 1e8
    rho = classes[jnp.minimum((client.astype(jnp.float32) * (n / K)).astype(jnp.int32), n - 1)]
    phase = jax.random.uniform(k_phase, (8, B), jnp.float32)
    amp, period = float(mix["amplitude"]), float(mix["period"])
    lo, hi = float(mix["rate_lo"]), float(mix["rate_hi"])

    def row(r):
        ang = 2.0 * jnp.pi * (r.astype(jnp.float32) / period + phase)
        rate = jnp.clip(rho + amp * jnp.sin(ang), lo, hi)
        return jax.random.uniform(jax.random.fold_in(k_bits, r), (8, B), jnp.float32) < rate

    return row, jnp.arange(rows, dtype=jnp.int32)


def diurnal_pool(key, K: int, rows: int, mix: dict):
    """``(rows, K//8)`` uint8 packed availability rows on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        row, rs = _diurnal_planes(key, K, rows, mix)
        weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[:, None]

        def packed(r):
            return jnp.sum(row(r).astype(jnp.uint8) * weights, axis=0, dtype=jnp.uint8)

        return jax.lax.map(packed, rs)

    return gen(key)


def diurnal_dense(key, K: int, rows: int, mix: dict) -> np.ndarray:
    """The same draws as ``diurnal_pool``, as a host ``(rows, K)`` uint8 0/1
    array in client order (small K only: the tests and the docs)."""
    import jax

    row, rs = _diurnal_planes(key, K, rows, mix)
    planes = np.asarray(jax.jit(lambda r: jax.lax.map(row, r))(rs))  # (rows, 8, B)
    return planes.transpose(0, 2, 1).reshape(rows, K).astype(np.uint8)


def class_bits(rng: np.random.Generator, K: int, rows: int, classes) -> np.ndarray:
    """``(rows, K)`` bool success bits, i.i.d. per client at its class rate."""
    rate = class_rates(K, classes)
    return rng.random((rows, K), dtype=np.float32) < rate
