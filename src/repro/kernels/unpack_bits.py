"""Bit-unpack as a Pallas kernel — streaming packed availability traces.

Recorded volatility traces store one success bit per client per round
(``repro.scenarios.replay``): a ``(T, ceil(K/8))`` uint8 array, 8 clients per
byte, little-endian bit order (bit ``j`` of byte ``b`` is client ``8*b + j``,
matching ``np.packbits(..., bitorder="little")``).  At replay time the scan
simulator needs the round's ``(K,)`` float32 bit-vector; materialising the
whole ``(T, K)`` float32 trace would be 32x the packed footprint (10 GB at
K=1e6, T=2500 vs ~312 MB packed), so each row is expanded on the fly.

This kernel does the expansion tile-by-tile: the grid walks byte tiles, each
program reads ``tile_b`` bytes from VMEM and writes the ``8 * tile_b``
float32 lane block.  Client ``8*b + j`` sits next to its byte-mates, so the
expansion interleaves lanes; Mosaic lowers no reshape from ``(tile_b, 8)`` to
the flat row, so ``expand_packed`` does the interleave on the MXU instead:
each 128-byte row is multiplied by 0/1 lane-expansion matrices, which copy
byte ``i`` to the lanes of its clients exactly (integers below 256 are exact
in every matmul precision), a per-lane shift picks the field, and a 0/1
permutation matmul puts the rows in order.  The op is
bandwidth-bound (1 byte in, 32 bytes out).

``unpack_bits_ref`` is the jnp reference (also the CPU fast path — the
interpreter would dominate a T-round scan); ``tests/test_scenarios.py`` pins
kernel == reference in interpret mode, ragged shapes included.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "BYTE_TILE",
    "expand_packed",
    "unpack_bits_ref",
    "unpack_bits_kernel_call",
    "unpack_bits",
    "unpack_crumbs_ref",
    "unpack_crumbs_kernel_call",
    "unpack_crumbs",
]


def unpack_bits_ref(packed: jax.Array, K: int) -> jax.Array:
    """Little-endian bit expansion: ``(..., B)`` uint8 -> ``(..., K)`` float32."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (packed[..., None] >> shifts) & jnp.uint8(1)
    flat = bits.reshape(*packed.shape[:-1], packed.shape[-1] * 8)
    return flat[..., :K].astype(jnp.float32)


BYTE_TILE = 1024  # byte tiles are whole multiples of this (8 sublanes x 128 lanes)


def expand_packed(b: jax.Array, cpb: int) -> jax.Array:
    """In-kernel little-endian field expansion: ``(n,)`` int32 byte values
    (``n`` a multiple of ``BYTE_TILE``) -> ``(n * cpb,)`` int32 fields,
    field ``j`` of byte ``i`` at position ``cpb * i + j``, each ``8 // cpb``
    bits wide.

    Byte row ``r`` of the ``(R, 128)`` view holds the clients of output rows
    ``cpb*r .. cpb*r + cpb - 1``.  Plane ``q`` (one matmul with a 0/1 lane
    expansion matrix, then a per-lane shift) is output row ``cpb*r + q``
    for every ``r``; a 0/1 permutation matmul interleaves the stacked planes
    into row order.  Every product picks exactly one small integer, so both
    matmuls are exact.
    """
    n = b.shape[0]
    R, width = n // 128, 8 // cpb
    rows = b.astype(jnp.float32).reshape(R, 128)
    src = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
    shift = (jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1) % cpb) * width
    planes = []
    for q in range(cpb):
        expand = (src == (128 // cpb) * q + lane // cpb).astype(jnp.float32)
        v = jnp.dot(rows, expand, preferred_element_type=jnp.float32).astype(jnp.int32)
        planes.append((jnp.right_shift(v, shift) & ((1 << width) - 1)).astype(jnp.float32))
    stacked = jnp.concatenate(planes, axis=0)  # row q*R + r holds output row cpb*r + q
    dst = jax.lax.broadcasted_iota(jnp.int32, (cpb * R, cpb * R), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cpb * R, cpb * R), 1)
    perm = (col == (dst % cpb) * R + dst // cpb).astype(jnp.float32)
    out = jnp.dot(perm, stacked, preferred_element_type=jnp.float32)
    return out.astype(jnp.int32).reshape(n * cpb)


def _byte_tile(tile_b: int, B: int) -> int:
    """Bytes per grid step: at most ``tile_b`` (and the row), rounded up to a
    whole ``BYTE_TILE``."""
    tile_b = min(tile_b, max(B, 1))
    return -(-tile_b // BYTE_TILE) * BYTE_TILE


def _kernel(p_ref, x_ref):
    x_ref[...] = expand_packed(p_ref[...].astype(jnp.int32), 8).astype(jnp.float32)


def unpack_bits_kernel_call(packed: jax.Array, K: int, tile_b: int = 1024, interpret: bool = False):
    """packed: (B,) uint8 with ``B >= ceil(K/8)``. Returns (K,) float32."""
    B = packed.shape[0]
    tile_b = _byte_tile(tile_b, B)
    B_p = math.ceil(B / tile_b) * tile_b
    if B_p != B:
        packed = jnp.pad(packed, (0, B_p - B))
    n_tiles = B_p // tile_b
    out = pl.pallas_call(
        _kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile_b,), lambda t: (t,))],
        out_specs=pl.BlockSpec((tile_b * 8,), lambda t: (t,)),
        out_shape=jax.ShapeDtypeStruct((B_p * 8,), jnp.float32),
        interpret=interpret,
    )(packed)
    return out[:K]


def unpack_bits(packed: jax.Array, K: int, tile_b: int = 1024) -> jax.Array:
    """Dispatching row unpack: Pallas kernel on accelerators, jnp reference
    on CPU (where the interpreter would be the bottleneck); routed per call
    by ``REPRO_INTERPRET`` (``repro.kernels.dispatch``)."""
    from .dispatch import kernel_route  # deferred: dispatch is dependency-free

    use_kernel, interpret = kernel_route(cpu_kernel_default=False)
    if not use_kernel:
        return unpack_bits_ref(packed, K)
    return unpack_bits_kernel_call(packed, K, tile_b=tile_b, interpret=interpret)


def unpack_crumbs_ref(packed: jax.Array, K: int) -> jax.Array:
    """Little-endian 2-bit ("crumb") expansion: ``(..., B)`` uint8 ->
    ``(..., K)`` int32 codes in {0, 1, 2, 3}, 4 clients per byte.

    The async engine's lag traces (``repro.scenarios.replay``) store one crumb
    per client per round: codes 0..2 are completion lags, code 3 is the dead
    sentinel (decoded to ``DEAD_LAG`` by the caller).
    """
    shifts = jnp.arange(4, dtype=jnp.uint8) * jnp.uint8(2)
    crumbs = (packed[..., None] >> shifts) & jnp.uint8(3)
    flat = crumbs.reshape(*packed.shape[:-1], packed.shape[-1] * 4)
    return flat[..., :K].astype(jnp.int32)


def _crumb_kernel(p_ref, x_ref):
    x_ref[...] = expand_packed(p_ref[...].astype(jnp.int32), 4)


def unpack_crumbs_kernel_call(packed: jax.Array, K: int, tile_b: int = 1024, interpret: bool = False):
    """packed: (B,) uint8 with ``B >= ceil(K/4)``. Returns (K,) int32 codes."""
    B = packed.shape[0]
    tile_b = _byte_tile(tile_b, B)
    B_p = math.ceil(B / tile_b) * tile_b
    if B_p != B:
        packed = jnp.pad(packed, (0, B_p - B))
    n_tiles = B_p // tile_b
    out = pl.pallas_call(
        _crumb_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile_b,), lambda t: (t,))],
        out_specs=pl.BlockSpec((tile_b * 4,), lambda t: (t,)),
        out_shape=jax.ShapeDtypeStruct((B_p * 4,), jnp.int32),
        interpret=interpret,
    )(packed)
    return out[:K]


def unpack_crumbs(packed: jax.Array, K: int, tile_b: int = 1024) -> jax.Array:
    """Dispatching crumb unpack (see ``unpack_bits`` for the idiom)."""
    from .dispatch import kernel_route  # deferred: dispatch is dependency-free

    use_kernel, interpret = kernel_route(cpu_kernel_default=False)
    if not use_kernel:
        return unpack_crumbs_ref(packed, K)
    return unpack_crumbs_kernel_call(packed, K, tile_b=tile_b, interpret=interpret)
