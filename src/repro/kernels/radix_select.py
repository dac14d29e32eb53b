"""Exact top-k as a cohort mask, without a sort: a counting radix select.

The E3CS round needs the *set* of the ``k`` largest Plackett-Luce scores,
never their order: every consumer (``e3cs_update``, the loss cache, the
selection counts, the taps, the lean outputs) reads the ``(K,)`` cohort
mask.  ``lax.top_k`` on a TPU lowers to a full sort of ``K`` (value, index)
pairs and the ids are then scattered back into a mask, so the ordering it
pays for is thrown away.  This module finds the same set from the bits of
the scores:

1. **Order-preserving keys.**  ``score_keys`` bitcasts the float32 scores to
   int32 and flips the low 31 bits of negatives (``u ^ ((u >> 31) &
   0x7fffffff)``): signed integer order on the keys is the float total
   order ``lax.top_k`` uses (``-0.0`` below ``+0.0``, ``-inf`` at the
   bottom; pinned in ``tests/test_radix_select.py``).
2. **Radix select** of ``T``, the ``k``-th largest key: the largest value
   with ``count(key >= T) >= k``, resolved ``RADIX_BITS`` at a time from
   the top.  A pass counts ``key >= lo + j * 2**shift`` for the
   ``2**RADIX_BITS - 1`` interior probes of the current bracket in **one
   read** of the keys (``count_ge``; the same shape as
   ``bisect_tiles.bisect_block_sums``) and takes the next digit from the
   monotone counts: ``32 / RADIX_BITS`` passes.
3. **Mask.**  ``key > T`` is always in; ``key == T`` fills the remaining
   ``need = k - count(key > T)`` slots.  Where every tied key fits
   (``count(key >= T) == k``) the mask is ``key >= T``.  Otherwise ties go
   lowest index first, as ``lax.top_k`` breaks them: a second radix select
   over ``-index`` of the tied keys (distinct, so untied) takes the ``need``
   lowest indices, under a ``lax.cond`` that runs it only on such calls.
   They are common at fleet scale: float32 Gumbel noise has a few hundred
   distinct values among the top ``k`` of ``1e7`` draws, so the ``k``-th
   score is tied on about 40% of rounds at K=1e7 (PERF.md).

The mask is bit-for-bit ``selection_mask(lax.top_k(scores, k)[1], K)``,
its oracle; ``threshold_select_route`` picks between the two for a round.

``count_ge`` routes per call through ``repro.kernels.dispatch``: the Pallas
kernel on a TPU (one ``ROWS * 128``-key int32 block per grid step, read
once, every probe counted against it into a lane-dense ``(probes, 8, 128)``
partial-count block), the jnp reference elsewhere.  The keys stay 1-D and
unpadded, in XLA's own layout (the kernel masks its last, partial block),
so the pass that writes them and the mask pass that reads them move no
extra copy.  Counts are integers, so kernel and reference agree exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "RADIX_BITS",
    "ROWS",
    "score_keys",
    "count_ge_ref",
    "count_ge_kernel_call",
    "count_ge",
    "threshold_select_route",
    "topk_mask",
]

LANES = 128
RADIX_BITS = 2  # key bits resolved per pass (divides 32): 3 probes, 16 passes over K (fastest on a v5e, PERF.md)
ROWS = 2048  # key rows per grid step: a 1 MiB int32 block
_BIAS = np.uint32(0x80000000)  # signed key <-> unsigned bracket arithmetic
KEY_MIN = np.iinfo(np.int32).min  # below every probe: never counted


def score_keys(scores: jax.Array) -> jax.Array:
    """float32 -> int32 keys whose signed order is the floats' total order."""
    u = jax.lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    return u ^ ((u >> 31) & 0x7FFFFFFF)


def _block(n: int) -> int:
    """Keys per grid step: ``ROWS`` rows of 128, or fewer where the keys are
    fewer, a whole number of 1024-key tiles (XLA's layout of a 1-D array)."""
    return -(-min(ROWS * LANES, n) // 1024) * 1024


def count_ge_ref(keys: jax.Array, thr: jax.Array) -> jax.Array:
    """``(P,)`` int32 counts ``count(keys >= thr[j])`` for ascending ``thr``
    (the jnp reference): each key's rank among the probes, a histogram of
    the ranks, its suffix sums — one pass, fast on a CPU."""
    rank = jnp.sum(keys[:, None] >= thr[None, :], axis=1, dtype=jnp.int32)
    hist = jnp.zeros(thr.shape[0] + 1, jnp.int32).at[rank].add(1)
    return jnp.cumsum(hist[::-1])[::-1][1:]


def _count_kernel(thr_ref, x_ref, out_ref, *, n_thr, n, block):
    # accumulates across grid steps into one resident output block: the
    # grid runs sequentially ("arbitrary")
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def count(x):  # the block's keys, read once for every probe
        x = x.reshape(-1, 8, LANES)
        for j in range(n_thr):
            out_ref[j] += jnp.sum((x >= thr_ref[j]).astype(jnp.int32), axis=0)

    last, tail = divmod(n, block)
    if not tail:
        count(x_ref[...])
        return

    @pl.when(i < last)
    def _whole():
        count(x_ref[...])

    @pl.when(i == last)
    def _partial():  # past the keys the block holds no data: count KEY_MIN there
        shape = (block // LANES, LANES)
        pos = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
               + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
        count(jnp.where(pos < tail, x_ref[...].reshape(shape), KEY_MIN))


def count_ge_kernel_call(keys: jax.Array, thr: jax.Array, interpret: bool = False):
    """Pallas ``count_ge`` (any ``thr``): ``ROWS * 128`` keys a grid step,
    the last step masked where ``keys`` are not whole steps."""
    n = keys.shape[0]
    block = _block(n)
    n_thr = thr.shape[0]
    part = pl.pallas_call(
        functools.partial(_count_kernel, n_thr=n_thr, n=n, block=block),
        grid=(pl.cdiv(n, block),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((n_thr, 8, LANES), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_thr, 8, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(thr.astype(jnp.int32), keys)
    return jnp.sum(part, axis=(1, 2))


def count_ge(keys: jax.Array, thr: jax.Array) -> jax.Array:
    """Dispatching probe count over ascending ``thr``: Pallas kernel on
    TPU, jnp reference elsewhere; routed per call by ``REPRO_INTERPRET``."""
    from .dispatch import kernel_route  # deferred: dispatch is dependency-free

    use_kernel, interpret = kernel_route(cpu_kernel_default=False)
    if not use_kernel:
        return count_ge_ref(keys, thr)
    return count_ge_kernel_call(keys, thr, interpret=interpret)


def threshold_select_route() -> bool:
    """Whether a round writes its cohort mask by ``topk_mask`` (read once,
    when the round is built): where the count kernel runs — a TPU, whose
    ``lax.top_k`` is a full sort over K, or ``REPRO_INTERPRET=1``.  On a
    CPU ``lax.top_k`` is a partial sort, ~15x faster than 16 reference
    passes at K=1e6 (4.6 against 68 ms on an 8-core CPU host), so CPU
    rounds (the tests, ``benchmarks/``) keep
    ``selection_mask(plackett_luce_sample(...))``, the same mask, where
    ``bench/tests/test_bench_faults.py`` plants its altered-cohort fault."""
    from .dispatch import kernel_route  # deferred: dispatch is dependency-free

    return kernel_route(cpu_kernel_default=False)[0]


def topk_mask(scores: jax.Array, k: int):
    """The exact top-``k`` of ``(K,)`` float32 ``scores`` as a float32 mask.

    Returns ``(mask, ties)``: ``mask`` equals
    ``selection_mask(lax.top_k(scores, k)[1], K)`` bit for bit; ``ties`` is
    a bool scalar, True on calls whose ``k``-th score was tied with a score
    left out (the tie branch ran).
    """
    K = scores.shape[0]
    if not 0 < k <= K:
        raise ValueError(f"need 0 < k <= K, got k={k}, K={K}")
    with jax.named_scope("radix_select"):
        return _topk_mask(scores, k)


def _kth_largest(keys, k):
    """Radix select over int32 ``keys``: ``(t, c_ge, c_gt)``, the
    ``k``-th largest key and ``count(key >= t)``, ``count(key > t)``
    (``k`` may be traced)."""
    bits = RADIX_BITS
    steps = jnp.arange(1, 1 << bits, dtype=jnp.uint32)

    def digit(i, carry):
        # bracket [lo, lo + 2**(shift + bits)) in unsigned key space, with
        # c_lo = count(key >= lo) >= k > c_hi = count(key >= its top)
        lo, c_lo, c_hi = carry
        shift = jnp.uint32(32 - bits) - jnp.uint32(bits) * i.astype(jnp.uint32)
        probes = lo + (steps << shift)
        cnt = count_ge(keys, jax.lax.bitcast_convert_type(probes ^ _BIAS, jnp.int32))
        j = jnp.sum((cnt >= k).astype(jnp.int32))
        full = jnp.concatenate([c_lo[None], cnt, c_hi[None]])
        return lo + (j.astype(jnp.uint32) << shift), full[j], full[j + 1]

    carry0 = (jnp.uint32(0), jnp.int32(keys.shape[0]), jnp.int32(0))
    lo, c_ge, c_gt = jax.lax.fori_loop(0, 32 // bits, digit, carry0)
    return jax.lax.bitcast_convert_type(lo ^ _BIAS, jnp.int32), c_ge, c_gt


def _topk_mask(scores, k):
    keys = score_keys(scores)
    t, c_ge, c_gt = _kth_largest(keys, k)
    ties = c_ge > k

    # each branch writes the mask itself: the barrier keeps XLA from moving
    # the mask's consumers into the branch and fusing the write under their
    # names (the trace reads an op's stage from its name)
    def untied(keys):
        return jax.lax.optimization_barrier((keys >= t).astype(jnp.float32))

    def tied(keys):
        # the k - c_gt lowest indices among the keys equal to t: the largest
        # of -index over them, by a second select (their keys are distinct)
        order = jnp.where(keys == t, -jnp.arange(keys.shape[0], dtype=jnp.int32), KEY_MIN)
        t_idx, _, _ = _kth_largest(order, k - c_gt)
        return jax.lax.optimization_barrier(((keys > t) | (order >= t_idx)).astype(jnp.float32))

    return jax.lax.cond(ties, tied, untied, keys), ties
