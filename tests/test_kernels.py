"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gumbel_topk import gumbel_topk_kernel_call

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("K,k,tile", [(100, 10, 32), (1000, 20, 256), (513, 7, 128), (2600, 30, 1024)])
def test_gumbel_topk_matches_lax_topk(K, k, tile):
    scores = jnp.asarray(RNG.normal(size=(K,)), jnp.float32)
    vals, idx = gumbel_topk_kernel_call(scores, k, tile=tile, interpret=True)
    idx_ref = ref.gumbel_topk_ref(scores, k)
    assert sorted(np.asarray(idx).tolist()) == sorted(np.asarray(idx_ref).tolist())


# Non-divisible tile sizes on purpose: every K here leaves a ragged final tile
# (K=7 pads 7 -> 8; 100 % 48 != 0; 10000 % 4096 != 0).
GUMBEL_CASES = [(7, 3, 8192), (7, 7, 8192), (100, 20, 48), (10000, 64, 4096), (10000, 200, 8192)]


@pytest.mark.parametrize("K,k,tile", GUMBEL_CASES, ids=[f"K{K}-k{k}-t{t}" for K, k, t in GUMBEL_CASES])
def test_gumbel_topk_perturbed_scores_agree_with_lax(K, k, tile):
    """Agreement with jax.lax.top_k on actual Gumbel-perturbed allocations."""
    p = jnp.asarray(RNG.gamma(1.0, 1.0, K).astype(np.float32))
    p = p / p.sum() * k
    g = jax.random.gumbel(jax.random.PRNGKey(K + k), p.shape, jnp.float32)
    scores = jnp.log(jnp.maximum(p, 1e-20)) + g
    vals, idx = gumbel_topk_kernel_call(scores, k, tile=tile, interpret=True)
    _, idx_ref = jax.lax.top_k(scores, k)
    idx = np.asarray(idx)
    assert sorted(idx.tolist()) == sorted(np.asarray(idx_ref).tolist())
    # duplicate-free guarantee and in-range indices
    assert len(set(idx.tolist())) == k
    assert (idx >= 0).all() and (idx < K).all()
    # values returned descending and consistent with the indices
    v = np.asarray(vals)
    assert (np.diff(v) <= 1e-6).all()
    np.testing.assert_allclose(v, np.asarray(scores)[idx], atol=1e-6)


@pytest.mark.parametrize("K,k,tile", [(7, 3, 8), (100, 20, 48), (10000, 64, 4096)])
def test_fused_gumbel_topk_matches_unfused(K, k, tile):
    """The fused perturb+topk kernel must agree with the jnp composition."""
    from repro.kernels.e3cs_tiles import fused_gumbel_topk_kernel_call

    p = jnp.asarray(RNG.gamma(1.0, 1.0, K).astype(np.float32))
    p = p / p.sum() * k
    u = jax.random.uniform(jax.random.PRNGKey(1), p.shape, jnp.float32)
    _, idx = fused_gumbel_topk_kernel_call(p, u, k, tile=tile, interpret=True)
    g = -jnp.log(-jnp.log(jnp.clip(u, 1e-20, 1.0 - 1e-7)))
    _, idx_ref = jax.lax.top_k(jnp.log(jnp.maximum(p, 1e-20)) + g, k)
    idx = np.asarray(idx)
    assert sorted(idx.tolist()) == sorted(np.asarray(idx_ref).tolist())
    assert len(set(idx.tolist())) == k


@pytest.mark.parametrize("K,k,tile", [(100, 20, 48), (5000, 100, 1024)])
def test_e3cs_update_kernel_matches_reference(K, k, tile):
    from repro.core.selection import E3CSState, e3cs_update, prob_alloc
    from repro.kernels.e3cs_tiles import e3cs_update_kernel_call

    logw = jnp.asarray(RNG.normal(0, 1, K).astype(np.float32))
    sigma = jnp.float32(0.3 * k / K)
    eta = 0.5
    w = jnp.exp(logw - jnp.max(logw))
    p, capped = prob_alloc(w, k, sigma)
    mask = jnp.zeros(K).at[jax.lax.top_k(p, k)[1]].set(1.0)
    x = jnp.asarray((RNG.random(K) < 0.6).astype(np.float32))
    expect = e3cs_update(E3CSState(logw=logw, t=jnp.zeros((), jnp.int32)), p, capped, mask, x, k, sigma, eta)
    scale = (k - K * sigma) * eta / K
    out, tmax = e3cs_update_kernel_call(logw, p, mask, x, capped.astype(jnp.float32), scale, tile=tile, interpret=True)
    np.testing.assert_allclose(np.asarray(out - jnp.max(tmax)), np.asarray(expect.logw), atol=1e-6)


def test_gumbel_topk_sampler_distribution():
    # inclusion frequency should favour high-probability arms
    p = jnp.asarray([0.05] * 16 + [0.8] * 4, jnp.float32)
    p = p / p.sum() * 4
    hits = np.zeros(20)
    for i in range(300):
        idx = ops.gumbel_topk_sample(jax.random.PRNGKey(i), p, 4, tile=32)
        hits[np.asarray(idx)] += 1
    assert hits[16:].mean() > 4 * hits[:16].mean()


# ---------------------------------------------------------------------------
# Dispatch routing: REPRO_INTERPRET is read per call, autotune tiles per size
# ---------------------------------------------------------------------------


def test_repro_interpret_flip_takes_effect_mid_process(monkeypatch):
    """Flipping REPRO_INTERPRET between calls must change the route without a
    process restart (the old wrappers read the env at trace time and froze
    it into the jit cache)."""
    from repro.kernels import ops as ops_mod

    calls = []
    real = ops_mod.gumbel_topk_kernel_call

    def spy(scores, k, tile=8192, interpret=False):
        calls.append(interpret)
        return real(scores, k, tile=tile, interpret=interpret)

    monkeypatch.setattr(ops_mod, "gumbel_topk_kernel_call", spy)
    # unique K so each route change compiles fresh (jit caches per static combo)
    p = jnp.asarray(RNG.gamma(1.0, 1.0, 263).astype(np.float32))
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    idx_ref = ops.gumbel_topk_sample(jax.random.PRNGKey(0), p, 5, tile=64)
    assert calls == []  # forced-ref mode: the kernel is never invoked
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    idx_kern = ops.gumbel_topk_sample(jax.random.PRNGKey(0), p, 5, tile=64)
    assert calls and calls[-1] is True  # flipped: kernel path, interpret on CPU
    assert sorted(np.asarray(idx_kern).tolist()) == sorted(np.asarray(idx_ref).tolist())
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    n = len(calls)
    ops.gumbel_topk_sample(jax.random.PRNGKey(1), p, 5, tile=64)
    assert len(calls) == n  # flipped back: ref again


def test_repro_interpret_rejects_garbage(monkeypatch):
    from repro.kernels.dispatch import interpret_mode

    monkeypatch.setenv("REPRO_INTERPRET", "maybe")
    with pytest.raises(ValueError):
        interpret_mode()


def test_dispatch_consults_autotune_cache(monkeypatch, tmp_path):
    """tile=None must resolve through the on-disk autotune cache."""
    import json

    from repro.kernels import autotune
    from repro.kernels import ops as ops_mod

    monkeypatch.setenv("REPRO_AUTOTUNE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    key = autotune.cache_key("gumbel_topk", 263)
    (tmp_path / "autotune.json").write_text(json.dumps({key: {"tile": 48}}))

    seen = []
    real = ops_mod.gumbel_topk_kernel_call

    def spy(scores, k, tile=8192, interpret=False):
        seen.append(tile)
        return real(scores, k, tile=tile, interpret=interpret)

    monkeypatch.setattr(ops_mod, "gumbel_topk_kernel_call", spy)
    p = jnp.asarray(RNG.gamma(1.0, 1.0, 263).astype(np.float32))
    ops.gumbel_topk_sample(jax.random.PRNGKey(0), p, 5)  # tile=None -> cache
    assert seen == [48]
    # a size outside the cached bucket falls back to the defaults, recorded cold
    autotune.reset_cold()
    bigp = jnp.asarray(RNG.gamma(1.0, 1.0, 3001).astype(np.float32))
    ops.gumbel_topk_sample(jax.random.PRNGKey(0), bigp, 5)
    assert seen[-1] == autotune.DEFAULTS["gumbel_topk"]["tile"]
    assert autotune.cache_key("gumbel_topk", 3001) in autotune.cold_keys()
