"""K-sharded engine tests.

Pins the PR-1 architecture promise now that it is wired to a real mesh:

* ``prob_alloc_shmap`` on a forced 8-device CPU mesh == the local bisection
  (``masked_prob_alloc``) == the paper's literal case-enumeration oracle;
* the compiled sharded allocator contains **no sort** and exactly **one
  all-reduce inside the bisection loop** (collective count is independent of
  the iteration count);
* the distributed Plackett-Luce top-k (per-shard top-k + candidate merge) is
  *exactly* the dense ``plackett_luce_sample`` given the same perturbed
  scores, ragged shards and ties included;
* the mesh=1 sharded scan is **bit-identical** to the unsharded engine
  (``allocator="bisect"``) across all five schemes;
* the fused ``bisect_tiles`` kernel matches its jnp reference in interpret
  mode (bit-exact against same-order accumulation), and block-mode bisection
  matches plain bisection;
* ``masked_prob_alloc`` keeps float64 weights in float64 (x64 mode) instead
  of downcasting through the scalar-cast path.

The 8-device host comes from ``tests/conftest.py`` setting
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax loads.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.selection import plackett_luce_sample, prob_alloc_reference
from repro.core.selection.sampling import merge_topk_candidates, perturbed_scores
from repro.engine.scan_sim import scan_selection_sim
from repro.engine.sharded import (
    distributed_topk,
    masked_prob_alloc,
    plackett_luce_shmap,
    prob_alloc_shmap,
    sharded_selection_sim,
)
from repro.kernels.bisect_tiles import bisect_block_sums_kernel_call, bisect_block_sums_ref
from repro.launch.mesh import make_host_mesh
from repro.scenarios.replay import pack_trace

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8"
)


@pytest.fixture(scope="module")
def mesh8():
    return make_host_mesh(8)


@pytest.fixture(scope="module")
def mesh1():
    return make_host_mesh(1)


@needs8
class TestProbAllocShmap:
    @pytest.mark.parametrize("K", [100, 1000, 10_007, 100_000])
    @pytest.mark.parametrize("sigma_frac", [0.0, 0.5])
    def test_matches_local_and_oracle(self, mesh8, K, sigma_frac):
        rng = np.random.default_rng(K)
        k = max(1, K // 5)
        sigma = sigma_frac * k / K
        w = jnp.asarray(rng.gamma(0.3, 1.0, K).astype(np.float32))  # heavy tail forces capping
        p, capped = prob_alloc_shmap(w, k, sigma, mesh8)
        pm, cm = masked_prob_alloc(w, k, sigma)
        # acceptance bar: <= 1e-6 in p vs the single-device path
        np.testing.assert_allclose(np.asarray(p), np.asarray(pm), atol=1e-6)
        assert (np.asarray(capped) == np.asarray(cm)).all()
        pr, cr = prob_alloc_reference(np.asarray(w), k, sigma)
        np.testing.assert_allclose(np.asarray(p), pr, atol=1e-5)
        assert (np.asarray(capped) == cr).all()
        assert abs(float(np.asarray(p).sum()) - k) < 1e-3 * k + 1e-3

    def test_active_mask_ragged(self, mesh8):
        rng = np.random.default_rng(7)
        K, k = 531, 60
        w = jnp.asarray(rng.gamma(0.3, 1.0, K).astype(np.float32))
        active = jnp.asarray((rng.random(K) < 0.8).astype(np.float32))
        p, _ = prob_alloc_shmap(w, k, 0.0, mesh8, active=active)
        pm, _ = masked_prob_alloc(w, k, 0.0, active=active)
        np.testing.assert_allclose(np.asarray(p), np.asarray(pm), atol=1e-6)
        assert np.asarray(p)[np.asarray(active) == 0].sum() == 0.0

    def test_block_mode_matches_plain(self, mesh8):
        rng = np.random.default_rng(2)
        K, k = 50_000, 5000
        w = jnp.asarray(rng.gamma(0.3, 1.0, K).astype(np.float32))
        p1, c1 = masked_prob_alloc(w, k, 0.03)
        for block in (2, 4, 6):
            pb, cb = masked_prob_alloc(w, k, 0.03, block=block)
            np.testing.assert_allclose(np.asarray(pb), np.asarray(p1), atol=1e-6)
            assert (np.asarray(cb) == np.asarray(c1)).all()
        ps, _ = prob_alloc_shmap(w, k, 0.03, mesh8, block=4)
        np.testing.assert_allclose(np.asarray(ps), np.asarray(p1), atol=1e-6)

    def test_hlo_no_sort_one_psum_per_step(self, mesh8):
        # the architecture promise: one scalar all-reduce per bisection step
        # (it lives in the loop body, so the instruction count is independent
        # of n_iters) and no sort anywhere in the compiled allocator
        w = jnp.asarray(np.random.default_rng(0).gamma(0.3, 1.0, 4096).astype(np.float32))

        def hlo(n_iters):
            f = jax.jit(lambda w: prob_alloc_shmap(w, 512, 0.05, mesh8, n_iters=n_iters)[0])
            return f.lower(w).compile().as_text()

        h48, h12 = hlo(48), hlo(12)
        assert "sort(" not in h48, "sharded ProbAlloc must not materialise a global sort"
        n48, n12 = h48.count("all-reduce("), h12.count("all-reduce(")
        assert n48 == n12, "all-reduce count must not grow with bisection steps (one per step, in the loop body)"
        # loop-body psum + the 4 bracket/normalisation reductions (K_act,
        # w_sum, w_max, final capped sum)
        assert 0 < n48 <= 6, h48.count("all-reduce(")


@needs8
class TestDistributedTopK:
    @pytest.mark.parametrize("K,k", [(100, 10), (10_000, 100)])
    def test_equals_dense_plackett_luce(self, mesh8, K, k):
        # same perturbed score field => the per-shard top-k union provably
        # contains the global top-k, and the merge recovers it exactly
        rng = np.random.default_rng(K)
        p = jnp.asarray(rng.random(K).astype(np.float32))
        key = jax.random.PRNGKey(3)
        idx_dense = plackett_luce_sample(key, p, k)
        idx_dist = distributed_topk(perturbed_scores(key, p), k, mesh8)
        assert np.array_equal(np.asarray(idx_dense), np.asarray(idx_dist))

    def test_tie_order_matches_dense(self, mesh8):
        # integer-valued scores force cross-shard ties; lax.top_k breaks ties
        # by lowest index and the candidate merge must preserve that
        rng = np.random.default_rng(0)
        s = jnp.asarray(rng.integers(0, 4, 1000).astype(np.float32))
        _, dense = jax.lax.top_k(s, 37)
        dist = distributed_topk(s, 37, mesh8)
        assert np.array_equal(np.asarray(dense, np.int32), np.asarray(dist))

    def test_ragged_shards(self, mesh8):
        s = jnp.asarray(np.random.default_rng(1).normal(size=101).astype(np.float32))
        _, dense = jax.lax.top_k(s, 12)
        assert np.array_equal(np.asarray(dense, np.int32), np.asarray(distributed_topk(s, 12, mesh8)))

    def test_k_larger_than_shard_raises(self, mesh8):
        with pytest.raises(ValueError, match="shard width"):
            distributed_topk(jnp.zeros(64), 16, mesh8)

    def test_merge_containment_property(self):
        # direct check of the proof obligation: global top-k ⊆ union of
        # per-shard top-ks, for every shard width
        rng = np.random.default_rng(5)
        s = rng.normal(size=96).astype(np.float32)
        k = 7
        _, top = jax.lax.top_k(jnp.asarray(s), k)
        for D in (2, 4, 8):
            shards = s.reshape(D, -1)
            vals, idxs = [], []
            for d in range(D):
                v, i = jax.lax.top_k(jnp.asarray(shards[d]), k)
                vals.append(np.asarray(v))
                idxs.append(np.asarray(i) + d * shards.shape[1])
            union = set(np.concatenate(idxs).tolist())
            assert set(np.asarray(top).tolist()) <= union
            merged = merge_topk_candidates(jnp.asarray(np.stack(vals)), jnp.asarray(np.stack(idxs)), k)
            assert np.array_equal(np.asarray(merged), np.asarray(top, np.int32))

    def test_plackett_luce_shmap_draws(self, mesh8):
        # production sampler: valid duplicate-free cohorts, deterministic per
        # key, and mass concentrates on high-p arms
        p = jnp.asarray(np.concatenate([np.full(32, 0.01), np.full(32, 0.99)]).astype(np.float32))
        counts = np.zeros(64)
        for s in range(200):
            idx = np.asarray(plackett_luce_shmap(jax.random.PRNGKey(s), p, 8, mesh8))
            assert len(set(idx.tolist())) == 8 and (idx >= 0).all() and (idx < 64).all()
            counts[idx] += 1
        again = np.asarray(plackett_luce_shmap(jax.random.PRNGKey(199), p, 8, mesh8))
        assert (again >= 0).all()  # deterministic re-draw works
        assert counts[32:].sum() > 5 * counts[:32].sum()


class TestShardedScanBitIdentity:
    SCHEMES = [
        ("e3cs", dict(frac=0.5)),
        ("e3cs", dict(frac=0.0, volatility="markov")),
        ("e3cs", dict(quota="inc")),
        ("random", {}),
        ("ucb", {}),
        ("fedcs", {}),
        ("pow_d", {}),
    ]

    @pytest.mark.parametrize("scheme,kw", SCHEMES, ids=[f"{s}-{i}" for i, (s, _) in enumerate(SCHEMES)])
    def test_mesh1_matches_unsharded(self, mesh1, scheme, kw):
        a = sharded_selection_sim(scheme, mesh1, K=100, k=20, T=120, **kw)
        b = scan_selection_sim(scheme, K=100, k=20, T=120, allocator="bisect", **kw)
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])
        assert np.array_equal(a["counts"], b["counts"])
        np.testing.assert_allclose(a["sigmas"], b["sigmas"], atol=0)
        np.testing.assert_allclose(a["ps"], b["ps"], atol=1e-6)

    def test_mesh1_packed_override_matches_unsharded(self, mesh1):
        rng = np.random.default_rng(0)
        xs = rng.binomial(1, 0.6, (80, 96)).astype(np.float32)
        packed = pack_trace(xs)
        a = sharded_selection_sim("e3cs", mesh1, K=96, k=12, T=80, frac=0.25, packed_override=packed)
        b = scan_selection_sim("e3cs", K=96, k=12, T=80, frac=0.25, packed_override=packed, allocator="bisect")
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])


@needs8
class TestShardedScanD8:
    def test_dense_equals_packed_when_widths_align(self, mesh8):
        # K = 8 * D bytes-aligned => the dense and packed paths shard to the
        # same width, so the PRNG streams coincide and runs are bit-identical
        rng = np.random.default_rng(3)
        K, T = 128, 60
        xs = rng.binomial(1, 0.5, (T, K)).astype(np.float32)
        a = sharded_selection_sim("e3cs", mesh8, K=K, k=10, T=T, frac=0.5, xs_override=xs)
        b = sharded_selection_sim("e3cs", mesh8, K=K, k=10, T=T, frac=0.5, packed_override=pack_trace(xs))
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])
        np.testing.assert_array_equal(a["xs"], xs)

    def test_cardinality_and_lean_counts(self, mesh8):
        full = sharded_selection_sim("e3cs", mesh8, K=100, k=10, T=90, frac=0.5, seed=4)
        lean = sharded_selection_sim("e3cs", mesh8, K=100, k=10, T=90, frac=0.5, seed=4, outputs="lean")
        np.testing.assert_array_equal(full["masks"].sum(1), np.full(90, 10.0))
        assert np.array_equal(full["counts"], lean["counts"])
        np.testing.assert_allclose((full["masks"] * full["xs"]).sum(1), lean["successes"], atol=1e-4)

    def test_fleet_learns_stable_clients(self, mesh8):
        # behavioural check at D=8: E3CS mass concentrates on the rho=0.9
        # class exactly like the unsharded engine
        out = sharded_selection_sim("e3cs", mesh8, K=128, k=16, T=400, frac=0.0, seed=0, outputs="lean")
        per_class = out["counts"].reshape(4, -1).sum(1)
        assert per_class[3] > 2 * per_class[0], per_class

    def test_block_bisect_inside_scan(self, mesh8):
        a = sharded_selection_sim("e3cs", mesh8, K=96, k=8, T=60, frac=0.5, block=4)
        assert (a["masks"].sum(1) == 8).all()
        assert a["counts"].sum() == 8 * 60

    def test_build_scan_runner_mesh_kwarg(self, mesh8):
        # the public engine entry point threads the sharded round through the
        # same (run, state0) contract as the unsharded builder
        from repro.configs.base import FLConfig
        from repro.core.volatility import make_volatility, paper_success_rates
        from repro.engine.scan_sim import build_scan_runner

        fl = FLConfig(K=100, k=10, rounds=40, scheme="e3cs", quota_frac=0.5, allocator="bisect")
        rho = paper_success_rates(100)
        vol = make_volatility("bernoulli", jnp.asarray(rho))
        run, state0 = build_scan_runner(fl, vol, rho, outputs="lean", mesh=mesh8)
        state, successes, sigmas = run(state0, jax.random.PRNGKey(0), jnp.zeros((40, 0), jnp.float32))
        assert successes.shape == (40,)
        assert float(np.asarray(state.sel_counts)[:100].sum()) == 400.0
        # carry_key composes with the mesh since the RoundProgram unification:
        # two 20-round chunks reproduce the one-shot horizon bit-for-bit
        run_c, s0c = build_scan_runner(fl, vol, rho, outputs="lean", mesh=mesh8, carry_key=True, scan_length=20)
        st, key = s0c, jax.random.PRNGKey(0)
        succ = []
        for _ in range(2):
            st, key, s, _ = run_c(st, key, jnp.zeros((20, 0), jnp.float32))
            succ.append(np.asarray(s))
        assert np.array_equal(np.concatenate(succ), np.asarray(successes))
        np.testing.assert_array_equal(np.asarray(st.sel_counts), np.asarray(state.sel_counts))

    def test_exchange_is_scoped_and_changes_no_bit(self, mesh8, monkeypatch):
        # the staged round's candidate exchange (the all-gathers of the
        # (D*k,) candidates and their merge) is named round.exchange inside
        # round.sample; the scopes are metadata: the round built without
        # them (as before they were added) gives the same masks and
        # log-weights bit for bit
        import contextlib
        import re

        from repro.configs.base import FLConfig
        from repro.engine import round_program, sharded
        from repro.engine.round_program import RoundProgram

        K, k, T = 4096, 32, 24
        fl = FLConfig(K=K, k=k, rounds=10**9, scheme="e3cs", sampler="plackett_luce", allocator="bisect",
                      quota="const", quota_frac=0.5, eta=0.5)
        xs = jnp.asarray(pack_trace(np.random.default_rng(5).binomial(1, 0.6, (T, K)).astype(np.float32)))

        def run():
            program = RoundProgram.from_config(fl, mesh=mesh8, override="packed", block=4)
            runner, state0 = program.build_runner(outputs="full", scan_length=T)
            compiled = jax.jit(runner).lower(state0, jax.random.PRNGKey(3), xs).compile()
            state, masks, *_ = compiled(state0, jax.random.PRNGKey(3), xs)
            return compiled.as_text(), np.asarray(masks), np.asarray(state.e3cs.logw)

        hlo, masks, logw = run()
        gathers = [re.search(r'op_name="([^"]*)"', line).group(1)
                   for line in hlo.splitlines() if re.search(r"= \S+ all-gather(-start)?\(", line)]
        assert len(gathers) >= 2, "the exchange gathers candidate scores and indices"
        assert all("round.sample/round.exchange/" in op for op in gathers), gathers
        assert (masks.sum(1) == k).all()

        stage = round_program.stage
        monkeypatch.setattr(sharded, "stage", lambda name: contextlib.nullcontext())
        monkeypatch.setattr(round_program, "stage",
                            lambda name: contextlib.nullcontext() if name == "round.sample" else stage(name))
        hlo0, masks0, logw0 = run()
        assert "round.exchange" not in hlo0
        assert np.array_equal(masks, masks0)
        assert np.array_equal(logw.view(np.uint32), logw0.view(np.uint32))


class TestBisectTilesKernel:
    @pytest.mark.parametrize("K,tile", [(64, 128), (1000, 256), (8193, 1024)])
    def test_kernel_matches_ref(self, K, tile):
        rng = np.random.default_rng(K)
        w = jnp.asarray(rng.gamma(0.3, 1.0, K).astype(np.float32))
        caps = jnp.asarray(np.sort(rng.gamma(0.3, 1.0, 15)).astype(np.float32))
        out = bisect_block_sums_kernel_call(w, caps, tile=tile, interpret=True)
        # bit-exact against same-order (sequential per-tile) accumulation
        acc = np.zeros(15, np.float32)
        wp = np.pad(np.asarray(w), (0, (-K) % tile))
        for lo in range(0, wp.shape[0], tile):
            acc = acc + np.asarray(bisect_block_sums_ref(jnp.asarray(wp[lo : lo + tile]), caps, tile=tile))
        np.testing.assert_array_equal(np.asarray(out), acc)
        # and within float roundoff of the vectorised two-level reference
        np.testing.assert_allclose(np.asarray(out), np.asarray(bisect_block_sums_ref(w, caps, tile=tile)), rtol=1e-6)

    def test_single_tile_bit_exact_vs_ref(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.gamma(0.3, 1.0, 500).astype(np.float32))
        caps = jnp.asarray(np.linspace(0.01, 2.0, 7).astype(np.float32))
        a = bisect_block_sums_ref(w, caps, tile=512)
        b = bisect_block_sums_kernel_call(w, caps, tile=512, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestAllocatorDtype:
    """Satellite: float64 weights must solve in float64 (x64 mode), not be
    squeezed through float32 scalar casts or a flat 1e-30 epsilon."""

    @pytest.mark.parametrize("dtype", ["float32", "float64"])
    def test_dtype_preserved_and_accurate(self, dtype):
        rng = np.random.default_rng(11)
        K, k = 5000, 500
        sigma = 0.25 * k / K
        if dtype == "float64":
            with jax.enable_x64():
                w = jnp.asarray(rng.gamma(0.3, 1.0, K))
                assert w.dtype == jnp.float64
                p, capped = masked_prob_alloc(w, k, sigma)
                assert p.dtype == jnp.float64
                pr, cr = prob_alloc_reference(np.asarray(w), k, sigma)
                np.testing.assert_allclose(np.asarray(p), pr, atol=1e-12)
                assert (np.asarray(capped) == cr).all()
                # the traced-scalar path must not downcast either
                p2, _ = jax.jit(lambda w, kk, s: masked_prob_alloc(w, kk, s))(
                    w, jnp.asarray(float(k)), jnp.asarray(sigma)
                )
                assert p2.dtype == jnp.float64
                np.testing.assert_allclose(np.asarray(p2), pr, atol=1e-12)
        else:
            w = jnp.asarray(rng.gamma(0.3, 1.0, K).astype(np.float32))
            p, _ = masked_prob_alloc(w, k, sigma)
            assert p.dtype == jnp.float32
            pr, _ = prob_alloc_reference(np.asarray(w), k, sigma)
            np.testing.assert_allclose(np.asarray(p), pr, atol=1e-5)

    def test_float64_block_mode(self):
        rng = np.random.default_rng(12)
        with jax.enable_x64():
            w = jnp.asarray(rng.gamma(0.3, 1.0, 2000))
            p1, _ = masked_prob_alloc(w, 200, 0.01)
            p4, _ = masked_prob_alloc(w, 200, 0.01, block=4)
            assert p4.dtype == jnp.float64
            np.testing.assert_allclose(np.asarray(p4), np.asarray(p1), atol=1e-10)
