"""Fused round path: the ``fused=True`` engine must reproduce the staged
goldens bit-for-bit, under both the default dispatch (jnp references on CPU)
and ``REPRO_INTERPRET=1`` (Pallas kernels in interpret mode); the kernels
themselves are pinned against the ``ref.py`` oracles at ragged sizes and
across tile choices.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig
from repro.core.volatility import CompletionLag, make_volatility, paper_success_rates
from repro.engine import scan_sim
from repro.engine.round_program import RoundProgram
from repro.engine.scan_sim import async_selection_sim, scan_selection_sim
from repro.engine.sharded import masked_prob_alloc_scalars, sharded_selection_sim
from repro.kernels import ref
from repro.kernels.round_fused import fused_alloc_select, fused_perturb_select, fused_round_tail
from repro.scenarios.replay import pack_trace

K, k, T, SEED, FRAC = 128, 16, 50, 3, 0.5
GOLD = np.load(os.path.join(os.path.dirname(__file__), "golden", "round_program_goldens.npz"))


@pytest.fixture(scope="module")
def mesh8():
    from repro.launch.mesh import make_host_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8 (set in conftest)")
    return make_host_mesh(8)


@pytest.fixture(scope="module")
def mesh1():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(1)


def _rho():
    return paper_success_rates(K)


def _lag_model():
    return CompletionLag(
        make_volatility("bernoulli", _rho()), p_late=0.7, lag_decay=0.5, max_lag=2
    )


def _dense_xs():
    return np.random.default_rng(11).binomial(1, 0.6, (T, K)).astype(np.float32)


class TestFusedSyncGoldens:
    """fused=True, D=1: identical masks to the staged pre-refactor goldens."""

    def test_sort_allocator(self):
        out = scan_selection_sim("e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, fused=True)
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d1_e3cs_masks"])
        assert np.array_equal(out["counts"], GOLD["sync_d1_e3cs_counts"])

    def test_bisect_allocator(self):
        out = scan_selection_sim(
            "e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, allocator="bisect", fused=True
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d1_e3cs_bisect_masks"])

    def test_dense_replay(self):
        out = scan_selection_sim(
            "e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, xs_override=_dense_xs(), fused=True
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d1_dense_masks"])

    def test_packed_replay(self):
        packed = pack_trace(_dense_xs())
        out = scan_selection_sim(
            "e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, packed_override=packed, fused=True
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d1_packed_masks"])

    @pytest.mark.parametrize("allocator,key", [("sort", "sync_d1_e3cs_masks"),
                                               ("bisect", "sync_d1_e3cs_bisect_masks")])
    def test_interpret_kernels_reproduce_goldens(self, monkeypatch, allocator, key):
        # REPRO_INTERPRET=1 swaps the jnp references for the Pallas kernels in
        # interpret mode INSIDE the scanned round — the goldens must survive.
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        scan_sim._compiled_runner.cache_clear()  # route is frozen at trace time
        try:
            out = scan_selection_sim(
                "e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, allocator=allocator, fused=True
            )
        finally:
            scan_sim._compiled_runner.cache_clear()  # don't leak interpret traces
        assert np.array_equal(pack_trace(out["masks"]), GOLD[key])

    def test_interpret_kernels_packed_replay(self, monkeypatch):
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        packed = pack_trace(_dense_xs())  # override path builds a fresh trace per call
        out = scan_selection_sim(
            "e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, packed_override=packed, fused=True
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d1_packed_masks"])


class TestFusedAsyncGoldens:
    """fused=True, S=2, D=1: the async staleness machinery runs inside the
    tail kernel (lag decode, credit-ring shift, late feedback)."""

    def _kw(self):
        return dict(K=K, k=k, T=T, frac=FRAC, seed=SEED, staleness=2, alpha=0.5, rho=_rho())

    def test_generated(self):
        out = async_selection_sim("e3cs", lag_model=_lag_model(), fused=True, **self._kw())
        assert np.array_equal(pack_trace(out["masks"]), GOLD["async_d1_e3cs_masks"])
        assert np.array_equal(out["lags"].astype(np.int8), GOLD["async_d1_e3cs_lags"])
        assert np.array_equal(out["counts"], GOLD["async_d1_e3cs_counts"])
        assert np.float32(out["cep"]) == GOLD["async_d1_e3cs_cep"]
        assert np.array_equal(out["on_time"], GOLD["async_d1_e3cs_on_time"])
        assert np.array_equal(out["stale"], GOLD["async_d1_e3cs_stale"])

    def test_packed_lags_override(self):
        lp = GOLD["lag_trace_packed"]
        out = async_selection_sim(
            "e3cs", lag_model=_lag_model(), packed_lag_override=lp, fused=True, **self._kw()
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["async_d1_replay_masks"])
        assert np.float32(out["cep"]) == GOLD["async_d1_replay_cep"]

    def test_late_credit_matches_staged(self):
        # no golden exists for late_credit; pin fused == staged directly
        outs = [
            async_selection_sim("e3cs", lag_model=_lag_model(), feedback="late_credit",
                                fused=f, **self._kw())
            for f in (True, False)
        ]
        assert np.array_equal(outs[0]["masks"], outs[1]["masks"])
        np.testing.assert_array_equal(outs[0]["final_logw"], outs[1]["final_logw"])
        assert outs[0]["cep"] == outs[1]["cep"]

    def test_interpret_kernels_packed_lags(self, monkeypatch):
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        lp = GOLD["lag_trace_packed"]
        out = async_selection_sim(
            "e3cs", lag_model=_lag_model(), packed_lag_override=lp, fused=True, **self._kw()
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["async_d1_replay_masks"])
        assert np.float32(out["cep"]) == GOLD["async_d1_replay_cep"]


class TestFusedSharded:
    """fused=True under the K-sharded engine: the select kernel emits local
    top-k candidates that merge across shards exactly like the staged path."""

    def test_sync_d8_goldens(self, mesh8):
        out = sharded_selection_sim("e3cs", mesh8, K=K, k=k, T=T, frac=FRAC, seed=SEED, fused=True)
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d8_e3cs_masks"])
        assert np.array_equal(out["counts"], GOLD["sync_d8_e3cs_counts"])

    def test_packed_d8_goldens(self, mesh8):
        packed = pack_trace(_dense_xs())
        out = sharded_selection_sim(
            "e3cs", mesh8, K=K, k=k, T=T, frac=FRAC, seed=SEED, packed_override=packed, fused=True
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d8_packed_masks"])

    def _async_run(self, mesh, fused, feedback="deadline"):
        fl = FLConfig(K=K, k=k, rounds=T, scheme="e3cs", quota_frac=FRAC, allocator="bisect")
        pm = RoundProgram(fl=fl, vol=_lag_model(), rho=_rho(), override="packed_lags",
                          staleness=2, alpha=0.5, feedback=feedback, mesh=mesh, fused=fused)
        run, s0 = pm.build_runner(outputs="full")
        st, masks, lags, *_ = run(s0, jax.random.PRNGKey(SEED), jnp.asarray(GOLD["lag_trace_packed"]))
        return np.asarray(masks)[:, :K], np.asarray(lags)[:, :K], float(st.cep), np.asarray(st.e3cs.logw)

    @pytest.mark.parametrize("feedback", ["deadline", "late_credit"])
    def test_async_d8_matches_staged(self, mesh8, feedback):
        mf, lf, cf, wf = self._async_run(mesh8, True, feedback)
        ms, ls, cs, ws = self._async_run(mesh8, False, feedback)
        assert np.array_equal(mf, ms)
        assert np.array_equal(lf, ls)
        assert cf == cs
        np.testing.assert_array_equal(wf[: K], ws[: K])

    def test_mesh1_matches_local(self, mesh1):
        mf, lf, cf, wf = self._async_run(mesh1, True)
        ml, ll, cl, wl = self._async_run(None, True)
        assert np.array_equal(mf, ml)
        assert np.array_equal(lf, ll)
        assert cf == cl
        np.testing.assert_array_equal(wf[:K], wl)


# ---------------------------------------------------------------------------
# Kernel-level: interpret-mode Pallas vs ref.py oracles, ragged K, tiles
# ---------------------------------------------------------------------------

RAGGED_K = 130  # 130 % 64 != 0: exercises the padded final tile


def _select_inputs(n=RAGGED_K, kk=16, with_active=False):
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.gamma(1.0, 1.0, n).astype(np.float32))
    g = jax.random.gumbel(jax.random.PRNGKey(17), (n,), jnp.float32)
    active = jnp.asarray((rng.random(n) < 0.85).astype(np.float32)) if with_active else None
    if active is not None:
        w = w * active
    sigma = jnp.float32(0.3 * kk / n)
    scalars = masked_prob_alloc_scalars(w, kk, sigma, active=active)
    return w, g, kk, sigma, scalars, active


@pytest.mark.parametrize("with_active", [False, True])
def test_alloc_select_kernel_matches_ref_ragged(with_active):
    w, g, kk, sigma, scalars, active = _select_inputs(with_active=with_active)
    pr, cr, vr, ir = ref.fused_alloc_select_ref(w, g, kk, sigma=sigma, scalars=scalars, active=active)
    pk, ck, vk, ik = fused_alloc_select(
        w, g, kk, sigma=sigma, scalars=scalars, active=active, tile=64, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(ck).astype(bool), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


def test_perturb_select_kernel_matches_ref_ragged():
    w, g, kk, sigma, scalars, _ = _select_inputs()
    p, *_ = ref.fused_alloc_select_ref(w, g, kk, sigma=sigma, scalars=scalars)
    vr, ir = ref.fused_perturb_select_ref(p, g, kk)
    vk, ik = fused_perturb_select(p, g, kk, tile=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


def test_select_kernel_tile_invariant():
    w, g, kk, sigma, scalars, _ = _select_inputs()
    outs = [
        fused_alloc_select(w, g, kk, sigma=sigma, scalars=scalars, tile=t, interpret=True)
        for t in (64, 8192)
    ]
    for a, b in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _tail_inputs(n=RAGGED_K, kind="bits", S=2, with_active=False, late_fb=False):
    rng = np.random.default_rng(9)
    p = rng.gamma(1.0, 1.0, n).astype(np.float32)
    p = np.clip(p / p.sum() * 16, 0.01, 0.97)
    mask = (rng.random(n) < 0.2).astype(np.float32)
    capped = jnp.asarray(rng.random(n) < 0.1)
    logw = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
    loss_cache = jnp.asarray(rng.random(n).astype(np.float32))
    if kind == "bits":
        obs = jnp.asarray(rng.integers(0, 256, (n + 7) // 8, dtype=np.uint8))
    elif kind == "crumbs":
        obs = jnp.asarray(rng.integers(0, 256, (n + 3) // 4, dtype=np.uint8))
    elif kind == "x":
        obs = jnp.asarray((rng.random(n) < 0.6).astype(np.float32))
    else:  # dense lags
        obs = jnp.asarray(rng.integers(0, 3, n, dtype=np.int32))
    credit = jnp.asarray(rng.random((S, n)).astype(np.float32)) if S else None
    fb = jnp.asarray(rng.normal(0, 0.1, (S, n)).astype(np.float32)) if late_fb else None
    active = jnp.asarray((rng.random(n) < 0.9).astype(np.float32)) if with_active else None
    kw = dict(kind=kind, residual=jnp.float32(16.0 - n * 0.02), eta=0.5, K_glob=n,
              decay=tuple(0.5 ** (s + 1) for s in range(S)), active=active)
    args = (obs, mask, jnp.asarray(p), capped, logw, loss_cache, credit, fb)
    return args, kw


TAIL_CASES = [
    ("bits", 0, False, False),
    ("x", 0, False, True),
    ("crumbs", 2, False, False),
    ("crumbs", 2, True, True),
    ("lag", 2, True, False),
]


@pytest.mark.parametrize("kind,S,late_fb,with_active", TAIL_CASES,
                         ids=[f"{c[0]}-S{c[1]}{'-fb' if c[2] else ''}{'-act' if c[3] else ''}"
                              for c in TAIL_CASES])
def test_round_tail_kernel_matches_ref_ragged(kind, S, late_fb, with_active):
    sync = kind in ("bits", "x")
    args, kw = _tail_inputs(kind=kind, S=0 if sync else S, with_active=with_active, late_fb=late_fb)
    want = ref.round_tail_ref(*args, **kw)
    got = fused_round_tail(*args, **kw, tile=64, interpret=True)
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(
            np.asarray(got[key]), np.asarray(want[key]), err_msg=f"tail product {key!r}"
        )


@pytest.mark.parametrize("with_active", [False, True])
def test_select_kernel_multi_tile_matches_ref(with_active):
    # three 1024-score tiles, the last one ragged: the cross-tile merge
    w, g, kk, sigma, scalars, active = _select_inputs(n=2 * 1024 + RAGGED_K, kk=40, with_active=with_active)
    pr, cr, vr, ir = ref.fused_alloc_select_ref(w, g, kk, sigma=sigma, scalars=scalars, active=active)
    pk, ck, vk, ik = fused_alloc_select(
        w, g, kk, sigma=sigma, scalars=scalars, active=active, tile=1024, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


@pytest.mark.parametrize("kind,S,late_fb,with_active", TAIL_CASES,
                         ids=[f"{c[0]}-S{c[1]}{'-fb' if c[2] else ''}{'-act' if c[3] else ''}"
                              for c in TAIL_CASES])
def test_round_tail_kernel_multi_tile_matches_ref(kind, S, late_fb, with_active):
    # three 8192-client tiles, the last one ragged: whole-byte-tile decode
    # across tile boundaries and the per-tile max blocks
    sync = kind in ("bits", "x")
    args, kw = _tail_inputs(n=2 * 8192 + RAGGED_K, kind=kind, S=0 if sync else S,
                            with_active=with_active, late_fb=late_fb)
    want = ref.round_tail_ref(*args, **kw)
    got = fused_round_tail(*args, **kw, tile=8192, interpret=True)
    for key in want:
        np.testing.assert_array_equal(
            np.asarray(got[key]), np.asarray(want[key]), err_msg=f"tail product {key!r}"
        )


def test_round_tail_tile_invariant():
    args, kw = _tail_inputs(kind="crumbs", S=2, late_fb=True)
    a = fused_round_tail(*args, **kw, tile=64, interpret=True)
    b = fused_round_tail(*args, **kw, tile=8192, interpret=True)
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]), err_msg=key)


class TestFusedConfigValidation:
    def test_rejects_non_e3cs_scheme(self):
        fl = FLConfig(K=32, k=4, rounds=5, scheme="random")
        vol = make_volatility("bernoulli", paper_success_rates(32))
        with pytest.raises(ValueError, match="fused"):
            RoundProgram(fl=fl, vol=vol, rho=None, fused=True)

    def test_rejects_non_gumbel_sampler(self):
        fl = FLConfig(K=32, k=4, rounds=5, scheme="e3cs", sampler="systematic")
        vol = make_volatility("bernoulli", paper_success_rates(32))
        with pytest.raises(ValueError, match="plackett_luce"):
            RoundProgram(fl=fl, vol=vol, rho=None, fused=True)

    def test_from_config_threads_fused(self):
        pm = RoundProgram.from_config(FLConfig(K=32, k=4, rounds=5, scheme="e3cs"), fused=True)
        assert pm.fused
