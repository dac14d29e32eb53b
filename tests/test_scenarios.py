"""Scenario subsystem tests: bit-packed replay == dense replay (bit-exact),
stateful scenario models inside the scan == the legacy per-round loop,
structured-trace statistics (diurnal marginals, regional correlation, flash
crowd windows, Markov stationarity), volatility dispatch satellites, and the
registry/harness surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sim import selection_sim, selection_sim_loop
from repro.core.volatility import MarkovVolatility, make_volatility, paper_success_rates
from repro.engine.scan_sim import scan_selection_sim
from repro.kernels.unpack_bits import unpack_bits_kernel_call, unpack_bits_ref
from repro.scenarios import (
    DiurnalVolatility,
    FlashCrowdVolatility,
    RegionalOutageVolatility,
    ReplayVolatility,
    evaluate_cell,
    get_scenario,
    list_scenarios,
    make_scenario,
    pack_trace,
    record_trace,
    run_grid_multi_job,
    unpack_trace,
)
from repro.scenarios.replay import pack_bits_jnp, packed_nbytes, packed_width


def roll(vol, T, seed=0):
    """Sample a volatility model T rounds via a compiled scan -> (T, K)."""

    def step(carry, _):
        key, vs = carry
        key, k2 = jax.random.split(key)
        x, vs = vol.sample(k2, vs)
        return (key, vs), x

    _, xs = jax.lax.scan(step, (jax.random.PRNGKey(seed), vol.init_state()), None, length=T)
    return np.asarray(xs)


class TestPackedTraces:
    @pytest.mark.parametrize("K", [5, 8, 17, 100, 1000])
    def test_pack_unpack_roundtrip(self, K):
        rng = np.random.default_rng(K)
        xs = rng.binomial(1, 0.5, (13, K)).astype(np.float32)
        packed = pack_trace(xs)
        assert packed.shape == (13, packed_width(K)) and packed.dtype == np.uint8
        np.testing.assert_array_equal(unpack_trace(packed, K), xs)

    def test_pack_bits_jnp_matches_numpy(self):
        rng = np.random.default_rng(0)
        xs = rng.binomial(1, 0.3, (7, 61)).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(pack_bits_jnp(jnp.asarray(xs))), pack_trace(xs))

    @pytest.mark.parametrize("K,tile_b", [(7, 1024), (64, 4), (1000, 16), (8192, 1024), (2 * 8192 + 13, 1024)])
    def test_unpack_kernel_interpret_matches_ref(self, K, tile_b):
        rng = np.random.default_rng(K)
        packed = jnp.asarray(rng.integers(0, 256, packed_width(K)), jnp.uint8)
        out = unpack_bits_kernel_call(packed, K, tile_b=tile_b, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(unpack_bits_ref(packed, K)))

    def test_packed_nbytes(self):
        assert packed_nbytes(2500, 1_000_000) == 2500 * 125_000  # ~312 MB


class TestPackedReplayThroughScan:
    def test_bit_identical_to_dense_override(self):
        # the tentpole acceptance criterion
        rng = np.random.default_rng(0)
        xs = rng.binomial(1, 0.5, (120, 100)).astype(np.float32)
        packed = pack_trace(xs)
        a = scan_selection_sim("e3cs", K=100, k=20, T=120, frac=0.25, xs_override=xs)
        b = scan_selection_sim("e3cs", K=100, k=20, T=120, frac=0.25, packed_override=packed)
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])
        np.testing.assert_allclose(a["ps"], b["ps"], atol=1e-6)

    def test_replay_volatility_model_matches_override(self):
        # the (init_state, sample) replay object carries the round index in
        # vol_state and must reproduce the override path bit-for-bit
        rng = np.random.default_rng(1)
        xs = rng.binomial(1, 0.6, (80, 64)).astype(np.float32)
        packed = pack_trace(xs)
        vol = ReplayVolatility(packed=jnp.asarray(packed), K=64)
        a = scan_selection_sim("e3cs", K=64, k=12, T=80, frac=0.5, vol=vol)
        b = scan_selection_sim("e3cs", K=64, k=12, T=80, frac=0.5, rho=np.asarray(vol.rho), xs_override=xs)
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])

    def test_lean_outputs_match_full(self):
        # lean mode changes what the scan EMITS, never the state math: counts
        # bit-identical, per-round successes == row-sums of the full outputs
        from repro.configs.base import FLConfig
        from repro.engine.scan_sim import build_scan_runner

        K, k, T = 64, 12, 50
        rho = paper_success_rates(K)
        packed = pack_trace(np.random.default_rng(2).binomial(1, 0.6, (T, K)).astype(np.float32))
        fl = FLConfig(K=K, k=k, rounds=T, scheme="e3cs", quota="const", quota_frac=0.5)
        vol = make_volatility("bernoulli", rho)
        key = jax.random.PRNGKey(0)
        xs_in = jnp.asarray(packed)
        run_f, s0f = build_scan_runner(fl, vol, rho, override="packed")
        run_l, s0l = build_scan_runner(fl, vol, rho, override="packed", outputs="lean")
        st_f, masks, xs, ps, _ = run_f(s0f, key, xs_in)
        st_l, succ, _ = run_l(s0l, key, xs_in)
        np.testing.assert_array_equal(np.asarray(st_f.sel_counts), np.asarray(st_l.sel_counts))
        np.testing.assert_array_equal(np.asarray(succ), (np.asarray(masks) * np.asarray(xs)).sum(1))

    def test_run_replay_shares_one_trace_across_selectors(self):
        from repro.scenarios import run_replay

        rows, packed = run_replay(("e3cs", "random"), "paper_iid", K=40, k=8, T=30)
        assert [r["selector"] for r in rows] == ["e3cs", "random"]
        assert packed.shape == (30, 5)

    def test_record_trace_chunked_equals_one_shot(self):
        vol, _ = make_scenario("markov", 40, 60, seed=3)
        np.testing.assert_array_equal(record_trace(vol, 60, seed=7, chunk=16), record_trace(vol, 60, seed=7, chunk=60))

    def test_both_overrides_rejected(self):
        xs = np.zeros((4, 8), np.float32)
        with pytest.raises(ValueError):
            scan_selection_sim("e3cs", K=8, k=2, T=4, xs_override=xs, packed_override=pack_trace(xs))


class TestStatefulVolInScan:
    """Scenario models carried inside the lax.scan match the legacy
    per-round loop bit-for-bit (same PRNG discipline, pytree states)."""

    def _vols(self, K, T):
        rho = jnp.asarray(paper_success_rates(K))
        rng = np.random.default_rng(0)
        return {
            "markov": MarkovVolatility(rho, 0.9),
            "diurnal": DiurnalVolatility(rho=rho, phase=jnp.asarray(rng.random(K, np.float32)), period=16),
            "regional": RegionalOutageVolatility(rho=rho, region=jnp.asarray(np.arange(K) % 4, jnp.int32), n_regions=4),
            "flash_crowd": FlashCrowdVolatility(  # tuple vol_state
                rho=rho, crowd=jnp.asarray((np.arange(K) < K // 2).astype(np.float32)), t_start=10, t_end=40
            ),
        }

    @pytest.mark.parametrize("name", ["markov", "diurnal", "regional", "flash_crowd"])
    def test_scan_matches_loop(self, name):
        K, k, T = 48, 10, 60
        vol = self._vols(K, T)[name]
        a = selection_sim("e3cs", K=K, k=k, T=T, frac=0.5, vol=vol, backend="scan")
        b = selection_sim_loop("e3cs", K=K, k=k, T=T, frac=0.5, vol=vol)
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])
        np.testing.assert_allclose(a["ps"], b["ps"], atol=1e-6)

    def test_string_markov_equals_object_markov(self):
        rho = jnp.asarray(paper_success_rates(32))
        a = selection_sim("e3cs", K=32, k=8, T=40, volatility="markov", stickiness=0.8, backend="scan")
        b = selection_sim("e3cs", K=32, k=8, T=40, vol=MarkovVolatility(rho, 0.8), backend="scan")
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])


class TestTraceStatistics:
    def test_markov_stationarity_across_stickiness(self):
        # satellite: the invariant MarkovVolatility claims in its docstring —
        # the stationary marginal stays rho for any stickiness
        K, T = 64, 6000
        rho = paper_success_rates(K)
        for s in (0.0, 0.5, 0.9):
            xs = roll(MarkovVolatility(jnp.asarray(rho), s), T, seed=int(s * 10))
            per_class = xs.mean(0).reshape(4, -1).mean(1)
            np.testing.assert_allclose(per_class, [0.1, 0.3, 0.6, 0.9], atol=0.03, err_msg=f"stickiness={s}")

    def test_diurnal_marginal_and_cycle(self):
        K, period = 32, 16
        rho = jnp.full((K,), 0.5)
        phase = jnp.asarray(np.random.default_rng(0).random(K, np.float32))
        vol = DiurnalVolatility(rho=rho, phase=phase, amplitude=0.3, period=period)
        xs = roll(vol, 200 * period)
        # marginal over whole periods ~ rho (no clipping at these rates)
        np.testing.assert_allclose(xs.mean(0), 0.5, atol=0.06)
        # but within a day the rate genuinely swings: peak-vs-trough spread
        by_tod = xs.reshape(-1, period, K).mean(0)  # (period, K) empirical rate
        assert float((by_tod.max(0) - by_tod.min(0)).mean()) > 0.4

    def test_regional_outage_correlation_structure(self):
        K = 16
        vol = RegionalOutageVolatility(
            rho=jnp.full((K,), 0.8),
            region=jnp.asarray(np.arange(K) // 8, jnp.int32),
            n_regions=2,
            p_fail=0.1,
            p_recover=0.3,
            severity=0.9,
        )
        xs = roll(vol, 4000)
        c = np.corrcoef(xs.T)
        within = np.mean([c[i, j] for i in range(8) for j in range(8) if i != j])
        cross = np.mean([c[i, j] for i in range(8) for j in range(8, 16)])
        assert within > 0.2, within  # shared regional factor binds the block
        assert abs(cross) < 0.1, cross  # regions fail independently
        # marginal matches the closed form the rho-hint uses
        np.testing.assert_allclose(xs.mean(), float(vol.marginal_rate().mean()), atol=0.03)

    def test_flash_crowd_window(self):
        K = 60
        crowd = jnp.asarray((np.arange(K) < 30).astype(np.float32))
        vol = FlashCrowdVolatility(
            rho=jnp.full((K,), 0.5), crowd=crowd, t_start=20, t_end=60, churn=0.05, base_avail=0.1, peak=0.95
        )
        xs = roll(vol, 100)
        crowd_rate_pre = xs[:20, :30].mean()
        crowd_rate_early = xs[20:30, :30].mean()
        crowd_rate_post = xs[60:, :30].mean()
        assert crowd_rate_pre < 0.2  # dormant before the event
        assert crowd_rate_early > 0.6  # surge at window start
        assert crowd_rate_post < 0.2  # churned away after
        np.testing.assert_allclose(xs[:, 30:].mean(), 0.5, atol=0.05)  # non-crowd unaffected


class TestVolatilityDispatch:
    def test_deadline_routes_and_matches_across_backends(self):
        # satellite: "deadline" used to silently fall back to Bernoulli
        a = selection_sim("e3cs", K=40, k=8, T=50, volatility="deadline", backend="scan")
        b = selection_sim("e3cs", K=40, k=8, T=50, volatility="deadline", backend="loop")
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["xs"], b["xs"])
        # deadline marginals differ from Bernoulli draws with the same key
        c = selection_sim("e3cs", K=40, k=8, T=50, volatility="bernoulli", backend="scan")
        assert not np.array_equal(a["xs"], c["xs"])

    @pytest.mark.parametrize("backend", ["scan", "loop"])
    def test_unknown_volatility_raises(self, backend):
        with pytest.raises(ValueError, match="unknown volatility"):
            selection_sim("e3cs", K=8, k=2, T=4, volatility="bogus", backend=backend)

    def test_make_volatility_unknown_raises(self):
        with pytest.raises(ValueError, match="unknown volatility"):
            make_volatility("bogus", jnp.ones(4) * 0.5)


class TestPaperSuccessRatesRemainder:
    def test_divisible_unchanged(self):
        out = paper_success_rates(100)
        assert out.shape == (100,)
        np.testing.assert_array_equal(np.unique(out, return_counts=True)[1], [25, 25, 25, 25])

    def test_stable_policy_is_legacy_behaviour(self):
        # satellite: remainder lands in the most stable class (documented skew)
        out = paper_success_rates(10)
        np.testing.assert_array_equal(out, np.float32([0.1, 0.1, 0.3, 0.3, 0.6, 0.6, 0.9, 0.9, 0.9, 0.9]))
        assert out.mean() == pytest.approx(0.56, abs=1e-6)  # optimistic vs ideal 0.475

    def test_spread_policy_bounds_class_imbalance(self):
        out = paper_success_rates(10, remainder="spread")
        _, counts = np.unique(out, return_counts=True)
        np.testing.assert_array_equal(counts, [3, 3, 2, 2])
        assert out.mean() == pytest.approx(0.42, abs=1e-6)  # pessimistic, not optimistic
        assert abs(out.mean() - 0.475) < abs(paper_success_rates(10).mean() - 0.475) + 0.03

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="remainder"):
            paper_success_rates(10, remainder="bogus")


class TestRegistryAndHarness:
    def test_all_scenarios_run_through_scan(self):
        for name in list_scenarios():
            vol, rho = make_scenario(name, 32, 30, seed=0)
            assert np.asarray(rho).shape == (32,)
            assert np.all((np.asarray(rho) >= 0) & (np.asarray(rho) <= 1))
            out = scan_selection_sim("e3cs", K=32, k=8, T=30, frac=0.5, vol=vol, rho=rho)
            np.testing.assert_array_equal(out["masks"].sum(1), np.full(30, 8.0))

    def test_unknown_scenario_raises(self):
        with pytest.raises(KeyError, match="unknown scenario"):
            get_scenario("bogus")

    def test_evaluate_cell_metrics(self):
        row = evaluate_cell("random", "paper_iid", K=40, k=8, T=50)
        assert row["cep"] > 0
        assert 0.0 < row["eff_participation"] <= 1.0
        assert 0.0 < row["jain"] <= 1.0
        assert 0.0 < row["entropy"] <= 1.0

    def test_select_serve_scenario_feedback(self):
        from repro.launch.select_serve import run_service

        report = run_service(J=2, K_max=64, rounds=5, seed=0, scenario="diurnal")
        assert report["scenario"] == "diurnal"
        assert report["ticks"] == 10

    def test_multi_job_grid_learns_per_scenario(self):
        rows = run_grid_multi_job(["paper_iid", "markov_sticky"], K=40, k=8, T=120, seed=0)
        assert len(rows) == 2
        for r in rows:
            assert r["cep"] > 0.3 * 120 * 8  # well above the 0.45-ish floor times slack
            assert 0.0 < r["jain"] <= 1.0


class TestLagTraces:
    """2-bit packed completion-lag traces: 4 clients/byte, codes {0,1,2,dead}
    (satellite of the K-sharding PR; ROADMAP "packed lag traces" follow-on)."""

    def test_pack_roundtrip_np_and_jnp(self):
        from repro.core.volatility import DEAD_LAG
        from repro.scenarios import lag_packed_width, pack_lags, unpack_lags
        from repro.scenarios.replay import pack_lags_jnp

        rng = np.random.default_rng(0)
        lags = rng.choice([0, 1, 2, DEAD_LAG], size=(9, 101)).astype(np.int32)
        packed = pack_lags(lags)
        assert packed.shape == (9, lag_packed_width(101)) and packed.dtype == np.uint8
        assert np.array_equal(unpack_lags(packed, 101), lags)
        assert np.array_equal(np.asarray(pack_lags_jnp(jnp.asarray(lags))), packed)

    def test_out_of_range_lag_rejected(self):
        from repro.scenarios import pack_lags

        with pytest.raises(ValueError, match="2-bit"):
            pack_lags(np.asarray([[0, 3, 1, 2]], np.int32))

    def test_unpack_crumbs_kernel_matches_ref(self):
        from repro.kernels.unpack_bits import unpack_crumbs_kernel_call, unpack_crumbs_ref

        rng = np.random.default_rng(1)
        for K in (4, 37, 4096, 2 * 4096 + 37):  # the last spans three ragged byte tiles
            packed = jnp.asarray(rng.integers(0, 256, (K + 3) // 4, dtype=np.uint8))
            a = unpack_crumbs_ref(packed, K)
            b = unpack_crumbs_kernel_call(packed, K, tile_b=16, interpret=True)
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_recorded_lag_replay_bit_identical_to_dense(self):
        # a frozen async scenario replays through the scan exactly like a
        # dense lag trace would — same masks, lags and staleness-aware CEP
        from repro.core.volatility import BernoulliVolatility, CompletionLag
        from repro.engine.scan_sim import async_selection_sim
        from repro.scenarios import ReplayLag, record_lag_trace, unpack_lags

        K, T = 64, 50
        base = BernoulliVolatility(jnp.asarray(paper_success_rates(K)))
        lm = CompletionLag(base, p_late=0.6, lag_decay=0.5, max_lag=2)
        trace = record_lag_trace(lm, T, seed=3, chunk=16)
        assert trace.shape == (T, (K + 3) // 4)
        replay = ReplayLag(packed=jnp.asarray(trace), K=K)

        class DenseLagReplay:
            def __init__(self, lags):
                self.lags = jnp.asarray(lags)

            def init_state(self):
                return jnp.zeros((), jnp.int32)

            def sample(self, rng, state):
                return jax.lax.dynamic_index_in_dim(self.lags, state, keepdims=False), state + 1

        dense = DenseLagReplay(unpack_lags(trace, K))
        rho = np.asarray(replay.rho)
        a = async_selection_sim("e3cs", K=K, k=8, T=T, staleness=2, lag_model=replay, rho=rho)
        b = async_selection_sim("e3cs", K=K, k=8, T=T, staleness=2, lag_model=dense, rho=rho)
        assert np.array_equal(a["masks"], b["masks"])
        assert np.array_equal(a["lags"], b["lags"])
        assert a["cep"] == b["cep"]
        # the trace really contains late completions, not just binary bits
        assert ((unpack_lags(trace, K) > 0).sum()) > 0

    def test_record_rejects_wide_lag_models(self):
        from repro.core.volatility import BernoulliVolatility, CompletionLag
        from repro.scenarios import record_lag_trace

        base = BernoulliVolatility(jnp.asarray(paper_success_rates(16)))
        with pytest.raises(ValueError, match="max_lag"):
            record_lag_trace(CompletionLag(base, max_lag=4), 4)


class TestDiskTraces:
    """mmap-backed packed traces: disk-bounded replay horizons (satellite;
    ROADMAP "trace IO" follow-on)."""

    def test_save_load_roundtrip_is_memmap(self, tmp_path):
        vol = make_volatility("bernoulli", jnp.asarray(paper_success_rates(96)))
        packed = record_trace(vol, 30, seed=1, chunk=16)
        from repro.scenarios import load_packed_trace, save_packed_trace

        path = save_packed_trace(str(tmp_path / "trace"), packed, 96, kind="bits")
        arr, meta = load_packed_trace(path)
        assert isinstance(arr, np.memmap)
        assert meta == {"kind": "bits", "K": 96, "T": 30, "clients_per_byte": 8}
        assert np.array_equal(np.asarray(arr), packed)

    def test_lag_kind_roundtrip(self, tmp_path):
        from repro.core.volatility import DEAD_LAG
        from repro.scenarios import load_packed_trace, pack_lags, save_packed_trace, unpack_lags

        lags = np.random.default_rng(0).choice([0, 1, 2, DEAD_LAG], size=(12, 50)).astype(np.int32)
        path = save_packed_trace(str(tmp_path / "lags"), pack_lags(lags), 50, kind="lags")
        arr, meta = load_packed_trace(path)
        assert meta["clients_per_byte"] == 4
        assert np.array_equal(unpack_lags(np.asarray(arr), 50), lags)

    def test_shape_validation(self, tmp_path):
        from repro.scenarios import save_packed_trace

        with pytest.raises(ValueError, match="must be"):
            save_packed_trace(str(tmp_path / "bad"), np.zeros((5, 3), np.uint8), 96, kind="bits")

    def test_streamed_replay_bit_identical_to_in_memory(self, tmp_path):
        # chunked memmap feed (incl. a ragged tail chunk) == one-shot packed
        # replay: same counts, same per-round successes, same quota schedule
        from repro.scenarios import replay_packed_stream, save_packed_trace

        K, T = 96, 70
        vol = make_volatility("bernoulli", jnp.asarray(paper_success_rates(K)))
        packed = record_trace(vol, T, seed=1, chunk=32)
        path = save_packed_trace(str(tmp_path / "tr"), packed, K, kind="bits")
        stream = replay_packed_stream("e3cs", path, k=12, chunk=16, frac=0.5)
        assert "rho" not in stream  # marginal pass skipped: only fedcs consumes it
        mem = scan_selection_sim("e3cs", K=K, k=12, T=T, frac=0.5, packed_override=packed, seed=0)
        assert np.array_equal(stream["counts"], mem["counts"])
        np.testing.assert_allclose(stream["successes"], (mem["masks"] * mem["xs"]).sum(1), atol=0)
        np.testing.assert_allclose(stream["sigmas"], mem["sigmas"], atol=0)

    def test_truncated_horizon_rho_stays_a_probability(self, tmp_path):
        # regression: the streamed marginal must not read rows past T — with
        # T < trace length the old slice summed the whole trace but divided
        # by T, pushing rho past 1
        from repro.scenarios import replay_packed_stream, save_packed_trace

        K = 64
        vol = make_volatility("bernoulli", jnp.asarray(paper_success_rates(K)))
        packed = record_trace(vol, 2000, seed=2, chunk=128)
        path = save_packed_trace(str(tmp_path / "tr"), packed, K, kind="bits")
        out = replay_packed_stream("fedcs", path, k=8, T=1500, chunk=512)
        assert out["rho"].max() <= 1.0
        true_marginal = unpack_trace(packed[:1500], K).mean(0)
        np.testing.assert_allclose(out["rho"], true_marginal, atol=1e-6)
        assert out["successes"].shape == (1500,)
