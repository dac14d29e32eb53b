"""Metrics-spine tests: in-scan taps bit-identity against the committed
goldens, the client-axis sketch layer (dense-recompute oracle, psum-merge
property, placement invariance, fairness series), chunked carry_key+taps
streams, windowed aggregates hand-checked, JSONL run-log round-trip (schema
v2: timestamps, alerts, NaN sanitation, overwrite protection), the alert
detector, the run-log explorer CLI, the latency histogram, the results
layout, and the check_bench gate edges.

The taps contract under test: ``taps=True`` adds one trailing
``{"series", "counters"}`` payload to every runner's outputs and changes
NOTHING else — the masks/lags/state streams must still equal
``tests/golden/round_program_goldens.npz`` bit-for-bit, in every placement.
``sketch=<SketchSpec>`` extends that contract: the payload gains a
``"sketches"`` stream and every other output still matches the goldens.
"""
import importlib.util
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import FLConfig
from repro.core.fairness import gini as gini_exact
from repro.core.fairness import jain_index
from repro.core.fairness import top_share as top_share_exact
from repro.core.volatility import CompletionLag, make_volatility, paper_success_rates
from repro.engine.round_program import RoundProgram
from repro.engine.scan_sim import async_selection_sim, scan_selection_sim
from repro.engine.sharded import sharded_selection_sim
from repro.obs import (
    ROUND_TAPS,
    SKETCH_FIELDS,
    AlertRules,
    LatencyHistogram,
    Reporter,
    RunLog,
    SketchSpec,
    SpanTimer,
    TapRegistry,
    TapSpec,
    detect_alerts,
    fairness_series,
    iter_alerts,
    merge_sketches,
    read_runlog,
    sketch_from_dense,
    stage,
    validate_records,
    window_reduce,
)
from repro.obs import paths as obs_paths
from repro.obs.alerts import Alert
from repro.obs.runlog import SCHEMA_VERSION, iter_metrics
from repro.obs.sketches import FAIRNESS_SERIES, lag_bins, region_ids
from repro.scenarios.replay import pack_trace

K, k, T, SEED, FRAC = 128, 16, 50, 3, 0.5
GOLD = np.load(os.path.join(os.path.dirname(__file__), "golden", "round_program_goldens.npz"))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_module(relpath, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def check_bench():
    return _load_module("scripts/check_bench.py", "check_bench")


@pytest.fixture(scope="module")
def mesh8():
    from repro.launch.mesh import make_host_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8 (set in conftest)")
    return make_host_mesh(8)


def _rho():
    return paper_success_rates(K)


def _lag_model():
    return CompletionLag(make_volatility("bernoulli", _rho()), p_late=0.7, lag_decay=0.5, max_lag=2)


class TestTapsBitIdentity:
    """taps=True reproduces the pre-taps goldens bit-for-bit — the telemetry
    stage must not touch the PRNG stream or the round math."""

    def test_sync_d1_golden(self):
        out = scan_selection_sim("e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, taps=True)
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d1_e3cs_masks"])
        assert np.array_equal(out["counts"], GOLD["sync_d1_e3cs_counts"])
        taps = out["taps"]
        assert set(taps["series"]) == set(ROUND_TAPS.gauge_names())
        assert all(v.shape == (T,) for v in taps["series"].values())
        np.testing.assert_array_equal(taps["series"]["selected"], out["masks"].sum(1))
        assert taps["counters"]["rounds"] == float(T)
        assert taps["counters"]["cum_selected"] == float(out["masks"].sum())
        # sync rounds have no staleness buffer: the stale gauge is flat zero
        np.testing.assert_array_equal(taps["series"]["stale"], np.zeros(T))

    def test_sync_d8_golden(self, mesh8):
        out = sharded_selection_sim("e3cs", mesh8, K=K, k=k, T=T, frac=FRAC, seed=SEED, taps=True)
        assert np.array_equal(pack_trace(out["masks"]), GOLD["sync_d8_e3cs_masks"])
        assert np.array_equal(out["counts"], GOLD["sync_d8_e3cs_counts"])
        np.testing.assert_array_equal(out["taps"]["series"]["selected"], np.full(T, float(k)))

    def test_async_d1_golden(self):
        out = async_selection_sim(
            "e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, staleness=2, alpha=0.5,
            lag_model=_lag_model(), rho=_rho(), taps=True,
        )
        assert np.array_equal(pack_trace(out["masks"]), GOLD["async_d1_e3cs_masks"])
        assert np.array_equal(out["lags"].astype(np.int8), GOLD["async_d1_e3cs_lags"])
        assert np.float32(out["cep"]) == GOLD["async_d1_e3cs_cep"]
        taps = out["taps"]
        np.testing.assert_allclose(taps["series"]["on_time"], out["on_time"], atol=1e-4)
        np.testing.assert_allclose(taps["series"]["stale"], out["stale"], atol=1e-4)
        assert taps["counters"]["cum_credit"] == pytest.approx(float(out["cep"]), rel=1e-5)

    def test_async_same_stream_every_placement(self, mesh8):
        """The schema contract: the D=8 sharded-async tap stream equals the
        D=1 stream (psum-reduced gauges are placement-invariant).  Uses the
        packed-lag replay + `random` selector composition, where D=8 is
        bit-identical to D=1 (generated e3cs runs draw shard-local
        randomness, so only mesh=1 matches those — covered below)."""
        lp = GOLD["lag_trace_packed"]
        fl = FLConfig(K=K, k=k, rounds=T, scheme="random", quota_frac=FRAC)

        def go(mesh):
            pm = RoundProgram(fl=fl, vol=_lag_model(), rho=_rho(), override="packed_lags",
                              staleness=2, alpha=0.5, mesh=mesh)
            run, s0 = pm.build_runner(outputs="lean", taps=True)
            st, on_time, stale, _, payload = run(s0, jax.random.PRNGKey(SEED), jnp.asarray(lp))
            return st, np.asarray(on_time), np.asarray(stale), payload

        st1, on1, stale1, tap1 = go(None)
        st8, on8, stale8, tap8 = go(mesh8)
        np.testing.assert_array_equal(on1, on8)
        np.testing.assert_array_equal(stale1, stale8)
        assert float(st1.cep) == float(st8.cep)
        for name in ROUND_TAPS.gauge_names():
            np.testing.assert_allclose(
                np.asarray(tap1["series"][name]), np.asarray(tap8["series"][name]), atol=1e-4, err_msg=name
            )
        for name, v in tap1["counters"].items():
            assert float(v) == pytest.approx(float(tap8["counters"][name]), rel=1e-5), name

    def test_async_mesh1_stream_matches_dense_e3cs(self):
        """Generated e3cs async: a 1-device mesh is bit-identical to the
        dense engine — taps included."""
        from repro.launch.mesh import make_host_mesh

        fl = FLConfig(K=K, k=k, rounds=T, scheme="e3cs", quota_frac=FRAC, allocator="bisect")

        def go(mesh):
            pm = RoundProgram(fl=fl, vol=_lag_model(), rho=_rho(), staleness=2, alpha=0.5, mesh=mesh)
            run, s0 = pm.build_runner(outputs="lean", taps=True)
            st, on_time, stale, _, payload = run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))
            return st, np.asarray(on_time), np.asarray(stale), payload

        st1, on1, stale1, tap1 = go(None)
        stm, onm, stalem, tapm = go(make_host_mesh(1))
        np.testing.assert_array_equal(on1, onm)
        np.testing.assert_array_equal(stale1, stalem)
        for name in ROUND_TAPS.gauge_names():
            np.testing.assert_array_equal(
                np.asarray(tap1["series"][name]), np.asarray(tapm["series"][name]), err_msg=name
            )

    def test_async_d8_taps_off_unchanged(self, mesh8):
        fl = FLConfig(K=K, k=k, rounds=T, scheme="e3cs", quota_frac=FRAC, allocator="bisect")

        def go(taps):
            pm = RoundProgram(fl=fl, vol=_lag_model(), rho=_rho(), staleness=2, alpha=0.5, mesh=mesh8)
            run, s0 = pm.build_runner(outputs="lean", taps=taps)
            return run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))

        st_off, on_off, stale_off, _ = go(False)
        st_on, on_on, stale_on, _, _ = go(True)
        np.testing.assert_array_equal(np.asarray(on_off), np.asarray(on_on))
        np.testing.assert_array_equal(np.asarray(stale_off), np.asarray(stale_on))
        np.testing.assert_array_equal(np.asarray(st_off.sel_counts), np.asarray(st_on.sel_counts))

    def test_sketch_validation(self):
        fl = FLConfig(K=32, k=4, rounds=8, scheme="e3cs", quota_frac=FRAC)
        pm = RoundProgram(fl=fl, vol=make_volatility("bernoulli", paper_success_rates(32)),
                          rho=paper_success_rates(32))
        with pytest.raises(ValueError, match="taps"):
            pm.build_runner(sketch=SketchSpec(window=4))
        with pytest.raises(ValueError, match="one-shot"):
            pm.build_runner(taps=True, carry_key=True, sketch=SketchSpec(window=4))


def _sync_program(mesh=None, allocator="sort"):
    """The exact composition behind the sync goldens (``scan_selection_sim``
    / ``sharded_selection_sim`` defaults at K,k,T,SEED,FRAC)."""
    fl = FLConfig(K=K, k=k, rounds=T, scheme="e3cs", quota="const", quota_frac=FRAC,
                  eta=0.5, sampler="plackett_luce", allocator=allocator)
    rho = jnp.asarray(paper_success_rates(K))
    vol = make_volatility("bernoulli", rho, stickiness=0.8, seed=SEED)
    return RoundProgram(fl=fl, vol=vol, rho=rho, mesh=mesh)


def _async_program(mesh=None, K_=K, k_=k):
    fl = FLConfig(K=K_, k=k_, rounds=T, scheme="e3cs", quota="const", quota_frac=FRAC,
                  eta=0.5, sampler="plackett_luce",
                  allocator="bisect" if mesh is not None else "sort")
    rho = paper_success_rates(K_)
    lag = CompletionLag(make_volatility("bernoulli", rho), p_late=0.7, lag_decay=0.5, max_lag=2)
    return RoundProgram(fl=fl, vol=lag, rho=rho, staleness=2, alpha=0.5, mesh=mesh)


class TestSketches:
    """The client-axis sketch layer: golden bit-identity, the dense-state
    oracle, psum-merge placement properties, and the fairness series."""

    W = 10
    SPEC = SketchSpec(window=W, count_bins=8, prob_bins=10, n_regions=4)

    # -- golden bit-identity ------------------------------------------------

    def test_sync_d1_sketch_on_matches_golden(self):
        run, s0 = _sync_program().build_runner(outputs="full", taps=True, sketch=self.SPEC)
        _, masks, xs, ps, _, payload = run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))
        assert np.array_equal(pack_trace(np.asarray(masks)), GOLD["sync_d1_e3cs_masks"])
        assert set(payload["sketches"]) == set(SKETCH_FIELDS)
        assert all(np.asarray(v).shape[0] == T // self.W for v in payload["sketches"].values())
        # oracle: every emission row equals the dense recompute at that round
        self._check_emissions(payload["sketches"], np.asarray(masks), np.asarray(xs),
                              np.asarray(ps), None, K)

    def test_sync_d8_sketch_on_matches_golden(self, mesh8):
        run, s0 = _sync_program(mesh8, allocator="bisect").build_runner(
            outputs="full", taps=True, sketch=self.SPEC
        )
        _, masks, xs, ps, _, payload = run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))
        masks = np.asarray(masks)[:, :K]
        assert np.array_equal(pack_trace(masks), GOLD["sync_d8_e3cs_masks"])
        self._check_emissions(payload["sketches"], np.asarray(masks), np.asarray(xs)[:, :K],
                              np.asarray(ps)[:, :K], None, K)

    def test_async_d1_sketch_on_matches_golden(self):
        run, s0 = _async_program().build_runner(outputs="full", taps=True, sketch=self.SPEC)
        _, masks, lags, ps, _, _, payload = run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))
        assert np.array_equal(pack_trace(np.asarray(masks)), GOLD["async_d1_e3cs_masks"])
        assert np.array_equal(np.asarray(lags).astype(np.int8), GOLD["async_d1_e3cs_lags"])
        self._check_emissions(payload["sketches"], np.asarray(masks), None,
                              np.asarray(ps), np.asarray(lags), K)

    def _check_emissions(self, sketches, masks, xs, ps, lags, K_true, active=None):
        """Every emitted sketch row equals ``sketch_from_dense`` of the run's
        own dense per-client state at that emission round."""
        spec, W = self.SPEC, self.W
        Kp = masks.shape[1]
        region = region_ids(spec, K_true)
        if Kp != K_true:  # shard padding: ids pad with 0, active mask excludes
            region = np.pad(region, (0, Kp - K_true))
        act = np.asarray(active, np.float64) if active is not None else (
            (np.arange(Kp) < K_true).astype(np.float64)
        )
        L = lag_bins(None if lags is None else 2)
        x_ontime = xs if lags is None else (lags == 0).astype(np.float64)
        code = (1 - x_ontime).astype(np.int64) if lags is None else np.where(
            lags < 0, L - 1, np.clip(lags, 0, L - 2)
        ).astype(np.int64)
        n_emits = T // W
        for i in range(n_emits):
            t = (i + 1) * W  # emission fires on the post-increment round counter
            counts = masks[:t].sum(0)
            cum = (masks[:t] * x_ontime[:t]).sum(0)
            lag_hist = np.zeros(L)
            np.add.at(lag_hist, code[:t].reshape(-1), (masks[:t]).reshape(-1))
            want = sketch_from_dense(spec, counts, ps[t - 1], cum, lag_hist, region, act)
            for n in SKETCH_FIELDS:
                np.testing.assert_allclose(
                    np.asarray(sketches[n][i], np.float64), want[n], rtol=1e-6,
                    err_msg=f"{n} @ emission {i}",
                )

    # -- placement invariance ----------------------------------------------

    def test_sync_mesh1_sketch_matches_dense_golden(self):
        """mesh=1 completes the sync golden matrix: bit-identical to the
        dense bisect engine (``sync_d1_e3cs_bisect_masks``), sketch stream
        byte-for-byte included."""
        from repro.launch.mesh import make_host_mesh

        def go(mesh):
            run, s0 = _sync_program(mesh, allocator="bisect").build_runner(
                outputs="full", taps=True, sketch=self.SPEC
            )
            _, masks, *_, payload = run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))
            return np.asarray(masks), payload

        m1, p1 = go(None)
        mm, pm_ = go(make_host_mesh(1))
        assert np.array_equal(pack_trace(m1), GOLD["sync_d1_e3cs_bisect_masks"])
        np.testing.assert_array_equal(m1, mm)
        for n in SKETCH_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(p1["sketches"][n]), np.asarray(pm_["sketches"][n]), err_msg=n
            )

    def test_async_mesh1_sketch_matches_dense(self):
        """Generated e3cs async: mesh=1 emits the byte-identical sketch
        stream to the dense engine (the async mesh=1 cell of the matrix)."""
        from repro.launch.mesh import make_host_mesh

        fl = FLConfig(K=K, k=k, rounds=T, scheme="e3cs", quota_frac=FRAC, allocator="bisect")

        def go(mesh):
            pm = RoundProgram(fl=fl, vol=_lag_model(), rho=_rho(), staleness=2, alpha=0.5, mesh=mesh)
            run, s0 = pm.build_runner(outputs="lean", taps=True, sketch=self.SPEC)
            *_, payload = run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))
            return payload

        p1, pm_ = go(None), go(make_host_mesh(1))
        for n in SKETCH_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(p1["sketches"][n]), np.asarray(pm_["sketches"][n]), err_msg=n
            )

    def test_sketch_stream_placement_invariant(self, mesh8):
        """Local and mesh=8 emit the byte-identical sketch stream under a
        replayed lag trace (the composition where the PRNG paths coincide;
        generated volatility draws shard-local randomness)."""
        lp = GOLD["lag_trace_packed"]
        fl = FLConfig(K=K, k=k, rounds=T, scheme="random", quota_frac=FRAC)

        def go(mesh):
            pm = RoundProgram(fl=fl, vol=_lag_model(), rho=_rho(), override="packed_lags",
                              staleness=2, alpha=0.5, mesh=mesh)
            run, s0 = pm.build_runner(outputs="lean", taps=True, sketch=self.SPEC)
            *_, payload = run(s0, jax.random.PRNGKey(SEED), jnp.asarray(lp))
            return payload

        p1, p8 = go(None), go(mesh8)
        for n in SKETCH_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(p1["sketches"][n]), np.asarray(p8["sketches"][n]), err_msg=n
            )
        for name in ROUND_TAPS.gauge_names():
            np.testing.assert_allclose(
                np.asarray(p1["series"][name]), np.asarray(p8["series"][name]), atol=1e-4, err_msg=name
            )

    def test_sharded_sketch_merge_property_ragged_async(self, mesh8):
        """Satellite: the psum-merged D=8 sketch of a ragged-K async run
        equals the dense recompute of that run's own (T, K_pad) streams —
        the merge is exact addition, shard padding excluded via the active
        mask."""
        K_r, k_r = 130, 12  # K_pad = 136, ragged final shard
        pm = _async_program(mesh8, K_=K_r, k_=k_r)
        run, s0 = pm.build_runner(outputs="full", taps=True, sketch=self.SPEC)
        _, masks, lags, ps, _, _, payload = run(
            s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32)
        )
        masks, lags, ps = np.asarray(masks), np.asarray(lags), np.asarray(ps)
        Kp = masks.shape[1]
        assert Kp == 136
        spec, W = self.SPEC, self.W
        region = np.pad(region_ids(spec, K_r), (0, Kp - K_r))
        act = (np.arange(Kp) < K_r).astype(np.float64)
        L = lag_bins(2)
        x_ontime = (lags == 0).astype(np.float64)
        code = np.where(lags < 0, L - 1, np.clip(lags, 0, L - 2)).astype(np.int64)
        for i in range(T // W):
            t = (i + 1) * W
            counts = masks[:t].sum(0)
            cum = (masks[:t] * x_ontime[:t]).sum(0)
            lag_hist = np.zeros(L)
            np.add.at(lag_hist, code[:t].reshape(-1), masks[:t].reshape(-1))
            want = sketch_from_dense(spec, counts, ps[t - 1], cum, lag_hist, region, act)
            for n in SKETCH_FIELDS:
                np.testing.assert_allclose(
                    np.asarray(payload["sketches"][n][i], np.float64), want[n], rtol=1e-6,
                    err_msg=f"{n} @ emission {i}",
                )

    def test_merge_sketches_is_addition(self):
        rng = np.random.default_rng(0)
        a = {n: rng.random((3, 4)) for n in SKETCH_FIELDS}
        b = {n: rng.random((3, 4)) for n in SKETCH_FIELDS}
        m = merge_sketches(a, b)
        for n in SKETCH_FIELDS:
            np.testing.assert_allclose(m[n], a[n] + b[n])

    # -- fairness series ----------------------------------------------------

    def test_fairness_series_uniform_fleet(self):
        """Uniform counts: Jain 1, Gini 0, top-decile share = 10%, region
        skew 1 — all exact, whatever the bucketing."""
        spec = SketchSpec(window=1, count_bins=8, prob_bins=4, n_regions=4)
        Kn = 200
        counts = np.full(Kn, 5.0)
        region = region_ids(spec, Kn)
        row = sketch_from_dense(spec, counts, np.full(Kn, 0.5), counts, np.zeros(2), region)
        stream = {n: np.asarray(v)[None] for n, v in row.items()}
        fair = fairness_series(stream)
        assert fair["jain"][0] == pytest.approx(1.0)
        assert fair["gini"][0] == pytest.approx(0.0, abs=1e-12)
        assert fair["top_decile_share"][0] == pytest.approx(0.1)
        assert fair["region_cep_skew"][0] == pytest.approx(1.0)

    def test_fairness_series_vs_exact_oracles(self):
        """On a real run: sketch Jain is *exact* (streamed moments), grouped
        Gini / top-decile track the ``core.fairness`` exact twins."""
        run, s0 = _sync_program().build_runner(outputs="full", taps=True, sketch=self.SPEC)
        state, masks, *_ , payload = run(s0, jax.random.PRNGKey(SEED), jnp.zeros((T, 0), jnp.float32))
        fair = fairness_series(payload["sketches"])
        masks = np.asarray(masks)
        for i in range(T // self.W):
            counts = jnp.asarray(masks[: (i + 1) * self.W].sum(0))
            assert fair["jain"][i] == pytest.approx(float(jain_index(counts)), rel=1e-5)
            assert abs(fair["gini"][i] - float(gini_exact(counts))) < 0.12
            assert abs(fair["top_decile_share"][i] - float(top_share_exact(counts))) < 0.12
        assert np.all(fair["region_cep_skew"] >= 1.0)

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            SketchSpec(window=0)
        with pytest.raises(ValueError):
            SketchSpec(count_bins=1)
        with pytest.raises(ValueError):
            SketchSpec(n_regions=0)
        with pytest.raises(ValueError):
            SketchSpec(n_regions=2, regions=np.array([0, 1, 2]))
        with pytest.raises(ValueError):
            region_ids(SketchSpec(n_regions=2, regions=np.array([0, 1])), K=3)
        np.testing.assert_array_equal(region_ids(SketchSpec(n_regions=2), 4), [0, 0, 1, 1])


class TestCarryKeyTapsStreams:
    """Satellite: ``taps=True`` + ``carry_key=True`` threads the counter
    pytree through the streamed carry — chunked horizons emit the bit-
    identical tap stream to one-shot runs, in every placement."""

    C = 10  # chunk length; T = 50 -> 5 chunks

    def _drive_chunks(self, pm, async_mode, mesh=False):
        run_full, s0 = pm.build_runner(outputs="lean", carry_key=True, taps=True)
        run_chunk, _ = pm.build_runner(outputs="lean", carry_key=True, taps=True,
                                       scan_length=self.C)
        key = jax.random.PRNGKey(SEED)
        tapc = ROUND_TAPS.init_counters()
        xs = jnp.zeros((T, 0), jnp.float32)
        if async_mode:
            rings = pm.init_rings()
            state, key_f, rings_f, tapc_f, *outs_f, row_f = run_full(s0, key, rings, tapc, xs)
            state_c, key_c, rings_c, tapc_c = s0, key, pm.init_rings(), ROUND_TAPS.init_counters()
            rows = []
            for c in range(T // self.C):
                state_c, key_c, rings_c, tapc_c, *outs, row = run_chunk(
                    state_c, key_c, rings_c, tapc_c, jnp.zeros((self.C, 0), jnp.float32)
                )
                rows.append(row)
        else:
            state, key_f, tapc_f, *outs_f, row_f = run_full(s0, key, tapc, xs)
            state_c, key_c, tapc_c = s0, key, ROUND_TAPS.init_counters()
            rows = []
            for c in range(T // self.C):
                state_c, key_c, tapc_c, *outs, row = run_chunk(
                    state_c, key_c, tapc_c, jnp.zeros((self.C, 0), jnp.float32)
                )
                rows.append(row)
        series_f = {n: np.asarray(row_f[n]) for n in ROUND_TAPS.gauge_names()}
        series_c = {n: np.concatenate([np.asarray(r[n]) for r in rows]) for n in ROUND_TAPS.gauge_names()}
        for n in ROUND_TAPS.gauge_names():
            np.testing.assert_array_equal(series_f[n], series_c[n], err_msg=n)
        for n, v in tapc_f.items():
            assert float(v) == float(tapc_c[n]), n
        np.testing.assert_array_equal(np.asarray(state.sel_counts), np.asarray(state_c.sel_counts))

    def test_local_sync_chunked_equals_oneshot(self):
        self._drive_chunks(_sync_program(), async_mode=False)

    def test_local_async_chunked_equals_oneshot(self):
        self._drive_chunks(_async_program(), async_mode=True)

    def test_sharded_sync_chunked_equals_oneshot(self, mesh8):
        self._drive_chunks(_sync_program(mesh8, allocator="bisect"), async_mode=False)

    def test_replay_packed_stream_emits_taps(self, tmp_path):
        """K=big horizons replayed in chunks emit the same telemetry as the
        one-shot in-memory run."""
        from repro.scenarios import replay_packed_stream, save_packed_trace

        lp = GOLD["lag_trace_packed"]
        path = save_packed_trace(str(tmp_path / "lags"), lp, K, kind="lags")
        out = replay_packed_stream("e3cs", path, k, chunk=16, frac=FRAC, seed=SEED, taps=True)
        ref = async_selection_sim(
            "e3cs", K=K, k=k, T=T, frac=FRAC, seed=SEED, staleness=2, alpha=0.5,
            packed_lag_override=lp, outputs="lean", taps=True,
        )
        for n in ROUND_TAPS.gauge_names():
            np.testing.assert_array_equal(
                out["taps"]["series"][n], ref["taps"]["series"][n], err_msg=n
            )
        for n, v in out["taps"]["counters"].items():
            assert v == pytest.approx(ref["taps"]["counters"][n], rel=1e-6), n


class TestAlerts:
    def test_severity_validation(self):
        with pytest.raises(ValueError):
            Alert("outage", "apocalyptic", {})

    def test_outage_fires_on_windowed_collapse(self):
        on_time = np.concatenate([np.full(40, 10.0), np.full(10, 1.0)])
        alerts = detect_alerts(series={"on_time": on_time}, rules=AlertRules(window=10))
        assert [a.rule for a in alerts] == ["outage"]
        assert alerts[0].severity == "critical"
        assert alerts[0].detail["window"] == 4
        # healthy series: silent
        assert detect_alerts(series={"on_time": np.full(50, 10.0)}, rules=AlertRules(window=10)) == []

    def test_starvation_fires_on_fairness_thresholds(self):
        fair = {"jain": np.array([0.9, 0.3]), "top_decile_share": np.array([0.2, 0.8])}
        alerts = detect_alerts(fairness=fair)
        assert sorted(a.rule for a in alerts) == ["starvation", "starvation"]
        assert all(a.severity == "warn" for a in alerts)
        assert detect_alerts(fairness={"jain": np.array([0.8]), "top_decile_share": np.array([0.3])}) == []

    def test_drift_fires_on_cohort_and_cap(self):
        alerts = detect_alerts(
            series={"selected": np.array([16.0, 16.0, 15.0]), "capped_frac": np.full(10, 0.9)},
            expected_selected=16,
            rules=AlertRules(window=5),
        )
        rules = sorted(a.rule for a in alerts)
        assert rules == ["drift", "drift"]
        sel = next(a for a in alerts if a.detail.get("metric") == "selected")
        assert sel.severity == "critical" and sel.detail["first_round"] == 2

    def test_empty_inputs_silent(self):
        assert detect_alerts() == []
        assert detect_alerts(series={}, fairness={}) == []


class TestRunLogV2:
    def test_alert_event_round_trip(self, tmp_path):
        path = str(tmp_path / "a.jsonl")
        with RunLog("unit", path=path) as log:
            log.alert("outage", "critical", {"window": 3}, "credit fell")
            log.summary(done=True)
        records = read_runlog(path)
        validate_records(records)
        alerts = list(iter_alerts(records))
        assert len(alerts) == 1
        assert alerts[0]["rule"] == "outage" and alerts[0]["severity"] == "critical"
        assert all("ts" in r for r in records)

    def test_nan_sanitized_everywhere(self, tmp_path):
        """Satellite regression: NaN/inf inside numpy scalars AND arrays
        serialize as null — the file must contain no bare NaN tokens."""
        path = str(tmp_path / "nan.jsonl")
        with RunLog("unit", path=path) as log:
            log.summary(
                a=np.float64("nan"), b=float("inf"),
                c=np.array([1.0, np.nan, np.inf]), d={"deep": jnp.float32(np.nan)},
            )
        raw = open(path).read()
        assert "NaN" not in raw and "Infinity" not in raw
        data = read_runlog(path)[-1]["data"]
        assert data["a"] is None and data["b"] is None
        assert data["c"] == [1.0, None, None]
        assert data["d"]["deep"] is None

    def test_overwrite_protection(self, tmp_path):
        """Satellite: a rerun under the same name refuses to truncate the
        existing log unless overwrite=True; unique=True writes a numbered
        sibling with the header run name unchanged."""
        path = str(tmp_path / "r.jsonl")
        RunLog("r", path=path).close()
        with pytest.raises(FileExistsError):
            RunLog("r", path=path)
        log2 = RunLog("r", path=path, unique=True)
        assert log2.path == str(tmp_path / "r.2.jsonl")
        log2.close()
        assert read_runlog(log2.path)[0]["run"] == "r"  # header name stays stable
        log3 = RunLog("r", path=path, overwrite=True)
        assert log3.path == path
        log3.close()

    def test_v1_records_still_validate(self):
        v1 = [
            {"schema": 1, "event": "header", "run": "x", "name": "x", "config": {}},
            {"schema": 1, "event": "summary", "run": "x", "data": {}},
        ]
        validate_records(v1)  # no ts required at v1
        with pytest.raises(ValueError, match="schema >= 2"):
            validate_records([
                {"schema": 1, "event": "header", "run": "x", "name": "x", "config": {}},
                {"schema": 1, "event": "alert", "run": "x", "rule": "r", "severity": "warn", "detail": {}},
            ])
        with pytest.raises(ValueError, match="ts"):
            validate_records([
                {"schema": 2, "event": "header", "run": "x", "name": "x", "config": {}},
            ])


class TestObsExplore:
    @pytest.fixture()
    def explorer(self):
        return _load_module("scripts/obs_explore.py", "obs_explore")

    def _write_log(self, path, run, jain_last=0.8, alert=False):
        with RunLog(run, config={"K": 8}, path=path) as log:
            log.metrics(
                "fairness",
                window_reduce({"jain": np.array([0.5, jain_last])}, window=1),
                better={"jain": "higher"},
            )
            if alert:
                log.alert("starvation", "warn", {"jain": jain_last}, "low jain")
            log.summary(rounds_per_s=10.0)
        return path

    def test_summarize_and_fairness(self, tmp_path, capsys, explorer):
        self._write_log(str(tmp_path / "a.jsonl"), "a", alert=True)
        assert explorer.main(["summarize", str(tmp_path)]) == 0
        text = capsys.readouterr().out
        assert "== a" in text and "ALERT [warn] starvation" in text and "fairness.jain" in text
        assert explorer.main(["fairness", str(tmp_path / "a.jsonl"), "--csv"]) == 0
        csv = capsys.readouterr().out.strip().splitlines()
        assert csv[0] == "run,stream,metric,window,p50"
        assert csv[1].startswith("a,fairness,jain,0,")

    def test_diff_pairs_by_header_name(self, tmp_path, capsys, explorer):
        a_dir, b_dir = tmp_path / "A", tmp_path / "B"
        a_dir.mkdir(), b_dir.mkdir()
        self._write_log(str(a_dir / "x.jsonl"), "run1", jain_last=0.8)
        # same header name, different filename: still paired
        self._write_log(str(b_dir / "y.jsonl"), "run1", jain_last=0.2, alert=True)
        rc = explorer.main(["diff", str(a_dir), str(b_dir), "--strict"])
        text = capsys.readouterr().out
        assert rc == 1  # jain dropped 75% under direction "higher" -> gated regression
        assert "REGRESSED" in text and "NEW ALERT" in text
        # tolerant run: reported but exit 0 without --strict
        assert explorer.main(["diff", str(a_dir), str(b_dir)]) == 0

    def test_output_file(self, tmp_path, capsys, explorer):
        self._write_log(str(tmp_path / "a.jsonl"), "a")
        out = str(tmp_path / "rep" / "report.txt")
        assert explorer.main(["summarize", str(tmp_path / "a.jsonl"), "-o", out]) == 0
        capsys.readouterr()
        assert "== a" in open(out).read()


class TestFairnessExactMetrics:
    """``core.fairness.gini`` / ``top_share`` — the dense oracles the sketch
    stream approximates."""

    def test_gini_edge_cases(self):
        assert float(gini_exact(jnp.full(10, 3.0))) == pytest.approx(0.0, abs=1e-6)
        # one client holds everything: G -> (K-1)/K
        one = jnp.zeros(10).at[3].set(5.0)
        assert float(gini_exact(one)) == pytest.approx(0.9, abs=1e-6)

    def test_top_share_edge_cases(self):
        assert float(top_share_exact(jnp.full(10, 2.0), 0.1)) == pytest.approx(0.1, rel=1e-5)
        one = jnp.zeros(10).at[3].set(5.0)
        assert float(top_share_exact(one, 0.1)) == pytest.approx(1.0, rel=1e-5)


class TestTapRegistry:
    def test_round_taps_schema(self):
        # the default "round" group is exactly the in-scan gauges — the
        # fairness group (host-derived from sketches) must not leak into it
        assert set(ROUND_TAPS.gauge_names()) == {
            "selected", "on_time", "stale", "sigma", "capped_frac", "topk_ties",
        }
        assert ROUND_TAPS.directions()["selected"] == "equal"
        assert ROUND_TAPS.directions()["on_time"] == "higher"
        assert set(ROUND_TAPS.gauge_names(group=None)) == {
            "selected", "on_time", "stale", "sigma", "capped_frac", "topk_ties",
            "jain", "gini", "top_decile_share", "region_cep_skew",
            "queue_depth", "batch_jobs", "shed", "restarts", "recovery_s",
        }
        assert set(ROUND_TAPS.gauge_names(group="fairness")) == set(FAIRNESS_SERIES)
        assert set(ROUND_TAPS.gauge_names(group="serve")) == {
            "queue_depth", "batch_jobs", "shed", "restarts", "recovery_s",
        }
        assert ROUND_TAPS.directions("serve")["shed"] == "lower"
        assert ROUND_TAPS.directions("serve")["restarts"] == "lower"
        assert ROUND_TAPS.directions("serve")["recovery_s"] == "lower"
        fair_dirs = ROUND_TAPS.directions("fairness")
        assert fair_dirs["jain"] == "higher"
        assert fair_dirs["gini"] == "lower"
        assert fair_dirs["top_decile_share"] == "lower"
        assert fair_dirs["region_cep_skew"] == "none"

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            TapSpec("x", "nope")
        with pytest.raises(ValueError):
            TapSpec("x", "gauge", better="sideways")

    def test_accumulate_sources(self):
        reg = TapRegistry(
            TapSpec("a", "gauge"),
            TapSpec("b", "gauge"),
            TapSpec("ticks", "counter"),
            TapSpec("total", "counter", source=("a", "b")),
        )
        c = reg.init_counters()
        row = {"a": jnp.float32(2.0), "b": jnp.float32(3.0)}
        c = reg.accumulate(c, row)
        c = reg.accumulate(c, row)
        assert float(c["ticks"]) == 2.0
        assert float(c["total"]) == 10.0


class TestWindowReduce:
    def test_hand_checked(self):
        # [1..7] window 3: two full windows, one element dropped;
        # p99 interpolates linearly inside each 3-sample window
        out = window_reduce({"v": np.arange(1.0, 8.0)}, window=3)
        assert out["n_windows"] == 2 and out["dropped"] == 1
        aggs = out["aggs"]["v"]
        np.testing.assert_allclose(aggs["sum"], [6.0, 15.0])
        np.testing.assert_allclose(aggs["mean"], [2.0, 5.0])
        np.testing.assert_allclose(aggs["p50"], [2.0, 5.0])
        np.testing.assert_allclose(aggs["p99"], [2.98, 5.98])

    def test_tiny_three_client_horizon(self):
        # a K=3, k=1 horizon: the selected gauge is exactly 1 every round,
        # so every windowed aggregate of it is hand-computable
        out = scan_selection_sim("random", K=3, k=1, T=8, frac=0.0, seed=0, taps=True)
        red = window_reduce(out["taps"]["series"], window=4)
        assert red["n_windows"] == 2 and red["dropped"] == 0
        np.testing.assert_allclose(red["aggs"]["selected"]["sum"], [4.0, 4.0])
        np.testing.assert_allclose(red["aggs"]["selected"]["p50"], [1.0, 1.0])
        np.testing.assert_allclose(red["aggs"]["selected"]["mean"], [1.0, 1.0])
        assert out["taps"]["counters"]["cum_selected"] == 8.0

    def test_mismatched_lengths_raise(self):
        with pytest.raises(ValueError):
            window_reduce({"a": np.arange(6.0), "b": np.arange(5.0)}, window=3)


class TestRunLogRoundTrip:
    def test_full_round_trip(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        hist = LatencyHistogram()
        hist.observe(0.002)
        with RunLog("unit", config={"K": 4}, path=path) as log:
            log.metrics("s1", window_reduce({"v": np.arange(8.0)}, window=4), better={"v": "higher"})
            log.grid_row({"selector": "e3cs", "cep": 1.0})
            log.histogram("lat", hist.to_record())
            log.summary(done=True)
        records = read_runlog(path)
        validate_records(records)
        assert records[0]["event"] == "header"
        assert records[0]["schema"] == SCHEMA_VERSION
        assert records[0]["config"] == {"K": 4}
        events = [r["event"] for r in records]
        assert events == ["header", "metrics", "grid_row", "histogram", "summary"]
        streams = {r["stream"]: r for r in iter_metrics(records)}
        assert "s1" in streams and streams["s1"]["windows"]["n_windows"] == 2
        assert streams["s1"]["better"] == {"v": "higher"}

    def test_jsonable_coercion(self, tmp_path):
        path = str(tmp_path / "np.jsonl")
        with RunLog("unit", path=path) as log:
            log.summary(a=np.float32(1.5), b=jnp.int32(2), c=float("nan"), d=np.arange(3))
        rec = read_runlog(path)[-1]["data"]
        assert rec["a"] == 1.5 and rec["b"] == 2 and rec["c"] is None and rec["d"] == [0, 1, 2]

    def test_validate_rejects_bad(self):
        with pytest.raises(ValueError):
            validate_records([])
        with pytest.raises(ValueError):  # missing required payload key
            validate_records([{"schema": SCHEMA_VERSION, "event": "metrics", "run": "x"}])
        with pytest.raises(ValueError):  # wrong schema version
            validate_records([{"schema": 99, "event": "header", "run": "x", "name": "x", "config": {}}])
        with pytest.raises(ValueError):  # first record must be the header
            validate_records([
                {"schema": SCHEMA_VERSION, "event": "summary", "run": "x", "data": {}},
            ])


class TestReporter:
    def test_bench_json_with_metrics_block(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_RESULTS", str(tmp_path))
        rep = Reporter("unit", config={"smoke": True})
        rep.metrics_stream("s", {"v": np.arange(10.0)}, window=5, better={"v": "higher"})
        path = rep.save({"rounds_per_s": 42.0})
        assert path == str(tmp_path / "bench" / "BENCH_unit.json")
        blob = json.load(open(path))
        assert blob["rounds_per_s"] == 42.0
        assert blob["metrics"]["s"]["n_windows"] == 2
        assert blob["metrics"]["s"]["better"] == {"v": "higher"}
        records = read_runlog(str(tmp_path / "runlogs" / "unit.jsonl"))
        validate_records(records)
        assert records[-1]["event"] == "summary"


class TestPaths:
    def test_env_layout(self, monkeypatch, tmp_path):
        monkeypatch.delenv("REPRO_RESULTS", raising=False)
        monkeypatch.delenv("REPRO_BENCH_OUT", raising=False)
        assert obs_paths.results_root() == "results"
        monkeypatch.setenv("REPRO_BENCH_OUT", str(tmp_path / "r" / "bench"))
        assert obs_paths.results_root() == str(tmp_path / "r")
        assert obs_paths.bench_dir() == str(tmp_path / "r" / "bench")
        monkeypatch.setenv("REPRO_RESULTS", str(tmp_path / "override"))
        assert obs_paths.results_root() == str(tmp_path / "override")
        assert obs_paths.artifact_path("x.json") == str(tmp_path / "override" / "x.json")
        assert obs_paths.bench_path("n").endswith(os.path.join("bench", "BENCH_n.json"))
        assert obs_paths.runlog_path("n").endswith(os.path.join("runlogs", "n.jsonl"))


class TestLatencyHistogram:
    def test_quantiles_bracket_samples(self):
        h = LatencyHistogram(lo=1e-4, hi=1.0, n_buckets=32)
        samples = [0.001, 0.002, 0.004, 0.008, 0.016]
        for s in samples:
            h.observe(s)
        s = h.summary()
        assert s["count"] == 5
        assert s["min_s"] == 0.001 and s["max_s"] == 0.016
        assert s["min_s"] <= s["p50_s"] <= s["max_s"]
        assert s["p50_s"] <= s["p99_s"] <= s["max_s"]
        assert s["mean_s"] == pytest.approx(np.mean(samples), rel=1e-6)
        rec = h.to_record()
        assert len(rec["counts"]) == 32 and sum(rec["counts"]) == 5

    def test_out_of_range_clamped(self):
        h = LatencyHistogram(lo=1e-3, hi=1e-2, n_buckets=8)
        h.observe(1e-6)
        h.observe(5.0)
        assert h.quantile(0.0) >= 1e-6
        assert math.isfinite(h.quantile(0.99))

    def test_span_timer(self):
        t = SpanTimer()
        with t.span("work"):
            pass
        with t.span("work"):
            pass
        assert t.get("work").summary()["count"] == 2
        assert "work" in t.summary()
        assert t.durations_ns("work").size == 2 and t.quantile("work", 0.5) >= 0

    def test_lifetime_histogram_outlives_the_ring(self):
        t = SpanTimer(capacity=4)
        for d in (0, 1_000, 50_000_000_000, 2_000, 3_000, 4_000):  # ns; 50 s clamps to the top bucket
            t.record("work", 10, 10 + d)
        h = t.get("work")
        assert t.dropped == 2 and t.durations_ns("work").size == 4
        assert h.count == 6 and int(h.counts.sum()) == 6
        assert (h.min, h.max) == (0.0, 50.0) and h.counts[-1] == 1
        assert h.sum == pytest.approx(50 + 1e-5, rel=1e-12)
        t.get("work").observe(float("nan"))  # dropped, as before
        assert h.count == 6


class TestSpanRecorder:
    """``SpanTimer`` as the host span recorder: ring, ids, quantiles, the
    profiler's timeline."""

    def test_parent_ids_when_spans_nest(self):
        t = SpanTimer()
        with t.span("outer", rid=5) as outer:
            with t.span("mid") as mid:
                with t.span("inner") as inner:
                    pass
            with t.span("sibling") as sib:
                pass
        with t.span("root") as root:
            pass
        ev = t.events()
        by = {n: i for i, n in enumerate(ev["name"])}
        assert ev["parent"][by["outer"]] == -1 and ev["parent"][by["root"]] == -1
        assert ev["parent"][by["mid"]] == outer.id and ev["parent"][by["sibling"]] == outer.id
        assert ev["parent"][by["inner"]] == mid.id
        assert [ev["id"][by[n]] for n in ("inner", "sibling", "root")] == [inner.id, sib.id, root.id]
        # the request id is inherited from the enclosing span, else -1
        assert [ev["rid"][by[n]] for n in ("outer", "mid", "inner", "sibling", "root")] == [5, 5, 5, 5, -1]
        assert len(set(ev["id"].tolist())) == 5

    def test_request_id_kept_across_two_threads(self):
        import threading

        t = SpanTimer()
        handed = {}

        def handler():
            with t.span("front", rid=42) as sp:
                handed["t0"] = time.perf_counter_ns()
                handed["front"] = sp.id

        def engine():
            t.record("wait", handed["t0"], time.perf_counter_ns(), rid=42)
            with t.span("work", rid=42):
                with t.span("step"):
                    pass

        for fn in (handler, engine):
            th = threading.Thread(target=fn)
            th.start()
            th.join()
        ev = t.events()
        assert sorted(ev["name"][ev["rid"] == 42]) == ["front", "step", "wait", "work"]
        # parents never cross threads: each thread's stack is its own
        assert ev["parent"][ev["name"] == "wait"][0] == -1
        assert ev["parent"][ev["name"] == "front"][0] == -1

    def test_ring_wraps_with_an_exact_dropped_count(self):
        t = SpanTimer(capacity=8)
        for i in range(21):
            t.record("a" if i % 3 else "b", 1000 * i, 1000 * i + i, rid=i)
        assert t.dropped == 13
        ev = t.events()
        assert ev["rid"].tolist() == list(range(13, 21))  # the newest 8, oldest first
        assert (ev["t1_ns"] - ev["t0_ns"]).tolist() == list(range(13, 21))
        assert ev["name"].tolist() == ["a" if i % 3 else "b" for i in range(13, 21)]
        # the lifetime histograms still hold every span
        assert t.get("a").count + t.get("b").count == 21 and t.get("b").count == 7
        t.reset()
        assert t.dropped == 0 and t.events()["name"].size == 0 and t.counters == {}

    def test_ring_quantiles_equal_numpy_quantile(self):
        t = SpanTimer()
        rng = np.random.default_rng(1)
        dur = rng.integers(1_000, 10_000_000, 999)
        for i, d in enumerate(dur):
            t.record("x", 10 * i, 10 * i + int(d))
            t.record("y", 0, 1)
        for q in (0.0, 0.5, 0.95, 0.99, 1.0):
            assert t.quantile("x", q) == float(np.quantile(dur, q)) * 1e-9
        d = t.digest()["x"]
        assert d["count"] == 999 and d["max_ms"] == dur.max() * 1e-6
        assert d["p50_ms"] == pytest.approx(np.quantile(dur, 0.5) * 1e-6, rel=1e-12)
        assert d["p95_ms"] == pytest.approx(np.quantile(dur, 0.95) * 1e-6, rel=1e-12)
        assert t.quantile("never", 0.5) is None

    def test_record_with_an_external_start(self):
        t = SpanTimer()
        t0 = time.perf_counter_ns()
        time.sleep(0.002)
        with t.span("dispatch", rid=9) as sp:
            sid = t.record("queue", t0, time.perf_counter_ns())
        ev = t.events()
        (i,) = np.nonzero(ev["name"] == "queue")[0]
        assert ev["id"][i] == sid and ev["parent"][i] == sp.id and ev["rid"][i] == 9
        assert ev["t0_ns"][i] == t0 and ev["t1_ns"][i] - t0 >= 2_000_000
        t.add("bytes", 4096)
        t.add("bytes", 4)
        t.add("syncs")
        assert t.counters == {"bytes": 4100, "syncs": 1}

    def test_span_lands_on_the_profilers_host_timeline(self, tmp_path):
        """A span's name is found in a ``jax.profiler`` trace read with
        ``ProfileData.from_file``, and its ring start, placed on the wall
        clock through the anchor, lies where the profiler put it."""
        t = SpanTimer()
        jax.profiler.start_trace(str(tmp_path))
        for i in range(9):
            with t.span("unit.traced_span", rid=i):
                time.sleep(0.001)
        jax.profiler.stop_trace()
        (path,) = sorted(tmp_path.rglob("*.xplane.pb"))
        pd = jax.profiler.ProfileData.from_file(str(path))
        starts, t_start = [], None
        for plane in pd.planes:
            t_start = dict(plane.stats).get("profile_start_time", t_start)
            for line in plane.lines:
                starts += [e.start_ns for e in line.events if e.name == "unit.traced_span"]
        assert len(starts) == 9 and t_start is not None
        ring = t.wall_ns(t.events()["t0_ns"]) - t_start
        assert float(np.median(np.abs(np.sort(starts) - ring))) < 200e3  # ns


class TestStage:
    def test_host_and_traced(self):
        with stage("unit.host"):
            x = jnp.ones(4)

        @jax.jit
        def f(v):
            with stage("unit.traced"):
                return v * 2

        np.testing.assert_array_equal(np.asarray(f(x)), np.full(4, 2.0))


class TestCheckBench:
    def _compare(self, cb, new, base, tol=0.3, metrics_only=False):
        if metrics_only:
            checked_m, regs_m, notes_m = cb.compare_metrics(new, base, tol)
            return checked_m, regs_m, [], notes_m
        cs, rs, imps, ns = cb.compare_scalars(new, base, tol)
        cm, rm, nm = cb.compare_metrics(new, base, tol)
        return cs + cm, rs + rm, imps, ns + nm

    def test_scalar_regression_and_improvement(self, check_bench):
        checked, regs, imps, notes = self._compare(
            check_bench,
            {"a": {"rounds_per_s": 5.0}, "b": {"ticks_per_s": 20.0}},
            {"a": {"rounds_per_s": 10.0}, "b": {"ticks_per_s": 10.0}},
        )
        assert checked == 2
        assert [r[0] for r in regs] == ["a.rounds_per_s"]
        assert [i[0] for i in imps] == ["b.ticks_per_s"]

    def test_zero_and_nonfinite_baselines_noted(self, check_bench):
        checked, regs, imps, notes = self._compare(
            check_bench,
            {"a": {"rounds_per_s": 5.0}, "b": {"rounds_per_s": 5.0}},
            {"a": {"rounds_per_s": 0.0}, "b": {"rounds_per_s": float("nan")}},
        )
        assert checked == 0 and not regs
        assert any("<= 0" in n for n in notes)
        assert any("non-finite" in n for n in notes)

    def test_one_sided_keys_noted_not_failed(self, check_bench):
        checked, regs, imps, notes = self._compare(
            check_bench,
            {"new_only": {"rounds_per_s": 5.0}},
            {"old_only": {"rounds_per_s": 5.0}},
        )
        assert checked == 0 and not regs
        assert any("no baseline" in n for n in notes)
        assert any("baseline only" in n for n in notes)

    def _metrics_doc(self, p50, window=5, direction="higher"):
        return {"metrics": {"s": {
            "window": window, "n_windows": len(p50), "dropped": 0,
            "better": {"v": direction},
            "aggs": {"v": {"p50": list(p50), "p99": list(p50), "mean": list(p50), "sum": list(p50)}},
        }}}

    def test_metrics_direction_gates(self, check_bench):
        base = self._metrics_doc([10.0, 10.0])
        ok = self._metrics_doc([9.0, 11.0])
        bad = self._metrics_doc([10.0, 6.0])
        assert not self._compare(check_bench, ok, base, metrics_only=True)[1]
        regs = self._compare(check_bench, bad, base, metrics_only=True)[1]
        assert [r[0] for r in regs] == ["metrics.s.v.p50[1]"]
        # "lower" flips the inequality
        base_l = self._metrics_doc([10.0], direction="lower")
        assert not self._compare(check_bench, self._metrics_doc([12.0], direction="lower"),
                                 base_l, metrics_only=True)[1]
        assert self._compare(check_bench, self._metrics_doc([14.0], direction="lower"),
                             base_l, metrics_only=True)[1]
        # "equal" gates any drift; "none" never gates
        base_e = self._metrics_doc([10.0], direction="equal")
        assert self._compare(check_bench, self._metrics_doc([10.0001], direction="equal"),
                             base_e, metrics_only=True)[1]
        base_n = self._metrics_doc([10.0], direction="none")
        assert not self._compare(check_bench, self._metrics_doc([0.0], direction="none"),
                                 base_n, metrics_only=True)[1]

    def test_window_mismatch_skipped(self, check_bench):
        base = self._metrics_doc([10.0, 10.0])
        new = self._metrics_doc([10.0, 10.0, 10.0])
        checked, regs, _, notes = self._compare(check_bench, new, base, metrics_only=True)
        assert checked == 0 and not regs
        assert any("windows" in n for n in notes)
        new_w = self._metrics_doc([10.0, 10.0], window=7)
        _, regs, _, notes = self._compare(check_bench, new_w, base, metrics_only=True)
        assert not regs and any("window" in n for n in notes)

    def test_metrics_block_not_gated_as_leaves(self, check_bench):
        doc = self._metrics_doc([10.0])
        assert dict(check_bench.numeric_leaves(doc)) == {}


class TestTimeFn:
    def test_both_modes(self):
        common = _load_module("benchmarks/common.py", "bench_common")
        us_block = common.time_fn(lambda: jnp.ones(8) * 2, iters=2, warmup=1)
        us_pipe = common.time_fn(lambda: jnp.ones(8) * 2, iters=2, warmup=1, blocking=False)
        assert us_block > 0 and us_pipe > 0
