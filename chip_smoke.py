#!/usr/bin/env python3
"""Chip smoke test: the E3CS round engine and the socket server on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the K-sharded path on a four-chip host

One process, no subprocesses; phases run in order and any failure exits
non-zero.  Per-phase facts are printed as JSON lines; the last line of
standard output is the verdict,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed.  Without a TPU (or with
``REPRO_INTERPRET`` forcing a route) the script refuses to run: there is no
CPU path.

One chip:

* **engine** — ``RoundProgram.from_config`` at K=10,000,000, k=1000 (E3CS,
  sort-free bisect allocator with 4-halving blocks) over a 20-round lean
  horizon with taps, fed a seeded packed ``diurnal`` trace: synchronous
  (1-bit rows, ``override="packed"``) and async S=2 with late credit (2-bit
  lag rows, ``override="packed_lags"``), each staged and fused, plus the
  jnp-reference route (``REPRO_INTERPRET=0``) of the staged program.  Every
  kernel runner must carry ``tpu_custom_call`` in its compiled text (the
  reference must not), finite log-weights, exactly k selections per round
  and positive success / credit totals; staged, fused and reference must
  pick the same cohorts (within ``COHORT_TOL``, see below).
* **serve** — the socket front end (``SelectionServer``) driven by a loopback
  ``ServeClient``: 8 tenants at K=4096 on a ``SlotEngine`` for 10
  round-tagged ticks each, then one K=1,000,000 job on a
  ``ShardedEngine(D=1)`` for 5 ticks.  Every tick must return k distinct
  in-range ids; a supervised engine restart or an error answer fails.

``--chips 4``: the sharded sync and async programs (staged and fused) on a
4-chip mesh at K=40,000,000 — state spread over the 4 chips, the ``(D*k,)``
top-k candidate exchange in the compiled text, fused == staged cohorts —
the fused programs on one chip at K=10,000,000 (the per-chip size), the
``random`` scheme's packed-lag replay on 4 chips == on one chip at
K=40,000,000 (the cross-placement equality the CPU goldens pin: E3CS draws
its noise per shard, so only a replicated selector is placement-free), and
a few ticks served through ``ShardedEngine(D=4)``.

Each phase traces its runners one by one and then compiles them
concurrently (XLA compiles outside the GIL); compile seconds are set-up
and reported per runner and per batch.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
ENGINE_K, ENGINE_k, ENGINE_T = 10_000_000, 1000, 20
SHARDED_K = 40_000_000
SLOT_K, SLOT_k, SLOT_TENANTS, SLOT_TICKS = 4096, 256, 8, 10
BIG_K, BIG_k, BIG_TICKS = 1_000_000, 1000, 5
# Largest share of cohort slots two routes may disagree on before the run
# fails.  The CPU goldens pin them equal; the chip's compiled kernels and
# XLA may round log / divide differently, which can swap a near-tied
# client at the top-k boundary.  A divergence is always reported first.
COHORT_TOL = 1e-3
# A first tick compiles its engine inside the server; give it time.
REQUEST_TIMEOUT_S = 600.0


def emit(**fields) -> None:
    print(json.dumps(fields, sort_keys=True), flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


@contextlib.contextmanager
def kernel_route_env(value):
    """Set ``REPRO_INTERPRET`` while a runner is traced (the route is baked
    in at trace time); None leaves the automatic route."""
    old = os.environ.get("REPRO_INTERPRET")
    if value is not None:
        os.environ["REPRO_INTERPRET"] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("REPRO_INTERPRET", None)
        else:
            os.environ["REPRO_INTERPRET"] = old


def peak_hbm() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def make_program(K, k, T, *, sync, fused=False, mesh=None, scheme="e3cs"):
    from repro.configs.base import FLConfig
    from repro.engine.round_program import RoundProgram

    fl = FLConfig(
        K=K, k=k, rounds=T, scheme=scheme, allocator="bisect", quota_frac=0.5,
        volatility="diurnal", seed=SEED, staleness_rounds=0 if sync else 2,
    )
    return RoundProgram.from_config(
        fl, mesh=mesh, override="packed" if sync else "packed_lags",
        feedback="deadline" if sync else "late_credit", block=4, fused=fused,
    )


def record(program, T):
    """The seeded packed trace the program replays (recorded on device).
    Its seed differs from the engine's: a recorder and an engine keyed alike
    split the same key chain, so the selection noise would be drawn from the
    very bits that decided availability."""
    from repro.scenarios import record_lag_trace, record_trace

    if program.staleness is None:
        return record_trace(program.vol, T, seed=SEED + 1, chunk=T)
    return record_lag_trace(program.vol, T, seed=SEED + 1, chunk=T)


def candidate_exchange(text: str, n: int) -> bool:
    """Whether the compiled text moves the ``(n,)`` top-k candidates between
    chips: the ``all_gather`` of ``(D*k,)`` values and ids, which XLA on a
    TPU may lower as an all-gather or as an all-reduce of that shape."""
    return re.search(rf"\[{n}\]\S* (?:all-gather|all-reduce)", text) is not None


class Runner:
    """One lean+taps horizon: traced at construction (under its kernel
    route), compiled by ``compile_all``, executed and checked by ``run``."""

    def __init__(self, tag, program, xs, *, route=None, kernels=True, gather=None, devices=None):
        import jax

        self.tag, self.program, self.kernels = tag, program, kernels
        self.gather, self.devices = gather, devices
        run, self.state0 = program.build_runner(outputs="lean", taps=True)
        self.key = jax.random.PRNGKey(SEED)
        self.xs = jax.numpy.asarray(xs)
        with kernel_route_env(route):
            self.lowered = run.lower(self.state0, self.key, self.xs)
        self.compiled, self.compile_s = None, None

    def compile(self):
        t0 = time.perf_counter()
        self.compiled = self.lowered.compile()
        self.compile_s = time.perf_counter() - t0
        self.lowered = None

    def run(self):
        """Run, check, report; returns host copies of what is compared."""
        import jax
        import numpy as np

        fl, tag = self.program.fl, self.tag
        K, k, T = fl.K, fl.k, self.xs.shape[0]
        sync = self.program.staleness is None
        text = self.compiled.as_text()
        if ("tpu_custom_call" in text) != self.kernels:
            fail(f"{tag}: compiled text {'lacks' if self.kernels else 'has'} tpu_custom_call")
        if self.gather is not None and not candidate_exchange(text, self.gather):
            fail(f"{tag}: compiled text moves no ({self.gather},) top-k candidates between chips")
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.compiled(self.state0, self.key, self.xs))
        run_s = time.perf_counter() - t0
        self.compiled = self.state0 = self.xs = None
        state, taps = out[0], out[-1]
        logw = np.asarray(state.e3cs.logw)[:K]
        counts = np.asarray(state.sel_counts)[:K]
        if not np.isfinite(logw).all():
            fail(f"{tag}: non-finite log-weights")
        if counts.sum() != k * T or np.asarray(taps["series"]["selected"]).min() != k:
            fail(f"{tag}: cohorts are not k={k} clients every round")
        # sync lean: (state, successes, sigmas); async lean: (state, on_time, stale, ...)
        successes = float(np.asarray(out[1]).sum())
        credit = 0.0 if sync else float(np.asarray(out[2]).sum())
        if successes <= 0 or (not sync and credit <= 0):
            fail(f"{tag}: success/credit totals not positive ({successes}, {credit})")
        devices = len({s.device for s in state.e3cs.logw.addressable_shards})
        if self.devices is not None and devices != self.devices:
            fail(f"{tag}: log-weights live on {devices} device(s), not {self.devices}")
        emit(
            phase="engine", run=tag, K=K, k=k, rounds=T, compile_s=self.compile_s,
            run_s=run_s, rounds_per_s=T / run_s, successes=successes, late_credit=credit,
            state_devices=devices, peak_hbm_bytes=peak_hbm(),
        )
        return {
            "counts": counts,
            "logw": logw,
            "series": {n: np.asarray(v) for n, v in taps["series"].items()},
        }


def compile_all(phase, runners):
    """Compile traced runners concurrently; each keeps its own seconds."""
    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(runners)) as ex:
        list(ex.map(Runner.compile, runners))
    emit(phase=phase, compiled=[r.tag for r in runners], compile_wall_s=time.perf_counter() - t0)


def first_divergence(prog_a, route_a, prog_b, route_b, xs):
    """Host-step two dense programs round by round; return the first round
    whose allocation differs and the first whose cohort differs."""
    import jax
    import numpy as np

    steps = []
    for prog, route in ((prog_a, route_a), (prog_b, route_b)):
        step, state0 = prog.build_step(lean=False)
        rings = () if prog.staleness is None else (prog.init_rings(),)
        with kernel_route_env(route):
            jstep = jax.jit(step).lower((state0, jax.random.PRNGKey(SEED), *rings), xs[0]).compile()
        steps.append([jstep, (state0, jax.random.PRNGKey(SEED), *rings)])
    first = {"allocate": None, "select": None}
    for t in range(xs.shape[0]):
        outs = []
        for s in steps:
            s[1], out = s[0](s[1], xs[t])
            outs.append(out)
        (mask_a, _, p_a, *_), (mask_b, _, p_b, *_) = outs
        if first["allocate"] is None and not np.array_equal(np.asarray(p_a), np.asarray(p_b)):
            first["allocate"] = t
        if first["select"] is None and not np.array_equal(np.asarray(mask_a), np.asarray(mask_b)):
            first["select"] = t
    return first


def compare(tag, a, b, diagnose=None, tol=COHORT_TOL):
    """Cohorts of two runs of one configuration: identical, or within
    ``tol`` after the divergence is reported."""
    import numpy as np

    k_T = a["counts"].sum()
    moved = float(np.abs(a["counts"] - b["counts"]).sum()) / 2
    same_series = all(np.array_equal(a["series"][n], b["series"][n]) for n in a["series"])
    logw_diff = float(np.max(np.abs(a["logw"] - b["logw"])))
    emit(phase="compare", pair=tag, identical=bool(moved == 0 and same_series),
         cohort_slots_moved=moved, max_logw_diff=logw_diff)
    if moved == 0 and same_series:
        return
    if diagnose is not None:
        emit(phase="compare", pair=tag, first_divergence=diagnose())
    if tol == 0 or moved / k_T > tol:
        fail(f"{tag}: {moved} of {k_T} cohort slots differ (tolerance {tol})")


def engine_phase():
    K, k, T = ENGINE_K, ENGINE_k, ENGINE_T
    for sync in (True, False):
        mode = "sync" if sync else "async"
        staged = make_program(K, k, T, sync=sync)
        fused = make_program(K, k, T, sync=sync, fused=True)
        xs = record(staged, T)
        runners = {
            "staged": Runner(f"{mode}/staged", staged, xs),
            "fused": Runner(f"{mode}/fused", fused, xs),
            "reference": Runner(f"{mode}/reference", staged, xs, route="0", kernels=False),
        }
        compile_all("engine", list(runners.values()))
        res = {name: r.run() for name, r in runners.items()}
        compare(f"{mode}: staged vs fused", res["staged"], res["fused"],
                lambda: first_divergence(staged, None, fused, None, xs))
        compare(f"{mode}: staged vs reference", res["staged"], res["reference"],
                lambda: first_divergence(staged, None, staged, "0", xs))
        compare(f"{mode}: fused vs reference", res["fused"], res["reference"],
                lambda: first_divergence(fused, None, staged, "0", xs))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def drive(engine, K, k, jobs, ticks, tag):
    """Serve ``jobs`` tenants for ``ticks`` round-tagged ticks each through
    the real socket front end; every answer is checked."""
    import numpy as np

    from repro.serve import SelectionServer, ServeClient

    rng = np.random.default_rng(SEED)
    latencies = []
    srv = SelectionServer(engine, request_timeout=REQUEST_TIMEOUT_S)
    srv.start()
    try:
        with ServeClient.connect(srv.address, timeout=3 * REQUEST_TIMEOUT_S) as client:  # no retries: an error answer raises
            uids = [client.admit(K=K, k=k, rounds=ticks, seed=SEED + j) for j in range(jobs)]
            for t in range(ticks):
                for uid in uids:
                    t0 = time.perf_counter()
                    out = client.tick(uid, round=t, bits=rng.random(K) < 0.7)
                    latencies.append(time.perf_counter() - t0)
                    cohort = out["cohort"]
                    if out["round"] != t or len(cohort) != k or len(set(cohort)) != k:
                        fail(f"{tag}: job {uid} round {t} answered {len(set(cohort))} distinct of k={k}")
                    if min(cohort) < 0 or max(cohort) >= K:
                        fail(f"{tag}: job {uid} round {t} answered an id outside [0, {K})")
    finally:
        srv.close()
    if srv.stats["restarts"] or srv.stats["errors"]:
        fail(f"{tag}: engine restarts={srv.stats['restarts']} errors={srv.stats['errors']}")
    lat = np.asarray(latencies)
    emit(phase="serve", engine=tag, K=K, k=k, tenants=jobs, ticks_answered=int(srv.stats["ticks"]),
         first_tick_s=float(lat[0]), p50_tick_s=float(np.median(lat[jobs:])) if len(lat) > jobs else None,
         peak_hbm_bytes=peak_hbm())


def serve_phase():
    from repro.serve import ShardedEngine, SlotEngine

    drive(SlotEngine(K_max=SLOT_K), SLOT_K, SLOT_k, SLOT_TENANTS, SLOT_TICKS, "slots")
    drive(ShardedEngine(D=1), BIG_K, BIG_k, 1, BIG_TICKS, "sharded D=1")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chip_phase():
    from repro.launch.mesh import make_host_mesh

    mesh4, mesh1 = make_host_mesh(4), make_host_mesh(1)
    K, k, T = SHARDED_K, ENGINE_k, ENGINE_T
    for sync in (True, False):
        mode = "sync" if sync else "async"
        staged = make_program(K, k, T, sync=sync, mesh=mesh4)
        xs = record(staged, T)
        runners = {
            name: Runner(f"D=4 {mode}/{name}", make_program(K, k, T, sync=sync, fused=fused, mesh=mesh4),
                         xs, gather=4 * k, devices=4)
            for name, fused in (("staged", False), ("fused", True))
        }
        one = make_program(ENGINE_K, k, T, sync=sync, fused=True, mesh=mesh1)
        runners["one"] = Runner(f"D=1 {mode}/fused (per-chip size)", one, record(one, T), devices=1)
        del xs
        compile_all("four", list(runners.values()))
        res = {name: r.run() for name, r in runners.items()}
        compare(f"D=4 {mode}: staged vs fused", res["staged"], res["fused"])
    # cross-placement equality: packed-lag replay draws no randomness per
    # shard and `random` draws its cohort replicated, so 4 chips == 1 chip
    meshes = {"D=4": mesh4, "one chip": None}
    progs = {where: make_program(K, k, T, sync=False, mesh=mesh, scheme="random") for where, mesh in meshes.items()}
    xs = record(progs["one chip"], T)
    runners = {where: Runner(f"random async {where}", prog, xs, devices=4 if meshes[where] else 1)
               for where, prog in progs.items()}
    del xs
    compile_all("four", list(runners.values()))
    res = {where: r.run() for where, r in runners.items()}
    compare("random async: D=4 vs one chip", res["D=4"], res["one chip"], tol=0.0)
    from repro.serve import ShardedEngine

    drive(ShardedEngine(D=4), BIG_K * 4, BIG_k, 1, BIG_TICKS, "sharded D=4")


# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the K-sharded four-chip path and what it is compared with")
    args = ap.parse_args(argv)

    route = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if route not in ("", "auto"):
        fail(f"REPRO_INTERPRET={route!r} forces a kernel route; the chip smoke runs the automatic one")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} chips; JAX found {len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    emit(phase="start", chips=args.chips, kind=devices[0].device_kind, count=len(devices),
         jax=jax.__version__, compile_cache=cache)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        engine_phase()
        serve_phase()
    emit(phase="done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
