"""Selection-as-a-service driver: request queue -> batched engine step ->
per-job cohort responses.

Each FL job posts a *tick* request carrying last round's success-bit feedback;
the server drains up to J requests from the queue, packs them into one
``MultiJobEngine`` dispatch (a single compiled vmap over jobs), and answers
every request with its cohort (selected client ids + the allocation used).
Volatile clients are simulated per job with the paper's Bernoulli classes, or
— with ``--scenario <name>`` — replayed from a bit-packed trace of any
``repro.scenarios`` regime (diurnal, regional_outage, flash_crowd, ...),
recorded per job and unpacked row-by-row at enqueue time.

Reports throughput (ticks/s and client-decisions/s) and per-request latency
percentiles.  Runs genuinely on this CPU box:

    python -m repro.launch.select_serve --jobs 8 --clients 4096 --rounds 30
    python -m repro.launch.select_serve --smoke

``--async`` switches to the *compiled steady-state* path
(``run_service_compiled``): the whole serving horizon folds into one
``jax.lax.scan`` over ticks — no host round-trip per tick, engine state
donated — with overlapping in-flight rounds: each job's round outcome is a
completion-lag draw, and late-but-alive cohorts are credited ``alpha**lag``
from a bounded ``(J, S, K)`` staleness ring instead of being dropped while
the engine keeps issuing the next cohorts.  ``--staleness 0`` gives the
compiled synchronous loop (the ROADMAP "compiled service loop" item on its
own).  ``--mesh D`` serves one fleet-scale job with the **K axis sharded
over a D-device mesh** (``run_service_sharded``: the
``repro.engine.round_program`` round compiled over the horizon via
``RoundProgram.from_config`` — per-device state and flops divide by D; on a
CPU host force devices first with
``XLA_FLAGS=--xla_force_host_platform_device_count=D``).  ``--mesh D
--async`` composes the two: sharded **async** serving, the ``(S, K/D)``
staleness ring riding inside the compiled sharded loop.  Reports are
written to ``results/bench/BENCH_select_serve*.json`` so CI uploads them
with the benchmark artifacts.
"""
from __future__ import annotations

import argparse
import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.volatility import BernoulliVolatility, BinaryLag, CompletionLag, paper_success_rates
from repro.engine.multi_job import make_multi_job, multi_job_init, pack_jobs
from repro.engine.round_program import staleness_ring_step
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import ROUND_TAPS, Reporter, SketchSpec, SpanTimer

__all__ = ["run_service", "run_service_compiled", "run_service_sharded", "run_server", "main"]


def run_service(
    J: int = 8,
    K_max: int = 4096,
    rounds: int = 30,
    seed: int = 0,
    n_iters: int = 48,
    tile: int = 8192,
    scenario: str | None = None,
    reporter: Reporter | None = None,
):
    """Simulate the service loop; returns the throughput/latency report.

    Request latency is accumulated in a bucketed ``LatencyHistogram`` via a
    ``SpanTimer`` (O(n_buckets) memory — nothing is stored per request); the
    report's p50/p95/p99 come from the histogram, and with a ``reporter``
    the full bucket counts land in the JSONL run log too.
    """
    rng = np.random.default_rng(seed)
    # heterogeneous fleet: population, cohort, fairness and learning rate vary
    Ks, ks, fracs, etas = _heterogeneous_fleet(J, K_max, rng)
    cfg, k_max = pack_jobs(Ks, ks, fracs, etas, K_max=K_max)
    _, batched_step = make_multi_job(k_max, n_iters=n_iters, tile=tile)
    state = multi_job_init(cfg)

    rhos = np.stack([np.pad(paper_success_rates(Kj), (0, K_max - Kj)) for Kj in Ks])
    base_keys = jax.random.split(jax.random.PRNGKey(seed), J)

    # request queue: (enqueue_time, job_id, feedback bits)
    queue: collections.deque = collections.deque()
    spans = SpanTimer(lo=1e-6, hi=60.0)
    request_hist = spans.get("request")
    n_ticks = 0
    if scenario is None:
        xs_host = (rng.random((rounds, J, K_max)) < rhos[None]).astype(np.float32)

        def feedback(t, j):
            return xs_host[t, j]

    else:
        from repro.scenarios import make_scenario, record_trace, unpack_trace

        # one bit-packed trace per job (jobs get distinct seeds); rows are
        # expanded only at enqueue time, the dense (rounds, J, K_max) trace
        # never exists
        traces = [
            record_trace(make_scenario(scenario, Kj, rounds, seed=seed + j)[0], rounds, seed=seed + j, chunk=min(64, rounds))
            for j, Kj in enumerate(Ks)
        ]

        def feedback(t, j):
            return np.pad(unpack_trace(traces[j][t], Ks[j]), (0, K_max - Ks[j]))

    # warm-up dispatch (compile once, off the clock)
    keys0 = jax.vmap(lambda kk: jax.random.fold_in(kk, rounds))(base_keys)
    xs0 = jnp.asarray(np.stack([feedback(0, j) for j in range(J)]))
    jax.block_until_ready(batched_step(cfg, state, keys0, xs0)[0].logw)

    t_start = time.perf_counter()
    n_decisions = 0
    for t in range(rounds):
        for j in range(J):
            queue.append((time.perf_counter(), j, feedback(t, j)))
        # drain one full batch of J requests into a single engine dispatch
        batch = [queue.popleft() for _ in range(min(J, len(queue)))]
        keys = jax.vmap(lambda kk: jax.random.fold_in(kk, t))(base_keys)
        xs = jnp.asarray(np.stack([b[2] for b in batch]))
        with spans.span("dispatch", annotate=True):
            state, out = batched_step(cfg, state, keys, xs)
            jax.block_until_ready(out["idx"])
        t_done = time.perf_counter()
        cohorts = np.asarray(out["idx"])  # (J, k_max), -1 padded
        for (t_enq, j, _), cohort in zip(batch, cohorts):
            request_hist.observe(t_done - t_enq)
            n_ticks += 1
            n_decisions += Ks[j]  # one accept/reject decision per live client
            assert (cohort >= 0).sum() == ks[j], (j, cohort)
    elapsed = time.perf_counter() - t_start

    report = {
        "jobs": J,
        "K_max": K_max,
        "rounds": rounds,
        "scenario": scenario or "paper_iid(static)",
        "ticks": n_ticks,
        "ticks_per_s": round(n_ticks / elapsed, 1),
        "client_decisions_per_s": round(n_decisions / elapsed, 1),
        "latency_ms": {
            "p50": round(request_hist.quantile(0.50) * 1e3, 3),
            "p95": round(request_hist.quantile(0.95) * 1e3, 3),
            "p99": round(request_hist.quantile(0.99) * 1e3, 3),
            "max": round(request_hist.max * 1e3, 3),
        },
        "cohort_sizes": ks,
        "populations": Ks,
    }
    if reporter is not None:
        reporter.histogram("request_latency", request_hist)
        reporter.histogram("dispatch_latency", spans.get("dispatch"))
    return report


def _heterogeneous_fleet(J: int, K_max: int, rng):
    """The service's standard heterogeneous job mix (shared by both paths)."""
    Ks = [int(K_max // (2 ** (j % 3))) for j in range(J)]
    ks = [max(4, Kj // 50) for Kj in Ks]
    fracs = [float(rng.choice([0.0, 0.5, 0.8])) for _ in range(J)]
    etas = [float(rng.choice([0.3, 0.5])) for _ in range(J)]
    return Ks, ks, fracs, etas


def run_service_compiled(
    J: int = 8,
    K_max: int = 4096,
    rounds: int = 30,
    seed: int = 0,
    staleness: int = 2,
    alpha: float = 0.5,
    p_late: float = 0.7,
    lag_decay: float = 0.5,
    n_iters: int = 48,
    tile: int = 8192,
    reps: int = 3,
    reporter: Reporter | None = None,
):
    """Compiled steady-state serving: the whole horizon in ONE ``lax.scan``.

    Per tick, inside the compiled program: a batched multi-job engine dispatch
    issues every job's next cohort, a completion-lag model decides which
    selected clients return on time / late / never, on-time bits feed the
    E3CS update, and a ``(J, S, K_max)`` staleness ring credits late arrivals
    ``alpha**lag`` ticks later — rounds overlap in flight instead of the
    service blocking on stragglers.  Engine state and the ring are donated,
    so steady-state serving runs allocation-free across ticks.

    ``staleness=0`` is the compiled *synchronous* loop (same drop semantics
    as ``run_service``, no ring in the program).  Returns the throughput
    report; per-request latency percentiles don't exist here (there is no
    host queue) — the per-tick cost is the latency.
    """
    S = int(staleness)
    rng = np.random.default_rng(seed)
    Ks, ks, fracs, etas = _heterogeneous_fleet(J, K_max, rng)
    cfg, k_max = pack_jobs(Ks, ks, fracs, etas, K_max=K_max)
    _, batched_step = make_multi_job(k_max, n_iters=n_iters, tile=tile)

    rhos = jnp.asarray(np.stack([np.pad(paper_success_rates(Kj), (0, K_max - Kj)) for Kj in Ks]))
    base = BernoulliVolatility(rhos)  # (J, K_max) marginals, one draw serves the fleet tick
    lag_model = (
        CompletionLag(base, p_late=p_late, lag_decay=lag_decay, max_lag=max(S, 1)) if S else BinaryLag(base)
    )
    base_keys = jax.random.split(jax.random.PRNGKey(seed), J)

    def tick(carry, t):
        state, pending, vs, key = carry
        key, k_vol = jax.random.split(key)
        keys = jax.vmap(lambda kk: jax.random.fold_in(kk, t))(base_keys)
        lag, vs = lag_model.sample(k_vol, vs)  # (J, K_max) int32
        x = (lag == 0).astype(jnp.float32)
        state, out = batched_step(cfg, state, keys, x)
        mask = out["mask"]
        arriving, pending = staleness_ring_step(pending, mask, lag, S, alpha)
        stale = jnp.sum(arriving, axis=1)
        on_time = jnp.sum(mask * x, axis=1)
        return (state, pending, vs, key), (on_time, stale)

    ts = jnp.arange(rounds, dtype=jnp.int32)

    def _run(state, pending, vs, key):
        (state, pending, _, _), (on_time, stale) = jax.lax.scan(tick, (state, pending, vs, key), ts)
        return state, pending, on_time, stale

    # engine state + staleness ring donated: steady-state serving reuses their
    # buffers instead of reallocating (J, S, K_max) every horizon
    run = jax.jit(_run, donate_argnums=(0, 1))

    def fresh():
        return (
            multi_job_init(cfg),
            jnp.zeros((J, S, K_max), jnp.float32),
            lag_model.init_state(),
            jax.random.PRNGKey(seed + 1),
        )

    jax.block_until_ready(run(*fresh())[0].logw)  # compile off the clock
    elapsed = []
    for _ in range(reps):
        args = fresh()
        jax.block_until_ready(args[0].logw)
        t0 = time.perf_counter()
        state, pending, on_time, stale = run(*args)
        jax.block_until_ready(state.logw)
        elapsed.append(time.perf_counter() - t0)
    best = min(elapsed)
    n_decisions = rounds * sum(Ks)
    if reporter is not None:
        # per-tick fleet-wide credit series (summed over the J jobs) ->
        # the windowed stream CI diffs per PR
        reporter.metrics_stream(
            "serve_async",
            {"on_time": np.asarray(on_time).sum(1), "stale": np.asarray(stale).sum(1)},
            window=max(1, rounds // 10),
            better={"on_time": "higher", "stale": "none"},
        )
        # detector pass over the credit series: an on-time collapse mid-serve
        # lands as an ``alert`` event in this run's JSONL log
        reporter.alerts(series={"on_time": np.asarray(on_time).sum(1)})
    return {
        "mode": "compiled_async" if S else "compiled_sync",
        "jobs": J,
        "K_max": K_max,
        "rounds": rounds,
        "staleness": S,
        "alpha": alpha,
        "ticks": rounds * J,
        "ticks_per_s": round(rounds * J / best, 1),
        "client_decisions_per_s": round(n_decisions / best, 1),
        "tick_us": round(best / (rounds * J) * 1e6, 1),  # per job-tick, = 1e6/ticks_per_s
        "scan_step_us": round(best / rounds * 1e6, 1),  # per compiled step (all J jobs)
        "on_time_total": float(np.asarray(on_time).sum()),
        "stale_credit_total": float(np.asarray(stale).sum()),
        "cohort_sizes": ks,
        "populations": Ks,
    }


def run_service_sharded(
    K: int = 1_000_000,
    rounds: int = 50,
    D: int | None = None,
    k: int | None = None,
    seed: int = 0,
    block: int = 4,
    reps: int = 3,
    staleness: int = 0,
    alpha: float = 0.5,
    fused: bool = False,
    reporter: Reporter | None = None,
):
    """Compiled steady-state serving of ONE fleet-scale job with the K axis
    sharded over a device mesh (``--mesh D``).

    Stands the mesh up via ``repro.launch.mesh.make_host_mesh`` (CI forces 8
    CPU devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``)
    and folds the whole serving horizon into one ``lax.scan`` of the sharded
    round: per-client state, allocation and volatility draw live as ``(K/D,)``
    shards, cross-device traffic is one scalar ``psum`` per bisection block
    plus the ``(D·k,)`` top-k candidate gather.  Per-device memory and
    per-device flops both divide by D, which is what lets the serving loop
    hold populations the single-device path cannot.

    ``staleness=S > 0`` serves *async* rounds: outcomes are completion-lag
    draws and the ``(S, K/D)``-sharded pending-credit ring credits
    late-but-alive cohorts ``alpha**lag`` — the sharded-async composition
    that falls out of ``RoundProgram`` (the config is resolved by the same
    ``RoundProgram.from_config`` the training server uses).

    ``fused=True`` serves through the fused round path
    (``repro.kernels.round_fused``): allocation epilogue / perturb / top-k in
    one dispatch and the observe/update/credit tail in another — bit-identical
    selections, fewer passes over the ``(K/D,)`` shards.
    """
    from repro.configs.base import FLConfig
    from repro.engine.round_program import RoundProgram
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(D)
    D = mesh.devices.size
    k = k or max(8, K // 1000)
    S = int(staleness)
    fl = FLConfig(
        K=K, k=k, rounds=rounds, scheme="e3cs", quota_frac=0.5, allocator="bisect",
        volatility="bernoulli", staleness_rounds=S, staleness_alpha=alpha,
    )
    program = RoundProgram.from_config(fl, mesh=mesh, block=block, fused=fused)
    # serve with the in-scan taps AND sketch stages on: the same compiled
    # horizon that answers requests emits the ROUND_TAPS telemetry stream
    # plus the psum-merged client-axis sketch stream (fairness telemetry)
    sk_spec = SketchSpec(window=max(1, rounds // 5), n_regions=4)
    run, state0 = program.build_runner(outputs="lean", taps=True, sketch=sk_spec)
    key = jax.random.PRNGKey(seed)
    xs = jnp.zeros((rounds, 0), jnp.float32)
    jax.block_until_ready(run(state0, key, xs)[0].sel_counts)  # compile off the clock
    elapsed = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run(state0, key, xs)
        jax.block_until_ready(out[0].sel_counts)
        elapsed.append(time.perf_counter() - t0)
    best = min(elapsed)
    taps = out[-1]
    report = {
        "mode": "compiled_sharded_async" if S else "compiled_sharded",
        "mesh_devices": int(D),
        "K": K,
        "k": k,
        "rounds": rounds,
        "bisect_block": block,
        "fused": bool(fused),
        "rounds_per_s": round(rounds / best, 2),
        "client_decisions_per_s": round(rounds * K / best, 1),
        "round_us": round(best / rounds * 1e6, 1),
        "per_device_state_mb": round(4.0 * K / D / 1e6, 2),  # one (K/D,) float32 vector
        "tap_counters": {n: float(v) for n, v in taps["counters"].items()},
    }
    if S:
        state, on_time, stale, _, _ = out
        report.update({
            "staleness": S,
            "alpha": alpha,
            "on_time_total": float(np.asarray(on_time).sum()),
            "stale_credit_total": float(np.asarray(stale).sum()),
        })
    else:
        report["successes_total"] = float(np.asarray(out[1]).sum())
    if reporter is not None:
        reporter.metrics_stream(
            "serve_sharded",
            {n: np.asarray(v) for n, v in taps["series"].items()},
            window=max(1, rounds // 10),
            better=ROUND_TAPS.directions(),
        )
        # client-axis fairness telemetry + the detector pass: starvation /
        # outage / drift land as ``alert`` events in the serving run log
        fair = reporter.fairness_stream("fairness", taps["sketches"])
        reporter.alerts(
            series={n: np.asarray(v) for n, v in taps["series"].items()},
            fairness=fair,
            expected_selected=k,
        )
    return report


def run_server(args, reporter: Reporter):
    """``--serve``: stand up the real socket front end (``repro.serve``)
    instead of a self-driving loop.

    ``--mesh D`` serves K-sharded ``RoundProgram`` jobs (``ShardedEngine``);
    otherwise the vmapped multi-tenant ``SlotEngine`` handles up to the
    bucket-ladder top in jobs.  Under ``--smoke`` a built-in loopback client
    admits ``--jobs`` tenants, drives ``--rounds`` rounds each and shuts the
    server down — the CI-runnable end-to-end path; without it the server
    runs until interrupted (clients speak ``repro.serve.protocol`` /
    ``docs/serving.md``).

    ``--chaos SEED`` arms a seeded ``FaultPlan`` (engine crashes,
    checkpoint corruption, dropped connections, slow dispatches) against
    the server; the built-in smoke client drives round-tagged ticks with
    retries and rewinds on ``round_desync``, so the horizon completes
    through the injected faults — the CI chaos smoke.
    """
    import tempfile

    from repro.serve import FaultPlan, SelectionServer, ServeClient, ServeError, ShardedEngine, SlotEngine

    S = args.staleness if args.async_mode else 0
    K_max = args.clients or (512 if args.smoke else 4096)
    if args.mesh is not None:
        engine = ShardedEngine(D=args.mesh, staleness=S, alpha=args.alpha)
    else:
        engine = SlotEngine(K_max=K_max, staleness=S, alpha=args.alpha)
    plan = None
    tmp_ckpt = None
    ckpt_dir, ckpt_every = args.ckpt_dir, args.ckpt_every
    if args.chaos is not None:
        plan = FaultPlan.sample(
            args.chaos, n_steps=args.jobs * args.rounds,
            crashes=1, corruptions=1, drops=2, slow=1, slow_s=0.005,
            first_step=args.jobs + 2,
        )
        # recovery needs restore points: default a checkpoint cadence + dir
        if ckpt_dir is None:
            ckpt_dir = tmp_ckpt = tempfile.mkdtemp(prefix="serve_chaos_")
        ckpt_every = ckpt_every or max(2, args.rounds // 4)
    srv = SelectionServer(
        engine, port=args.port, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        ckpt_keep=4 if plan else 0, faults=plan,
        restart_backoff=0.01 if plan else 0.05,
    )
    srv.start()
    host, port = srv.address
    print(f"serving {engine.kind} engine (S={S}) on {host}:{port}"
          + (f" under chaos seed {args.chaos}" if plan else ""), flush=True)
    try:
        if args.smoke:
            rng = np.random.default_rng(args.seed)
            K = min(K_max, 256)
            with ServeClient.connect(srv.address, retries=8, seed=args.seed) as c:
                jobs = [c.admit(K=K, k=max(1, K // 16), seed=args.seed + j) for j in range(args.jobs)]
                cursors = {j: 0 for j in jobs}
                while any(t < args.rounds for t in cursors.values()):
                    for j in jobs:
                        t = cursors[j]
                        if t >= args.rounds:
                            continue
                        if S:
                            lag = rng.integers(0, S + 2, K)
                            feed = dict(lags=np.where(lag > S, -1, lag))
                        else:
                            feed = dict(bits=rng.random(K) < 0.7)
                        try:
                            out = c.tick(j, round=t, **feed)
                        except ServeError as e:
                            if e.code == "round_desync":
                                # recovery rolled the job back: replay from there
                                cursors[j] = int(e.response["expected"])
                                continue
                            raise
                        cursors[j] = out["round"] + 1
        else:
            while True:
                time.sleep(1.0)
    except KeyboardInterrupt:
        print("interrupt: draining", flush=True)
    finally:
        srv.close()
        srv.attach_report(reporter)
        if tmp_ckpt is not None:
            import shutil

            shutil.rmtree(tmp_ckpt, ignore_errors=True)
    report = {"address": f"{host}:{port}", "engine": engine.kind, "staleness": S}
    if plan is not None:
        fired = plan.fired()
        assert srv.stats["ticks"] >= args.jobs * args.rounds
        report.update(
            chaos_seed=args.chaos, fired=fired, restarts=srv.stats["restarts"],
            recovery_s_total=float(sum(srv.recoveries)), replayed=srv.stats["replayed"],
        )
        print(f"chaos survived: fired={fired} restarts={srv.stats['restarts']}", flush=True)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--clients", type=int, default=None,
                    help="K_max: largest job population (default 4096, or 512 under --smoke; "
                         "with --mesh: 1,000,000, or 65,536 under --smoke)")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scenario", type=str, default=None, help="repro.scenarios name to replay as feedback")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="compiled lax.scan steady-state path with overlapping in-flight rounds")
    ap.add_argument("--staleness", type=int, default=2,
                    help="async buffer depth S (with --async, alone or combined with --mesh; 0 = compiled sync)")
    ap.add_argument("--alpha", type=float, default=0.5, help="staleness decay per round of lag")
    ap.add_argument("--fused", action="store_true",
                    help="with --mesh: serve through the fused round kernel path "
                         "(repro.kernels.round_fused) — bit-identical selections, "
                         "fewer passes over the per-device shards")
    ap.add_argument("--mesh", type=int, default=None, metavar="D",
                    help="serve one K-sharded job over a D-device mesh (forced CPU devices: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=D)")
    ap.add_argument("--serve", action="store_true",
                    help="stand up the real socket front end (repro.serve) instead of a "
                         "self-driving loop; combine with --mesh for K-sharded jobs, --async "
                         "for staleness-ring serving, --smoke for a loopback-driven CI run")
    ap.add_argument("--port", type=int, default=0, help="--serve listen port (0 = ephemeral)")
    ap.add_argument("--ckpt-dir", type=str, default=None,
                    help="--serve: checkpoint directory for elastic restart")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="--serve: checkpoint every N served rounds (0 = only on drain)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="--serve: arm a seeded FaultPlan (engine crashes, checkpoint "
                         "corruption, dropped connections, slow dispatches) and prove the "
                         "horizon completes through it")
    ap.add_argument("--smoke", action="store_true", help="tiny CPU-friendly run")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.jobs, args.rounds = 4, 10
    K_max = args.clients or (512 if args.smoke else 4096)
    if args.serve:
        rep = Reporter("serve_front_cli", config=vars(args))
        report = run_server(args, rep)
    elif args.mesh is not None:
        K = args.clients or (65_536 if args.smoke else 1_000_000)
        S = args.staleness if args.async_mode else 0
        rep = Reporter("select_serve_sharded_async" if S else "select_serve_sharded", config=vars(args))
        report = run_service_sharded(
            K=K, rounds=args.rounds, D=args.mesh, seed=args.seed, staleness=S, alpha=args.alpha,
            fused=args.fused, reporter=rep,
        )
    elif args.async_mode:
        rep = Reporter("select_serve_async", config=vars(args))
        report = run_service_compiled(
            J=args.jobs, K_max=K_max, rounds=args.rounds, seed=args.seed,
            staleness=args.staleness, alpha=args.alpha, reporter=rep,
        )
    else:
        rep = Reporter("select_serve", config=vars(args))
        report = run_service(
            J=args.jobs, K_max=K_max, rounds=args.rounds, seed=args.seed, scenario=args.scenario,
            reporter=rep,
        )
    path = rep.save(report)
    with open(path) as f:
        print(f.read())  # the saved artifact IS the CLI output — one emission path


if __name__ == "__main__":
    main()
