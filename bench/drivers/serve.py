"""Driver for served cells: the socket server and its engine in this process,
the load from a separate process.

Set-up builds the configuration's engine (``repro.serve.<class>(**args)``)
behind a ``SelectionServer`` on a loopback port, admits the tenants (job seeds
drawn from the run's seed), and starts ``benchkit/loadgen.py``, which draws
each tenant's feedback rows from the seed, connects one socket per tenant and
sends ``warmup_ticks`` ticks per tenant (the first compiles the engine's
step).  The window opens when the generator says ``READY``: it runs the mix's
open or closed loop for ``--seconds`` and waits for every tick due in the
window.  Tick latency is the client's, from when each tick was due.

Spans: each ``engine.tick`` dispatch inside the server is timed here (and
named ``bench.engine_tick`` on the profiler's timeline in traced runs).

Correctness, after the window: every answered tick of every tenant, warm-up
included, is replayed by the configuration's plain reference, teacher-forced
with the served cohorts: each cohort's lowest member may lie no further below
the reference's k-th largest score than the limit, every cohort holds k
distinct clients of the job, and each job's log-weights and round counter
after the window agree with the reference's.
"""
from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

STREAM_JOBS, STREAM_BITS = 4, 5
LOADGEN = Path(__file__).resolve().parents[1] / "benchkit" / "loadgen.py"


def _job_state(engine, uid: int, K: int):
    """The job's log-weights and round counter as the engine holds them."""
    job = engine.jobs[uid]
    if engine.kind == "slots":
        slot = job["slot"]
        return np.asarray(engine.state.logw[slot])[:K], int(np.asarray(engine.state.t)[slot])
    return np.asarray(job["state"].e3cs.logw)[:K], int(job["t"])


def _drive(ctx, srv, plan):
    """Run the load generator; returns its result and the window's bounds on
    this process's clock."""
    proc = subprocess.Popen([sys.executable, str(LOADGEN)], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True)
    try:
        proc.stdin.write(json.dumps(plan) + "\n")
        proc.stdin.flush()
        with ctx.phase("warmup"):
            ready = proc.stdout.readline()
        if not ready.startswith("READY "):
            raise RuntimeError(f"load generator failed during warm-up: {ready!r}")
        ctx.fact(**json.loads(ready[len("READY "):]))
        ctx.window_open()
        t_go = time.perf_counter()
        proc.stdin.write("GO\n")
        proc.stdin.flush()
        if ctx.tracing:
            time.sleep(ctx.trace_seconds)
            ctx.trace_stop()
        line = proc.stdout.readline()
        t_end = time.perf_counter()
        if proc.wait(timeout=120) != 0 or not line:
            raise RuntimeError(f"load generator exited with {proc.returncode}")
        return json.loads(line), t_go, t_end
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def run(ctx) -> dict:
    from benchkit.device import sub_seed
    from benchkit.schedule import expand_tenants, open_arrivals
    from repro import serve

    cfg, mix = ctx.config, ctx.mix
    engine = getattr(serve, cfg["engine"]["class"])(**cfg["engine"].get("args", {}))
    dispatch = []
    engine_tick = engine.tick

    def timed_tick(items):
        t0 = time.perf_counter()
        with ctx.span("bench.engine_tick"):
            out = engine_tick(items)
        dispatch.append((t0, time.perf_counter()))
        return out

    engine.tick = timed_tick
    srv = serve.SelectionServer(engine, **cfg.get("server", {}))
    srv.start()
    try:
        tenants = expand_tenants(cfg["tenants"])
        with ctx.phase("admit"), serve.ServeClient.connect(srv.address) as client:
            for t in tenants:
                t["seed"] = sub_seed(ctx.seed, STREAM_JOBS, t["index"])
                spec = {k: t[k] for k in ("K", "k", "rounds", "sigma_frac", "eta", "quota") if k in t}
                t["uid"] = client.admit(seed=t["seed"], **spec)
                t["bits_seed"] = [ctx.seed, STREAM_BITS, t["index"]]
                t["classes"] = mix["classes"]
        plan = {
            "address": list(srv.address), "tenants": tenants, "seconds": ctx.seconds,
            "grace_s": float(mix.get("grace_s", 60.0)), "loop": mix["loop"],
            "warmup_ticks": int(mix["warmup_ticks"]), "payload_rows": int(mix["payload_rows"]),
        }
        if mix["loop"] == "open":
            plan["arrivals"] = [a.tolist() for a in open_arrivals(
                ctx.seed, float(mix["rate_per_s"]), float(mix["zipf_s"]), len(tenants), ctx.seconds)]
        rows0 = len(srv.serve_rows)
        result, t_go, t_end = _drive(ctx, srv, plan)
        ctx.window_close()
        rows = srv.serve_rows[rows0:]
        memory = ctx.read_memory()
        state = {t["index"]: _job_state(engine, t["uid"], int(t["K"])) for t in tenants}
    finally:
        srv.close(checkpoint=False)
    stats = dict(srv.stats)
    del engine, srv

    ticks = result["ticks"]
    grace = plan["grace_s"]
    ok = [t for t in ticks if t[5] == "ok"]
    lat = [(t[4] - t[2]) if t[5] == "ok" else ctx.seconds + grace for t in ticks]
    window_dispatch = [b - a for a, b in dispatch if t_go <= a <= t_end]
    ctx.fact(ticks_due=len(ticks), ticks_sent=sum(t[5] != "not_sent" for t in ticks), ticks_answered=len(ok),
             ticks_failed=len(ticks) - len(ok),
             generator_lateness_s=result["lateness_s"], dispatches=len(window_dispatch),
             dispatch_p50_ms=float(np.median(window_dispatch)) * 1e3 if window_dispatch else None,
             answered_per_s=np.bincount([int(t[4]) for t in ok if t[4] < ctx.seconds],
                                        minlength=int(ctx.seconds)).tolist(),
             server_errors=stats["errors"], server_shed=stats["shed"], server_timeouts=stats["timeouts"])

    checks = _check(ctx, tenants, result["warmup"] + ok, state, int(plan["payload_rows"]))
    return {
        "attempted": len(ticks),
        "failed": len(ticks) - len(ok),
        "record": {
            "latencies_s": lat, "dispatch_s": window_dispatch,
            "queue_depth": [r["queue_depth"] for r in rows], "batch_jobs": [r["batch_jobs"] for r in rows],
        },
        "memory_peak_bytes": memory,
        "checks": checks,
    }


def _check(ctx, tenants, answered, state, pool_rows):
    """Teacher-forced replay of every answered tick, per tenant."""
    from benchkit.loadgen import payload_rows

    ref, args = ctx.reference, ctx.config.get("reference_args", {})
    by_tenant = {t["index"]: [] for t in tenants}
    for tick in answered:
        if tick[5] == "ok":
            by_tenant[tick[0]].append(tick)
    gap, logw_gap, bad, off = 0.0, 0.0, 0, 0
    with ctx.phase("reference"):
        for t in tenants:
            K, k = int(t["K"]), int(t["k"])
            got = sorted(by_tenant[t["index"]], key=lambda x: x[1])
            rounds = [x[1] for x in got]
            n_engine = state[t["index"]][1]
            off += abs(n_engine - len(got)) + int(rounds != list(range(len(got))))
            cohorts = []
            for x in got:
                c = np.asarray(x[6], np.int64)
                good = c.size == k and np.unique(c).size == k and c.min() >= 0 and c.max() < K
                bad += int(not good)
                c = np.unique(c[(c >= 0) & (c < K)])
                cohorts.append(np.resize(c, k) if c.size else np.zeros(k, np.int64))
            if not cohorts:
                continue
            g, lg = compare_job(ref, job_spec(t), payload_rows(t, pool_rows), [r % pool_rows for r in rounds],
                                cohorts, state[t["index"]][0], args)
            gap, logw_gap = max(gap, g), max(logw_gap, lg)
    return checks(ctx.config["limits"], gap, logw_gap, bad, off)


def job_spec(tenant: dict) -> dict:
    """What the reference needs of one tenant's job."""
    return {k: tenant[k] for k in ("K", "k", "sigma_frac", "eta", "seed")}


def compare_job(ref, job, bits, rows, cohorts, logw, args) -> tuple:
    """One job's served cohorts and log-weights after its last tick against
    the reference's teacher-forced replay of the same ticks:

    * ``cohort_gap`` — how far the lowest member of a served cohort lies
      below the reference's k-th largest score, worst tick;
    * ``logw_gap`` — the widest log-weight difference, common shift removed
      (inf where the served log-weights are not finite)."""
    g, ref_logw = ref.replay_job(job, bits, rows, cohorts, **args)
    d = np.asarray(logw, np.float64) - np.asarray(ref_logw, np.float64)
    logw_gap = float(np.max(np.abs(d - np.median(d)))) if np.all(np.isfinite(d)) else np.inf
    return float(np.max(g)), logw_gap


def checks(limits, cohort_gap, logw_gap, bad_cohorts, rounds_off) -> list:
    """The numbers ``correct`` is decided on, each with its limit
    (``bad_cohorts``: cohorts that are not k distinct clients of the job;
    ``rounds_off``: answered rounds out of order or not counted by the
    engine; both exact)."""
    return [
        {"name": "cohort_gap", "value": cohort_gap, "limit": limits["cohort_gap"]},
        {"name": "logw_gap", "value": logw_gap, "limit": limits["logw_gap"]},
        {"name": "bad_cohorts", "value": float(bad_cohorts), "limit": 0.0},
        {"name": "rounds_off", "value": float(rounds_off), "limit": 0.0},
    ]
