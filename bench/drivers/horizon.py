"""Driver for horizon cells: a compiled round program replayed over a fleet.

Set-up builds ``RoundProgram.from_config`` from the configuration, compiles
its chunked runner (``build_runner(outputs="lean", carry_key=True,
taps=True, scan_length=C)``), draws one period of packed availability rows
on the device from the seed, and runs the first chunk (compile included).
The window then calls the same compiled runner chunk after chunk, state,
key and tap counters carried, the pool of rows replayed cyclically (round
``t`` reads row ``t % C``), keeping one chunk queued behind the running one.
``rounds_per_s`` is every round of every chunk the window ran over the
window's seconds, from the first call to the last result.

Correctness: once the window has closed and memory has been read, the plain
reference (``bench/reference/<config reference>.py``) replays every round
the program ran, from the same initial state, key and rows, and the two
are compared on the cohorts (selection counts per client), the log-weights
of the clients both selected alike, and the per-round successes.
"""
from __future__ import annotations

import time

import numpy as np

STREAM_ENGINE, STREAM_TRAFFIC = 1, 2


def _program(cfg: dict, devices):
    from repro.configs.base import FLConfig
    from repro.engine.round_program import RoundProgram

    mesh = None
    if len(devices) > 1:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(len(devices))
    fl = FLConfig(**cfg["fl"])
    return RoundProgram.from_config(fl, mesh=mesh, **cfg["program"])


def run(ctx) -> dict:
    import jax

    from benchkit.availability import diurnal_pool
    from benchkit.device import seed_key
    from repro.obs.taps import ROUND_TAPS

    cfg, mix = ctx.config, ctx.mix
    K, k, C = cfg["fl"]["K"], cfg["fl"]["k"], int(mix["chunk_rounds"])
    program = _program(cfg, ctx.devices)
    run_fn, state0 = program.build_runner(outputs="lean", carry_key=True, taps=True, scan_length=C)
    key0 = seed_key(ctx.seed, STREAM_ENGINE)
    pool = diurnal_pool(seed_key(ctx.seed, STREAM_TRAFFIC), K, C, mix)
    tapc0 = ROUND_TAPS.init_counters()
    with ctx.phase("compile"):
        compiled = run_fn.lower(state0, key0, tapc0, pool).compile()
    # the first chunk: the program's first steps, through the window's own call
    with ctx.phase("first_chunk"):
        out = jax.block_until_ready(compiled(state0, key0, tapc0, pool))
    del state0
    succ, caps = [np.asarray(out[3])], [np.asarray(out[5]["capped_frac"])]
    carry = (out[0], out[1], out[2])

    # -- the window ---------------------------------------------------------
    ctx.window_open()
    t0 = time.perf_counter()
    chunks, traced, pending = 0, 0, None

    def collect(o):
        with ctx.span("bench.wait_chunk"):
            jax.block_until_ready(o)
        succ.append(np.asarray(o[3]))
        caps.append(np.asarray(o[5]["capped_frac"]))

    while True:
        with ctx.span("bench.runner_call"):
            out = compiled(*carry, pool)
        carry = (out[0], out[1], out[2])
        chunks += 1
        if pending is not None:
            collect(pending)
        pending = out
        elapsed = time.perf_counter() - t0
        if ctx.tracing and not traced and elapsed >= ctx.trace_seconds:
            collect(pending)  # the trace holds whole chunks only
            pending = None
            ctx.trace_stop()
            traced = chunks
        if elapsed >= ctx.seconds:
            break
    if pending is not None:
        collect(pending)
    window_s = time.perf_counter() - t0
    ctx.window_close()

    rounds = chunks * C
    capped = np.concatenate(caps)
    ctx.fact(rounds_per_chunk=C, chunks=chunks, rounds=rounds, window_s=window_s,
             capped_frac_max=float(capped.max()), capped_rounds=int((capped > 0).sum()))
    memory = ctx.read_memory()
    if ctx.tracing:
        ctx.set_scope_map_from_hlo(compiled.as_text())

    # -- correctness, after the window: the program's state is freed first --
    state = carry[0]
    prog_counts = np.asarray(state.sel_counts)[:K]
    prog_logw = np.asarray(state.e3cs.logw)[:K]
    prog_t = int(np.asarray(state.t))
    del carry, out, pending, state, compiled
    ref = ctx.reference
    spec = ref.RoundSpec(K, k, cfg["fl"]["quota_frac"], cfg["fl"]["eta"], shards=len(ctx.devices))
    n = (chunks + 1) * C
    with ctx.phase("reference"):
        rstate, _, rsucc, rcap = ref.free_run(spec, ref.init_state(spec), key0, pool, n, C)
        rstate = jax.tree.map(np.asarray, rstate)
    checks = compare_horizon(
        k=k, rounds=n, prog_counts=prog_counts, prog_logw=prog_logw, prog_t=prog_t,
        prog_succ=np.concatenate(succ), ref_state=rstate, ref_succ=np.asarray(rsucc),
        limits=cfg["limits"],
    )
    ctx.fact(reference_rounds=n, reference_capped_rounds=int((np.asarray(rcap) > 0).sum()))
    return {
        "attempted": rounds,
        "failed": 0,
        "record": {"rounds": rounds, "window_s": window_s, "traced_rounds": traced * C,
                   "K": K, "k": k},
        "memory_peak_bytes": memory,
        "checks": checks,
    }


def compare_horizon(*, k, rounds, prog_counts, prog_logw, prog_t, prog_succ, ref_state, ref_succ, limits):
    """The numbers ``correct`` is decided on, each with its limit.

    * ``slots_moved`` — share of the ``k * rounds`` cohort slots on which the
      program's and the reference's selection counts disagree;
    * ``logw_gap`` — the widest log-weight difference over clients selected
      equally often by both, after removing the common shift (ProbAlloc is
      shift-invariant);
    * ``succ_rounds`` — share of rounds whose count of successful selected
      clients differs;
    * ``rounds_off`` — rounds the program's state counted minus rounds run
      (exact: 0).
    """
    ref_counts = np.asarray(ref_state["sel_counts"])
    moved = float(np.abs(prog_counts - ref_counts).sum()) / 2.0 / (k * rounds)
    same = prog_counts == ref_counts
    d = prog_logw[same].astype(np.float64) - np.asarray(ref_state["logw"])[same].astype(np.float64)
    gap = float(np.max(np.abs(d - np.median(d)))) if d.size else float("inf")
    if not np.all(np.isfinite(prog_logw)):
        gap = float("inf")
    succ_off = float(np.mean(prog_succ[:rounds] != ref_succ[:rounds])) if prog_succ.size >= rounds else 1.0
    return [
        {"name": "slots_moved", "value": moved, "limit": limits["slots_moved"]},
        {"name": "logw_gap", "value": gap, "limit": limits["logw_gap"]},
        {"name": "succ_rounds", "value": succ_off, "limit": limits["succ_rounds"]},
        {"name": "rounds_off", "value": float(abs(prog_t - rounds)), "limit": 0.0},
    ]
