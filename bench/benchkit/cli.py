"""``python3 bench/run.py``: one run of one cell.

Order of a run: resolve the cell from ``BENCHMARK.json``; on the cell's
first run in a checkout, fill the compile cache from a child process
(``prime``); turn on the checkout's compile cache; refuse without enough TPU
chips; hand the cell to its driver (``bench/drivers/<mix driver>.py``),
which sets up, opens the window, measures for ``--seconds``, reads peak
memory, frees the program's state and runs the correctness comparison; then
read the cell's metrics, each with its own reader
(``bench/metrics/<name>.py``), and print the result line.

Earlier lines of standard output are one JSON object each (``{"fact": ...}``):
set-up phases with their compile-cache hits and misses, compilations inside
the window, what the host did in the window (CPU time, page faults, context
switches, garbage collections, the busiest threads), generator lateness, the
allocator's capped share, rounds per chunk and chunk count.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import subprocess
import sys
import tempfile
import time
import traceback
from pathlib import Path

from .registry import ROOT, Registry

__all__ = ["main", "RunContext", "run_cell"]

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


def _emit(stream, obj) -> None:
    print(json.dumps(obj, allow_nan=False), file=stream, flush=True)


def _finite(x):
    """JSON has no inf/nan: a non-finite compared number is reported as a
    huge one, which fails every limit."""
    return x if math.isfinite(x) else 1e308


class RunContext:
    """What a driver sees of the harness: the cell's pieces, the clock and
    the hooks for the window, spans, the trace and facts."""

    trace_seconds = 3.0

    def __init__(self, *, registry, cell, config, mix, seed, seconds, trace, t_start, devices, watch, host,
                 out=sys.stdout):
        self.registry, self.cell, self.config, self.mix = registry, cell, config, mix
        self.seed, self.seconds, self.tracing = int(seed), float(seconds), bool(trace)
        self.trace_seconds = min(self.trace_seconds, self.seconds / 2)
        self.t_start, self.devices, self.watch, self.host, self.out = t_start, devices, watch, host, out
        self._host0 = None
        self.setup_s = None
        self.scope_map: dict = {}
        self._win = None
        self._profiling = False
        self._annotation = None
        self._trace_tmp = None
        self.trace_path = None

    # -- the cell's pieces -------------------------------------------------

    @property
    def reference(self):
        return self.registry.reference(self.config["reference"])

    # -- facts, phases, spans ----------------------------------------------

    def fact(self, **kw) -> None:
        _emit(self.out, {"fact": self.cell["name"], **kw})

    @contextlib.contextmanager
    def phase(self, name: str):
        before = self.watch.snapshot()
        t0 = time.perf_counter()
        yield
        self.fact(phase=name, s=time.perf_counter() - t0, **self.watch.delta(self.watch.snapshot(), before))

    def span(self, name: str):
        """A host span on the profiler's timeline (traced runs only)."""
        if not self._profiling:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    # -- the window ----------------------------------------------------------

    def window_open(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self._win = self.watch.snapshot()
        self.fact(phase="setup", setup_s=self.setup_s, **{f"setup_{k}": v for k, v in self._win.items()})
        self._host0 = self.host.snapshot()
        if self.tracing:
            import jax

            self._trace_tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
            jax.profiler.start_trace(self._trace_tmp.name)
            self._profiling = True
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()

    def window_close(self) -> None:
        host = self.host.delta(self.host.snapshot(), self._host0)
        if self._profiling:
            self.trace_stop()
        delta = self.watch.delta(self.watch.snapshot(), self._win)
        self.fact(phase="window", compiles_in_window=delta["backend_compiles"],
                  cache_requests_in_window=delta["cache_requests"], host=host)

    def trace_stop(self) -> None:
        if not self._profiling:
            return
        import jax

        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._profiling = False
        found = sorted(Path(self._trace_tmp.name).rglob("*.xplane.pb"))
        self.trace_path = str(found[-1]) if found else None

    def set_scope_map_from_hlo(self, hlo_text: str) -> None:
        from .trace import scope_map_from_hlo

        self.scope_map.update(scope_map_from_hlo(hlo_text))

    def read_memory(self):
        from .device import memory_peak_bytes

        return memory_peak_bytes(self.devices)

    def close(self) -> None:
        if self._trace_tmp is not None:
            self._trace_tmp.cleanup()
            self._trace_tmp = None


def _metrics(registry, cell_name, trace, run) -> dict:
    out = {}
    for m in registry.metrics_for(cell_name, trace):
        value = registry.metric(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(registry: Registry, workload: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             devices, out=sys.stdout, err=sys.stderr) -> dict:
    """Run one cell on ``devices``; returns the result line's object."""
    from .device import CompileWatch, device_info
    from .host import HostWatch

    cell = registry.cell(workload)
    config = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    driver = registry.driver(mix["driver"])
    peaks = registry.peaks(devices[0].device_kind)
    watch, host = CompileWatch(), HostWatch()
    ctx = RunContext(registry=registry, cell=cell, config=config, mix=mix, seed=seed, seconds=seconds,
                     trace=trace, t_start=t_start, devices=devices, watch=watch, host=host, out=out)
    try:
        res = driver.run(ctx)
        summary = None
        if trace and ctx.trace_path:
            from .trace import load_xplane, reduce_trace

            summary = reduce_trace(load_xplane(ctx.trace_path), ctx.scope_map)
    finally:
        ctx.close()
        watch.close()
        host.close()
    run = {
        "cell": cell, "config": config, "mix": mix, "peaks": peaks, "chips": len(devices),
        "setup_s": ctx.setup_s, "memory_peak_bytes": res["memory_peak_bytes"], "trace": summary,
        **res["record"],
    }
    checks = res["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks)
    device = device_info(devices)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    line = {
        "correct": bool(correct),
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
        "metrics": _metrics(registry, workload, trace, run),
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["top_ops"], "idle_gaps": summary["idle_gaps"]}
    for c in checks:
        print(f"check {c['name']} = {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}", file=err, flush=True)
    line["checks"] = {c["name"]: {"value": _finite(c["value"]), "limit": c["limit"]} for c in checks}
    return line


def prime(workload: str, seed: int) -> dict | None:
    """Fill the checkout's compile cache for ``workload`` in a child process.

    The first run of a cell in a checkout runs the cell once more in a
    child, with a one-second window and before this process touches JAX (a
    chip holds one process), so that this process loads every program from
    the persistent cache and compiles none.  A served run whose process had
    compiled its programs ticked 11-14% slower through its whole window, in
    six of seven runs, than one that loaded them (PERF.md); priming makes
    the first run of a checkout a run of the same kind as every later one.
    The child's time counts as this run's set-up.  Returns the child's exit
    code and seconds, or None where the cell was primed before."""
    from .device import CACHE_DIR

    marker = CACHE_DIR.parent / "primed" / workload
    if marker.exists():
        return None
    t0 = time.perf_counter()
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", workload, "--seed", str(seed),
           "--seconds", "1", "--trace", "0", "--child", "1"]
    try:
        proc = subprocess.run(cmd, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True, timeout=900)
    except subprocess.TimeoutExpired:  # the child is killed; this process compiles instead
        return {"rc": None, "s": time.perf_counter() - t0}
    if proc.returncode != 0:
        return {"rc": proc.returncode, "s": time.perf_counter() - t0, "stderr_tail": proc.stderr[-300:]}
    marker.parent.mkdir(parents=True, exist_ok=True)
    marker.touch()
    return {"rc": 0, "s": time.perf_counter() - t0}


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py", description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--child", type=int, choices=(0, 1), default=0, help=argparse.SUPPRESS)  # set by prime()
    args = ap.parse_args(argv)

    registry = Registry(ROOT)
    cell = registry.cell(args.workload)  # an unknown cell fails before the chip is touched
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({src}/repro is missing)", file=sys.stderr)
        return EXIT_NO_PROGRAM
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "2")
    if not args.child:
        primed = prime(args.workload, args.seed)
        if primed is not None:  # a failed child leaves the compiling to this process, or its refusal
            _emit(sys.stdout if primed["rc"] == 0 else sys.stderr, {"fact": args.workload, "phase": "prime", **primed})

    from .device import NoChip, enable_compile_cache, require_chips

    try:
        devices = require_chips(int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    enable_compile_cache()
    try:
        line = run_cell(registry, args.workload, args.seed, args.seconds, bool(args.trace),
                        t_start=t_start, devices=devices)
    except Exception:
        traceback.print_exc()
        return 1
    _emit(sys.stdout, line)
    return 0
