"""Serving front end benchmark: tick latency and saturation over the wire.

Measures the full loopback path — client socket -> framing -> admission
queue -> streaming batcher -> vmapped slot engine -> response — not the
bare engine step, because coordinator-side latency is what a fleet
actually observes.

Rows (name,us_per_call,derived):
  serve/closed/J=...     — closed-loop saturation: J tenant jobs, each with
                           its own connection, ticking as fast as the
                           server answers; us per tick end-to-end, derived
                           carries ticks/sec and the mean coalesced batch
                           width (the batcher's whole point: width -> J as
                           clients pile up)
  serve/load/r=...       — offered-load sweep: J clients posting at a target
                           aggregate rate r ticks/sec against a small
                           admission queue; derived carries achieved rate,
                           client-observed p50/p99 ms and sheds (the
                           backpressure path under overload)

Bench JSON (gated by scripts/check_bench.py against
results/bench/baseline/BENCH_serve_front.json):
  closed_ticks_per_s     — the gated saturation scalar (*_per_s convention)
  hists.*                — client/dispatch latency histograms (reported,
                           never gated: wall-clock quantiles are too noisy
                           to diff across CI machines)
  metrics.serve          — the windowed ``serve`` tap-group stream
                           (queue_depth / batch_jobs / shed) sampled per
                           dispatch on the server, gate direction
                           ``shed: lower``

CLI:  python benchmarks/serve_front.py [--smoke]
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

import numpy as np

try:
    from .common import emit, reporter
except ImportError:  # running as a script
    from common import emit, reporter

from repro.obs import LatencyHistogram, SpanTimer
from repro.serve import SelectionServer, ServeClient, ServeError, SlotEngine


def _drive_closed(address, spec: dict, rounds: int, hist: LatencyHistogram, lock):
    """One closed-loop tenant: admit, then tick back-to-back."""
    with ServeClient.connect(address) as c:
        job = c.admit(**spec)
        bits = np.ones(spec["K"])
        for _ in range(rounds):
            t0 = time.perf_counter()
            c.tick(job, bits=bits)
            dt = time.perf_counter() - t0
            with lock:
                hist.observe(dt)


def bench_closed_loop(J: int, K: int, rounds: int, rep) -> float:
    # J timed tenants + 1 warm tenant share one slot bucket: the timed phase
    # reuses the exact compiled step the warmup built
    srv = SelectionServer(SlotEngine(K_max=K, k_cap=max(8, K // 8), buckets=(J + 1,)), spans=SpanTimer())
    hist = LatencyHistogram(lo=1e-5, hi=10.0)
    lock = threading.Lock()
    with srv:
        # warm the compiled step before timing (a throwaway tenant hits the
        # same J-bucket step the timed tenants will reuse)
        _drive_closed(srv.address, dict(K=K, k=K // 16, seed=99), 2, LatencyHistogram(), lock)
        warm_dispatches = srv.stats["dispatches"]
        t0 = time.perf_counter()
        threads = [
            threading.Thread(
                target=_drive_closed,
                args=(srv.address, dict(K=K, k=K // 16, seed=i), rounds, hist, lock),
            )
            for i in range(J)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        ticks = J * rounds
        width = ticks / max(srv.stats["dispatches"] - warm_dispatches, 1)
        srv.attach_report(rep)
    ticks_per_s = ticks / wall
    emit(
        f"serve/closed/J={J}",
        wall / ticks * 1e6,
        f"K={K};ticks_per_s={ticks_per_s:.0f};mean_batch={width:.2f}",
    )
    rep.histogram("client_closed", hist)
    rep.update(closed_ticks_per_s=ticks_per_s, closed_mean_batch=width)
    return ticks_per_s


def bench_offered_load(J: int, K: int, rates, seconds: float, rep) -> None:
    """Sweep target aggregate rates; under overload the bounded queue sheds
    rather than stretching the tail."""
    for rate in rates:
        srv = SelectionServer(
            SlotEngine(K_max=K, k_cap=max(8, K // 8), buckets=(J,)), max_queue=8
        )
        hist = LatencyHistogram(lo=1e-5, hi=10.0)
        lock = threading.Lock()
        done = 0
        shed = 0

        def drive(i):
            nonlocal done, shed
            interval = J / rate
            with ServeClient.connect(srv.address) as c:
                job = c.admit(K=K, k=K // 16, seed=i)
                bits = np.ones(K)
                deadline = time.perf_counter() + seconds
                while time.perf_counter() < deadline:
                    t0 = time.perf_counter()
                    try:
                        c.tick(job, bits=bits)
                        with lock:
                            hist.observe(time.perf_counter() - t0)
                            done += 1
                    except ServeError:
                        with lock:
                            shed += 1
                    time.sleep(max(0.0, interval - (time.perf_counter() - t0)))

        with srv:
            threads = [threading.Thread(target=drive, args=(i,)) for i in range(J)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        achieved = done / seconds
        p50 = hist.quantile(0.5) * 1e3
        p99 = hist.quantile(0.99) * 1e3
        emit(
            f"serve/load/r={rate}",
            (1.0 / max(achieved, 1e-9)) * 1e6,
            f"achieved_per_s={achieved:.0f};p50_ms={p50:.2f};p99_ms={p99:.2f};shed={shed}",
        )
        rep.histogram(f"client_load_r{rate}", hist)
        rep.update(**{f"load_r{rate}_achieved": achieved, f"load_r{rate}_shed": shed})


def run(smoke: bool = True) -> None:
    J = 4 if smoke else 16
    K = 256 if smoke else 4096
    rounds = 40 if smoke else 400
    rep = reporter("serve_front", config={"smoke": smoke, "J": J, "K": K, "rounds": rounds})
    sat = bench_closed_loop(J, K, rounds, rep)
    # sweep from comfortable to past saturation
    rates = [max(10, int(sat * f)) for f in ((0.5, 2.0) if smoke else (0.25, 0.5, 1.0, 2.0))]
    bench_offered_load(J, K, rates, seconds=1.5 if smoke else 10.0, rep=rep)
    rep.save()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run(smoke=args.smoke)


if __name__ == "__main__":
    main(sys.argv[1:])
