"""Benchmark harness entrypoint — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Usage::

    python -m benchmarks.run [names ...] [--smoke]

Positional ``names`` select a subset (default: everything); ``--smoke``
forces the reduced CI protocol regardless of env.  Also honors:
  REPRO_BENCH_QUICK=0   full paper-scale protocol (hours on this CPU box)
  REPRO_BENCH_ONLY=a,b  subset of benches (when no positional names given)
"""
import argparse
import os
import sys
import traceback


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    from . import async_bench, engine_scale, fig3_selection, fig4_cep, fig7_cardinality, inclusion, kernels, regret, roofline, scenarios_bench, serve_chaos, serve_front, table_training

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("names", nargs="*", help="benches to run (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="force the reduced CI protocol (overrides REPRO_BENCH_QUICK)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    quick = args.smoke or os.environ.get("REPRO_BENCH_QUICK", "1") == "1"
    benches = {
        "fig3": fig3_selection.run,
        "fig4": fig4_cep.run,
        "fig7": fig7_cardinality.run,
        "regret": regret.run,
        "inclusion": inclusion.run,
        "kernels": lambda: kernels.run(smoke=quick),
        "roofline": roofline.run,
        "tables": table_training.run,
        "engine": lambda: engine_scale.run(smoke=quick),
        "scenarios": lambda: scenarios_bench.run(smoke=quick),
        "async": lambda: async_bench.run(smoke=quick),
        "serve": lambda: serve_front.run(smoke=quick),
        "serve_chaos": lambda: serve_chaos.run(smoke=quick),
    }
    only = os.environ.get("REPRO_BENCH_ONLY")
    names = args.names or (only.split(",") if only else list(benches))
    unknown = [n for n in names if n not in benches]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; available: {', '.join(benches)}")
    failed = []
    print("name,us_per_call,derived")
    for n in names:
        try:
            benches[n]()
        except Exception as e:  # noqa: BLE001
            failed.append(n)
            print(f"{n},0,FAILED:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == '__main__':
    main()
