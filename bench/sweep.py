#!/usr/bin/env python3
"""Find an open-loop cell's knee: run it at a ladder of offered rates.

    python3 bench/sweep.py --workload <config>.<open-loop mix> --seed 5 --seconds 8 --rates 200 400 800 1600

One process, one chip: each rate is a full run of the cell (set-up, window,
correctness check) with the mix's ``rate_per_s`` replaced.  Prints one JSON
line per rate: ticks due and answered, the tick tails, the generator's
lateness and ``correct``.  The knee is the highest rate whose ticks are all
answered within the window's pace and whose tail has not started to climb;
the cell's mix runs at about four fifths of it.  Benchmark runs never run
this; it is run once when a cell is defined (``PERF.md``).
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from benchkit.cli import run_cell
    from benchkit.device import enable_compile_cache, require_chips
    from benchkit.registry import ROOT, Registry

    sys.path.insert(0, str(ROOT / "src"))
    reg = Registry(ROOT)
    devices = require_chips(int(reg.cell(args.workload)["chips"]))
    enable_compile_cache()
    traffic = reg.traffic
    for rate in args.rates:
        reg.traffic = lambda name, rate=rate: {**traffic(name), "rate_per_s": rate}
        facts, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stderr(err):
            line = run_cell(reg, args.workload, args.seed, args.seconds, False, t_start=time.perf_counter(),
                            devices=devices, out=facts)
        seen = {}
        for row in facts.getvalue().splitlines():
            seen.update(json.loads(row))
        print(json.dumps({
            "rate_per_s": rate, "correct": line["correct"], "attempted": line["attempted"],
            "failed": line["failed"], "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "answered": seen.get("ticks_answered"), "lateness_s": seen.get("generator_lateness_s"),
            "dispatches": seen.get("dispatches"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
