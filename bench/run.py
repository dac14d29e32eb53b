#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload fleet_1e7.diurnal_horizon --seed 7 --seconds 20 --trace 0

The cell, its configuration, its traffic mix and its metrics are read from
``BENCHMARK.json`` at the root of the checkout and from the files under
``bench/`` named there.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and with
``--trace 1`` a ``breakdown``); the numbers the correctness check compared are
printed beside their limits as the last lines of standard error and under the
``checks`` key, last in the result line.  Without a TPU, with fewer chips than
the cell asks for, or without the program under ``src/``, the command exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here

import sys  # noqa: E402

from benchkit.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t_start=T_START))
