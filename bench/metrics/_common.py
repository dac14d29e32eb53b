"""Helpers the metric readers share (not a metric: no BENCHMARK.json entry
names a file that starts with ``_``)."""
from __future__ import annotations


def stage_ms(run: dict, stage: str):
    """Device milliseconds per round spent in one round stage, over the
    rounds the trace holds; None without a trace or without that stage."""
    trace, rounds = run.get("trace"), run.get("traced_rounds", 0)
    if not trace or not rounds or stage not in trace["stage_s"]:
        return None
    return trace["stage_s"][stage] / rounds * 1e3


def essential_bytes(run: dict, stage: str) -> float:
    """Bytes a round stage must move through HBM at the configuration's K,
    whatever implements it, in the configuration's dtypes:

    * observe (the ``unpack_bits`` kernel) — reads the packed row (K/8
      bytes), writes the ``(K,)`` float32 success bits;
    * select — reads the allocation ``p`` and writes the ``(K,)`` float32
      cohort mask (the Gumbel noise is generated, not read).
    """
    K = run["K"]
    return {"observe": K / 8 + 4 * K, "select": 4.0 * K + 4.0 * K}[stage]


def roofline(run: dict, stage: str):
    """Share (%) of the HBM roofline: the least time the stage's essential
    bytes take at the chip's peak bandwidth, over its measured device time."""
    trace, rounds = run.get("trace"), run.get("traced_rounds", 0)
    if not trace or not rounds or not trace["stage_s"].get(stage):
        return None
    least_s = essential_bytes(run, stage) * rounds / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (trace["stage_s"][stage] * run["chips"])


def tick_ms(run: dict, q: float):
    """Quantile ``q`` of every tick's latency (ms), from when it was due."""
    import numpy as np

    lat = run.get("latencies_s")
    return None if not lat else float(np.quantile(np.asarray(lat), q)) * 1e3


def dispatch_ms(run: dict):
    """Median milliseconds of one ``engine.tick`` call in the window."""
    import numpy as np

    d = run.get("dispatch_s")
    return None if not d else float(np.median(np.asarray(d))) * 1e3


def idle_share(run: dict):
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
