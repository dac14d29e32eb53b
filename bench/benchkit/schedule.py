"""Tick schedules for served cells: a pure function of the seed and the mix.

Open loop: tenant ``i`` (in the configuration's order, groups interleaved
round-robin) has Zipf rank ``i + 1``; its rate is the mix's aggregate rate
times its Zipf share (``rank**-s`` normalised).  Its arrivals in a window of
``seconds`` are ``round(rate * seconds)`` points drawn uniformly and sorted: a
Poisson process conditioned on its count, so every seed offers the same
number of ticks per tenant, at other times.  Closed loop: no schedule; each
tenant's next tick is due when its previous answer arrives.
"""
from __future__ import annotations

import numpy as np

__all__ = ["expand_tenants", "zipf_shares", "open_arrivals"]


def expand_tenants(groups: list) -> list:
    """``[{"count": n, ...}, ...]`` -> one dict per tenant, the groups
    interleaved round-robin (so Zipf ranks alternate between groups)."""
    queues = [[{k: v for k, v in g.items() if k != "count"} for _ in range(int(g["count"]))] for g in groups]
    out = []
    while any(queues):
        for q in queues:
            if q:
                out.append(dict(q.pop(0), index=len(out)))
    return out


def zipf_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** float(s)
    return w / w.sum()


def open_arrivals(seed: int, rate: float, zipf_s: float, n_tenants: int, seconds: float) -> list:
    """Per tenant, the sorted due times (seconds from the window's start)."""
    rng = np.random.default_rng([int(seed), 11])
    out = []
    for share in zipf_shares(n_tenants, zipf_s):
        n = int(round(rate * share * seconds))
        out.append(np.sort(rng.uniform(0.0, seconds, n)))
    return out
