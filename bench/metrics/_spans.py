"""Helpers the readers of the program's own spans and counters share (not a
metric: no BENCHMARK.json entry names a file that starts with ``_``).

They read the program's process-wide span recorder,
``repro.obs.trace.SPANS``, which the server and its engine record into on
every tick, traced or not; the harness runs one cell per process, so it
holds the cell's warm-up ticks and its window.  A program without that
recorder, or one that recorded no such span or counter, gives None.
"""
from __future__ import annotations


def _recorder():
    try:
        from repro.obs import trace
    except ImportError:
        return None
    return getattr(trace, "SPANS", None)


def median_ms(span: str):
    """Median milliseconds of every ``span`` the recorder holds; None once
    its ring has wrapped, since the median would then cover only the
    newest spans while the counters cover the whole run."""
    import numpy as np

    rec = _recorder()
    if rec is None or not hasattr(rec, "durations_ns") or getattr(rec, "dropped", 0):
        return None
    d = rec.durations_ns(span)
    return float(np.median(d)) * 1e-6 if d.size else None


def per_tick(counter: str):
    """The counter ``counter`` over the jobs stepped (``engine.ticks``)."""
    rec = _recorder()
    counters = getattr(rec, "counters", None) or {}
    ticks = counters.get("engine.ticks", 0)
    return counters[counter] / ticks if ticks and counter in counters else None
