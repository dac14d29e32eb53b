"""Median ms of the program's ``engine.wait`` span (the host blocked on the device: the NaN/inf guard on the new log-weights), over every tick of the run (served cells; host clock)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.median_ms("engine.wait")
