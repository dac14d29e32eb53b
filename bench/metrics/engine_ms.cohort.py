"""Median ms of the program's ``engine.cohort`` span (host post-processing of a tick: np.nonzero, the on-time sum, the id list), over every tick of the run (served cells; host clock)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.median_ms("engine.cohort")
