"""Median ms of the program's ``engine.launch`` span (the asynchronous dispatch of the compiled step), over every tick of the run (served cells; host clock)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.median_ms("engine.launch")
