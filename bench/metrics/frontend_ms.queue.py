"""Median ms of the program's ``serve.queue`` span (a request's wait in the admission queue until the engine thread's dispatch takes it), over every tick of the run (served cells; host clock)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.median_ms("serve.queue")
