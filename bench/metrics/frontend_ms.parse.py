"""Median ms of the program's ``serve.parse`` span (the frame body's read and JSON parse on the connection's handler thread, after its 4-byte header arrived), over every tick of the run (served cells; host clock)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.median_ms("serve.parse")
