"""Median tick latency of a closed-loop served cell over every tick due in the window (host clock)."""

from benchkit.registry import load_sibling

_c = load_sibling(__file__, "_common")


def read(run: dict):
    return _c.tick_ms(run, 0.50)
