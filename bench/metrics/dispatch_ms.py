"""Median ms of one engine.tick dispatch inside the server over the window (served cells; host span around the call)."""

from benchkit.registry import load_sibling

_c = load_sibling(__file__, "_common")


def read(run: dict):
    return _c.dispatch_ms(run)
