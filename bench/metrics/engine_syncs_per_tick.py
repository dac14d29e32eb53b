"""Blocking device-to-host reads per job stepped: the program's ``engine.syncs`` counter over ``engine.ticks`` (served cells)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.per_tick("engine.syncs")
