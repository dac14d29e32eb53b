"""MB copied between host and device per job stepped: the program's ``engine.copy_bytes`` counter over ``engine.ticks``, over 1e6 (served cells)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    v = _s.per_tick("engine.copy_bytes")
    return None if v is None else v / 1e6
