"""Device ms per round under round.select and round.sample, allocate excluded (device trace)."""

from benchkit.registry import load_sibling

_c = load_sibling(__file__, "_common")


def read(run: dict):
    return _c.stage_ms(run, "select")
