"""Share (%) of the traced window in which no operation ran on the chip (every cell; one reader for
idle_share.horizon, idle_share.serve and any later idle_share.<suffix>)."""

from benchkit.registry import load_sibling

_c = load_sibling(__file__, "_common")


def read(run: dict):
    return _c.idle_share(run)
