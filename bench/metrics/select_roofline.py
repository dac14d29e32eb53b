"""Share (%) of the HBM roofline the select stage reaches: p read and the mask written per round at the chip's peak, over its device time."""

from benchkit.registry import load_sibling

_c = load_sibling(__file__, "_common")


def read(run: dict):
    return _c.roofline(run, "select")
