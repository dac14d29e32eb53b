"""Share (%) of the HBM roofline the observe stage's unpack_bits kernel reaches: K/8 bytes read and 4K written per round at the chip's peak, over its device time."""

from benchkit.registry import load_sibling

_c = load_sibling(__file__, "_common")


def read(run: dict):
    return _c.roofline(run, "observe")
