"""Median ms of the program's ``serve.decode`` span (the tick's feedback decode: base64, unpackbits and the lag mapping), over every tick of the run (served cells; host clock)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.median_ms("serve.decode")
