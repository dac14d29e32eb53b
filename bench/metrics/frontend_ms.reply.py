"""Median ms of the program's ``serve.reply`` span (from the engine thread's answer to the reply's last byte on the socket), over every tick of the run (served cells; host clock)."""

from benchkit.registry import load_sibling

_s = load_sibling(__file__, "_spans")


def read(run: dict):
    return _s.median_ms("serve.reply")
