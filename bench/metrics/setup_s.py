"""Seconds from process start to the window's first round or tick (host clock)."""


def read(run: dict):
    return run["setup_s"]
