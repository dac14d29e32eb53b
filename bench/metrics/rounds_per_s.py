"""Rounds completed per second over the window (host clock)."""


def read(run: dict):
    return run["rounds"] / run["window_s"] if "rounds" in run else None
