"""Peak device memory in use on the fullest chip after the window, GB (the device runtime's allocator statistics)."""


def read(run: dict):
    b = run.get("memory_peak_bytes")
    return None if b is None else b / 1e9
