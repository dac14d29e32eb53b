"""The host side of a run: what this process's threads, its allocator and
Python's garbage collector did between two snapshots (printed as a fact for
the measured window, so a window that ran slower than its neighbours says
where the host's time went)."""
from __future__ import annotations

import gc
import os
import resource
import time
from pathlib import Path

__all__ = ["HostWatch"]

_TICK = os.sysconf("SC_CLK_TCK")


def _threads() -> dict:
    """CPU seconds of each live thread of this process, by ``tid``: ``(name, s)``."""
    out = {}
    for task in Path("/proc/self/task").iterdir():
        try:
            stat = (task / "stat").read_text()
        except OSError:  # the thread ended while we looked
            continue
        name = stat[stat.index("(") + 1:stat.rindex(")")]
        fields = stat[stat.rindex(")") + 2:].split()
        out[task.name] = (name, (int(fields[11]) + int(fields[12])) / _TICK)  # utime + stime
    return out


class HostWatch:
    """Snapshots of the process's CPU time, page faults, context switches,
    garbage collections (count and seconds, per generation) and per-thread
    CPU time; ``delta`` tells what happened between two of them."""

    def __init__(self):
        self.gc_n = [0, 0, 0]
        self.gc_s = [0.0, 0.0, 0.0]
        self._gc_t0 = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            g = int(info["generation"])
            self.gc_n[g] += 1
            self.gc_s[g] += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def snapshot(self) -> dict:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "wall_s": time.perf_counter(), "cpu_user_s": ru.ru_utime, "cpu_sys_s": ru.ru_stime,
            "minor_faults": ru.ru_minflt, "major_faults": ru.ru_majflt,
            "voluntary_switches": ru.ru_nvcsw, "involuntary_switches": ru.ru_nivcsw,
            "gc_n": list(self.gc_n), "gc_s": list(self.gc_s), "threads": _threads(),
        }

    @staticmethod
    def delta(after: dict, before: dict, top: int = 6) -> dict:
        """The change between two snapshots; ``busiest_threads`` lists the
        threads that used most CPU in between, ``[name, s]``."""
        out = {k: after[k] - before[k] for k in after if k not in ("gc_n", "gc_s", "threads")}
        out["gc_n"] = [a - b for a, b in zip(after["gc_n"], before["gc_n"])]
        out["gc_s"] = [a - b for a, b in zip(after["gc_s"], before["gc_s"])]
        used = [(name, s - before["threads"].get(tid, (name, 0.0))[1])
                for tid, (name, s) in after["threads"].items()]
        out["threads"] = len(after["threads"])
        out["busiest_threads"] = [[n, s] for n, s in sorted(used, key=lambda x: -x[1])[:top] if s > 0]
        return out

    def close(self) -> None:
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)
