"""The device side of a run: the chip check, seeds, the compile cache and
its counters, peak memory.

Nothing here touches JAX at import time; ``require_chips`` is the first call
that does.
"""
from __future__ import annotations

import os
import threading
from pathlib import Path

import numpy as np

__all__ = [
    "NoChip",
    "CACHE_DIR",
    "enable_compile_cache",
    "CompileWatch",
    "require_chips",
    "device_info",
    "memory_peak_bytes",
    "seed_key",
    "np_rng",
    "sub_seed",
]

# JAX's persistent compilation cache: a fixed directory inside the checkout,
# so the second run of a cell in one checkout finds what the first compiled.
CACHE_DIR = Path(__file__).resolve().parents[1] / ".cache" / "jax"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for: the run prints no result."""


def enable_compile_cache(path: Path = CACHE_DIR) -> str:
    """Keep every compiled program (however short its compile) in ``path``.

    The directory is always the checkout's own: a cache directory given in
    the environment would be shared between the two sides of a comparison."""
    import jax

    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


class CompileWatch:
    """Counts JAX's compile and persistent-cache events from
    ``jax.monitoring``: ``snapshot()`` before and after a phase tells what
    that phase compiled and whether the cache answered."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }
    _DURATIONS = {
        "/jax/core/compile/backend_compile_duration": "backend_compiles",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }

    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.counts = {v: 0 for v in (*self._EVENTS.values(), *self._DURATIONS.values())}
        self.seconds = {v: 0.0 for v in self._DURATIONS.values()}
        self._mon = mon
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            with self._lock:
                self.counts[name] += 1

    def _duration(self, event: str, duration_secs: float, **_kw) -> None:
        name = self._DURATIONS.get(event)
        if name is not None:
            with self._lock:
                self.counts[name] += 1
                self.seconds[name] += float(duration_secs)

    def snapshot(self) -> dict:
        with self._lock:
            return {**self.counts, **{f"{k}_s": v for k, v in self.seconds.items()}}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}

    def close(self) -> None:
        self._mon.unregister_event_listener(self._event)
        self._mon.unregister_event_duration_listener(self._duration)


def require_chips(chips: int):
    """The local TPU devices, at least ``chips`` of them; raises ``NoChip``."""
    route = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if route not in ("", "auto"):
        raise NoChip(f"REPRO_INTERPRET={route!r} forces a kernel route; the benchmark runs the automatic one")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devices)} {devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest chip (None where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def seed_key(seed: int, stream: int):
    """A JAX key for one named stream of the run, from all bits of ``seed``
    (``PRNGKey`` alone keeps only the low 32)."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, int(stream))


def np_rng(seed: int, stream: int) -> np.random.Generator:
    """A NumPy generator for one named stream of the run."""
    return np.random.default_rng([int(seed), int(stream)])


def sub_seed(seed: int, stream: int, index: int) -> int:
    """A 31-bit seed (what a job spec carries) for item ``index`` of a stream."""
    return int(np.random.SeedSequence([int(seed), int(stream), int(index)]).generate_state(1)[0] >> 1)
