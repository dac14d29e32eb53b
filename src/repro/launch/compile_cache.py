"""JAX's persistent compilation cache, kept at one fixed place.

``enable_compile_cache()`` is called at the start of the command-line entry
points (``chip_smoke.py``, ``repro.launch.select_serve``, ``benchmarks.run``),
never on import.  The cache directory is part of what a later run must find
again, so it never depends on the working directory, a temp dir, a pid or
the time:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is set
  here, so nothing is written anywhere else.
* unset — ``<checkout>/.jax_cache``, resolved from this file's location.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its fixed directory; returns the
    directory in use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
