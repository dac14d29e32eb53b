"""Kernel-vs-reference routing, resolved at call time.

Every Pallas entry point in this package routes through :func:`kernel_route`
so one documented environment variable controls dispatch everywhere:

``REPRO_INTERPRET``
    * unset / ``"auto"`` — per-backend default: compiled Pallas kernels on
      TPU; on CPU either the Pallas interpreter or the jnp reference,
      whichever the call site declares as its CPU default
      (``cpu_kernel_default``).
    * ``"1"`` — force the Pallas kernel path everywhere, in interpret mode
      off-TPU.  This is the bit-identity validation mode: the fused round
      path is pinned against the committed goldens under this setting.
    * ``"0"`` — force the jnp reference path everywhere (no Pallas at all);
      the escape hatch when a kernel misbehaves on some backend.

The variable is read *per call* by the thin, non-jitted wrappers (interpret
mode is then passed into the inner jit as a static argument), so flipping it
mid-process takes effect on the next call — ``tests/test_kernels.py`` pins
this.  Runners compiled by ``RoundProgram.build_runner`` bake the route in
at trace time, like every other static configuration they close over.
"""
from __future__ import annotations

import os
from typing import Optional, Tuple

import jax

__all__ = ["interpret_mode", "kernel_route"]

_ENV = "REPRO_INTERPRET"


def interpret_mode() -> Optional[bool]:
    """Tri-state read of ``REPRO_INTERPRET``: True / False / None (auto)."""
    raw = os.environ.get(_ENV, "").strip().lower()
    if raw in ("", "auto"):
        return None
    if raw in ("1", "true", "yes", "on"):
        return True
    if raw in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"{_ENV}={raw!r}: expected 1/0/auto")


def kernel_route(cpu_kernel_default: bool = True) -> Tuple[bool, bool]:
    """Resolve ``(use_kernel, interpret)`` for one kernel call.

    ``cpu_kernel_default`` is the auto-mode CPU behaviour: True runs the
    kernel through the Pallas interpreter (cheap ops where the interpreter
    is fine), False uses the jnp reference (hot paths where the interpreter
    is too slow).  On TPU the compiled kernel always runs unless
    ``REPRO_INTERPRET=0`` asks for the reference.
    """
    mode = interpret_mode()
    if mode is False:
        return False, False
    backend = jax.default_backend()
    if mode is True:
        return True, backend != "tpu"
    if backend == "tpu":
        return True, False
    return cpu_kernel_default, True
