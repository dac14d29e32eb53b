"""Production meshes (DESIGN.md §6).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The single-pod mesh is
(16, 16) = 256 chips with axes (data, model); the multi-pod mesh prepends a
``pod`` axis: (2, 16, 16) = 512 chips.
"""
from __future__ import annotations

from typing import Dict

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_mesh", "make_host_mesh", "axis_sizes"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small fake-device meshes, e.g. (2, 4))."""
    return jax.make_mesh(tuple(shape), tuple(axes))


def make_host_mesh(D: int | None = None, axis_name: str = "shards"):
    """1-D mesh over the first D local devices — what the K-sharded selection
    engine (``repro.engine.sharded``) runs on.

    On a CPU host, multiple devices must be forced **before jax initialises**:

        XLA_FLAGS=--xla_force_host_platform_device_count=8

    (the CI sharded smoke and ``tests/conftest.py`` both set this).  Raises
    when fewer than D devices exist: with that hint on a CPU host (the flag
    cannot be applied retroactively from here), with the chip count on an
    accelerator host.
    """
    devs = jax.devices()
    D = len(devs) if D is None else int(D)
    if D < 1 or D > len(devs):
        platform = devs[0].platform
        if platform == "cpu":
            hint = (f"on a CPU host set XLA_FLAGS=--xla_force_host_platform_device_count={max(D, 2)} "
                    "before the process starts")
        else:
            hint = f"this {platform} host has {len(devs)} chip(s); run on a host with at least {D}"
        raise RuntimeError(f"need {D} devices for the {axis_name!r} mesh but jax sees {len(devs)}; {hint}")
    return jax.sharding.Mesh(np.asarray(devs[:D]), (axis_name,))


def axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
