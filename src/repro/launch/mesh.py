"""Production mesh construction.

A *function*, not a module-level constant — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialisation; tests must see one device).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[Sequence] = None) -> Mesh:
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, found {len(devices)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            "=512 before importing jax"
        )
    return make_mesh(shape, axes, devices=devices)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh over the first ``prod(shape)`` devices, every axis ``Auto``."""
    if devices is None:
        devices = jax.devices()[:math.prod(shape)]
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )
