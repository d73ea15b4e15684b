"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module touches no jax device state — required because the dry-run must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over host devices for tests (requires
    --xla_force_host_platform_device_count)."""
    if pod:
        return jax.make_mesh(
            (pod, data, model), ("pod", "data", "model"), axis_types=(AxisType.Auto,) * 3
        )
    return jax.make_mesh((data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
