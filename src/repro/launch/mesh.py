"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state.  Target hardware is a
TPU v5e pod: 256 chips arranged (16 data x 16 model); multi-pod adds a
leading ``pod`` axis (2 x 16 x 16 = 512 chips).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over forced host devices for tests."""
    if pod > 1:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             (AxisType.Auto,) * 3)
    return jax.make_mesh((data, model), ("data", "model"),
                         (AxisType.Auto,) * 2)
