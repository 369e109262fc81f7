"""Production mesh builders (TPU v5e target: 16x16 = 256 chips per pod)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # sharding here is GSPMD-style (with_sharding_constraint), which
    # needs Auto axes; jax.make_mesh defaults to Explicit ones
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_dev_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for subprocess-based sharding tests (8 host devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
