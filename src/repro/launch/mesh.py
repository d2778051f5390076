"""Production meshes (TPU v5e).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first backend init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple:
    """Auto axis types: the model code places arrays with sharding
    constraints under ``jit``, not with explicit-sharding types."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False,
                         layout: str = "tp"):
    """layout='tp': data x model (tensor-parallel inner axis).
    layout='dp_only': both axes are data parallelism — the right layout for
    small models (e.g. whisper-base) whose heads/FFN can't use a 16-wide
    model axis (§Perf iteration 1)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    if layout == "dp_only":
        axes = ("pod", "data", "data2") if multi_pod else ("data", "data2")
    else:
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_debug_mesh(n_model: int = 4, n_data: int = 2):
    """Small mesh for tests running under a handful of host devices."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto(2))


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a != "model")
