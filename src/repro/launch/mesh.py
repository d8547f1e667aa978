"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state -- the 512-placeholder-device XLA flag is set only
by dryrun.py before any jax import.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def _axis_types_kw(n: int) -> dict:
    """Auto (compiler-propagated) sharding on every mesh axis."""
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_types_kw(len(axes)))


def make_mesh_for(devices: int, model_parallel: int = 16):
    """Elastic helper: best (data, model) mesh for an arbitrary chip count."""
    model = min(model_parallel, devices)
    while devices % model:
        model -= 1
    return jax.make_mesh((devices // model, model), ("data", "model"),
                         **_axis_types_kw(2))
