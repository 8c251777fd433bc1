"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first backend init, and only
dryrun.py is allowed to force 512 host devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips when multi_pod.  Auto
    axes: the sharding rules place activations with sharding constraints."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
