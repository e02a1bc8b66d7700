"""Production mesh construction.

Functions only (no module-level jax device state): importing this module
never initializes devices, so smoke tests keep their single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_from_spec(shape, axes)


def make_mesh_from_spec(shape, axes):
    """Arbitrary mesh for scale-out (e.g. (8, 32, 16) = 4096 chips).

    Axes are ``Auto``: the sharding rules place arrays through
    ``with_sharding_constraint``, which ``jax.make_mesh``'s default
    ``Explicit`` axes refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
