"""Production meshes. Importing this module never touches jax device state —
meshes are built by functions only (the dry-run sets XLA_FLAGS first).

Single pod : (16, 16)    -> ("data", "model")      = 256 chips (one v5e pod)
Multi pod  : (2, 16, 16) -> ("pod", "data", "model") = 512 chips

Every axis is ``Auto``: the models place activations with
``with_sharding_constraint``, which refuses the ``Explicit`` axes that
``jax.make_mesh`` creates by default.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """Arbitrary mesh for tests (e.g. (2, 2, 2) on 8 host devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
