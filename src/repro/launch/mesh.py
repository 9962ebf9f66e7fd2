"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py sets
XLA_FLAGS for 512 placeholder devices).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 chips ('data','model').  Multi-pod: 2×16×16 with a
    leading 'pod' axis (DCN-connected)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small fake meshes, e.g. (2,2,2)).

    Every axis is ``Auto``: the model code places activations with
    ``with_sharding_constraint`` and lets GSPMD propagate, which explicit
    axes (``jax.make_mesh``'s default) reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
