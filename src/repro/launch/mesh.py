"""Mesh construction.

A function, not a module-level constant: importing this module must never
touch jax device state (a caller may still set XLA_FLAGS before jax
initializes its backend).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (tests on host devices, the chips of one host),
    over ``devices`` where given."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
