"""Device meshes — the one place this repo builds a `jax.sharding.Mesh`.

Every axis is `AxisType.Auto`: the sharding rules (`parallel/sharding.py`)
steer the partitioner with `with_sharding_constraint`, which only accepts
Auto axes (`jax.make_mesh` defaults to Explicit ones).

A function, never a module-level constant, so importing this module never
touches jax device state: a caller that forces a host device count sets
XLA_FLAGS before jax initializes.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
