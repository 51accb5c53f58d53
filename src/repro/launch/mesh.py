"""Device meshes — the one place this repo builds a `jax.sharding.Mesh`.

Every axis is `AxisType.Auto`: the sharding rules (`parallel/sharding.py`)
steer the partitioner with `with_sharding_constraint`, which only accepts
Auto axes (`jax.make_mesh` defaults to Explicit ones).

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization, while smoke tests want the plain
1-device CPU.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: one v5e pod = 16×16 = 256 chips as
    (data=16, model=16); two pods = 512 chips as (pod=2, data=16, model=16).
    The `pod` axis carries only data parallelism (and the hierarchical /
    compressed gradient reduction) — it crosses DCI, not ICI."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(n_data: int = 2, n_model: int = 2, n_pod: int = 0):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    if n_pod:
        return make_mesh((n_pod, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_device_count(*, multi_pod: bool = False) -> int:
    return 512 if multi_pod else 256
