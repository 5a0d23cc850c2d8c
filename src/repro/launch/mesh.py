"""Production mesh definitions.

Functions, not module-level constants — importing this module never
touches jax device state (jax locks the device count on first use).

Every mesh here has Auto axes: the sharded steps place data with
``with_sharding_constraint`` and ``shard_map``, which ``jax.make_mesh``'s
default Explicit axes refuse."""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model); the 'pod'
    axis carries only the cross-pod DP gradient all-reduce (DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Mesh over the local devices (tests / local runs). ``data``
    defaults to every device the ``model`` axis leaves over, so a
    pixel- or batch-parallel job spans the whole host."""
    n = len(jax.devices())
    if data is None:
        data = max(1, n // model)
    if data * model > n:
        data, model = n, 1
    return make_mesh((data, model), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    return math.prod(mesh.shape.values())
