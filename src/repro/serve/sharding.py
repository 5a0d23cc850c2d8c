"""Pixel-parallel sharding for the render engine.

Rendering is embarrassingly pixel-parallel (the dry-run's field cells
already shard 2^21-pixel requests over every chip), so the engine's unit of
parallelism is the megabatch's pixel axis: ``shard_map`` splits it over the
mesh axes that the shared partitioning rules bind to the ``field_batch``
logical axis (all of them, by default — rendering wants pure DP), while
scene tables/weights, the camera, and the scene id stay replicated. This
reuses ``launch/mesh`` meshes and ``common/partitioning`` rules unchanged —
the same machinery the LM path shards with.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.common import partitioning
from repro.common.partitioning import LogicalRules


def _pixel_axes(mesh: Mesh, rules: Optional[LogicalRules] = None):
    rules = rules or partitioning.DEFAULT_RULES
    return partitioning.present_axes(mesh, rules.mesh_axes("field_batch"))


def pixel_shard_count(mesh: Mesh,
                      rules: Optional[LogicalRules] = None) -> int:
    """Number of pixel shards the engine's megabatch must divide by."""
    axes = _pixel_axes(mesh, rules)
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def check_sample_budget(settings, shards: int) -> None:
    """The static sample budget must divide across the pixel shards.

    Each shard traces the tile fn at ``tile_pixels / shards`` rays and
    gets ``sample_budget / shards`` of the evaluation budget
    (``RenderSettings.tile_budget``); a non-divisible budget would
    silently round per shard and the global budget would drift."""
    if not getattr(settings, "occupancy", False):
        return
    budget = settings.sample_budget
    if budget is not None and budget % shards != 0:
        raise ValueError(
            f"sample_budget={budget} not divisible by the mesh's "
            f"{shards} pixel shards")


def shard_tile_fn(tile_fn: Callable, mesh: Mesh,
                  rules: Optional[LogicalRules] = None,
                  with_aux: bool = False) -> Callable:
    """Wrap a multi-scene tile fn with a pixel-parallel ``shard_map``.

    ``tile_fn(stacked_params, scene_id, cam, pixel_ids, mask) -> rgb``:
    pixel_ids/mask/rgb shard over the 'field_batch' mesh axes; stacked
    params, scene id, and camera are replicated (the grid_sram residency
    model — every chip holds every scene's tables).

    With ``with_aux`` the tile fn also returns a ``(1, 3)`` live-sample
    row; each shard's row shards along its leading axis (the host sums
    the ``(shards, 3)`` result). Note the evaluation budget is
    partitioned per shard, so budget overflow sheds samples per shard
    rather than globally — exact whenever no shard overflows.
    """
    axes = _pixel_axes(mesh, rules)
    if axes is None:
        return tile_fn
    pix = P(axes)
    rep = P()
    return jax.shard_map(tile_fn, mesh=mesh,
                         in_specs=(rep, rep, rep, pix, pix),
                         out_specs=(pix, pix) if with_aux else pix,
                         check_vma=False)
