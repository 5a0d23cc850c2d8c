"""Gigapixel image approximation (gia): each pixel's position
(x / W, y / H) in [0, 1)^2 through the 2-D grid and the MLP, sigmoid
colour."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import field

init_weights = field.init_weights


def render(w: dict, cfg: dict, intrinsics, c2w, ids, n_samples: int,
           precision: str):
    """Pixels (R, 3) of flat ids of an H x W image; the camera's pose
    and the sample count play no part."""
    del c2w, n_samples
    h, wd = float(intrinsics[0]), int(intrinsics[1])
    y = (ids // wd).astype(jnp.float32) / h
    x = (ids % wd).astype(jnp.float32) / float(wd)
    feats = field.encode(jnp.stack([x, y], axis=-1), w["grid"], cfg["grid"])
    return jax.nn.sigmoid(field.mlp(w["mlp"], feats, precision))
