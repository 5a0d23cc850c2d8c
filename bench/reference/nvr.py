"""Neural volume rendering (nvr): rays, 32 midpoint samples per ray, the
field at each sample (sigmoid rgb, exp density) and emission-absorption
compositing; and the training step against the analytic volume."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import field

init_weights = field.init_weights
batch = field.ray_batch
adam = field.adam


def field_at(w: dict, cfg: dict, points, precision: str):
    out = field.mlp(w["mlp"], field.encode(points, w["grid"], cfg["grid"]),
                    precision)
    return jax.nn.sigmoid(out[:, :3]), jnp.exp(out[:, 3])


def render(w: dict, cfg: dict, intrinsics, c2w, ids, n_samples: int,
           precision: str):
    """Pixels (R, 3) of flat ids seen by the camera."""
    return field.render_pixels(
        lambda p, d: field_at(w, cfg, p, precision), intrinsics, c2w, ids,
        n_samples, precision)


def loss(w: dict, cfg: dict, b, n_samples: int, precision: str):
    return field.ray_loss(lambda p, d: field_at(w, cfg, p, precision), b,
                          n_samples)
