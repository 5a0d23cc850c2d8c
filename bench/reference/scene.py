"""The analytic volume the trainer fits: four Gaussian blobs of density
with a colour each and a mild view-dependent highlight. It is the data of
the training traffic, written out here from its definition."""
from __future__ import annotations

import jax.numpy as jnp

BLOBS = ((0.0, 0.0, 0.0, 4.0, 28.0),       # x, y, z, 1/radius, density
         (0.55, 0.2, 0.1, 7.0, 40.0),
         (-0.4, -0.35, 0.3, 6.0, 35.0),
         (0.1, 0.5, -0.4, 8.0, 45.0))
COLOURS = ((0.9, 0.3, 0.2), (0.2, 0.8, 0.3), (0.25, 0.35, 0.9),
           (0.9, 0.8, 0.2))
LIGHT = (0.577, 0.577, 0.577)
HIGHLIGHT = 0.15


def volume(world, dirs):
    """world (R, S, 3), dirs (R, 3) -> rgb (R, S, 3), sigma (R, S)."""
    blobs = jnp.asarray(BLOBS, jnp.float32)
    d2 = jnp.sum((world[..., None, :] - blobs[:, :3]) ** 2, axis=-1)
    g = jnp.exp(-d2 * blobs[:, 3] ** 2)                     # (R, S, K)
    sigma = jnp.sum(g * blobs[:, 4], axis=-1)
    share = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    rgb = jnp.sum(share[..., None] * jnp.asarray(COLOURS, jnp.float32),
                  axis=-2)
    spec = HIGHLIGHT * jnp.maximum(
        jnp.sum(dirs * jnp.asarray(LIGHT, jnp.float32), axis=-1), 0.0)
    rgb = jnp.clip(rgb + spec[:, None, None], 0.0, 1.0)
    return rgb, sigma
