"""Neural volume rendering (nvr): rays, 32 midpoint samples per ray, the
field at each sample (sigmoid rgb, exp density) and emission-absorption
compositing; and the training step against the analytic volume."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import field, scene

NEAR, FAR = 0.5, 4.5


def field_at(w: dict, cfg: dict, points, precision: str):
    out = field.mlp(w["mlp"], field.encode(points, w["grid"], cfg["grid"]),
                    precision)
    return jax.nn.sigmoid(out[:, :3]), jnp.exp(out[:, 3])


def render_rays(w: dict, cfg: dict, origins, dirs, n_samples: int,
                precision: str):
    pts, dt = field.samples(origins, dirs, NEAR, FAR, n_samples)
    r, s = dt.shape
    rgb, sigma = field_at(w, cfg, field.to_unit(pts).reshape(r * s, 3),
                          precision)
    return field.composite(rgb.reshape(r, s, 3), sigma.reshape(r, s), dt)


def render(w: dict, cfg: dict, intrinsics, c2w, ids, n_samples: int,
           precision: str):
    """Pixels (R, 3) of flat ids seen by the camera."""
    origins, dirs = field.rays(intrinsics, c2w, ids, precision)
    return render_rays(w, cfg, origins, dirs, n_samples, precision)


# ------------------------------------------------------------- training
def batch(key, intrinsics, c2w, n_rays: int, gt_samples: int):
    """Random pixels of the training camera and their analytic colours:
    the key splits into the pixel key and the stratification key."""
    k_pix, k_strat = jax.random.split(key)
    hw = jnp.int32(int(intrinsics[0]) * int(intrinsics[1]))
    ids = jax.random.randint(k_pix, (n_rays,), 0, hw)
    origins, dirs = field.rays(intrinsics, c2w, ids, "highest")
    u = jax.random.uniform(k_strat, (n_rays, gt_samples))
    pts, dt = field.samples(origins, dirs, NEAR, FAR, gt_samples, u)
    world = field.to_unit(pts) * 4.0 - 2.0
    rgb, sigma = scene.volume(world, dirs)
    return origins, dirs, field.composite(rgb, sigma, dt)


def loss(w: dict, cfg: dict, b, n_samples: int, precision: str):
    origins, dirs, target = b
    return jnp.mean((render_rays(w, cfg, origins, dirs, n_samples, precision)
                     - target) ** 2)


def adam(w, grads, mu, nu, step: int, opt: dict):
    """One Adam step (bias-corrected, no weight decay); ``step`` counts
    from 1."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    w = jax.tree.map(
        lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
                         ).astype(p.dtype), w, mu, nu)
    return w, mu, nu
