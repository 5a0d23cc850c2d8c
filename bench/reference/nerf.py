"""NeRF with the multi-resolution grid (arXiv:2303.05735 Table I; the
model is instant-NGP's, arXiv:2201.05989): the encode feeds a density MLP,
sigma = exp(its output 0); the colour MLP reads the ray direction's
spherical harmonics beside the density MLP's whole output and gives rgb
through a sigmoid. Rays, midpoint samples, compositing and the training
batch are ``field.py``'s, as for nvr.

Departures from the published description, as the configuration reads it:

* Table I's "layers=3" (density) and "layers=4" (colour) are read as that
  many ReLU layers of width 64, the input layer among them, then a linear
  output; no biases (tiny-cuda-nn's fully fused MLPs have none).
* Table I's density output "1" is read as instant-NGP's 16 outputs, of
  which the first is sigma's and all 16 go on to the colour MLP.
* sigma is ``exp``, not instant-NGP's truncated exponential (the same
  forward); the direction is the unit ray direction itself (instant-NGP
  stores it mapped to [0, 1] and maps it back before the basis).
* Samples are 32 midpoints on [0.5, 4.5] with no occupancy grid, and the
  scene box [-2, 2]^3 maps to the unit cube: the serving path's samples.

Weights are drawn as the program draws them: table, colour MLP, density
MLP.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import field

batch = field.ray_batch
adam = field.adam


# ------------------------------------------------------------ direction
def _norm(l: int, m: int) -> float:
    """The real basis's normalisation: K_l^|m|, times sqrt(2) where m is
    not 0, with K_l^m = sqrt((2l + 1) / (4 pi) * (l - m)! / (l + m)!)."""
    k = math.sqrt((2 * l + 1) / (4 * math.pi) * math.factorial(l - abs(m))
                  / math.factorial(l + abs(m)))
    return k * math.sqrt(2.0) if m else k


def sh(dirs, degree: int):
    """The real spherical harmonics of unit directions (N, 3) in the bands
    l < ``degree``, m = -l..l in order: (N, degree^2).

    Y_l^m = norm(l, m) P_l^|m|(cos theta) trig(|m| phi), with trig cos
    for m > 0 and sin for m < 0, and the Condon-Shortley phase in P. With
    z = cos theta and x + iy = sin theta e^(i phi), P_l^|m| carries
    sin^|m| theta, and sin^m theta cos(m phi), sin^m theta sin(m phi) are
    the real and imaginary parts of (x + iy)^m: each term below is
    P_l^|m| times one of those, written in x, y, z."""
    if not 1 <= degree <= 4:
        raise ValueError(f"bands up to l = 3 are written out, not {degree}")
    x, y, z = dirs[:, 0], dirs[:, 1], dirs[:, 2]
    re2, im2 = x * x - y * y, 2 * x * y                # (x + iy)^2
    re3, im3 = x * (x * x - 3 * y * y), y * (3 * x * x - y * y)
    p = {(0, 0): jnp.ones_like(x),
         (1, -1): -y, (1, 0): z, (1, 1): -x,
         (2, -2): 3 * im2, (2, -1): -3 * z * y, (2, 0): (3 * z * z - 1) / 2,
         (2, 1): -3 * z * x, (2, 2): 3 * re2,
         (3, -3): -15 * im3, (3, -2): 15 * z * im2,
         (3, -1): -1.5 * (5 * z * z - 1) * y, (3, 0): z * (5 * z * z - 3) / 2,
         (3, 1): -1.5 * (5 * z * z - 1) * x, (3, 2): 15 * z * re2,
         (3, 3): -15 * re3}
    return jnp.stack([_norm(l, m) * p[l, m] for l in range(degree)
                      for m in range(-l, l + 1)], axis=-1)


# ---------------------------------------------------------------- field
def colour_width(cfg: dict) -> int:
    """The colour MLP's input: the basis's terms and the density output."""
    return cfg["sh_degree"] ** 2 + cfg["density_mlp"]["out_dim"]


def init_weights(key, cfg: dict) -> dict:
    """{"grid", "mlp" (colour), "density_mlp"}."""
    key, k_grid = jax.random.split(key)
    key, k_colour = jax.random.split(key)
    key, k_density = jax.random.split(key)
    g = cfg["grid"]
    return {"grid": field.table(k_grid, g),
            "mlp": field.mlp_weights(k_colour, colour_width(cfg),
                                     cfg["mlp"]),
            "density_mlp": field.mlp_weights(k_density, field.grid_width(g),
                                             cfg["density_mlp"])}


def field_at(w: dict, cfg: dict, points, dirs, precision: str):
    h = field.mlp(w["density_mlp"],
                  field.encode(points, w["grid"], cfg["grid"]), precision)
    colour_in = jnp.concatenate([sh(dirs, cfg["sh_degree"]), h], axis=-1)
    rgb = jax.nn.sigmoid(field.mlp(w["mlp"], colour_in, precision))
    return rgb, jnp.exp(h[:, 0])


def render(w: dict, cfg: dict, intrinsics, c2w, ids, n_samples: int,
           precision: str):
    """Pixels (R, 3) of flat ids seen by the camera."""
    return field.render_pixels(
        lambda p, d: field_at(w, cfg, p, d, precision), intrinsics, c2w, ids,
        n_samples, precision)


def loss(w: dict, cfg: dict, b, n_samples: int, precision: str):
    return field.ray_loss(lambda p, d: field_at(w, cfg, p, d, precision), b,
                          n_samples)
