"""The neural field written out plainly: weights from a seed, the
multi-resolution grid encode, the bias-free MLP, rays and compositing,
and what the ray apps share: rendering through a field, the training
batch, its loss and the Adam step.

The configuration is the dict of a file under ``bench/configs``. Weights
follow the published initialisation (instant-NGP): table features
U(-1e-4, 1e-4), MLP matrices N(0, 1/fan_in), drawn from the key in this
order: table, then each MLP in the order its app's module gives, and in
each its input, output and hidden matrices.
Matmuls run at ``precision``: ``"highest"``, full float32, or ``"high"``,
three bfloat16 passes (each operand split into a bfloat16 high part and a
bfloat16 remainder, the remainders' product dropped), written out so that
it reads the same on every platform. The high part is the float32 rounded
to bfloat16's 8 bits (to nearest, ties to even) in integer arithmetic on
its bits: a round trip through bfloat16 is a pair of conversions that a
compiler allowing excess precision may drop, which would leave the
remainder 0 and one pass. ``"high"`` is the control: the
reference one step below the precision the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import scene

HASH_PRIMES = (1, 2654435761, 805459861)
TABLE_INIT = 1e-4
NEAR, FAR = 0.5, 4.5            # the ray apps' sampled interval


def _split(key):
    key, sub = jax.random.split(key)
    return key, sub


def grid_width(grid: dict) -> int:
    """The encode's output width, L * F."""
    return grid["n_levels"] * grid["n_features"]


def table(key, grid: dict):
    """The grid's tables (L, T, F), U(-1e-4, 1e-4)."""
    return jax.random.uniform(
        key, (grid["n_levels"], 1 << grid["log2_table_size"],
              grid["n_features"]), minval=-TABLE_INIT, maxval=TABLE_INIT)


def mlp_weights(key, in_dim: int, m: dict) -> dict:
    """{"w_in", "w_hidden", "w_out"} of an MLP of ``in_dim`` inputs, drawn
    from the key in the order input, output, hidden matrices."""
    h = m["hidden_dim"]
    key, k_in = _split(key)
    key, k_out = _split(key)
    key, k_hid = _split(key)
    w_in = jax.random.normal(k_in, (in_dim, h)) / jnp.sqrt(float(in_dim))
    w_out = jax.random.normal(k_out, (h, m["out_dim"])) / jnp.sqrt(float(h))
    hidden = jnp.stack([jax.random.normal(k, (h, h)) / jnp.sqrt(float(h))
                        for k in jax.random.split(k_hid, m["n_hidden"] - 1)])
    return {"w_in": w_in, "w_hidden": hidden, "w_out": w_out}


def init_weights(key, cfg: dict) -> dict:
    """{"grid": (L, T, F), "mlp": {"w_in", "w_hidden", "w_out"}}: a field
    whose one MLP reads the encode."""
    key, k_grid = _split(key)
    key, k_mlp = _split(key)
    return {"grid": table(k_grid, cfg["grid"]),
            "mlp": mlp_weights(k_mlp, grid_width(cfg["grid"]), cfg["mlp"])}


# ------------------------------------------------------------- encode
def level_resolution(grid: dict, level: int) -> int:
    return int(math.floor(grid["base_resolution"] * grid["growth"] ** level))


def _row(corner, grid: dict, level: int):
    """Table row of integer vertex coordinates (B, d): the level's grid
    laid out row-major while it fits the table, else the spatial hash
    xor_i(x_i * pi_i); both taken modulo T."""
    t = 1 << grid["log2_table_size"]
    res = level_resolution(grid, level)
    c = corner.astype(jnp.uint32)
    if grid["kind"] == "hash" and (res + 1) ** grid["dim"] > t:
        row = c[:, 0] * jnp.uint32(HASH_PRIMES[0])
        for i in range(1, grid["dim"]):
            row = row ^ (c[:, i] * jnp.uint32(HASH_PRIMES[i]))
    else:
        row = jnp.zeros(c.shape[:1], jnp.uint32)
        stride = 1
        for i in range(grid["dim"]):
            row = row + c[:, i] * jnp.uint32(stride)
            stride *= res + 1
    return (row % jnp.uint32(t)).astype(jnp.int32)


def encode(points, tables, grid: dict):
    """(B, d) points in [0, 1] -> (B, L*F): per level, the d-linear
    interpolation of the 2^d vertices around each point."""
    d = grid["dim"]
    feats = []
    for level in range(grid["n_levels"]):
        res = level_resolution(grid, level)
        pos = points * res
        base = jnp.floor(pos)
        frac = pos - base
        base = jnp.clip(base.astype(jnp.int32), 0, res - 1)
        out = jnp.zeros((points.shape[0], grid["n_features"]), jnp.float32)
        for corner in range(1 << d):
            bits = np.array([(corner >> i) & 1 for i in range(d)], np.int32)
            rows = _row(base + bits[None, :], grid, level)
            weight = jnp.prod(jnp.where(bits[None, :] == 1, frac, 1 - frac),
                              axis=-1)
            out = out + weight[:, None] * tables[level][rows]
        feats.append(out)
    return jnp.concatenate(feats, axis=-1)


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)
                ) & jnp.uint32(0xFFFF0000)
        hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def mlp(w: dict, x, precision: str):
    """ReLU hidden layers, linear output, no biases."""
    h = jax.nn.relu(matmul(x, w["w_in"], precision))
    for i in range(w["w_hidden"].shape[0]):
        h = jax.nn.relu(matmul(h, w["w_hidden"][i], precision))
    return matmul(h, w["w_out"], precision)


# --------------------------------------------------------------- rays
def look_at(eye, target=(0.0, 0.0, 0.0), up=(0.0, 0.0, 1.0)) -> np.ndarray:
    """Camera-to-world (4, 4): columns right, down, forward, eye."""
    eye, target, up = (np.asarray(v, np.float64) for v in (eye, target, up))
    fwd = (target - eye) / np.linalg.norm(target - eye)
    right = np.cross(fwd, up)
    right /= np.linalg.norm(right)
    down = np.cross(fwd, right)
    c2w = np.eye(4)
    c2w[:3, 0], c2w[:3, 1], c2w[:3, 2], c2w[:3, 3] = right, down, fwd, eye
    return c2w.astype(np.float32)


def rays(intrinsics, c2w, ids, precision: str):
    """Pinhole rays through flat pixel ids: (origins, unit dirs), (R, 3)."""
    h, w, f = (jnp.asarray(v, jnp.float32) for v in intrinsics)
    py = (ids // w.astype(jnp.int32)).astype(jnp.float32)
    px = (ids % w.astype(jnp.int32)).astype(jnp.float32)
    c2w = jnp.asarray(c2w, jnp.float32)
    d = matmul(jnp.stack([(px - w / 2 + 0.5) / f, (py - h / 2 + 0.5) / f,
                          jnp.ones_like(px)], axis=-1), c2w[:3, :3].T,
               precision)
    d = d / jnp.sqrt(jnp.sum(d * d, axis=-1, keepdims=True))
    return jnp.broadcast_to(c2w[:3, 3], d.shape), d


def samples(origins, dirs, near: float, far: float, n: int, u=0.5):
    """Stratified samples along each ray: points (R, n, 3), lengths (R, n);
    ``u`` is the offset in each stratum (0.5: its midpoint)."""
    edges = jnp.linspace(near, far, n + 1)
    lo, hi = edges[:-1], edges[1:]
    shape = (origins.shape[0], n)
    t = jnp.broadcast_to(lo[None, :] + (hi - lo)[None, :] * u, shape)
    dt = jnp.broadcast_to((hi - lo)[None, :], shape)
    return origins[:, None, :] + t[..., None] * dirs[:, None, :], dt


def to_unit(points, lo: float = -2.0, hi: float = 2.0):
    return jnp.clip((points - lo) / (hi - lo), 0.0, 1.0)


def composite(rgb, sigma, dt):
    """Emission-absorption: sum_i T_i (1 - exp(-sigma_i dt_i)) c_i with
    T_i = exp(-sum_{j<i} sigma_j dt_j)."""
    depth = sigma * dt
    trans = jnp.exp(-(jnp.cumsum(depth, axis=-1) - depth))
    weight = trans * (1 - jnp.exp(-depth))
    return jnp.sum(weight[..., None] * rgb, axis=-2)


# ---------------------------------------------------------- ray apps
def render_rays(field_at, origins, dirs, n_samples: int):
    """Pixels (R, 3) of rays through ``n_samples`` midpoint samples each;
    ``field_at(points (N, 3) in [0, 1], unit dirs (N, 3))`` gives the
    samples' (rgb (N, 3), sigma (N,))."""
    pts, dt = samples(origins, dirs, NEAR, FAR, n_samples)
    r, s = dt.shape
    rgb, sigma = field_at(to_unit(pts).reshape(r * s, 3),
                          jnp.repeat(dirs, s, axis=0))
    return composite(rgb.reshape(r, s, 3), sigma.reshape(r, s), dt)


def render_pixels(field_at, intrinsics, c2w, ids, n_samples: int,
                  precision: str):
    """Pixels (R, 3) of flat ids seen by the camera."""
    origins, dirs = rays(intrinsics, c2w, ids, precision)
    return render_rays(field_at, origins, dirs, n_samples)


def ray_batch(key, intrinsics, c2w, n_rays: int, gt_samples: int):
    """Random pixels of the training camera and their analytic colours:
    the key splits into the pixel key and the stratification key."""
    k_pix, k_strat = jax.random.split(key)
    hw = jnp.int32(int(intrinsics[0]) * int(intrinsics[1]))
    ids = jax.random.randint(k_pix, (n_rays,), 0, hw)
    origins, dirs = rays(intrinsics, c2w, ids, "highest")
    u = jax.random.uniform(k_strat, (n_rays, gt_samples))
    pts, dt = samples(origins, dirs, NEAR, FAR, gt_samples, u)
    world = to_unit(pts) * 4.0 - 2.0
    rgb, sigma = scene.volume(world, dirs)
    return origins, dirs, composite(rgb, sigma, dt)


def ray_loss(field_at, b, n_samples: int):
    """Mean squared error of a batch's rendered pixels."""
    origins, dirs, target = b
    return jnp.mean((render_rays(field_at, origins, dirs, n_samples)
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
