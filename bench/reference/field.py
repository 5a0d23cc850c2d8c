"""The neural field written out plainly: weights from a seed, the
multi-resolution grid encode, the bias-free MLP, rays and compositing.

The configuration is the dict of a file under ``bench/configs``. Weights
follow the published initialisation (instant-NGP): table features
U(-1e-4, 1e-4), MLP matrices N(0, 1/fan_in), drawn from the key in this
order: table, then the MLP's input, output and hidden matrices.
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

HASH_PRIMES = (1, 2654435761, 805459861)
TABLE_INIT = 1e-4


def _split(key):
    key, sub = jax.random.split(key)
    return key, sub


def init_weights(key, cfg: dict) -> dict:
    """{"grid": (L, T, F), "mlp": {"w_in", "w_hidden", "w_out"}}."""
    g, m = cfg["grid"], cfg["mlp"]
    key, k_grid = _split(key)
    key, k_mlp = _split(key)
    grid = jax.random.uniform(
        k_grid, (g["n_levels"], 1 << g["log2_table_size"], g["n_features"]),
        minval=-TABLE_INIT, maxval=TABLE_INIT)
    in_dim, h = g["n_levels"] * g["n_features"], m["hidden_dim"]
    k_mlp, k_in = _split(k_mlp)
    k_mlp, k_out = _split(k_mlp)
    k_mlp, k_hid = _split(k_mlp)
    w_in = jax.random.normal(k_in, (in_dim, h)) / jnp.sqrt(float(in_dim))
    w_out = jax.random.normal(k_out, (h, m["out_dim"])) / jnp.sqrt(float(h))
    hidden = jnp.stack([jax.random.normal(k, (h, h)) / jnp.sqrt(float(h))
                        for k in jax.random.split(k_hid, m["n_hidden"] - 1)])
    return {"grid": grid,
            "mlp": {"w_in": w_in, "w_hidden": hidden, "w_out": w_out}}


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
