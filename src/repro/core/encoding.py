"""Input encodings — the paper's first bottleneck kernel (Section II-A).

Implements the three parametric encodings studied by the paper plus the
fixed-function encodings it references:

  * multi-resolution hashgrid   (instant-NGP, Eq. 1 hash, L=16)
  * multi-resolution densegrid  (1:1 mapping, L=8)
  * low-resolution densegrid    ("tiled", L=2, F=8, Nmin=128)
  * frequency (sin/cos) encoding        [vanilla-NeRF]
  * spherical harmonics direction encoding (degree 4 -> 16 features)

This module is the pure-JAX implementation: it is both the production XLA
path for meshes without Pallas and the oracle for the Pallas kernels in
``repro.kernels``. Tables are stored uniformly as (L, T, F) — the paper
bounds trainable encoding parameters by T*L*F (Section II-A); uniform
allocation keeps the kernel BlockSpecs and sharding rules shape-static.

The hash (Eq. 1): h(x) = (xor_i x_i * pi_i) mod T, with T a power of two so
``mod`` is an AND mask — the same modulo->shift strength reduction the NGPC
hardware applies (Section V).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.param import Boxed, uniform_init
from repro.obs.trace import annotate

# instant-NGP's spatial hash primes (pi_1 = 1 keeps coherence in x).
HASH_PRIMES = (1, 2654435761, 805459861, 3674653429)


@dataclasses.dataclass(frozen=True)
class GridConfig:
    """Parameters exactly as in the paper's Table I."""
    dim: int = 3            # input dimensionality d
    n_levels: int = 16      # L
    n_features: int = 2     # F
    log2_table_size: int = 19  # T = 2**log2_table_size
    base_resolution: int = 16  # Nmin
    growth: float = 1.51572    # b
    kind: str = "hash"      # 'hash' | 'dense' | 'tiled'

    @property
    def table_size(self) -> int:
        return 1 << self.log2_table_size

    @property
    def out_dim(self) -> int:
        return self.n_levels * self.n_features

    def level_resolution(self, level: int) -> int:
        return int(math.floor(self.base_resolution * self.growth ** level))

    def level_rows(self, level: int) -> int:
        """R: how many of the level's T rows an index can address — the
        dense grid's (res+1)^d rows where it fits in T, else all T."""
        return min(self.table_size,
                   (self.level_resolution(level) + 1) ** self.dim)

    def level_is_hashed(self, level: int) -> bool:
        """Dense 1:1 mapping while the level's grid fits in T, else hash."""
        if self.kind in ("dense", "tiled"):
            return False
        n = self.level_resolution(level)
        return (n + 1) ** self.dim > self.table_size

    def params_bound(self) -> int:
        return self.table_size * self.n_levels * self.n_features


# Table I rows -> GridConfig
def hashgrid_config(dim=3, growth=1.51572, log2_T=19) -> GridConfig:
    return GridConfig(dim=dim, n_levels=16, n_features=2, log2_table_size=log2_T,
                      base_resolution=16, growth=growth, kind="hash")


def densegrid_config(dim=3, log2_T=19) -> GridConfig:
    return GridConfig(dim=dim, n_levels=8, n_features=2, log2_table_size=log2_T,
                      base_resolution=16, growth=1.405, kind="dense")


def tiledgrid_config(dim=3, log2_T=19) -> GridConfig:
    return GridConfig(dim=dim, n_levels=2, n_features=8, log2_table_size=log2_T,
                      base_resolution=128, growth=1.0, kind="tiled")


def init_grid(key, cfg: GridConfig, dtype=jnp.float32) -> Boxed:
    """instant-NGP initializes features U(-1e-4, 1e-4)."""
    tables = uniform_init(
        key, (cfg.n_levels, cfg.table_size, cfg.n_features), dtype=dtype)
    return Boxed(tables, ("level", "table", "feature"))


def _corner_offsets(dim: int) -> np.ndarray:
    """(2^d, d) binary corner offsets of the surrounding cell."""
    return np.array(
        [[(c >> i) & 1 for i in range(dim)] for c in range(1 << dim)],
        dtype=np.int32)


def hash_index(coords: jnp.ndarray, table_size: int) -> jnp.ndarray:
    """Eq. 1. coords (..., d) int32 -> (...,) int32 in [0, T).

    T is a power of two for every configuration in the paper, so the modulo
    strength-reduces to a bitwise AND — the NGPC 'modulo as shift' trick.
    """
    dim = coords.shape[-1]
    acc = coords[..., 0].astype(jnp.uint32) * jnp.uint32(HASH_PRIMES[0])
    for i in range(1, dim):
        acc = acc ^ (coords[..., i].astype(jnp.uint32)
                     * jnp.uint32(HASH_PRIMES[i]))
    return (acc & jnp.uint32(table_size - 1)).astype(jnp.int32)


def dense_index(coords: jnp.ndarray, resolution: int,
                table_size: int) -> jnp.ndarray:
    """1:1 row-major mapping for dense/tiled levels; wraps into T."""
    dim = coords.shape[-1]
    stride = 1
    acc = jnp.zeros(coords.shape[:-1], dtype=jnp.uint32)
    for i in range(dim):
        acc = acc + coords[..., i].astype(jnp.uint32) * jnp.uint32(stride)
        stride *= resolution + 1
    # Table is T-bounded: for levels whose dense grid exceeds T the paper's
    # 'TiledGrid' wraps (tiles) the coordinates. T is a power of two.
    return (acc & jnp.uint32(table_size - 1)).astype(jnp.int32)


def encode_level(points: jnp.ndarray, table: jnp.ndarray, level: int,
                 cfg: GridConfig) -> jnp.ndarray:
    """Encode one resolution level: lookup 2^d corners + d-linear interp.

    points: (B, d) in [0, 1]; table: (T, F) -> (B, F).
    """
    res = cfg.level_resolution(level)
    pos = points.astype(jnp.float32) * res
    cell = jnp.floor(pos)
    frac = pos - cell
    cell = jnp.clip(cell.astype(jnp.int32), 0, res - 1)

    offsets = _corner_offsets(cfg.dim)  # (C, d) static
    if cfg.level_is_hashed(level):
        idx = tuple(hash_index(cell + offsets[c][None, :], cfg.table_size)
                    for c in range(offsets.shape[0]))
        shifts = ((0,),) * len(idx)
    else:
        # dense_index is row-major and masked by T: corner c lies a fixed
        # row offset from corner 0, modulo the level's rows, so one index
        # names the whole cell
        idx = (dense_index(cell, res, cfg.table_size),)
        strides = (res + 1) ** np.arange(cfg.dim)
        shifts = (tuple(int(s) for s in offsets @ strides),)
    corner_feats = gather_corners(cfg.level_rows(level), shifts, table, idx)
    out = jnp.zeros((points.shape[0], cfg.n_features), jnp.float32)
    for c, feats in enumerate(corner_feats):
        w = jnp.prod(
            jnp.where(offsets[c][None, :] == 1, frac, 1.0 - frac), axis=-1)
        out = out + w[:, None] * feats.astype(jnp.float32)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def gather_corners(rows: int, shifts: Tuple[Tuple[int, ...], ...],
                   table: jnp.ndarray, idx: Tuple[jnp.ndarray, ...]
                   ) -> Tuple[jnp.ndarray, ...]:
    """The 2^d corner rows of one level, (T, F) -> C x (B, F): index
    ``idx[k]`` names the rows ``(idx[k] + s) mod rows`` for each ``s`` of
    ``shifts[k]``, in corner order. Every index lies in ``[0, rows)``.

    A hashed level gives each corner its own index and the shift 0: a
    plain ``jnp.take`` of the table. A non-hashed level gives one index,
    its cell's corner 0, and each corner's row offset: it takes one row of
    a brick (:func:`_brick`, under a ``brick`` scope) whose row i holds
    the cell's 2^d rows side by side, so one index fetches what 2^d did.
    The values are the table's, so the result is bitwise that of the
    per-corner takes.

    Its table gradient is a sorted-row sum (:func:`row_sum`) over the
    corners' rows, not the scatter-add that differentiating ``jnp.take``
    gives: XLA's scatter pays for every update it applies, whatever the
    collisions (DESIGN.md §4, "The encode's backward on the XLA route").
    The brick has no gradient path of its own.
    """
    n_feat, feats = table.shape[1], []
    for i, s in zip(idx, shifts):
        got = jnp.take(_brick(table, rows, s), i, axis=0)
        feats.extend(got[:, k * n_feat:(k + 1) * n_feat]
                     for k in range(len(s)))
    return tuple(feats)


def _brick(table: jnp.ndarray, rows: int,
           shifts: Tuple[int, ...]) -> jnp.ndarray:
    """(T, F) -> (rows, len(shifts) F): row i holds ``table[(i + s) mod
    rows]`` for each s, side by side; the table itself for the shift 0."""
    if shifts == (0,):
        return table
    with annotate("brick"):
        return jnp.concatenate(
            [jnp.roll(table[:rows], -s, axis=0) for s in shifts], axis=-1)


def _gather_corners_fwd(rows, shifts, table, idx):
    return gather_corners(rows, shifts, table, idx), (table, idx)


def _gather_corners_bwd(rows, shifts, res, cts):
    table, idx = res
    with annotate("rowsum"):
        corner_rows = [i if s == 0 else (i + s) % rows
                       for i, ss in zip(idx, shifts) for s in ss]
        grad = row_sum(jnp.concatenate(corner_rows),
                       jnp.concatenate(cts).astype(jnp.float32), rows)
        grad = jnp.pad(grad, ((0, table.shape[0] - rows), (0, 0)))
    return grad.astype(table.dtype), None


gather_corners.defvjp(_gather_corners_fwd, _gather_corners_bwd)


def row_sum(idx: jnp.ndarray, vals: jnp.ndarray, rows: int) -> jnp.ndarray:
    """``zeros((rows, F)).at[idx].add(vals)`` without a scatter or a
    gather: (N,) int32 in ``[0, rows)``, (N, F) f32 -> (rows, F) f32.

    One zero sentinel per row is keyed ``2r+1`` after the updates' ``2i``,
    so that it sorts last in its row's run; a segmented inclusive sum over
    runs of one row leaves each row's total on its sentinel; a second sort
    puts the sentinels first, in row order. Neither sort need be stable
    (ties only order a row's sum, or the entries past the sentinels), and
    an unstable TPU sort compiles in half the time.
    """
    n_feat = vals.shape[1]
    r = jnp.arange(rows, dtype=jnp.int32)
    keys = jnp.concatenate([2 * idx, 2 * r + 1])
    zeros = jnp.zeros((rows,), vals.dtype)
    cols = [jnp.concatenate([vals[:, f], zeros]) for f in range(n_feat)]
    keys, *cols = jax.lax.sort([keys, *cols], num_keys=1, is_stable=False)
    row = keys >> 1
    cols = _segmented_sum(row, cols)
    first = jnp.where((keys & 1) == 1, row, rows)
    _, *cols = jax.lax.sort([first, *cols], num_keys=1, is_stable=False)
    return jnp.stack([c[:rows] for c in cols], axis=-1)


def _segmented_sum(seg: jnp.ndarray, cols):
    """Inclusive sum of each column over runs of equal ``seg`` (sorted),
    by doubling: after the step of shift s an entry holds the sum of the
    entries of its run within the last 2s, so ceil(log2 N) steps make it
    the run's prefix sum."""
    n, s = seg.shape[0], 1
    while s < n:
        same = seg == jnp.pad(seg[:-s], (s, 0), constant_values=-1)
        cols = [c + jnp.where(same, jnp.pad(c[:-s], (s, 0)), 0)
                for c in cols]
        s *= 2
    return cols


def grid_encode(points: jnp.ndarray, tables: jnp.ndarray,
                cfg: GridConfig) -> jnp.ndarray:
    """Full multi-resolution encoding: (B, d) -> (B, L*F).

    Levels are unrolled (<=16) — on the NGPC each level has a dedicated
    engine; on TPU the levels vectorize across the VPU within one chip while
    the *pixels* shard across chips (see DESIGN.md §2).
    """
    feats = []
    for l in range(cfg.n_levels):
        # one scope per level (DESIGN.md §8): profiles split the encode's
        # device time by level, by dense or hashed, and (a transpose keeps
        # its scopes) by forward or backward
        with annotate(level_scope(cfg, l)):
            feats.append(encode_level(points, tables[l], l, cfg))
    return jnp.concatenate(feats, axis=-1)


def level_scope(cfg: GridConfig, level: int) -> str:
    """``lvl07_hash``/``lvl00_dense``: the named scope of one level."""
    kind = "hash" if cfg.level_is_hashed(level) else "dense"
    return f"lvl{level:02d}_{kind}"


# ----------------------------------------------------------------------------
# Fixed-function encodings (paper §II-A.1)
# ----------------------------------------------------------------------------

def frequency_encode(x: jnp.ndarray, n_freqs: int = 10) -> jnp.ndarray:
    """vanilla-NeRF sin/cos encoding: (..., d) -> (..., d*2*n_freqs)."""
    freqs = (2.0 ** jnp.arange(n_freqs)) * jnp.pi
    ang = x[..., None] * freqs            # (..., d, K)
    enc = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return enc.reshape(*x.shape[:-1], x.shape[-1] * 2 * n_freqs)


def sh_encode(dirs: jnp.ndarray) -> jnp.ndarray:
    """Real spherical harmonics, degree 4 -> 16 features (instant-NGP's
    direction encoding; the paper's Color model '3-[Composite]->16+16')."""
    x, y, z = dirs[..., 0], dirs[..., 1], dirs[..., 2]
    xx, yy, zz = x * x, y * y, z * z
    xy, yz, xz = x * y, y * z, x * z
    return jnp.stack([
        0.28209479177387814 * jnp.ones_like(x),
        -0.48860251190291987 * y,
        0.48860251190291987 * z,
        -0.48860251190291987 * x,
        1.0925484305920792 * xy,
        -1.0925484305920792 * yz,
        0.94617469575755997 * zz - 0.31539156525251999,
        -1.0925484305920792 * xz,
        0.54627421529603959 * (xx - yy),
        0.59004358992664352 * y * (-3.0 * xx + yy),
        2.8906114426405538 * xy * z,
        0.45704579946446572 * y * (1.0 - 5.0 * zz),
        0.3731763325901154 * z * (5.0 * zz - 3.0),
        0.45704579946446572 * x * (1.0 - 5.0 * zz),
        1.4453057213202769 * z * (xx - yy),
        0.59004358992664352 * x * (-xx + 3.0 * yy),
    ], axis=-1)
