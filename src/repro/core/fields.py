"""The four neural-graphics applications (paper Fig. 4, Table I).

Each app is `encoding -> fully-fused MLP(s)`; NeRF/NVR add the composite
direction input to a second (color) MLP. All graphs support the three
encoding types (hash / dense / tiled grid) — app x encoding = the 12
configurations of Table I.

`apply_field` has two routes. `use_pallas=True` runs encode+MLP in the
Pallas fused-field kernel (the NFP: one pallas_call, features never leave
VMEM). Otherwise XLA runs them, and `fused` picks the structure there:
`fused=True` lets XLA fuse across the two, `fused=False` is the
GPU-baseline structure, where encode materializes its output (an
optimization barrier = the DRAM round trip of Fig. 7) and the MLP reads it
back.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.param import KeyGen, unbox
from repro.core import encoding as enc
from repro.core.encoding import GridConfig
from repro.core.mlp import MLPConfig, apply_mlp, init_mlp
from repro.obs.trace import annotate
from repro.quant import api as quant_api
from repro.quant.qtypes import QuantSpec, dequantize


@dataclasses.dataclass(frozen=True)
class FieldConfig:
    """One row of Table I."""
    app: str                      # 'nerf' | 'nsdf' | 'gia' | 'nvr'
    grid: GridConfig
    density_mlp: Optional[MLPConfig] = None   # NeRF only
    mlp: MLPConfig = None                     # main model MLP
    name: str = ""
    # post-training quantization recipe (repro.quant, DESIGN.md §10);
    # None = dense params. Frozen here so it is part of the scene's
    # compiled identity — serve buckets key on the full config.
    quant: Optional[QuantSpec] = None

    @property
    def in_dim(self) -> int:
        return self.grid.dim

    @property
    def out_dim(self) -> int:
        return {"nerf": 4, "nvr": 4, "gia": 3, "nsdf": 1}[self.app]

    def with_grid(self, grid: GridConfig) -> "FieldConfig":
        """Replace the grid and recompute every MLP dim derived from it.

        The grid-facing MLP's ``in_dim`` is ``grid.out_dim`` (= L*F): for
        nerf that is the *density* MLP (the color MLP's input is
        SH(16) + density feats, grid-independent); for every other app it
        is the main MLP. Use this instead of hand-patching ``mlp.in_dim``
        after ``dataclasses.replace(cfg, grid=...)``."""
        cfg = dataclasses.replace(self, grid=grid)
        if self.app == "nerf":
            return dataclasses.replace(
                cfg, density_mlp=dataclasses.replace(
                    self.density_mlp, in_dim=grid.out_dim))
        return dataclasses.replace(
            cfg, mlp=dataclasses.replace(self.mlp, in_dim=grid.out_dim))

    def with_quant(self, quant: Optional[QuantSpec]) -> "FieldConfig":
        """The config twin of ``repro.quant.api.quantize_field``: pair the
        quantized param tree with ``cfg.with_quant(spec)`` so the serve
        engine can check params/config agreement at add_scene time."""
        return dataclasses.replace(self, quant=quant)


def _grid_for(encoding_kind: str, dim: int, growth_hash: float,
              log2_T: int) -> GridConfig:
    if encoding_kind == "hash":
        return enc.hashgrid_config(dim=dim, growth=growth_hash, log2_T=log2_T)
    if encoding_kind == "dense":
        return enc.densegrid_config(dim=dim, log2_T=log2_T)
    if encoding_kind == "tiled":
        return enc.tiledgrid_config(dim=dim, log2_T=log2_T)
    raise ValueError(encoding_kind)


def make_field_config(app: str, encoding_kind: str) -> FieldConfig:
    """Exact Table I parameterizations."""
    growth = {"nerf": 1.51572, "nsdf": 1.38191,
              "nvr": 1.275, "gia": 1.25992}[app]
    log2_T = 24 if app == "gia" else 19
    dim = 2 if app == "gia" else 3
    grid = _grid_for(encoding_kind, dim, growth, log2_T)
    if app == "nerf":
        # Density: enc -> MLP(64; layers=3) -> 16 (sigma = feat[0], as in
        # instant-NGP; Table I's '->1' is the sigma channel).
        # Color: SH(dir) 16 + density feats 16 -> MLP(64; layers=4) -> 3.
        return FieldConfig(
            app=app, grid=grid,
            density_mlp=MLPConfig(in_dim=grid.out_dim, n_hidden=3, out_dim=16),
            mlp=MLPConfig(in_dim=32, n_hidden=4, out_dim=3),
            name=f"nerf_{encoding_kind}")
    n_hidden = 4
    out = {"nsdf": 1, "gia": 3, "nvr": 4}[app]
    return FieldConfig(
        app=app, grid=grid,
        mlp=MLPConfig(in_dim=grid.out_dim, n_hidden=n_hidden, out_dim=out),
        name=f"{app}_{encoding_kind}")


def init_field(key, cfg: FieldConfig, dtype=jnp.float32) -> Dict:
    """Boxed param tree (strip with common.param.unbox)."""
    kg = KeyGen(key)
    params = {"grid": enc.init_grid(kg(), cfg.grid, dtype=dtype),
              "mlp": init_mlp(kg(), cfg.mlp, dtype=dtype)}
    if cfg.density_mlp is not None:
        params["density_mlp"] = init_mlp(kg(), cfg.density_mlp, dtype=dtype)
    return params


def _encode(points, tables, grid_cfg, fused_barrier: bool):
    feats = enc.grid_encode(points, tables, grid_cfg)
    if fused_barrier:
        # The GPU baseline's DRAM round trip between the encoding kernel and
        # the MLP kernel (paper Fig. 7): forbid XLA from fusing across it.
        feats = jax.lax.optimization_barrier(feats)
    return feats


def apply_field(params: Dict, cfg: FieldConfig, points: jnp.ndarray,
                dirs: Optional[jnp.ndarray] = None,
                fused: bool = True,
                use_pallas: bool = False) -> jnp.ndarray:
    """Evaluate the field at points (B, d) [+ dirs (B, 3) for nerf/nvr].

    Returns: nerf/nvr -> (B, 4) [rgb, sigma]; gia -> (B, 3); nsdf -> (B, 1).
    """
    if use_pallas:
        from repro.kernels.fused_field import ops as ff_ops
        return ff_ops.apply_field_fused(params, cfg, points, dirs)

    # quantized scenes (repro.quant sibling-leaf convention): the XLA
    # route dequantizes the whole table up front with the SAME
    # qtypes.dequantize formula the kernels apply per gather — the
    # quality oracle the Pallas quantized route is tested against
    tables = params["grid"]
    if "grid_scale" in params:
        tables = dequantize(tables, params["grid_scale"])
    dmlp = (quant_api.maybe_dequant_mlp(params["density_mlp"])
            if "density_mlp" in params else None)
    mlp_p = quant_api.maybe_dequant_mlp(params["mlp"])

    # phase scopes (DESIGN.md §8): XLA profiles / HLO metadata carry the
    # encode|mlp taxonomy (and, inside encode, one scope per level); nerf's
    # sub-scopes are one word each, so the enclosing phase still names them
    barrier = not fused
    if cfg.app == "nerf":
        with annotate("encode"):
            h = _encode(points, tables, cfg.grid, barrier)
        with annotate("mlp"), annotate("density_mlp"):
            dfeat = apply_mlp(dmlp, h, cfg.density_mlp)
            sigma = jnp.exp(dfeat[:, :1])      # instant-NGP exp activation
        with annotate("encode"), annotate("sh_encode"):
            sh = enc.sh_encode(dirs)
        with annotate("mlp"), annotate("color_mlp"):
            color_in = jnp.concatenate([sh, dfeat], axis=-1)
            rgb = jax.nn.sigmoid(apply_mlp(mlp_p, color_in,
                                           cfg.mlp))
        return jnp.concatenate([rgb, sigma], axis=-1)

    with annotate("encode"):
        h = _encode(points, tables, cfg.grid, barrier)
    with annotate("mlp"):
        out = apply_mlp(mlp_p, h, cfg.mlp)
    if cfg.app == "gia":
        return jax.nn.sigmoid(out)
    if cfg.app == "nvr":
        rgb = jax.nn.sigmoid(out[:, :3])
        sigma = jnp.exp(out[:, 3:])
        return jnp.concatenate([rgb, sigma], axis=-1)
    return out  # nsdf: signed distance


def field_param_count(cfg: FieldConfig) -> int:
    n = cfg.grid.params_bound()
    def mlp_n(m: MLPConfig):
        return (m.in_dim * m.hidden_dim
                + (m.n_hidden - 1) * m.hidden_dim * m.hidden_dim
                + m.hidden_dim * m.out_dim)
    n += mlp_n(cfg.mlp)
    if cfg.density_mlp is not None:
        n += mlp_n(cfg.density_mlp)
    return n
