"""Per-kernel shape/dtype sweeps, asserting allclose against the pure-jnp
ref.py oracles (interpret=True executes the Pallas body on CPU)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.param import unbox
from repro.core import encoding as enc, render
from repro.core.mlp import MLPConfig, init_mlp
from repro.kernels.common import (DEFAULT_VMEM_BUDGET_BYTES,
                                  pick_level_group, table_block_bytes)
from repro.kernels.fused_field import ops as ff_ops, ref as ff_ref
from repro.kernels.fused_mlp import ops as mlp_ops, ref as mlp_ref
from repro.kernels.hashgrid import ops as hg_ops, ref as hg_ref
from repro.kernels.hashgrid.hashgrid import table_block_spec
from repro.kernels.ray_march import ops as rm_ops


# ------------------------------------------------------------- hashgrid
def _small_grid_cfg(kind, dim, log2_T=11, n_levels=4):
    """Interpret-mode cost is linear in L and the kernel's per-level math
    is level-count-invariant (bit-identity test below), so the fast-tier
    oracle sweeps run few levels; paper-L coverage is in the slow tier.
    log2_T=13 for 'hash' keeps a dense-coarse + hashed-fine level mix."""
    mk = {"hash": enc.hashgrid_config, "dense": enc.densegrid_config,
          "tiled": enc.tiledgrid_config}[kind]
    cfg = dataclasses.replace(mk(dim=dim), log2_table_size=log2_T)
    return dataclasses.replace(
        cfg, n_levels=min(n_levels, cfg.n_levels))


@pytest.mark.parametrize("kind,dim", [("hash", 3), ("hash", 2),
                                      ("dense", 3), ("tiled", 2),
                                      ("tiled", 3)])
@pytest.mark.parametrize("n", [64, 1000])
def test_hashgrid_vs_ref(kind, dim, n):
    cfg = _small_grid_cfg(kind, dim, log2_T=13 if kind == "hash" else 11)
    if kind == "hash" and dim == 3:   # the shrunk cfg still mixes
        assert {cfg.level_is_hashed(l)          # dense-coarse/hashed-fine
                for l in range(cfg.n_levels)} == {False, True}
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value
    pts = jax.random.uniform(jax.random.PRNGKey(1), (n, dim))
    out_k = hg_ops.encode(pts, tables, cfg, block_b=256)
    out_r = hg_ref.encode_ref(pts, tables, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("kind,dim", [("hash", 3), ("dense", 3),
                                      ("tiled", 2)])
def test_hashgrid_vs_ref_paper_levels(kind, dim):
    """Full Table-I level counts, multi-tile batch."""
    cfg = _small_grid_cfg(kind, dim, log2_T=11, n_levels=16)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value
    pts = jax.random.uniform(jax.random.PRNGKey(1), (4096, dim))
    out_k = hg_ops.encode(pts, tables, cfg, block_b=256)
    out_r = hg_ref.encode_ref(pts, tables, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_hashgrid_table_dtypes(dtype):
    cfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=10,
                              n_levels=4)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg, dtype=dtype).value
    pts = jax.random.uniform(jax.random.PRNGKey(1), (256, 3))
    out_k = hg_ops.encode(pts, tables, cfg, block_b=128)
    out_r = hg_ref.encode_ref(pts, tables, cfg)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=tol, rtol=tol)


def test_hashgrid_edge_coordinates():
    """0.0 and 1.0 inputs must not index out of table bounds."""
    cfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=10,
                              n_levels=4)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value
    pts = jnp.array([[0.0, 0.0, 0.0], [1.0, 1.0, 1.0], [0.0, 1.0, 0.5]])
    out_k = hg_ops.encode(pts, tables, cfg, block_b=8)
    out_r = hg_ref.encode_ref(pts, tables, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-6)


# ------------------------------------------------- level-group table tiling
# Budgets chosen to force distinct group sizes at log2_T=11, L=8
# (16 KB/level): 16 KB -> g=1, 64 KB -> g=4, default (8 MB) -> g=8.
@pytest.mark.parametrize("budget", [1 << 14, 1 << 16, None])
def test_hashgrid_budget_sweep_bit_identical(budget):
    """The VMEM tiling only changes residency, never math: outputs are
    bit-identical across every level-group size the budget induces."""
    cfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=11,
                              n_levels=8)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value
    pts = jax.random.uniform(jax.random.PRNGKey(1), (512, 3))
    base = hg_ops.encode(pts, tables, cfg, block_b=256, level_group=8)
    g = pick_level_group(cfg, tables.dtype, budget)
    if budget is not None:
        assert g < 8, "budget too large to exercise the tiling"
    out = hg_ops.encode(pts, tables, cfg, block_b=256,
                        vmem_budget_bytes=budget)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


@pytest.mark.parametrize("budget", [1 << 14, 1 << 16, None])
def test_fused_field_budget_sweep_bit_identical(budget):
    gcfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=11,
                               n_levels=8)
    mcfg = MLPConfig(in_dim=gcfg.out_dim, n_hidden=3, out_dim=16)
    tables = enc.init_grid(jax.random.PRNGKey(0), gcfg).value
    params, _ = unbox(init_mlp(jax.random.PRNGKey(1), mcfg))
    pts = jax.random.uniform(jax.random.PRNGKey(2), (256, 3))
    base = ff_ops.field(pts, tables, params, gcfg, mcfg, block_b=128,
                        level_group=8)
    out = ff_ops.field(pts, tables, params, gcfg, mcfg, block_b=128,
                       vmem_budget_bytes=budget)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


def test_vmem_plan_feasible_at_paper_scale():
    """Acceptance: at Table I scale (log2_T=19, L=16, F=2) the chosen
    table BlockSpec keeps resident table bytes <= 16 MB — the whole
    (L, T, F) stack would be 64 MB, 4x a TPU core's VMEM."""
    cfg = enc.hashgrid_config()
    assert cfg.log2_table_size == 19 and cfg.n_levels == 16 \
        and cfg.n_features == 2
    for dtype in (jnp.float32, jnp.bfloat16):
        g = pick_level_group(cfg, dtype)
        assert cfg.n_levels % g == 0
        spec = table_block_spec(cfg, g)
        assert tuple(spec.block_shape) == (g, cfg.table_size,
                                           cfg.n_features)
        nbytes = (spec.block_shape[0] * spec.block_shape[1]
                  * spec.block_shape[2] * jnp.dtype(dtype).itemsize)
        assert nbytes == table_block_bytes(cfg, g, dtype)
        assert nbytes <= 16 * 1024 * 1024
        assert nbytes <= DEFAULT_VMEM_BUDGET_BYTES
        # the index map pins the level-group dim to the group id and is
        # batch-invariant (block loads once per group)
        assert spec.index_map(3, 7) == (3, 0, 0)
    # fp16-style tables double the resident level count (paper §V)
    assert (pick_level_group(cfg, jnp.bfloat16)
            == 2 * pick_level_group(cfg, jnp.float32))


def test_fused_field_bf16_tables():
    """The accelerator stores fp16 features; the kernel path must accept
    sub-f32 tables with f32 accumulation."""
    gcfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=10,
                               n_levels=4)
    mcfg = MLPConfig(in_dim=gcfg.out_dim, n_hidden=2, out_dim=4)
    tables = enc.init_grid(jax.random.PRNGKey(0), gcfg,
                           dtype=jnp.bfloat16).value
    params, _ = unbox(init_mlp(jax.random.PRNGKey(1), mcfg))
    pts = jax.random.uniform(jax.random.PRNGKey(2), (256, 3))
    out_k = ff_ops.field(pts, tables, params, gcfg, mcfg, block_b=128)
    out_r = ff_ref.field_ref(pts, tables, params, gcfg, mcfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-2, rtol=2e-2)


# --------------------------------------------------------- custom VJPs
@pytest.mark.parametrize("kind", ["hash", "dense", "tiled"])
def test_encode_grad_matches_pure_jax(kind):
    """The kernel route's backward (vjp.py scatter-add) == jax.grad of the
    pure-JAX oracle, for both tables and points."""
    mk = {"hash": enc.hashgrid_config, "dense": enc.densegrid_config,
          "tiled": enc.tiledgrid_config}[kind]
    cfg = dataclasses.replace(mk(dim=3), log2_table_size=10, n_levels=4)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value
    pts = jax.random.uniform(jax.random.PRNGKey(1), (200, 3))

    def loss_k(t, p):
        return jnp.sum(jnp.sin(hg_ops.encode(p, t, cfg, block_b=128)))

    def loss_r(t, p):
        return jnp.sum(jnp.sin(enc.grid_encode(p, t, cfg)))

    gk_t, gk_p = jax.grad(loss_k, argnums=(0, 1))(tables, pts)
    gr_t, gr_p = jax.grad(loss_r, argnums=(0, 1))(tables, pts)
    np.testing.assert_allclose(np.asarray(gk_t), np.asarray(gr_t),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gk_p), np.asarray(gr_p),
                               atol=1e-4, rtol=1e-4)


def test_apply_field_pallas_grad_matches_xla():
    """Acceptance: jax.grad through apply_field(..., use_pallas=True)
    matches the pure-JAX gradient on tables AND MLP params."""
    from repro.core import fields
    from tests.conftest import small_field_config
    for app in ("gia", "nsdf"):
        cfg = small_field_config(app, "hash", log2_T=10, n_levels=4)
        params, _ = unbox(fields.init_field(jax.random.PRNGKey(3), cfg))
        pts = jax.random.uniform(jax.random.PRNGKey(4),
                                 (64, cfg.grid.dim))
        tgt = jax.random.uniform(
            jax.random.PRNGKey(5), (64, cfg.out_dim))

        def loss(p, use_pallas, cfg=cfg):
            pred = fields.apply_field(p, cfg, pts, use_pallas=use_pallas)
            return jnp.mean((pred - tgt) ** 2)

        g_pl = jax.grad(loss)(params, True)
        g_ref = jax.grad(loss)(params, False)
        flat_pl, tree = jax.tree.flatten(g_pl)
        flat_ref, _ = jax.tree.flatten(g_ref)
        for a, b in zip(flat_pl, flat_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-4)


def test_fused_mlp_grad_matches_pure_jax():
    cfg = MLPConfig(in_dim=32, n_hidden=3, out_dim=16)
    params, _ = unbox(init_mlp(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (200, 32))

    def loss_k(p, x):
        return jnp.sum(mlp_ops.mlp(p, x, cfg, block_b=128) ** 2)

    def loss_r(p, x):
        return jnp.sum(mlp_ref.mlp_ref(p, x, cfg) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1))(params, x)
    gr = jax.grad(loss_r, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_field_train_step_runs_on_pallas_route():
    """One optimizer step through use_pallas=True moves the loss — the
    end-to-end trainability the custom VJPs exist for."""
    from repro.core import fields, train
    from repro.train import optim
    from tests.conftest import small_field_config
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    params, _ = unbox(fields.init_field(jax.random.PRNGKey(0), cfg))
    opt_state = optim.adam_init(params)
    batch = train.make_batch(cfg, jax.random.PRNGKey(1), 256)
    step = train.make_field_train_step(cfg, use_pallas=True)
    p1, opt_state, m1 = step(params, opt_state, batch)
    _, _, m2 = step(p1, opt_state, batch)
    assert float(m2["loss"]) < float(m1["loss"])


# ------------------------------------------------------------- fused MLP
@pytest.mark.parametrize("in_dim,n_hidden,out_dim",
                         [(32, 3, 16), (32, 4, 3), (16, 1, 1),
                          (64, 2, 4), (2, 4, 3)])
def test_fused_mlp_vs_ref(in_dim, n_hidden, out_dim):
    cfg = MLPConfig(in_dim=in_dim, n_hidden=n_hidden, out_dim=out_dim)
    params, _ = unbox(init_mlp(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (300, in_dim))
    out_k = mlp_ops.mlp(params, x, cfg, block_b=128)
    out_r = mlp_ref.mlp_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n", [8, 100, 512, 1000])
def test_fused_mlp_batch_padding(n):
    cfg = MLPConfig(in_dim=32, n_hidden=3, out_dim=16)
    params, _ = unbox(init_mlp(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (n, 32))
    out_k = mlp_ops.mlp(params, x, cfg, block_b=256)
    assert out_k.shape == (n, 16)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(mlp_ref.mlp_ref(params, x, cfg)),
                               atol=1e-4, rtol=1e-4)


def test_fused_mlp_bf16_weights():
    cfg = MLPConfig(in_dim=32, n_hidden=2, out_dim=8)
    params, _ = unbox(init_mlp(jax.random.PRNGKey(0), cfg,
                               dtype=jnp.bfloat16))
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    out_k = mlp_ops.mlp(params, x, cfg, block_b=64)
    out_r = mlp_ref.mlp_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=3e-2, rtol=3e-2)


# ----------------------------------------------------------- fused field
@pytest.mark.parametrize("kind,n_hidden,out_dim",
                         [("hash", 3, 16), ("dense", 4, 4), ("tiled", 4, 1)])
def test_fused_field_vs_ref(kind, n_hidden, out_dim):
    gcfg = _small_grid_cfg(kind, 3)
    mcfg = MLPConfig(in_dim=gcfg.out_dim, n_hidden=n_hidden,
                     out_dim=out_dim)
    tables = enc.init_grid(jax.random.PRNGKey(0), gcfg).value
    params, _ = unbox(init_mlp(jax.random.PRNGKey(1), mcfg))
    pts = jax.random.uniform(jax.random.PRNGKey(2), (500, 3))
    out_k = ff_ops.field(pts, tables, params, gcfg, mcfg, block_b=128)
    out_r = ff_ref.field_ref(pts, tables, params, gcfg, mcfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_fused_field_vs_ref_paper_levels():
    gcfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=11)
    mcfg = MLPConfig(in_dim=gcfg.out_dim, n_hidden=3, out_dim=16)
    tables = enc.init_grid(jax.random.PRNGKey(0), gcfg).value
    params, _ = unbox(init_mlp(jax.random.PRNGKey(1), mcfg))
    pts = jax.random.uniform(jax.random.PRNGKey(2), (500, 3))
    out_k = ff_ops.field(pts, tables, params, gcfg, mcfg, block_b=128)
    out_r = ff_ref.field_ref(pts, tables, params, gcfg, mcfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=1e-4, rtol=1e-4)


def test_fused_field_matches_unfused_apply():
    """The NFP fusion is bit-compatible with the two-kernel GPU path."""
    from repro.core import fields
    from tests.conftest import small_field_config
    for app in ("gia", "nsdf", "nvr", "nerf"):
        cfg = small_field_config(app, "hash", n_levels=4)
        params, _ = unbox(fields.init_field(jax.random.PRNGKey(3), cfg))
        pts = jax.random.uniform(jax.random.PRNGKey(4),
                                 (200, cfg.grid.dim))
        dirs = None
        if app in ("nerf", "nvr"):
            d = jax.random.normal(jax.random.PRNGKey(5), (200, 3))
            dirs = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
        fused = fields.apply_field(params, cfg, pts, dirs, use_pallas=True)
        xla = fields.apply_field(params, cfg, pts, dirs, use_pallas=False)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(xla),
                                   atol=1e-4, rtol=1e-3)


# ------------------------------------------------------------- ray march
@pytest.mark.parametrize("r,s", [(64, 16), (500, 32), (256, 192)])
def test_ray_march_vs_ref(r, s):
    """Kernel and render.composite share the exp(-prefix-sum(sigma*dt))
    formulation (DESIGN.md §7), but the kernel sums the prefix as a
    triangular matmul (Mosaic has no cumsum) while XLA scans it in order.
    The two summation orders differ by a few f32 ulps in the exponent,
    so pixels and opacity (values in [0, 1]) agree to atol 5e-7, the
    opacity bound this test held before the kernel changed."""
    rgb = jax.random.uniform(jax.random.PRNGKey(0), (r, s, 3))
    sigma = jax.random.uniform(jax.random.PRNGKey(1), (r, s)) * 8
    dts = jnp.full((r, s), 0.07)
    pk, ok = rm_ops.composite(rgb, sigma, dts, block_r=128)
    pr, orr = render.composite(rgb, sigma, dts)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), atol=5e-7,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(orr), atol=5e-7,
                               rtol=0)


def test_ray_march_broadcast_dts():
    """Deterministic sampling (render.sample_along_rays, rng=None) emits
    (1, S)-broadcast dts; the kernel wrapper must materialize it — the
    seed read out of bounds and returned NaN for every ray but the
    first."""
    r, s = 64, 8
    rgb = jax.random.uniform(jax.random.PRNGKey(0), (r, s, 3))
    sigma = jax.random.uniform(jax.random.PRNGKey(1), (r, s)) * 4
    dts = jnp.full((1, s), 0.5)
    pk, ok = rm_ops.composite(rgb, sigma, dts, block_r=64)
    pr, orr = render.composite(rgb, sigma, dts)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), atol=1e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(orr), atol=1e-5,
                               rtol=1e-4)


def test_render_rays_pallas_composite_matches_xla():
    """render_rays(use_pallas_composite=True) — the route RenderSettings
    use_pallas drives — agrees with the XLA composite."""
    o = jnp.zeros((32, 3)) + jnp.array([0.0, 0.0, -2.0])
    d = jnp.tile(jnp.array([[0.0, 0.0, 1.0]]), (32, 1))

    def fapply(p, dd):
        rgb = jax.nn.sigmoid(p[:, :3])
        sigma = jnp.exp(-jnp.sum(p ** 2, -1, keepdims=True))
        return jnp.concatenate([rgb, sigma], -1)

    a = render.render_rays(fapply, o, d, n_samples=8,
                           use_pallas_composite=True)
    b = render.render_rays(fapply, o, d, n_samples=8,
                           use_pallas_composite=False)
    assert bool(jnp.isfinite(a).all())
    # shared transmittance formulation; at S=8 the kernel's triangular
    # matmul and XLA's scan round the prefix sums identically on the CPU
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ray_march_opaque_and_empty():
    """Opaque volume -> first sample's color; empty -> zeros."""
    r, s = 32, 16
    rgb = jnp.broadcast_to(jnp.array([1.0, 0.5, 0.25]), (r, s, 3))
    sigma_opaque = jnp.full((r, s), 1e4)
    sigma_empty = jnp.zeros((r, s))
    dts = jnp.full((r, s), 0.1)
    pk, ok = rm_ops.composite(rgb, sigma_opaque, dts, block_r=32)
    np.testing.assert_allclose(np.asarray(pk),
                               np.asarray(rgb[:, 0]), atol=1e-3)
    np.testing.assert_allclose(np.asarray(ok), 1.0, atol=1e-3)
    pk, ok = rm_ops.composite(rgb, sigma_empty, dts, block_r=32)
    np.testing.assert_allclose(np.asarray(pk), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ok), 0.0, atol=1e-6)
