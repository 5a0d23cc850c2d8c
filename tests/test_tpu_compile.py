"""Compile the main path for a described TPU v5e, at Table I widths.

No chip is used: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (block
tiling, VMEM, lowering). A compile that passes is not a chip run.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker given this
file loads the TPU library. Where it cannot be described, the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.common.param import unbox
from repro.configs import registry
from repro.core import fields
from repro.kernels.fused_field.fused_field import fused_field_pallas
from repro.kernels.fused_mlp.fused_mlp import fused_mlp_pallas
from repro.kernels.hashgrid.hashgrid import hashgrid_encode_pallas
from repro.kernels.ray_march.ray_march import composite_pallas

# field evaluations in one served 4096-pixel tile at 32 samples per ray
TILE_SAMPLES = 4096 * 32
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _mlp_args(sharding, m, rows):
    return (_sds(sharding, (rows, m.in_dim)),
            _sds(sharding, (m.in_dim, m.hidden_dim)),
            _sds(sharding, (max(m.n_hidden - 1, 1), m.hidden_dim,
                            m.hidden_dim)),
            _sds(sharding, (m.hidden_dim, m.out_dim)))


@pytest.mark.parametrize("app,which", [("nvr", "mlp"),
                                       ("nerf", "density_mlp"),
                                       ("nerf", "mlp")])
def test_fused_mlp_compiles(one_chip, app, which):
    m = getattr(registry.field_config(app, "hash"), which)
    compiled = _compile(
        lambda x, a, b, c: fused_mlp_pallas(x, a, b, c, m, interpret=False),
        *_mlp_args(one_chip, m, TILE_SAMPLES))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("r,s", [(4096, 32), (256, 192)])
def test_composite_compiles(one_chip, r, s):
    compiled = _compile(
        lambda rgb, sigma, dts: composite_pallas(rgb, sigma, dts,
                                                 interpret=False),
        _sds(one_chip, (r, s, 3)), _sds(one_chip, (r, s)),
        _sds(one_chip, (r, s)))
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_field_route_compiles(one_chip):
    """nvr/hash through apply_field(use_pallas=False): the default route
    of serve and train. It fits the chip's HBM with room to spare."""
    cfg = registry.field_config("nvr", "hash")
    params = jax.eval_shape(
        lambda: unbox(fields.init_field(jax.random.PRNGKey(0), cfg))[0])
    params = jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), params)
    compiled = _compile(
        lambda p, x, d: fields.apply_field(p, cfg, x, d, use_pallas=False),
        params, _sds(one_chip, (2 * TILE_SAMPLES, 3)),
        _sds(one_chip, (2 * TILE_SAMPLES, 3)))
    mem = compiled.memory_analysis()
    assert "tpu_custom_call" not in compiled.as_text()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES // 8)


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Mosaic refuses the (1024, g*F) = (1024, 4) out block of the (B, 32) "
    "features: the last two block dims must be divisible by (8, 128) or "
    "equal the array's"))
def test_hashgrid_encode_compiles(one_chip):
    g = registry.field_config("nvr", "hash").grid
    _compile(lambda p, t: hashgrid_encode_pallas(p, t, g, interpret=False),
             _sds(one_chip, (TILE_SAMPLES, 3)),
             _sds(one_chip, (g.n_levels, g.table_size, g.n_features)))


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Mosaic cannot lower the per-lane jnp.take gather from the VMEM "
    "(g, T, F=2) table block: 'Shape mismatch in input, indices and "
    "output'"))
def test_fused_field_compiles(one_chip):
    cfg = registry.field_config("nvr", "hash")
    g, m = cfg.grid, cfg.mlp
    _compile(lambda p, t, a, b, c: fused_field_pallas(
                 p, t, a, b, c, g, m, interpret=False),
             _sds(one_chip, (TILE_SAMPLES, 3)),
             _sds(one_chip, (g.n_levels, g.table_size, g.n_features)),
             *_mlp_args(one_chip, m, TILE_SAMPLES)[1:])
