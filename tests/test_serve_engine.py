"""RenderEngine (repro.serve): camera-as-data, bucketed compile cache,
megabatch pad+mask, multi-scene stacking, pixel-parallel sharding.

Parity bar: engine output == pipeline.render_frame per scene (f32, 1e-5);
compile bar: a mixed stream (2 scenes x 3 cameras, same bucket) traces the
bucket executable exactly once."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.param import unbox
from repro.core import fields, pipeline, render
from repro.data import scenes
from repro.launch.mesh import make_local_mesh
from repro.serve import RenderEngine, RenderRequest
from tests.conftest import small_field_config


def _params(cfg, seed):
    params, _ = unbox(fields.init_field(jax.random.PRNGKey(seed), cfg))
    return params


def _orbit_cam(height, width, ang):
    return scenes.orbit_camera(height, width, ang)


# ------------------------------------------------------------ camera-as-data
def test_camera_is_a_pytree_of_arrays():
    cam = scenes.default_camera(8, 12)
    leaves = jax.tree.leaves(cam)
    assert [l.shape for l in leaves] == [(3,), (4, 4)]
    assert cam.resolution == (8, 12)
    # same treedef regardless of resolution/pose -> one jit cache entry
    cam2 = _orbit_cam(16, 16, 1.0)
    assert (jax.tree.structure(cam) == jax.tree.structure(cam2))


def test_make_rays_traces_once_across_cameras():
    traces = []

    @jax.jit
    def rays(cam, ids):
        traces.append(1)
        return render.make_rays(cam, ids)

    ids = jnp.arange(16, dtype=jnp.int32)
    for cam in (scenes.default_camera(4, 4), scenes.default_camera(8, 8),
                _orbit_cam(8, 8, 2.0)):
        o, d = rays(cam, ids)
        assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(d).all())
    assert len(traces) == 1


def test_make_rays_matches_per_resolution_decode():
    # the traced int32 decode must equal the old static-shape decode
    cam = scenes.default_camera(5, 7)
    ids = jnp.arange(5 * 7, dtype=jnp.int32)
    o, d = render.make_rays(cam, ids)
    py, px = np.divmod(np.arange(5 * 7), 7)
    x = (px - 7 * 0.5 + 0.5) / float(cam.focal)
    y = (py - 5 * 0.5 + 0.5) / float(cam.focal)
    d_cam = np.stack([x, y, np.ones_like(x)], -1)
    dirs = d_cam @ np.asarray(cam.c2w)[:3, :3].T
    dirs /= np.linalg.norm(dirs, axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(d), dirs, atol=1e-5)


# ------------------------------------------------------------------- parity
@pytest.mark.parametrize("use_pallas", [False, True])
def test_engine_matches_render_frame_per_scene_gia(use_pallas):
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    settings = pipeline.RenderSettings(tile_pixels=64,
                                       use_pallas=use_pallas)
    engine = RenderEngine(settings)
    for s in range(2):
        engine.add_scene(f"s{s}", cfg, _params(cfg, s))
    engine.warmup()
    cam = scenes.default_camera(12, 12)   # 144 px -> 3 tiles, last masked
    for s in range(2):
        got = engine.render_frame(f"s{s}", cam)
        ref = pipeline.render_frame(_params(cfg, s), cfg, cam, settings)
        np.testing.assert_allclose(got, np.asarray(ref), atol=1e-5)


def test_engine_matches_render_frame_ray_marched():
    cfg = small_field_config("nvr", "hash", log2_T=10, n_levels=4)
    settings = pipeline.RenderSettings(tile_pixels=32, n_samples=4)
    engine = RenderEngine(settings)
    for s in range(2):
        engine.add_scene(f"s{s}", cfg, _params(cfg, s))
    engine.warmup()
    cam = scenes.default_camera(8, 8)
    for s in range(2):
        got = engine.render_frame(f"s{s}", cam)
        ref = pipeline.render_frame(_params(cfg, s), cfg, cam, settings)
        np.testing.assert_allclose(got, np.asarray(ref), atol=1e-5)


# ------------------------------------------------------------ compile count
@pytest.mark.parametrize("use_pallas", [False, True])
def test_one_compile_serves_mixed_cameras_and_scenes(use_pallas):
    """Acceptance: >=2 scenes, >=3 distinct cameras, one bucket -> exactly
    one trace of the bucket executable (camera/scene stay traced data)."""
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    settings = pipeline.RenderSettings(tile_pixels=64,
                                       use_pallas=use_pallas)
    engine = RenderEngine(settings)
    for s in range(2):
        engine.add_scene(f"s{s}", cfg, _params(cfg, s))
    engine.warmup()
    cams = [_orbit_cam(8, 8, 0.0), _orbit_cam(8, 8, 2.1),
            _orbit_cam(16, 16, 4.2)]   # incl. a different resolution
    rng = np.random.default_rng(0)
    for r in range(6):
        h, w = cams[r % 3].resolution
        ids = rng.integers(0, h * w, 48).astype(np.int32)
        engine.submit(RenderRequest(scene=f"s{r % 2}", camera=cams[r % 3],
                                    pixel_ids=ids))
    engine.flush()
    assert engine.total_traces() == 1, engine.trace_counts()
    st = engine.stats()
    assert st["n_requests"] == 6
    assert np.isfinite(st["p50_ms"]) and np.isfinite(st["p99_ms"])
    assert st["p99_ms"] >= st["p50_ms"]


def test_scene_outputs_differ_and_match_direct_eval():
    """The traced scene_id gather must select the right table stack."""
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    settings = pipeline.RenderSettings(tile_pixels=64)
    engine = RenderEngine(settings)
    p0, p1 = _params(cfg, 0), _params(cfg, 1)
    engine.add_scene("a", cfg, p0)
    engine.add_scene("b", cfg, p1)
    engine.warmup()
    cam = scenes.default_camera(8, 8)
    a = engine.render_frame("a", cam)
    b = engine.render_frame("b", cam)
    assert not np.allclose(a, b)          # different scenes, same executable
    np.testing.assert_allclose(
        a, np.asarray(pipeline.render_frame(p0, cfg, cam, settings)),
        atol=1e-5)


# ---------------------------------------------------------------- sharding
def test_sharded_engine_matches_unsharded():
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    settings = pipeline.RenderSettings(tile_pixels=64)
    mesh = make_local_mesh()
    sharded = RenderEngine(settings, mesh=mesh)
    plain = RenderEngine(settings)
    for s in range(2):
        sharded.add_scene(f"s{s}", cfg, _params(cfg, s))
        plain.add_scene(f"s{s}", cfg, _params(cfg, s))
    sharded.warmup()
    plain.warmup()
    cam = scenes.default_camera(8, 8)
    np.testing.assert_allclose(sharded.render_frame("s1", cam),
                               plain.render_frame("s1", cam), atol=1e-6)


# ------------------------------------------------------------------- guards
def test_heterogeneous_configs_get_their_own_bucket():
    """Same app/encoding but a different graph (table size) must not
    stack — it compiles its own bucket executable and still serves."""
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    other = small_field_config("gia", "hash", log2_T=11, n_levels=4)
    settings = pipeline.RenderSettings(tile_pixels=64)
    engine = RenderEngine(settings)
    ka = engine.add_scene("a", cfg, _params(cfg, 0))
    kb = engine.add_scene("b", other, _params(other, 1))
    assert ka != kb and len(engine.trace_counts()) == 2
    engine.warmup()
    cam = scenes.default_camera(8, 8)
    np.testing.assert_allclose(
        engine.render_frame("b", cam),
        np.asarray(pipeline.render_frame(_params(other, 1), other, cam,
                                         settings)), atol=1e-5)
    assert engine.total_traces() == 2         # one per bucket, not per scene


def test_engine_rejects_oversized_and_unknown_requests():
    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    engine = RenderEngine(pipeline.RenderSettings(tile_pixels=32))
    engine.add_scene("a", cfg, _params(cfg, 0))
    with pytest.raises(ValueError, match="tile_pixels"):
        engine.submit(RenderRequest(
            scene="a", camera=scenes.default_camera(8, 8),
            pixel_ids=np.arange(64, dtype=np.int32)))
    with pytest.raises(KeyError):
        engine.submit(RenderRequest(
            scene="missing", camera=scenes.default_camera(8, 8),
            pixel_ids=np.arange(4, dtype=np.int32)))


# -------------------------------------------------------- compiled program
def test_compiled_text_is_the_tile_program_the_engine_runs(monkeypatch):
    """The public route to the compiled tile program gives the HLO of the
    route the benchmark's serve driver takes (``bench/drivers/serve.py``:
    the first bucket's jitted function lowered for the stacked scenes, a
    scene id, a camera, pixel ids and a mask), and traces nothing new
    after warmup."""
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    from bench.drivers import serve as serve_driver

    cfg = small_field_config("gia", "hash", log2_T=10, n_levels=4)
    engine = RenderEngine(pipeline.RenderSettings(tile_pixels=64))
    engine.add_scene("a", cfg, _params(cfg, 0))
    engine.warmup()
    traces = engine.total_traces()
    driver = serve_driver._hlo_texts(engine, 64, _orbit_cam(8, 8, 1.0))
    assert engine.compiled_text() == driver[0]
    assert engine.total_traces() == traces
