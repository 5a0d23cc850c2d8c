"""The plain reference has the program's semantics: on the CPU, at a
table of 2^14 rows and every level, it makes the program's weights from a
seed, it matches the engine's served tiles (nvr, gia, nerf), and it
follows ``train_field``'s first steps (losses, first gradient, update)."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_small import harness, rehearse, small_cell  # noqa: E402

SERVE = ["nvr_hash.tiles", "gia_hash.pan", "nerf_hash.tiles"]


@pytest.mark.parametrize("cell", SERVE)
def test_weights_from_a_seed_match_the_program(cell):
    import jax
    from repro.common.param import unbox
    from repro.core import fields
    cell = small_cell(cell, log2_table_size=8, n_levels=16)
    key = harness.base_key(2 ** 40 + 3)
    got = unbox(fields.init_field(key, harness.field_config(cell.config)))[0]
    want = harness.reference(cell.config).init_weights(key, cell.config)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("name", SERVE)
def test_reference_matches_served_tiles(name):
    r = rehearse(small_cell(name, log2_table_size=14, n_levels=16))
    assert r["correct"]
    assert r["checks"]["pixel_gap"]["value"] <= 1e-5


def test_reference_follows_the_first_train_steps():
    r = rehearse(small_cell("nvr_hash.train", log2_table_size=14,
                            n_levels=16))
    c = {k: v["value"] for k, v in r["checks"].items()}
    assert c["loss_gap"] <= 1e-5
    assert c["grad_gap"] <= 1e-4
    assert c["change_gap"] <= 1e-2


def test_spherical_harmonics_are_orthonormal():
    """The basis written out from its definition integrates to the
    identity over the sphere (midpoint rule in theta and phi)."""
    import jax.numpy as jnp
    from bench.reference import nerf
    n_t, n_p = 256, 512
    theta = (np.arange(n_t) + 0.5) * np.pi / n_t
    phi = (np.arange(n_p) + 0.5) * 2 * np.pi / n_p
    t, p = (a.ravel() for a in np.meshgrid(theta, phi, indexing="ij"))
    dirs = np.stack([np.sin(t) * np.cos(p), np.sin(t) * np.sin(p),
                     np.cos(t)], axis=-1)
    y = np.asarray(nerf.sh(jnp.asarray(dirs, jnp.float32), 4), np.float64)
    weight = np.sin(t) * (np.pi / n_t) * (2 * np.pi / n_p)
    gram = (y * weight[:, None]).T @ y
    np.testing.assert_allclose(gram, np.eye(16), atol=1e-4)


def test_spherical_harmonics_match_the_program():
    """Terms in instant-NGP's order and sign, as the colour MLP's
    weights expect them."""
    import jax.numpy as jnp
    from bench.reference import nerf
    from repro.core.encoding import sh_encode
    d = np.random.default_rng(5).normal(size=(512, 3))
    d = jnp.asarray(d / np.linalg.norm(d, axis=-1, keepdims=True),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(nerf.sh(d, 4)),
                               np.asarray(sh_encode(d)), atol=1e-6)
