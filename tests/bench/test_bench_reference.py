"""The plain reference has the program's semantics: on the CPU, at a
table of 2^14 rows and every level, it makes the program's weights from a
seed, it matches the engine's served tiles (nvr, gia), and it follows
``train_field``'s first steps (losses, first gradient, update)."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_small import harness, rehearse, small_cell  # noqa: E402


@pytest.mark.parametrize("config", ["nvr_hash", "gia_hash"])
def test_weights_from_a_seed_match_the_program(config):
    import jax
    from repro.common.param import unbox
    from repro.core import fields
    from bench.reference import field
    cell = small_cell(f"{config}.{'tiles' if config == 'nvr_hash' else 'pan'}",
                      log2_table_size=8, n_levels=16)
    key = harness.base_key(2 ** 40 + 3)
    got = unbox(fields.init_field(key, harness.field_config(cell.config)))[0]
    want = field.init_weights(key, cell.config)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert jax.tree.structure(got) == jax.tree.structure(want)


@pytest.mark.parametrize("name", ["nvr_hash.tiles", "gia_hash.pan"])
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
