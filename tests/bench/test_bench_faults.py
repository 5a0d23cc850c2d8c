"""The check that decides ``correct`` fails on a broken timed path and
separates the control from the program. Each fault is planted under the
harness at a tiny size on the CPU, and the rest of a run goes as on the
chip: ``correct`` has to come out false."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_small import rehearse, small_cell  # noqa: E402

SERVE_ONE_CHIP = ["nvr_hash.tiles", "gia_hash.pan", "nerf_hash.tiles"]
TRAIN_ONE_CHIP = ["nvr_hash.train", "nerf_hash.train"]


def _failed(r: dict, *names):
    assert not r["correct"]
    assert any(r["checks"][n]["value"] > r["checks"][n]["limit"]
               for n in names), r["checks"]


@pytest.mark.parametrize("name", SERVE_ONE_CHIP)
def test_an_altered_pixel_fails(name, monkeypatch):
    from repro.core import pipeline
    make = pipeline.make_multi_scene_tile_fn

    def altered(*a, **k):
        tile = make(*a, **k)
        return lambda *args: tile(*args).at[0, 0].add(0.05)

    monkeypatch.setattr(pipeline, "make_multi_scene_tile_fn", altered)
    _failed(rehearse(small_cell(name)), "pixel_gap")


@pytest.mark.parametrize("name", TRAIN_ONE_CHIP)
def test_a_step_that_keeps_its_state_fails(name, monkeypatch):
    from repro.train import optim
    update = optim.adam_update

    def unchanged(grads, state, params, cfg):
        _, _, metrics = update(grads, state, params, cfg)
        return params, state, metrics

    monkeypatch.setattr(optim, "adam_update", unchanged)
    _failed(rehearse(small_cell(name)), "grad_gap", "change_gap")


@pytest.mark.parametrize("name", TRAIN_ONE_CHIP)
def test_half_the_batch_fails(name, monkeypatch):
    import jax
    from repro.core import train as train_mod
    loss = train_mod.field_loss

    def half(params, cfg, batch, **k):
        n = batch["origins"].shape[0] // 2
        return loss(params, cfg, jax.tree.map(lambda x: x[:n], batch), **k)

    monkeypatch.setattr(train_mod, "field_loss", half)
    _failed(rehearse(small_cell(name)), "loss_gap", "grad_gap", "change_gap")
