"""The control (the reference one precision step below the
configuration's) put in the program's place comes out as not correct
under each cell's own limits, at a tiny size on the CPU as at the cells'
own sizes on the chip (PERF.md), while the program's own numbers of the
same run stay within them; half of the batch left out reads far above the
program too."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_small import rehearse, small_cell  # noqa: E402

SERVE_ONE_CHIP = ["nvr_hash.tiles", "gia_hash.pan", "nerf_hash.tiles"]


def _serve_cell(name):
    """A serve cell with all 16 levels at a small table, its whole tile
    and block: the control's widest gap grows with the levels and the
    pixels compared, and at four levels of 64 pixels it stays within the
    limits that the cells' own sizes set."""
    cell = small_cell(name, log2_table_size=12, n_levels=16)
    t = cell.traffic
    t["tile_pixels"] = 4096
    if t["walk"] == "tour":
        t["block"] = [64, 64]
    return cell


def _control_fails(r: dict):
    """The control's numbers are the checks; at least one is over its
    limit, and every program number of the same run is within it."""
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values()), \
        r["checks"]
    for name, c in r["checks"].items():
        if f"program.{name}" in r["counts"]:
            assert r["counts"][f"program.{name}"] <= c["limit"]


@pytest.mark.parametrize("name", SERVE_ONE_CHIP)
def test_the_control_separates_from_the_program_serve(name):
    r = rehearse(_serve_cell(name), control=True)
    _control_fails(r)
    program = r["counts"]["program.pixel_gap"]
    assert r["checks"]["pixel_gap"]["value"] >= 3 * max(program, 1e-7)


@pytest.mark.parametrize("name", ["nvr_hash.train", "nerf_hash.train"])
def test_the_control_separates_from_the_program_train(name):
    r = rehearse(small_cell(name), control=True)
    _control_fails(r)
    ratios = [r["checks"][k]["value"] / max(r["counts"][f"program.{k}"],
                                            1e-12)
              for k in ("loss_gap", "grad_gap", "change_gap")]
    assert max(ratios) >= 3
    # half of the batch left out reads far above the program
    assert r["counts"]["half_batch.grad_gap"] > 10 * r["counts"][
        "program.grad_gap"]
