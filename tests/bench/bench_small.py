"""Cells of BENCHMARK.json cut to a size the CPU runs in a second: the
same configuration and traffic files, with the table, the tiles, the
images and the batch made small. Only the tests use this."""
import copy
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


FIXTURES = Path(__file__).resolve().parent / "fixtures"

# cells that only files under tests/bench/fixtures define, as a later
# cell of BENCHMARK.json would: its configuration file, its traffic mix,
# the cell whose metrics it reports, and its limits (the tests' own, set
# from CPU readings at the tests' sizes)
FIXTURE_CELLS = {
    "nerf_hash.tiles": ("nerf_hash", "tiles", "nvr_hash.tiles",
                        {"pixel_gap": 1e-5}),
    "nerf_hash.train": ("nerf_hash", "train", "nvr_hash.train",
                        {"loss_gap": 1e-4, "grad_gap": 2e-4,
                         "change_gap": 5e-3}),
}


def load_cell(name: str) -> "harness.Cell":
    """A cell of BENCHMARK.json or of FIXTURE_CELLS, by name."""
    if name not in FIXTURE_CELLS:
        return harness.load_cell(name)
    config, traffic, like, limits = FIXTURE_CELLS[name]
    base = harness.load_cell(like)
    return harness.Cell(
        name, base.chips,
        json.loads((FIXTURES / f"{config}.json").read_text()),
        json.loads((harness.BENCH / "traffic" / f"{traffic}.json")
                   .read_text()),
        dict(limits), base.end_to_end, base.per_layer)


def small_cell(cell, log2_table_size: int = 10,
               n_levels: int = 4) -> "harness.Cell":
    """A cell, by name or loaded, cut to a tiny size."""
    cell = (load_cell(cell) if isinstance(cell, str)
            else copy.deepcopy(cell))
    cell.config = copy.deepcopy(cell.config)
    cell.config["grid"]["log2_table_size"] = log2_table_size
    cell.config["grid"]["n_levels"] = n_levels
    t = cell.traffic = copy.deepcopy(cell.traffic)
    if t["driver"] == "serve":
        if t["walk"] == "row_major":
            t["tile_pixels"] = 64
        else:
            t["tile_pixels"], t["block"] = 256, [16, 16]
            t["tour_blocks"] = 6
        t["check_requests"] = 3
    else:
        t["batch_rays"] = 64
    return cell


def rehearse(cell, seconds: float = 0.5, seed: int = 2 ** 33 + 7,
             control: bool = False) -> dict:
    """One run of the cell on whatever devices JAX has (no chip check)."""
    return harness.run_loaded(cell, seed, seconds, False,
                              time.perf_counter(), require_tpu=False,
                              control=control)


def assert_sound(r: dict, cell):
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in r["metrics"].values():
        assert m["value"] > 0
    assert r["checks"]["window_compiles"]["value"] == 0
    assert list(r)[-1] == "checks"
