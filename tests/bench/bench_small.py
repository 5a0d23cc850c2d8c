"""Cells of BENCHMARK.json cut to a size the CPU runs in a second: the
same configuration and traffic files, with the table, the tiles, the
images and the batch made small. Only the tests use this."""
import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def small_cell(cell, log2_table_size: int = 10,
               n_levels: int = 4) -> "harness.Cell":
    """A cell, by name or loaded, cut to a tiny size."""
    cell = (harness.load_cell(cell) if isinstance(cell, str)
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
