"""The benchmark's yardstick: operation and byte counts against hand
counts, the dense-versus-hashed level rule against the program's, and the
peaks table."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import peaks, work  # noqa: E402

# L=2 levels of a 2-D grid with T=16 rows: level 0 has 3x3 = 9 vertices
# (dense), level 1 has 5x5 = 25 > 16 (hashed)
SMALL = {"dim": 2, "n_levels": 2, "n_features": 2, "log2_table_size": 4,
         "base_resolution": 2, "growth": 2.0, "kind": "hash"}
MLP = {"hidden_dim": 8, "n_hidden": 2, "out_dim": 3}


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_level_rule_small():
    assert [work.level_resolution(SMALL, l) for l in (0, 1)] == [2, 4]
    assert [work.level_is_hashed(SMALL, l) for l in (0, 1)] == [False, True]
    assert [work.level_rows(SMALL, l) for l in (0, 1)] == [9, 16]


@pytest.mark.parametrize("name", ["nvr_hash", "gia_hash"])
def test_level_rule_matches_program(name):
    from repro.core.encoding import GridConfig
    grid = _config(name)["grid"]
    g = GridConfig(**grid)
    for level in range(grid["n_levels"]):
        assert work.level_resolution(grid, level) == g.level_resolution(level)
        assert work.level_is_hashed(grid, level) == g.level_is_hashed(level)


def test_table_rows_of_the_configurations():
    nvr = _config("nvr_hash")["grid"]
    hashed = [work.level_is_hashed(nvr, l) for l in range(16)]
    assert hashed == [False] * 7 + [True] * 9
    dense_bytes = sum(work.level_rows(nvr, l) for l in range(7)) * 2 * 4
    assert 5.0e6 < dense_bytes < 5.2e6
    gia = _config("gia_hash")["grid"]
    assert not any(work.level_is_hashed(gia, l) for l in range(16))
    assert sum(work.level_rows(gia, l) for l in range(16)) == 709_675


def test_encode_counts_by_hand():
    # per level: 3d = 6, then 4 corners x ((d - 1) + 2F) = 4 x 5 = 20
    assert work.encode_flops(SMALL, 1) == 2 * 26
    # backward adds 4 corners x 2F = 16 per level
    assert work.encode_flops(SMALL, 1, backward=True) == 2 * (26 + 16)
    # one point: 4 corners per level < 9 and < 16 rows -> 8 rows x 2 x 4 B,
    # the point (2 x 4 B) and its features (2 levels x 2 x 4 B)
    assert work.encode_bytes(SMALL, 1) == 64 + 8 + 16
    # ten points: 40 corners per level, so every row: 9 + 16 rows
    assert work.encode_bytes(SMALL, 10) == 25 * 8 + 80 + 160
    assert work.encode_bytes(SMALL, 10, backward=True) == (
        25 * 8 + 80 + 160 + 160 + 25 * 8)


def test_mlp_counts_by_hand():
    # in 4 x 8 + one 8 x 8 hidden + 8 x 3 out = 120 weights
    assert work.mlp_weights(SMALL, MLP) == 120
    assert work.mlp_flops(SMALL, MLP, 5) == 5 * 240
    assert work.mlp_flops(SMALL, MLP, 5, backward=True) == 3 * 5 * 240
    assert work.mlp_bytes(SMALL, MLP, 5) == 120 * 4 + 5 * (4 + 3) * 4
    assert work.field_flops(SMALL, MLP, 5) == 5 * (52 + 240)


def test_nvr_field_flops_per_point():
    cfg = _config("nvr_hash")
    # 16 levels x 57 for the encode, 2 x (32x64 + 3x64x64 + 64x4) the MLP
    assert work.field_flops(cfg["grid"], cfg["mlp"], 1) == 912 + 29184


def test_least_time_takes_the_larger_bound():
    p = peaks.peaks_for("TPU v5 lite")
    t, bound = work.least_time(197e12, 1.0, p)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = work.least_time(1.0, 819e9, p)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_peaks_table():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_peaks_refuse_an_unknown_device():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
