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


def _nerf():
    return json.loads((ROOT / "tests" / "bench" / "fixtures"
                       / "nerf_hash.json").read_text())


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
    small = {"grid": SMALL, "mlp": MLP}
    # in 4 x 8 + one 8 x 8 hidden + 8 x 3 out = 120 weights
    assert work.mlp_weights(small) == 120
    assert work.mlp_flops(small, 5) == 5 * 240
    assert work.mlp_flops(small, 5, backward=True) == 3 * 5 * 240
    assert work.mlp_bytes(small, 5) == 120 * 4 + 5 * (4 + 3) * 4
    assert work.field_flops(small, 5) == 5 * (52 + 240)
    assert work.dir_encode_flops(small, 5) == 0.0


def test_nvr_field_flops_per_point():
    cfg = _config("nvr_hash")
    # 16 levels x 57 for the encode, 2 x (32x64 + 3x64x64 + 64x4) the MLP
    assert work.field_flops(cfg, 1) == 912 + 29184


def test_nerf_counts_by_hand():
    cfg = _nerf()
    # levels 0-3 (res 16, 24, 36, 55) fit 2^19 rows; 4-15 are hashed
    assert [work.level_is_hashed(cfg["grid"], l) for l in range(16)] == (
        [False] * 4 + [True] * 12)
    # density 32x64 + 2x64x64 + 64x16 = 11,264; colour (16 + 16)x64 +
    # 3x64x64 + 64x3 = 14,528
    assert work.mlp_weights(cfg) == 11264 + 14528 == 25792
    assert work.mlp_flops(cfg, 3) == 3 * 2 * 25792
    assert work.mlp_flops(cfg, 3, backward=True) == 3 * 3 * 2 * 25792
    # each MLP reads its input and writes its output: 32 + 16 and 32 + 3
    assert work.mlp_bytes(cfg, 3) == 25792 * 4 + 3 * (48 + 35) * 4
    # the basis: xx, yy, zz, xy, yz, xz (6); band 1, one product a term
    # (3); band 2: xy, yz, xz scaled (3), C zz - C' (2), C (xx - yy) (2);
    # band 3: y(-3xx + yy), y(1 - 5zz), z(5zz - 3), x(1 - 5zz),
    # x(-xx + 3yy) four each, xy z two, z(xx - yy) three (25)
    assert work.SH_FLOPS[4] == 6 + 3 + 7 + 25
    assert work.dir_encode_flops(cfg, 3) == 3 * 41
    # the direction in, 16 terms out
    assert work.dir_encode_bytes(cfg, 3) == 3 * (3 + 16) * 4
    # the grid encode as nvr's, 16 levels x 57
    assert work.field_flops(cfg, 1) == 912 + 2 * 25792 + 41
    assert work.field_flops(cfg, 1, backward=True) == (
        16 * (57 + 32) + 3 * 2 * 25792 + 41)
    flops, nbytes = work.field_encode(cfg, 3)
    assert flops == work.encode_flops(cfg["grid"], 3) + 3 * 41
    assert nbytes == work.encode_bytes(cfg["grid"], 3) + 3 * 19 * 4


# the counts of the cells' configurations as they read before the MLPs were
# counted from the whole configuration: points 1, a tile (4096 x 32), a
# train step (8192 x 32), a gia block; mlp_weights, mlp_flops forward and
# backward, mlp_bytes, field_flops forward and backward
PINNED = {
    "nvr_hash": [
        (1, 14592, 29184.0, 87552.0, 58512.0, 30096.0, 88976.0),
        (131072, 14592, 3825205248.0, 11475615744.0, 18932736.0,
         3944742912.0, 11662262272.0),
        (262144, 14592, 7650410496.0, 22951231488.0, 37807104.0,
         7889485824.0, 23324524544.0),
        (65536, 14592, 1912602624.0, 5737807872.0, 9495552.0,
         1972371456.0, 5831131136.0)],
    "gia_hash": [
        (1, 14528, 29056.0, 87168.0, 58252.0, 29472.0, 87840.0),
        (131072, 14528, 3808428032.0, 11425284096.0, 18408192.0,
         3862953984.0, 11513364480.0),
        (262144, 14528, 7616856064.0, 22850568192.0, 36758272.0,
         7725907968.0, 23026728960.0),
        (65536, 14528, 1904214016.0, 5712642048.0, 9233152.0,
         1931476992.0, 5756682240.0)],
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_counts_of_the_cells_are_pinned(name):
    cfg = _config(name)
    for n, weights, fwd, bwd, nbytes, field, field_bwd in PINNED[name]:
        assert work.mlp_weights(cfg) == weights
        assert work.mlp_flops(cfg, n) == fwd
        assert work.mlp_flops(cfg, n, backward=True) == bwd
        assert work.mlp_bytes(cfg, n) == nbytes
        assert work.field_flops(cfg, n) == field
        assert work.field_flops(cfg, n, backward=True) == field_bwd
        assert work.field_encode(cfg, n) == (
            work.encode_flops(cfg["grid"], n),
            work.encode_bytes(cfg["grid"], n))


# the readers' numbers on fixed counts and device times, as they read
# before the MLPs were counted from the whole configuration
READINGS = {
    "nvr_hash.tiles": {"mfu.serve": 0.017020464341116752,
                       "encode_roofline.serve": 0.0646014185136897,
                       "mlp_roofline.serve": 7.368509890109889},
    "gia_hash.pan": {"mfu.serve": 0.0005208614984771574,
                     "encode_roofline.serve": 0.015399207301173404,
                     "mlp_roofline.serve": 3.593488644688645},
    "nvr_hash.train": {"mfu.train": 0.03247051954243417,
                       "encode_roofline.train": 0.05293585083090167},
}


@pytest.mark.parametrize("cell", sorted(READINGS))
def test_readings_of_the_cells_are_pinned(cell):
    from bench import harness
    c = harness.load_cell(cell)
    counts = {"held_pixels": 4096 * 255, "held_requests": 255,
              "n_samples": c.traffic["n_samples"], "window_s": 30.0,
              "tile_pixels": c.traffic.get("tile_pixels"), "steps": 82,
              "rays_per_step": c.traffic.get("batch_rays"), "train_s": 29.9}
    rctx = harness.ReadCtx(c, counts, {"phase_s": {"encode": 29.5,
                                                   "mlp": 0.08}},
                           peaks.peaks_for("TPU v5 lite"), 1)
    got = {m: harness.reader(m)(rctx) for m in READINGS[cell]}
    assert got == READINGS[cell]


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
