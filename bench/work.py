"""Operations and bytes the field's work needs, computed from its shapes.

These count the work itself and not how an implementation does it, so that
the same work reads the same count whatever a later change implements it
with. ``config`` is the dict of a configuration file under
``bench/configs``, and ``grid`` its ``grid``.

Grid encode, per point and level: scale the point (d), its fraction (d) and
one minus it (d), then for each of the 2^d corners the weight's product
(d - 1) and the weighted accumulate (2F). Table traffic per level is the
smaller of the rows the level uses and the corners gathered, times F times
the table's bytes; the points are read and the features written once. The
backward pass (training) adds 2F per corner into the table gradient,
reads the features' gradient and writes the table gradient with the same
row count.

MLP (no biases): 2 * (in*h + (layers - 1)*h*h + h*out) per point forward;
its bytes are the weights once, the features in and the outputs out. A
backward pass costs twice the forward's operations. A field's MLPs are
those its configuration states, each at its own input width: ``mlp``
alone on the grid's L*F features, or a ``density_mlp`` on them whose
output goes on beside the direction's spherical harmonics to ``mlp``.

Direction encode (degree 4, 16 terms): the six pairwise products of x, y,
z, then the terms as instant-NGP writes them, 41 operations per point; it
reads the direction and writes its terms. The direction is data, so no
backward pass.
"""
from __future__ import annotations

import math

F32 = 4


def level_resolution(grid: dict, level: int) -> int:
    return int(math.floor(grid["base_resolution"] * grid["growth"] ** level))


def level_is_hashed(grid: dict, level: int) -> bool:
    """Dense 1:1 rows while the level's vertices fit in the table."""
    if grid["kind"] != "hash":
        return False
    res = level_resolution(grid, level)
    return (res + 1) ** grid["dim"] > 1 << grid["log2_table_size"]


def level_rows(grid: dict, level: int) -> int:
    """Rows of the level's table that any point can reach."""
    vertices = (level_resolution(grid, level) + 1) ** grid["dim"]
    return min(vertices, 1 << grid["log2_table_size"])


def corners(grid: dict) -> int:
    return 1 << grid["dim"]


def encode_flops(grid: dict, n_points: int, backward: bool = False) -> float:
    d, f = grid["dim"], grid["n_features"]
    per_level = 3 * d + corners(grid) * ((d - 1) + 2 * f)
    if backward:
        per_level += corners(grid) * 2 * f
    return float(n_points) * grid["n_levels"] * per_level


def table_bytes_touched(grid: dict, n_points: int,
                        dtype_bytes: int = F32) -> float:
    rows = sum(min(level_rows(grid, l), corners(grid) * n_points)
               for l in range(grid["n_levels"]))
    return float(rows) * grid["n_features"] * dtype_bytes


def encode_bytes(grid: dict, n_points: int, backward: bool = False,
                 dtype_bytes: int = F32) -> float:
    feats = float(n_points) * grid["n_levels"] * grid["n_features"] * F32
    points = float(n_points) * grid["dim"] * F32
    total = table_bytes_touched(grid, n_points, dtype_bytes) + points + feats
    if backward:
        # the features' gradient in, the table gradient out
        total += feats + table_bytes_touched(grid, n_points, dtype_bytes)
    return total


def mlps(config: dict) -> list:
    """(input width, MLP dict) of each of the field's MLPs."""
    g = config["grid"]
    width = g["n_levels"] * g["n_features"]
    density = config.get("density_mlp")
    if density is None:
        return [(width, config["mlp"])]
    return [(width, density),
            (config["sh_degree"] ** 2 + density["out_dim"], config["mlp"])]


def mlp_weights(config: dict) -> int:
    total = 0
    for in_dim, m in mlps(config):
        h, n = m["hidden_dim"], m["n_hidden"]
        total += in_dim * h + (n - 1) * h * h + h * m["out_dim"]
    return total


def mlp_flops(config: dict, n_points: int, backward: bool = False) -> float:
    fwd = 2.0 * mlp_weights(config) * n_points
    return 3.0 * fwd if backward else fwd


def mlp_bytes(config: dict, n_points: int, dtype_bytes: int = F32) -> float:
    io = sum(in_dim + m["out_dim"] for in_dim, m in mlps(config))
    return (float(mlp_weights(config)) * dtype_bytes
            + float(n_points) * io * F32)


# operations per point of the direction encode, by its degree
SH_FLOPS = {4: 41}


def dir_encode_flops(config: dict, n_points: int) -> float:
    if "sh_degree" not in config:
        return 0.0
    return float(n_points) * SH_FLOPS[config["sh_degree"]]


def dir_encode_bytes(config: dict, n_points: int) -> float:
    if "sh_degree" not in config:
        return 0.0
    return float(n_points) * (3 + config["sh_degree"] ** 2) * F32


def field_encode(config: dict, n_points: int, backward: bool = False):
    """(operations, bytes) of the field's encode: the grid encode and,
    where the field has one, the direction encode."""
    g = config["grid"]
    return (encode_flops(g, n_points, backward)
            + dir_encode_flops(config, n_points),
            encode_bytes(g, n_points, backward)
            + dir_encode_bytes(config, n_points))


def field_flops(config: dict, n_points: int, backward: bool = False
                ) -> float:
    """The field's operations (encodes and MLPs), forward and, for
    training, backward; recomputed work does not count."""
    return (field_encode(config, n_points, backward)[0]
            + mlp_flops(config, n_points, backward))


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the larger of operations over peak and bytes over
    the memory's bandwidth, and which of the two it is."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
