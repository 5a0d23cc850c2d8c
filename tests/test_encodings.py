"""Unit + property tests for the input-encoding layer (paper §II-A)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import encoding as enc


def test_hash_index_range_and_mask_equivalence():
    """Eq. 1: power-of-two T means mod == AND-mask (the NGPC shift trick)."""
    coords = jax.random.randint(jax.random.PRNGKey(0), (512, 3), 0, 10000)
    for log2_T in (4, 14, 19):
        T = 1 << log2_T
        idx = enc.hash_index(coords, T)
        assert int(idx.min()) >= 0 and int(idx.max()) < T
        # reference modulo implementation
        acc = coords[:, 0].astype(jnp.uint32) * np.uint32(enc.HASH_PRIMES[0])
        for i in (1, 2):
            acc = acc ^ (coords[:, i].astype(jnp.uint32)
                         * np.uint32(enc.HASH_PRIMES[i]))
        np.testing.assert_array_equal(
            np.asarray(idx), np.asarray((acc % T).astype(jnp.int32)))


def test_dense_index_bijective_on_small_grid():
    res = 7
    cfg = enc.GridConfig(dim=3, log2_table_size=10)
    coords = jnp.stack(jnp.meshgrid(*[jnp.arange(res + 1)] * 3,
                                    indexing="ij"), -1).reshape(-1, 3)
    idx = enc.dense_index(coords, res, cfg.table_size)
    assert len(np.unique(np.asarray(idx))) == (res + 1) ** 3


def test_level_resolution_growth():
    cfg = enc.hashgrid_config()
    res = [cfg.level_resolution(l) for l in range(cfg.n_levels)]
    assert res[0] == 16 and all(b > a for a, b in zip(res, res[1:]))
    # paper: coarse levels dense, fine levels hashed
    hashed = [cfg.level_is_hashed(l) for l in range(cfg.n_levels)]
    assert not hashed[0] and hashed[-1]
    assert hashed == sorted(hashed)   # monotone switch


def test_table_param_bound():
    cfg = enc.hashgrid_config()
    assert cfg.params_bound() == 2 ** 19 * 16 * 2   # T*L*F (paper §II-A)


@pytest.mark.parametrize("kind,dim", [("hash", 3), ("dense", 3),
                                      ("tiled", 2)])
def test_encoding_shapes_and_finiteness(kind, dim):
    mk = {"hash": enc.hashgrid_config, "dense": enc.densegrid_config,
          "tiled": enc.tiledgrid_config}[kind]
    cfg = dataclasses.replace(mk(dim=dim), log2_table_size=10)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value
    pts = jax.random.uniform(jax.random.PRNGKey(1), (64, dim))
    out = enc.grid_encode(pts, tables, cfg)
    assert out.shape == (64, cfg.out_dim)
    assert bool(jnp.isfinite(out).all())


@settings(max_examples=20, deadline=None)
@given(st.floats(0.01, 0.99), st.floats(0.01, 0.99), st.floats(0.01, 0.99))
def test_encoding_is_continuous(x, y, z):
    """d-linear interpolation: a tiny step moves the encoding by O(step)."""
    cfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=10,
                              n_levels=4)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value * 1e4
    p = jnp.array([[x, y, z]], jnp.float32)
    eps = 1e-6
    a = enc.grid_encode(p, tables, cfg)
    b = enc.grid_encode(p + eps, tables, cfg)
    # lipschitz: |f(p+e)-f(p)| <= max_res * e * d * max|feat| * margin
    bound = cfg.level_resolution(cfg.n_levels - 1) * eps * 3 * \
        float(jnp.abs(tables).max()) * 8
    assert float(jnp.abs(a - b).max()) <= bound + 1e-5


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_encoding_batch_equivariance(seed):
    """Encoding is a per-point map: permuting inputs permutes outputs."""
    cfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=8,
                              n_levels=3)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value
    pts = jax.random.uniform(jax.random.PRNGKey(seed % 2**31), (32, 3))
    perm = jax.random.permutation(jax.random.PRNGKey(1), 32)
    a = enc.grid_encode(pts, tables, cfg)[perm]
    b = enc.grid_encode(pts[perm], tables, cfg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_sh_encoding_degree4():
    d = jax.random.normal(jax.random.PRNGKey(0), (128, 3))
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    sh = enc.sh_encode(d)
    assert sh.shape == (128, 16)
    # band 0 is constant
    np.testing.assert_allclose(np.asarray(sh[:, 0]), 0.282095, atol=1e-5)


def test_frequency_encoding():
    x = jnp.zeros((4, 3))
    out = enc.frequency_encode(x, n_freqs=6)
    assert out.shape == (4, 3 * 12)
    # layout: per input dim, [sin(6 freqs) | cos(6 freqs)]
    blocks = np.asarray(out).reshape(4, 3, 2, 6)
    np.testing.assert_allclose(blocks[:, :, 0], 0.0, atol=1e-6)  # sin(0)
    np.testing.assert_allclose(blocks[:, :, 1], 1.0, atol=1e-6)  # cos(0)


def test_grad_sparsity_of_hash_tables():
    """Only touched rows receive gradient (basis for sparse-grad
    compression in multi-host field training)."""
    cfg = dataclasses.replace(enc.hashgrid_config(), log2_table_size=12,
                              n_levels=2)
    tables = enc.init_grid(jax.random.PRNGKey(0), cfg).value

    def loss(t):
        pts = jax.random.uniform(jax.random.PRNGKey(1), (8, 3))
        return jnp.sum(enc.grid_encode(pts, t, cfg) ** 2)

    g = jax.grad(loss)(tables)
    touched = jnp.any(g != 0, axis=-1)
    frac = float(jnp.mean(touched))
    assert 0 < frac < 0.1   # 8 points touch <= 8*8 rows of 4096


def _program(hlo: str) -> str:
    """Optimized HLO without its module name, metadata and the source
    tables that metadata points into."""
    import re
    out, skip = [], False
    for line in hlo.splitlines()[1:]:
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and line.startswith("%"):
            skip = False
        if not skip:
            out.append(re.sub(r",?\s*metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def test_level_scopes_name_each_level_and_leave_the_program_unchanged():
    """Each level of the encode runs in a ``lvlNN_hash``/``lvlNN_dense``
    scope that the compiled HLO's op_name keeps inside the ``encode``
    phase, forward and under the gradient's ``transpose(``; with metadata
    stripped the optimized HLO equals that of the same levels encoded
    without scopes."""
    from repro.obs.trace import annotate
    cfg = enc.GridConfig(dim=3, n_levels=4, n_features=2,
                         log2_table_size=8, base_resolution=4, growth=1.5)
    scopes = [enc.level_scope(cfg, l) for l in range(4)]
    assert scopes == ["lvl00_dense", "lvl01_hash", "lvl02_hash",
                      "lvl03_hash"]
    pts = jax.random.uniform(jax.random.PRNGKey(0), (64, 3))
    tables = enc.init_grid(jax.random.PRNGKey(1), cfg).value

    def plain(points, t):
        with annotate("encode"):
            return jnp.concatenate([enc.encode_level(points, t[l], l, cfg)
                                    for l in range(cfg.n_levels)], axis=-1)

    def scoped(points, t):
        with annotate("encode"):
            return enc.grid_encode(points, t, cfg)

    def grad(f):
        return jax.grad(lambda points, t: jnp.sum(f(points, t) ** 2), 1)

    texts = {}
    for name, f, g in (("forward", scoped, plain),
                       ("grad", grad(scoped), grad(plain))):
        texts[name] = jax.jit(f).lower(pts, tables).compile().as_text()
        want = jax.jit(g).lower(pts, tables).compile().as_text()
        assert _program(texts[name]) == _program(want)
    for s in scopes:
        assert f"/encode/{s}/" in texts["forward"]
        assert f"/transpose(jvp(encode))/{s}/" in texts["grad"]


# three levels each: level 0 has one cell, so every point's 2^d corners
# land on the same 2^d rows (every update collides); the finer levels
# leave rows no point touches (the points fill a quarter of the domain
# or less), and where (res+1)^d < T rows no index can address
GRAD_CASES = {
    "hash": lambda d: enc.GridConfig(dim=d, n_levels=3, n_features=2,
                                     log2_table_size=10, base_resolution=1,
                                     growth=4.0, kind="hash"),
    "dense": lambda d: enc.GridConfig(dim=d, n_levels=3, n_features=2,
                                      log2_table_size=10, base_resolution=1,
                                      growth=4.0, kind="dense"),
    "tiled": lambda d: enc.GridConfig(dim=d, n_levels=2, n_features=8,
                                      log2_table_size=10, base_resolution=1,
                                      growth=16.0, kind="tiled"),
}


def _take_level(points, table, level, cfg):
    """``encode_level`` with its corner gathers written as plain
    ``jnp.take``: autodiff then transposes them to XLA's scatter-add."""
    res = cfg.level_resolution(level)
    pos = points.astype(jnp.float32) * res
    cell = jnp.floor(pos)
    frac = pos - cell
    cell = jnp.clip(cell.astype(jnp.int32), 0, res - 1)
    offsets = enc._corner_offsets(cfg.dim)
    idx = []
    for c in range(offsets.shape[0]):
        corner = cell + offsets[c][None, :]
        idx.append(enc.hash_index(corner, cfg.table_size)
                   if cfg.level_is_hashed(level)
                   else enc.dense_index(corner, res, cfg.table_size))
    feats = [jnp.take(table, i, axis=0) for i in idx]
    out = jnp.zeros((points.shape[0], cfg.n_features), jnp.float32)
    for c in range(offsets.shape[0]):
        w = jnp.prod(
            jnp.where(offsets[c][None, :] == 1, frac, 1.0 - frac), axis=-1)
        out = out + w[:, None] * feats[c].astype(jnp.float32)
    return out


def _take_encode(points, tables, cfg):
    from repro.obs.trace import annotate
    feats = []
    for l in range(cfg.n_levels):
        with annotate(enc.level_scope(cfg, l)):
            feats.append(_take_level(points, tables[l], l, cfg))
    return jnp.concatenate(feats, axis=-1)


def _grad_case(kind, dim):
    cfg = GRAD_CASES[kind](dim)
    pts = jax.random.uniform(jax.random.PRNGKey(0), (512, dim)) * 0.5
    tables = jax.random.normal(jax.random.PRNGKey(1),
                               (cfg.n_levels, cfg.table_size,
                                cfg.n_features))
    ct = jax.random.normal(jax.random.PRNGKey(2), (512, cfg.out_dim))
    return cfg, pts, tables, ct


@pytest.mark.parametrize("kind", ["hash", "dense", "tiled"])
@pytest.mark.parametrize("dim", [2, 3])
def test_grid_encode_gradient_equals_take_scatter_add(kind, dim):
    """The table and points gradients of ``grid_encode`` (sorted-row sum)
    equal those of plain ``jnp.take`` (XLA's scatter-add) to summation
    order, f32 at 1e-6 of the largest; rows no point touches, and rows
    past what the level can address, read exactly zero."""
    cfg, pts, tables, ct = _grad_case(kind, dim)

    def grads(encode):
        return jax.jit(jax.grad(
            lambda p, t: jnp.sum(encode(p, t, cfg) * ct), (0, 1)))(
                pts, tables)

    (gp, gt), (wp, wt) = grads(enc.grid_encode), grads(_take_encode)
    for got, want in ((gp, wp), (gt, wt)):
        got, want = np.asarray(got), np.asarray(want)
        scale = np.abs(want).max()
        assert scale > 0
        assert np.abs(got - want).max() <= 1e-6 * scale
    gt, wt = np.asarray(gt), np.asarray(wt)
    # level 0: 512 points x 2^d corners on its 2^d rows
    assert cfg.level_rows(0) == 2 ** dim
    assert np.all(wt[0, :2 ** dim] != 0) and np.all(wt[0, 2 ** dim:] == 0)
    untouched = np.all(wt == 0, axis=-1)
    assert untouched[1:].sum() > 0
    np.testing.assert_array_equal(gt[untouched], 0.0)


# two or three levels each, with points at 0.0 and 1.0 on every axis and
# at every corner of the unit cube: hash mixes dense and hashed levels;
# dense and tiled levels hold more than T rows in 3-D, and tiled in 2-D
# too, so that their index wraps into T
FORWARD_CASES = {
    "hash": lambda d: enc.GridConfig(dim=d, n_levels=3, n_features=2,
                                     log2_table_size=8, base_resolution=2,
                                     growth=3.0, kind="hash"),
    "dense": lambda d: enc.GridConfig(dim=d, n_levels=3, n_features=2,
                                      log2_table_size=10, base_resolution=2,
                                      growth=3.0, kind="dense"),
    "tiled": lambda d: enc.GridConfig(dim=d, n_levels=2, n_features=8,
                                      log2_table_size=8, base_resolution=20,
                                      growth=1.0, kind="tiled"),
}


@pytest.mark.parametrize("kind", ["hash", "dense", "tiled"])
@pytest.mark.parametrize("dim", [2, 3])
def test_grid_encode_forward_equals_per_corner_takes(kind, dim):
    """``grid_encode`` (one brick row per point on a non-hashed level) is
    bitwise the encode written as 2^d plain ``jnp.take`` per level."""
    cfg = FORWARD_CASES[kind](dim)
    hashed = [cfg.level_is_hashed(l) for l in range(cfg.n_levels)]
    assert any(hashed) == (kind == "hash") and not all(hashed)
    wraps = [(cfg.level_resolution(l) + 1) ** dim > cfg.table_size
             for l in range(cfg.n_levels)]
    assert any(w and not h for w, h in zip(wraps, hashed)) == (
        kind == "tiled" or (kind == "dense" and dim == 3))
    cube = enc._corner_offsets(dim).astype(np.float32)
    pts = jnp.concatenate([
        jax.random.uniform(jax.random.PRNGKey(0), (256, dim)),
        jnp.asarray(cube), jnp.full((1, dim), 0.5).at[0, 0].set(0.0),
        jnp.full((1, dim), 0.5).at[0, -1].set(1.0)])
    tables = jax.random.normal(jax.random.PRNGKey(1),
                               (cfg.n_levels, cfg.table_size,
                                cfg.n_features))
    got = jax.jit(enc.grid_encode, static_argnums=2)(pts, tables, cfg)
    want = jax.jit(_take_encode, static_argnums=2)(pts, tables, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ["hash", "dense", "tiled"])
@pytest.mark.parametrize("dim", [2, 3])
def test_grid_encode_forward_is_the_take_program_and_its_gradient_sorts(
        kind, dim):
    """In each level's scope the forward's optimized HLO holds 2^d gathers
    on a hashed level, and one gather of a brick built under a ``brick``
    scope on a non-hashed one; the gradient's holds each level's sorts
    under ``transpose(jvp(encode))/lvlNN_kind/rowsum/`` and no scatter."""
    from repro.obs.trace import annotate
    cfg, pts, tables, ct = _grad_case(kind, dim)

    def scoped(encode):
        def f(p, t):
            with annotate("encode"):
                return encode(p, t, cfg)
        return f

    def grad(f):
        return jax.grad(lambda p, t: jnp.sum(f(p, t) * ct), (0, 1))

    def text(f):
        return jax.jit(f).lower(pts, tables).compile().as_text()

    forward = text(scoped(enc.grid_encode))
    for l in range(cfg.n_levels):
        scope = f"/encode/{enc.level_scope(cfg, l)}/"
        gathers = [line for line in forward.splitlines()
                   if " gather(" in line and scope in line]
        hashed = cfg.level_is_hashed(l)
        assert len(gathers) == (2 ** dim if hashed else 1), scope
        assert (f"{scope}brick/" in forward) == (not hashed), scope
    assert " scatter(" in text(grad(scoped(_take_encode)))
    backward = text(grad(scoped(enc.grid_encode)))
    assert " scatter(" not in backward
    for l in range(cfg.n_levels):
        scope = f"/transpose(jvp(encode))/{enc.level_scope(cfg, l)}/rowsum/"
        sorts = [line for line in backward.splitlines()
                 if " sort(" in line and scope in line]
        assert len(sorts) == 2, scope


def test_row_sum_matches_scatter_add():
    """``row_sum`` against ``.at[].add``: runs of every length, one row
    holding every update, and rows with none."""
    rng = np.random.default_rng(0)
    row_sum = jax.jit(enc.row_sum, static_argnums=2)
    for rows, n in ((1, 300), (7, 5000), (200, 5000), (4096, 100)):
        idx = jnp.asarray(rng.integers(0, rows, n), jnp.int32)
        vals = jnp.asarray(rng.normal(size=(n, 3)), jnp.float32)
        got = np.asarray(row_sum(idx, vals, rows))
        want = np.asarray(jnp.zeros((rows, 3)).at[idx].add(vals))
        assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
        np.testing.assert_array_equal(got[np.all(want == 0, -1)], 0.0)
