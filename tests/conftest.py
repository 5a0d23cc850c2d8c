"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches see
the real single CPU device; multi-device tests spawn subprocesses."""
import dataclasses
import sys

import jax
import pytest

# Tests keep JAX's persistent compilation cache off, even where
# JAX_COMPILATION_CACHE_DIR is set: compiles for a described (absent) TPU
# would be written to it and could not be read back. Tier-1 speed comes
# from the `slow` marker and shrunk test configs instead.
jax.config.update("jax_enable_compilation_cache", False)

# Property tests import `hypothesis`; the hermetic container image may not
# ship it (it is declared in pyproject's dev extras). Gate in the vendored
# deterministic stub so those modules still collect and run. The real
# package always wins when installed.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    from repro._compat import hypothesis_stub
    sys.modules["hypothesis"] = hypothesis_stub
    sys.modules["hypothesis.strategies"] = hypothesis_stub.strategies


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def small_grid(cfg_grid, log2_T=12):
    return dataclasses.replace(cfg_grid, log2_table_size=log2_T)


def small_field_config(app: str, encoding: str, log2_T: int = 12,
                       n_levels: int | None = None):
    """Paper config shrunk to test scale. ``n_levels`` additionally cuts
    the level count (kernel tests: interpret-mode cost is linear in L and
    the per-level math is level-count-invariant)."""
    from repro.core import fields
    cfg = fields.make_field_config(app, encoding)
    g = dataclasses.replace(cfg.grid, log2_table_size=log2_T)
    if n_levels is not None:
        g = dataclasses.replace(g, n_levels=n_levels)
    return cfg.with_grid(g)
