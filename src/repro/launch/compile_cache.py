"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so the directory is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX
reads it itself, and this module sets nothing), otherwise ``.jax_cache``
at the repository root. Entry points call :func:`enable_compile_cache`
from ``main()``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory,
    unless the environment already names one; returns the directory in
    use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
