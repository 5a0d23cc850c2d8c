"""Shared Pallas kernel utilities."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def is_quantized_dtype(dtype) -> bool:
    """True for table storage dtypes the kernels dequantize in-kernel
    (``repro.quant`` codecs: int8 symmetric, fp8-e4m3). Quantized tables
    ride with a small f32 scale operand — see each kernel's
    ``vmem_plan`` — but the (g, T, F) table block itself stays in the
    storage dtype, so its VMEM bytes shrink by ``4 / itemsize``."""
    dt = jnp.dtype(dtype)
    return dt == jnp.dtype(jnp.int8) or dt == jnp.dtype(jnp.float8_e4m3fn)


def default_interpret() -> bool:
    """Pallas TPU kernels compile with Mosaic on a TPU backend and run in
    interpret mode on any other backend (CPU tests validate the body)."""
    return not on_tpu()


def pad_batch(x: jnp.ndarray, block: int):
    """Pad dim 0 up to a multiple of ``block``. Returns (padded, orig_n)."""
    n = x.shape[0]
    padded = -(-n // block) * block
    if padded == n:
        return x, n
    pad = [(0, padded - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad), n


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# --------------------------------------------------------------- VMEM budget
# TPU cores have ~16 MB of VMEM. The encode/fused-field kernels keep a
# *group* of grid-table levels resident per grid step (DESIGN.md §2); the
# group size is the largest one whose table block fits this budget. The
# default is half the core's VMEM so the point/feature/weight blocks and
# Pallas's double-buffering always have headroom.
#
# This accounting is a *checked* contract: the static analysis suite
# (repro.analysis, DESIGN.md §9, rule RJ201 vmem-budget) recomputes the
# resident bytes of every Table-I kernel configuration from the kernels'
# BlockSpecs + grids and fails the lint gate if any config exceeds the
# budget. ``table_block_bytes`` below is the ONE shared formula — the
# runtime group picker and the static estimator both call it, and it
# reads the shape off the hashgrid kernel's actual BlockSpec, so the
# checker and the kernel tiling cannot drift.
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024
DEFAULT_VMEM_BUDGET_BYTES = VMEM_BYTES_PER_CORE // 2


def block_bytes(block_shape, dtype) -> int:
    """VMEM bytes of one resident block of ``block_shape`` and ``dtype``."""
    n = 1
    for s in block_shape:
        n *= int(s)
    return n * jnp.dtype(dtype).itemsize


def table_block_bytes(cfg, level_group: int, dtype) -> int:
    """VMEM bytes of one (level_group, T, F) table block.

    Derived from the hashgrid kernel's ``table_block_spec`` (the
    BlockSpec the ``pallas_call`` actually runs with) rather than a
    parallel hand-written product — the runtime picker
    (:func:`pick_level_group`) and the static VMEM estimator
    (``repro.analysis.vmem``) therefore share one source of truth."""
    from repro.kernels.hashgrid.hashgrid import table_block_spec
    return block_bytes(table_block_spec(cfg, level_group).block_shape, dtype)


def pick_level_group(cfg, dtype, vmem_budget_bytes: int | None = None) -> int:
    """Largest divisor of L whose (g, T, F) table block fits the budget.

    The floor is 1: at extreme table sizes (gia's log2_T=24) even a single
    level exceeds any realistic budget — row-tiling within a level is the
    documented follow-up (DESIGN.md §2), so we degrade to one level per
    step rather than refuse to run.

    The budget is gated on the TABLE block alone (dtype-aware through
    ``itemsize``, so int8/fp8 tables earn 4x larger groups — the freed
    VMEM is exactly the quantization win). The per-level scale ride-along
    of a quantized table is (g, 1, 1) f32 — 4g bytes, noise next to the
    MB-scale table block — and is charged by the static estimator
    (RJ201) but deliberately not here: charging it would split a group
    whose table block exactly meets the budget.
    """
    budget = (vmem_budget_bytes if vmem_budget_bytes is not None
              else DEFAULT_VMEM_BUDGET_BYTES)
    for g in range(cfg.n_levels, 0, -1):
        if cfg.n_levels % g == 0 and table_block_bytes(cfg, g, dtype) <= budget:
            return g
    return 1
