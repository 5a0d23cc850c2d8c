"""Ray generation, sampling, and volume compositing.

These are the paper's 'pre-processing' and 'post-processing' kernels — the
ones it fuses in Vulkan for a ~9.94x kernel-level win (Section I). Here they
are JAX functions that XLA fuses; the Pallas ``ray_march`` kernel fuses
sampling+compositing explicitly for the TPU path.

Compositing follows classical emission-absorption volume rendering
(paper refs [7], [11], [40]): alpha_i = 1 - exp(-sigma_i * dt_i),
T_i = prod_{j<i}(1 - alpha_j), C = sum_i T_i * alpha_i * c_i. The XLA
and Pallas composites share one transmittance formulation —
``exp(-prefix_sum(sigma*dt))`` — and differ only in how the prefix sum
is ordered (a scan here, a triangular matmul in the kernel), so the two
routes agree to a few f32 ulps.

``render_rays`` optionally runs occupancy-culled: samples in empty
space or behind an opaque prefix are compacted away and only a *static*
sample budget reaches the (dominant) encode+MLP cost — see
``core/occupancy.py`` and DESIGN.md §7 for the contract.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.trace import annotate


@jax.tree_util.register_pytree_node_class
class Camera:
    """Pinhole camera as *traced data*; pose is camera-to-world.

    The camera is a pytree of two arrays — ``intrinsics`` (3,) holding
    [height, width, focal] and the (4, 4) ``c2w`` pose — so it is passed
    as an *argument* into jitted render functions rather than baked into
    the traced closure. One compiled tile executable therefore serves
    arbitrary viewpoints and resolutions (the serve-engine contract,
    DESIGN.md §3); only pixel-count shapes, never camera values, are
    compile-time constants.

    ``height``/``width``/``focal`` are traced scalars. Host-side code that
    needs concrete frame dimensions (frame assembly, request generation)
    uses ``resolution``, which is only valid on concrete cameras.
    """

    def __init__(self, height=None, width=None, focal=None, c2w=None, *,
                 intrinsics=None):
        if intrinsics is None:
            intrinsics = jnp.stack([
                jnp.asarray(height, jnp.float32),
                jnp.asarray(width, jnp.float32),
                jnp.asarray(focal, jnp.float32)])
            c2w = jnp.asarray(c2w, jnp.float32)
        self.intrinsics = intrinsics
        self.c2w = c2w  # (4, 4)

    def tree_flatten(self):
        return (self.intrinsics, self.c2w), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(intrinsics=children[0], c2w=children[1])

    @property
    def height(self):
        return self.intrinsics[0]

    @property
    def width(self):
        return self.intrinsics[1]

    @property
    def focal(self):
        return self.intrinsics[2]

    @property
    def resolution(self) -> Tuple[int, int]:
        """(height, width) as python ints; concrete cameras only."""
        return int(self.intrinsics[0]), int(self.intrinsics[1])

    def __repr__(self):
        try:
            h, w = self.resolution
            return f"Camera({h}x{w}, focal={float(self.focal):.1f})"
        except (TypeError, jax.errors.TracerArrayConversionError):
            return "Camera(<traced>)"


def look_at(eye, target, up=(0.0, 0.0, 1.0)) -> jnp.ndarray:
    eye = jnp.asarray(eye, jnp.float32)
    target = jnp.asarray(target, jnp.float32)
    up = jnp.asarray(up, jnp.float32)
    fwd = target - eye
    fwd = fwd / jnp.linalg.norm(fwd)
    right = jnp.cross(fwd, up)
    right = right / jnp.linalg.norm(right)
    down = jnp.cross(fwd, right)
    c2w = jnp.eye(4, dtype=jnp.float32)
    c2w = c2w.at[:3, 0].set(right).at[:3, 1].set(down).at[:3, 2].set(fwd)
    return c2w.at[:3, 3].set(eye)


def make_rays(cam: Camera, pixel_ids: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """pixel_ids (R,) flat indices -> (origins (R,3), dirs (R,3)).

    All camera values are traced — the pixel-id decode divides by the
    *runtime* width (int32, exact), so one compiled executable serves any
    resolution/viewpoint."""
    w_i = cam.intrinsics[1].astype(jnp.int32)
    py = (pixel_ids // w_i).astype(jnp.float32)
    px = (pixel_ids % w_i).astype(jnp.float32)
    x = (px - cam.width * 0.5 + 0.5) / cam.focal
    y = (py - cam.height * 0.5 + 0.5) / cam.focal
    d_cam = jnp.stack([x, y, jnp.ones_like(x)], axis=-1)
    dirs = d_cam @ cam.c2w[:3, :3].T
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    origins = jnp.broadcast_to(cam.c2w[:3, 3], dirs.shape)
    return origins, dirs


def sample_along_rays(origins: jnp.ndarray, dirs: jnp.ndarray,
                      near: float, far: float, n_samples: int,
                      rng: Optional[jax.Array] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stratified sampling -> points (R, S, 3), dts (R, S)."""
    t = jnp.linspace(near, far, n_samples + 1)
    lo, hi = t[:-1], t[1:]
    if rng is not None:
        u = jax.random.uniform(rng, (origins.shape[0], n_samples))
    else:
        u = 0.5
    ts = lo[None, :] + (hi - lo)[None, :] * u          # (R, S)
    dts = jnp.diff(t)[None, :] * jnp.ones_like(ts)
    pts = origins[:, None, :] + ts[..., None] * dirs[:, None, :]
    return pts, dts


def composite(rgb: jnp.ndarray, sigma: jnp.ndarray, dts: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Emission-absorption integration.

    rgb (R, S, 3), sigma (R, S), dts (R, S) -> (pixel (R, 3), opacity (R,)).

    Transmittance is realized as ``exp(cumsum(-sigma*dt))`` — the
    formulation of the Pallas ``ray_march`` kernel (since
    ``1-alpha == exp(-sigma*dt)`` exactly, no ``log`` call and no
    epsilon are needed, and opaque samples stay finite). The kernel sums
    the same prefix as a triangular matmul, so the XLA/Pallas parity is
    a few f32 ulps rather than epsilon-noise-tolerant.
    """
    alpha = 1.0 - jnp.exp(-sigma * dts)                       # (R, S)
    log1m = -sigma * dts                                      # log(1-alpha)
    trans = jnp.exp(jnp.cumsum(log1m, axis=-1) - log1m)       # excl. scan
    w = trans * alpha                                          # (R, S)
    pixel = jnp.sum(w[..., None] * rgb, axis=-2)
    return pixel, jnp.sum(w, axis=-1)


def normalize_to_unit(points: jnp.ndarray, lo: float = -2.0,
                      hi: float = 2.0) -> jnp.ndarray:
    """World coords -> [0,1]^d for the grid encoding (the paper's
    'normalized input coordinates' entering the input FIFO)."""
    return jnp.clip((points - lo) / (hi - lo), 0.0, 1.0)


def _cull_mask(occupancy: Dict, unit_pts: jnp.ndarray, dts: jnp.ndarray,
               early_term_eps: float) -> jnp.ndarray:
    """Live mask (R, S): occupied cell AND prefix still transmissive.

    (a) Empty-space skip: a sample whose occupancy cell is empty is dead.
    (b) Early termination: a cheap prefix-transmittance *estimate* from
    the grid's coarse sigma (``T_est = exp(-cumsum(sigma_est*dt))``,
    exclusive) marks samples behind an already-opaque prefix dead. Both
    are VPU-cheap (int gather + bit test, one float gather + cumsum) —
    no field evaluation happens before the mask."""
    from repro.core import occupancy as occ_mod
    r, s, _ = unit_pts.shape
    flat = unit_pts.reshape(-1, 3)
    live = occ_mod.query(occupancy, flat).reshape(r, s)
    sig_est = occ_mod.query_sigma(occupancy, flat).reshape(r, s)
    od = sig_est * dts                         # per-sample optical depth
    acc = jnp.cumsum(od, axis=-1) - od         # exclusive prefix
    return live & (acc < -math.log(early_term_eps))


def render_rays(field_apply: Callable, origins: jnp.ndarray,
                dirs: jnp.ndarray, *, near: float = 0.5, far: float = 4.5,
                n_samples: int = 32, rng: Optional[jax.Array] = None,
                use_pallas_composite: bool = False,
                occupancy: Optional[Dict] = None,
                sample_budget: Optional[int] = None,
                early_term_eps: float = 1e-3,
                return_aux: bool = False):
    """Full per-ray pipeline: sample -> field -> composite. (R,) rays.

    ``field_apply(points (N,3), dirs (N,3)) -> (N, 4) [rgb, sigma]``.

    With ``occupancy`` (a ``core/occupancy.py`` grid) the march is
    *culled*: dead samples — empty cell, or prefix already opaque — are
    partitioned behind live ones by a stable argsort on the dead mask
    (fixed shape, no host sync), the field evaluates only a **static**
    ``sample_budget``-sample prefix (default ``R*S``: exactly the dense
    cost), and results scatter back with dead samples forced to
    ``sigma = 0`` before compositing. If live samples exceed the budget
    the *farthest* ones fall off the prefix first (near samples
    dominate the emission-absorption integral) and ``aux['n_dropped']``
    reports the overflow — degradation is graceful and observable,
    never silent. With occupancy ``None`` the dense path runs
    unchanged; with an all-occupied grid and a full budget the culled
    path is bit-identical to it (DESIGN.md §7).

    ``return_aux`` additionally returns ``{'n_live', 'n_budget',
    'n_dropped'}`` (traced int32 scalars; ``n_budget`` is the static
    evaluation count).
    """
    n_rays = origins.shape[0]
    # phase scopes (DESIGN.md §8): raymarch = sampling bookkeeping,
    # compact = cull mask + static-budget sort, composite = integration
    with annotate("raymarch"):
        pts, dts = sample_along_rays(origins, dirs, near, far, n_samples,
                                     rng)
        flat_pts = normalize_to_unit(pts.reshape(-1, 3))
        flat_dirs = jnp.repeat(dirs, n_samples, axis=0)
    n_total = n_rays * n_samples

    if occupancy is None:
        out = field_apply(flat_pts, flat_dirs)             # (R*S, 4)
        out = out.reshape(n_rays, n_samples, 4)
        rgb, sigma = out[..., :3], out[..., 3]
        aux = {"n_live": jnp.int32(n_total), "n_budget": n_total,
               "n_dropped": jnp.int32(0)}
    else:
        budget = (n_total if sample_budget is None
                  else max(1, min(int(sample_budget), n_total)))
        with annotate("compact"):
            live = _cull_mask(occupancy, flat_pts.reshape(
                n_rays, n_samples, 3), dts, early_term_eps)    # (R, S)
            # Drop-order key: live samples first, ordered near-to-far (the
            # march index s), dead last — so budget overflow sheds the
            # farthest live samples first. Stable sort keeps ray order
            # within a depth slice deterministic.
            s_idx = jnp.broadcast_to(
                jnp.arange(n_samples, dtype=jnp.int32)[None, :],
                (n_rays, n_samples))
            key = jnp.where(live, s_idx, s_idx + n_samples).reshape(-1)
            order = jnp.argsort(key, stable=True)              # (R*S,)
            sel = order[:budget]                               # static shape
        out_sel = field_apply(flat_pts[sel], flat_dirs[sel])  # (budget, 4)
        with annotate("compact"):
            out = jnp.zeros((n_total, 4),
                            out_sel.dtype).at[sel].set(out_sel)
            out = out.reshape(n_rays, n_samples, 4)
            rgb = out[..., :3]
            # dead-in-budget samples carry garbage -> force transparent;
            # live-beyond-budget samples were never written -> already 0.
            sigma = jnp.where(live, out[..., 3], 0.0)
            n_live = jnp.sum(live, dtype=jnp.int32)
            aux = {"n_live": n_live, "n_budget": budget,
                   "n_dropped": jnp.maximum(n_live - budget, 0)}

    with annotate("composite"):
        if use_pallas_composite:
            from repro.kernels.ray_march import ops as rm_ops
            pixel, _ = rm_ops.composite(rgb, sigma, dts)
        else:
            pixel, _ = composite(rgb, sigma, dts)
    return (pixel, aux) if return_aux else pixel
