"""JAX-semantic rules (RJ2xx): import the live code and check the
contracts the AST layer cannot see.

These rules build *tiny* instances (4-element states, 8x8 cameras) and
inspect tracing artifacts — ``jax.eval_shape``, treedefs, lowered
StableHLO — never running real workloads, so the whole layer costs
milliseconds and works on any backend.

  RJ201 vmem-budget    — static VMEM estimate of every Table-I kernel
                         config vs the budget (repro.analysis.vmem).
  RJ202 bucket-retrace — the serve engine's one-trace-per-bucket
                         contract: Camera treedefs and leaf shapes must
                         be identical across viewpoints AND resolutions
                         (DESIGN.md §3), and equal BucketKeys must hash
                         equal so bucket lookup never re-traces.
  RJ203 donation       — ``TrainEngine._chunk_fn`` must actually donate
                         the state buffers when ``cfg.donate`` is set:
                         the lowered module carries ``tf.aliasing_output``
                         on the state operands (and must NOT when donate
                         is off).
"""
from __future__ import annotations

from typing import List

from repro.analysis.registry import Finding, rule

_ENGINE = "src/repro/serve/engine.py"
_RENDER = "src/repro/core/render.py"
_LOOP = "src/repro/train/loop.py"


@rule("vmem-budget", "RJ201", "semantic",
      "Static per-grid-step VMEM estimate of the four Pallas kernels for "
      "every Table-I (app, encoding) config at f32/bf16, from the "
      "kernels' own vmem_plan() BlockSpec mirrors, vs the budget.")
def check_vmem_budget() -> List[Finding]:
    from repro.analysis import vmem
    return vmem.check_vmem()


@rule("bucket-retrace", "RJ202", "semantic",
      "One-trace-per-bucket: Camera treedef/leaf-signature stability "
      "across viewpoints and resolutions, and BucketKey hash/eq "
      "stability across equal configs.")
def check_bucket_retrace() -> List[Finding]:
    import numpy as np
    import jax

    from repro.core import render
    from repro.core.fields import make_field_config
    from repro.serve.engine import BucketKey

    findings: List[Finding] = []

    # camera signature across viewpoint AND resolution families
    c2w_a = np.eye(4, dtype=np.float32)
    c2w_b = np.eye(4, dtype=np.float32)
    c2w_b[:3, 3] = (1.0, -2.0, 3.0)
    cams = [render.Camera(8, 8, 10.0, c2w_a),
            render.Camera(8, 8, 10.0, c2w_b),      # new viewpoint
            render.Camera(32, 48, 55.0, c2w_b)]    # new resolution
    sigs = [jax.tree_util.tree_flatten(c) for c in cams]
    treedefs = {str(s[1]) for s in sigs}
    if len(treedefs) != 1:
        findings.append(Finding(
            rule="bucket-retrace", code="RJ202", path=_RENDER, line=0,
            message=(f"Camera treedef differs across viewpoints/"
                     f"resolutions ({treedefs}) — every new camera would "
                     f"re-trace the bucket executable (DESIGN.md §3)")))
    shapes = {tuple((leaf.shape, str(leaf.dtype)) for leaf in s[0])
              for s in sigs}
    if len(shapes) != 1:
        findings.append(Finding(
            rule="bucket-retrace", code="RJ202", path=_RENDER, line=0,
            message=(f"Camera leaf shapes/dtypes differ across cameras "
                     f"({shapes}) — resolution must be *data* (the "
                     f"(3,) intrinsics vector), never a leaf shape")))
    aux = [jax.tree_util.tree_flatten(c)[1] for c in cams]
    try:
        {a for a in aux}
    except TypeError:
        findings.append(Finding(
            rule="bucket-retrace", code="RJ202", path=_RENDER, line=0,
            message=("Camera tree_flatten aux_data is unhashable — jit "
                     "cannot cache traces keyed on it (the no-static-aux "
                     "contract; aux must be None)")))

    # BucketKey: equal configs -> equal, hashable keys (no retrace)
    def key(cfg):
        return BucketKey(app=cfg.app, encoding=cfg.grid.kind,
                         tile_pixels=4096, n_samples=32,
                         dtype="float32", cfg=cfg)
    k1 = key(make_field_config("nerf", "hash"))
    k2 = key(make_field_config("nerf", "hash"))
    try:
        ok = hash(k1) == hash(k2) and k1 == k2 and {k1: 1}[k2] == 1
    except TypeError:
        ok = False
    if not ok:
        findings.append(Finding(
            rule="bucket-retrace", code="RJ202", path=_ENGINE, line=0,
            message=("equal BucketKeys do not hash/compare equal — every "
                     "request would miss the bucket cache and re-trace; "
                     "keep BucketKey and FieldConfig frozen, hashable "
                     "dataclasses")))
    return findings


@rule("donation", "RJ203", "semantic",
      "TrainEngine chunk donation: with cfg.donate the lowered chunk "
      "carries tf.aliasing_output on the state operands (buffers are "
      "actually reused), and without it it must not.")
def check_donation() -> List[Finding]:
    import jax
    import jax.numpy as jnp

    from repro.train.loop import EngineConfig, TrainEngine

    findings: List[Finding] = []

    def step_fn(state, step, batch):
        del step
        new = {"w": state["w"] + 0.1 * jnp.sum(batch)}
        return new, {"loss": jnp.sum(batch)}

    def batch_fn(step, state):
        return jnp.ones((4,), jnp.float32) * step

    state = {"w": jnp.zeros((4,), jnp.float32)}

    def lowered_text(donate: bool) -> str:
        eng = TrainEngine(
            EngineConfig(steps=2, chunk_steps=2, donate=donate),
            step_fn, device_batch_fn=batch_fn)
        return eng._chunk_fn(2).lower(state, jnp.int32(0)).as_text()

    marker = "tf.aliasing_output"
    if marker not in lowered_text(True):
        findings.append(Finding(
            rule="donation", code="RJ203", path=_LOOP, line=0,
            message=("cfg.donate=True but the lowered chunk carries no "
                     f"{marker} aliasing — state buffers are being copied "
                     "every chunk instead of reused (donate_argnums lost "
                     "in _chunk_fn?)")))
    if marker in lowered_text(False):
        findings.append(Finding(
            rule="donation", code="RJ203", path=_LOOP, line=0,
            message=("cfg.donate=False yet the lowered chunk aliases its "
                     "inputs — callers that reuse the passed state would "
                     "read invalidated buffers")))
    return findings
