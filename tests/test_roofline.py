"""Roofline extraction: HLO collective parsing + term math."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import roofline

REPO = str(Path(__file__).resolve().parents[1])

HLO = """
HloModule jit_f, entry_computation_layout={...}

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y)
}

%while_body (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %gte = f32[128,256]{1,0} get-tuple-element(%p), index=1
  %ar.1 = f32[128,256]{1,0} all-reduce(%gte), channel_id=5, to_apply=%add.clone
  ROOT %t = (s32[], f32[128,256]) tuple(%gte, %ar.1)
}

ENTRY %main (param: f32[1024,128]) -> f32[32,1024] {
  %param = f32[1024,128]{1,0} parameter(0)
  %all-gather = f32[1024,128]{1,0} all-gather(%param), channel_id=1, replica_groups=[8,8]<=[8,8]T(1,0), dimensions={0}
  %copy = f32[32,1024]{0,1} copy(%all-gather)
  %all-gather.1 = f32[32,1024]{0,1} all-gather(%copy), channel_id=3, dimensions={1}
  %dot.1 = f32[128,1024]{1,0} dot(%param, %all-gather.1)
  %all-reduce = f32[128,1024]{1,0} all-reduce(%dot.1), channel_id=2, to_apply=%add.clone
  %rs = bf16[16,512]{1,0} reduce-scatter(%all-reduce), channel_id=7, dimensions={0}
  %cp-start = f32[32,1024]{0,1} collective-permute-start(%copy), channel_id=9
  %cp-done = f32[32,1024]{0,1} collective-permute-done(%cp-start)
  ROOT %out = f32[32,1024]{0,1} copy(%cp-done)
}
"""


def test_collective_bytes_parser():
    got = roofline.collective_bytes(HLO)
    f32 = 4
    assert got["all-gather"] == (1024 * 128 + 32 * 1024) * f32
    # two all-reduces: one in while body (128*256), one in entry (128*1024)
    assert got["all-reduce"] == (128 * 256 + 128 * 1024) * f32
    assert got["reduce-scatter"] == 128 * 1024 * f32   # operand is f32
    # permute: -start counted once, -done skipped
    assert got["collective-permute"] == 32 * 1024 * f32
    assert got["total"] == sum(got[k] for k in roofline.COLLECTIVE_OPS)


def test_param_scoping():
    """%param names repeat per computation; sizes must not leak."""
    got = roofline.collective_bytes(HLO)
    assert got["n_all-reduce"] == 2


def test_rooflines_dominance():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    rl = roofline.rooflines(cost, coll_bytes=0, chips=256)
    assert rl["dominant"] == "memory_s"
    assert abs(rl["compute_s"] - 1.0) < 1e-6
    assert abs(rl["memory_s"] - 2.0) < 1e-6


def test_model_flops_train_vs_decode():
    from repro.configs.shapes import SHAPES
    n = 7_000_000_000
    tr = roofline.model_flops(None, SHAPES["train_4k"], n)
    assert tr == 6.0 * n * 4096 * 256
    de = roofline.model_flops(None, SHAPES["decode_32k"], n)
    assert de == 2.0 * n * 128


def test_dtype_bytes_table():
    assert roofline._shape_bytes("bf16", "8,128") == 8 * 128 * 2
    assert roofline._shape_bytes("f32", "") == 4        # scalar
    assert roofline._shape_bytes("pred", "7") == 7
    assert roofline._shape_bytes("unknown", "8") == 0


def test_parser_on_real_compiled_module():
    """End-to-end: parse a really-compiled 8-device module."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        import sys
        sys.path.insert(0, "src")
        from repro.launch import roofline
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        W = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        X = jax.ShapeDtypeStruct((64, 256), jnp.float32)
        def f(w, x):
            return jnp.sum((x @ w) ** 2)
        with mesh:
            g = jax.jit(jax.grad(f), in_shardings=(
                NamedSharding(mesh, P("data", "model")),
                NamedSharding(mesh, P("data", None))))
            comp = g.lower(W, X).compile()
        got = roofline.collective_bytes(comp.as_text())
        assert got["total"] > 0, got
        print("COLLECTIVE_BYTES_OK", got["total"])
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=REPO)
    assert "COLLECTIVE_BYTES_OK" in r.stdout, r.stderr[-2000:]
