"""End-to-end serving driver (the paper's deployment scenario): train
several small neural fields, then serve a mixed multi-scene,
multi-viewpoint request stream through the RenderEngine — one compiled
executable per bucket, including the Pallas fused-field kernel path — and
report p50/p99 latency + Mpix/s (paper Fig. 10/14 style; DESIGN.md §3).

  PYTHONPATH=src python examples/serve_render.py [--app nvr] [--pallas]
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.serve import serve_render  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="gia",
                    choices=["gia", "nsdf", "nvr", "nerf"])
    ap.add_argument("--encoding", default="hash",
                    choices=["hash", "dense", "tiled"])
    ap.add_argument("--pallas", action="store_true",
                    help="serve through the fused Pallas NFP kernel "
                         "(interpret mode on CPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--scenes", type=int, default=2)
    ap.add_argument("--cameras", type=int, default=3)
    ap.add_argument("--shard", action="store_true",
                    help="pixel-parallel shard_map over the local mesh")
    ap.add_argument("--log2-table-size", type=int, default=14,
                    help="hash-table size (Table I: 19, gia 24)")
    args = ap.parse_args()
    serve_render(args.app, args.encoding, train_steps=args.train_steps,
                 n_requests=args.requests, use_pallas=args.pallas,
                 n_scenes=args.scenes, n_cameras=args.cameras,
                 shard=args.shard, log2_table_size=args.log2_table_size)


if __name__ == "__main__":
    main()
