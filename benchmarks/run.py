"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig5,table1] \
      [--json-out benchmarks/results]

Prints ``name,us_per_call,derived`` CSV. ``--json-out DIR`` additionally
writes every structured payload (``Csv.add_json``) as
``DIR/BENCH_<name>.json`` — the artifacts CI uploads and
``make_report.py`` renders. A module that raises still gets an
``<name>/ERROR`` row, the others still run, and the exit code is 1."""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import Csv  # noqa: E402

MODULES = [
    ("table1", "benchmarks.table1_models"),
    ("fig5", "benchmarks.fig5_breakdown"),
    ("fig8", "benchmarks.fig8_encode_ops"),
    ("fig12", "benchmarks.fig12_scaling"),
    ("fig13", "benchmarks.fig13_kernels"),
    ("fig14", "benchmarks.fig14_fps"),
    ("table3", "benchmarks.table3_bandwidth"),
    ("serve_engine", "benchmarks.serve_engine"),
    ("quant", "benchmarks.quant_tradeoff"),
    ("train", "benchmarks.train_field"),
    ("roofline", "benchmarks.roofline_report"),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(k for k, _ in MODULES))
    ap.add_argument("--json-out", default=None,
                    help="directory for BENCH_<name>.json artifacts")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    csv = Csv()
    failed = []
    import importlib
    for key, modname in MODULES:
        if only is not None and key not in only:
            continue
        mod = importlib.import_module(modname)
        try:
            mod.run(csv)
        except Exception as e:  # noqa: BLE001 — report, keep going
            csv.add(f"{key}/ERROR", 0.0, f"{type(e).__name__}")
            failed.append(key)
            import traceback
            traceback.print_exc()
    csv.emit()
    if args.json_out:
        from repro.obs import log as obs_log
        log = obs_log.get_logger("bench")
        for p in csv.write_json(args.json_out):
            log.info("artifact_written", path=str(p))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
