"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
      --seconds 5 --control

Runs the cell once per seed, all in one process (one compile), and prints
one JSON line per seed: the numbers compared with the reference and
``correct``. With ``--control`` the control (the reference one precision
step below the configuration's) stands in the program's place: ``checks``
and ``correct`` are then the control's, judged by the cell's own limits,
and ``counts`` holds the program's numbers (``program.*``) and those of
the planted faults the cell's driver reads. ``--precision`` runs the
program at another matmul precision than the configuration states (a
reading of the program's own lower-precision path). ``--keep-trace DIR``
makes the runs traced and keeps each trace as read, described plane by
plane, with its HLO. The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--precision", default=None)
    ap.add_argument("--keep-trace", type=Path, default=None)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    if args.precision:
        cell.config = {**cell.config, "matmul_precision": args.precision}
    for seed in (int(s) for s in args.seeds.split(",")):
        keep = (args.keep_trace / f"seed{seed}" if args.keep_trace
                else None)
        try:
            r = harness.run_loaded(cell, seed, args.seconds,
                                   keep is not None, time.perf_counter(),
                                   control=args.control, keep_trace=keep)
        except harness.NoAccelerator as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        except Exception:      # one seed's failure is that seed's reading
            traceback.print_exc()
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "error": traceback.format_exc(limit=1)}),
                  flush=True)
            continue
        row = {"workload": args.workload, "seed": seed,
               "precision": cell.config["matmul_precision"],
               "control": args.control, "correct": r["correct"],
               "checks": {k: c["value"] for k, c in r["checks"].items()},
               "counts": r.get("counts", {}), "metrics": r["metrics"],
               "window_stats": r["window_stats"],
               "device": r["device"]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
