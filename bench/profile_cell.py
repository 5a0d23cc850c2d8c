"""Run one cell traced, with the program's tracer in profile mode, and
print what the program's own spans and level scopes add to the trace.

  python3 bench/profile_cell.py --workload <cell> --seed 7 --seconds 30

A traced ``bench/run.py`` run, except that the window turns the tracer's
profile mode on (``repro.obs.trace.TRACER.enable(buffer=False,
profile=True)``) when it opens and off when its work is done, so the
program's ``serve.*``/``train.*`` spans land on the profile's host plane.
``harness.Ctx`` has no such hook, and a traced run's readers see only the
reduced trace, so this script runs the harness with a ``Ctx`` subclass
that adds the hook and keeps the profile's program spans, device events
and HLO, and with each driver's ``Outcome`` kept.
The last line of standard output is one JSON object: the result line of
the traced run, the end-to-end values of the window (a traced run's own
line leaves them out), and under ``program_trace`` the device time per
level scope and pass (``level_s``), the share of the ``encode`` phase
they cover, the idle time by program span (``idle_by_span``) and the
per-layer numbers of ``program_trace.readings``. With ``--profile 0`` the
tracer stays off, as in a plain traced run. ``--keep DIR`` keeps the
compiled programs' HLO there.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    from bench import harness, peaks as peaks_mod
    from bench import program_trace as pt
    from bench import trace_reduce
    from repro.obs.trace import TRACER

    seen = {}

    class ProfiledCtx(harness.Ctx):
        def open_window(self):
            t = super().open_window()
            if args.profile and self.trace:
                TRACER.enable(buffer=False, profile=True)
            return t

        def end_window_work(self):
            TRACER.disable()
            super().end_window_work()

        def read_trace(self, hlo_texts):
            files = glob.glob(f"{self._trace_dir}/**/*.xplane.pb",
                              recursive=True)
            if len(files) == 1:
                seen["spans"] = pt.read_program_spans(files[0])
                seen["trace"] = trace_reduce.read_xplane(files[0])
            seen["hlo"] = hlo_texts
            return super().read_trace(hlo_texts)

    def keep_outcome(traffic):
        mod = original_driver(traffic)

        class Kept:
            @staticmethod
            def run(ctx):
                seen["outcome"] = mod.run(ctx)
                return seen["outcome"]
        return Kept

    original_driver, harness.driver = harness.driver, keep_outcome
    harness.Ctx = ProfiledCtx
    try:
        cell = harness.load_cell(args.workload)
        result = harness.run_loaded(cell, args.seed, args.seconds, True,
                                    T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    out = seen["outcome"]
    trace, spans = seen["trace"], seen["spans"]
    level_s = pt.level_seconds(trace, pt.level_map(seen["hlo"]))
    encode_s = out.reduced["phase_s"].get("encode", 0.0)
    peaks = peaks_mod.peaks_for(result["device"]["kind"])
    result["values"] = out.values
    result["program_trace"] = {
        "level_s": level_s,
        "level_share_of_encode": (sum(level_s.values()) / encode_s
                                  if encode_s else None),
        "idle_by_span": pt.idle_by_span(trace, spans),
        "n_program_spans": len(spans),
        "readings": pt.readings(cell, out.counts, peaks, cell.chips,
                                level_s, spans, trace),
    }
    if args.keep:
        keep = Path(args.keep)
        keep.mkdir(parents=True, exist_ok=True)
        for i, text in enumerate(seen["hlo"]):
            (keep / f"hlo{i}.txt").write_text(text)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
