#!/usr/bin/env python3
"""Readings that set the check limits: a cell run on several seeds in one
process, each printing the program's compared numbers and the control's.

    python3 bench/readings.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--check-share 0.3]

The control is the reference recomputed from values rounded to bfloat16
(``precision="bfloat16"``: drawn-row statistics, the anchor and the ISLA
estimate) and held to the float64 reference in the program's place; its
gaps are the upper readings, the program's the lower.  The benchmark's own
runs do not compute it.  ``--check-share`` raises the share of answers
compared, so a short window compares as many as a full run.  One JSON line
per seed on standard output.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from isla_bench.harness import NoAccelerator, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check-share", type=float, default=None)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            out = run_cell(BENCH.parent, args.workload, seed, args.seconds,
                           bool(args.trace), t0, control=True,
                           check_share=args.check_share)
        except NoAccelerator as exc:
            print(f"readings: {exc}", file=sys.stderr)
            return 2
        res = out["result"]
        print(json.dumps({
            "seed": seed, "control_correct": res["correct"],
            "control": {k: v["value"] for k, v in res["checks"].items()},
            "limits": {k: v["limit"] for k, v in res["checks"].items()},
            "notes": out["notes"], "metrics": res["metrics"],
            "device": res["device"],
            "wall_s": time.perf_counter() - t0}, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
