#!/usr/bin/env python3
"""Readings that the limits in bench/limits/<cell>.json are set from.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10

Runs the cell once per seed in one process (the compiled steps are built
once), each with a short window at the cell's own load, and prints for
each seed the program's widest gap and, on the same served positions, the
float8 control's.  One JSON line per seed; the last line sums them up:
the program's largest reading (the lower one) and the control's smallest
(the upper one).  Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    harness.enable_compile_cache()
    device = harness.check_device(cell.chips)
    from repro.models import build_model
    model = build_model(harness.model_config(cell.cfg))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = harness.run(cell, seed, args.seconds, False, t0,
                          device=device, model=model, control=True,
                          log=lambda s: print(s, file=sys.stderr,
                                              flush=True))
        row = {"seed": seed, "correct": res["correct"],
               "max_gap_sigma": res["checks"]["max_gap_sigma"]["value"],
               "control_max_gap_sigma": res["control_max_gap_sigma"],
               "metrics": res["metrics"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": max(r["max_gap_sigma"] for r in rows),
        "control_min": min(r["control_max_gap_sigma"] for r in rows)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
