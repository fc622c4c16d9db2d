#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains: the sweep
that sets the rate written in its traffic file.

    python3 bench/sweep.py --workload <name> --rates 4,6,8 --seconds 30

For each rate, in one process: a fresh engine, the cell's warm load, then
a window at that rate.  Printed per rate: requests due per second, output
tokens per second, the backlog (requests due but without a first token)
at the window's open and close, and the median TTFT of the requests due
in the window's first and last thirds.  A rate is sustained where the
backlog does not grow over the window (a request due in the last half
second has no first token yet, so one more at the close is not growth)
and the last third's TTFT is not above the first third's by more than
half.  Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import driver, harness  # noqa: E402
from bench.loadgen import schedule  # noqa: E402
from bench.spec import load_cell, reference_module  # noqa: E402


def backlog(loop, t: float) -> int:
    return sum(q.due <= t and (q.first is None or q.first > t)
               for q in loop.reqs)


def thirds(loop):
    span = loop.w1 - loop.w0
    out = []
    for lo, hi in ((0, 1 / 3), (2 / 3, 1)):
        t = [q.first - q.due for q in loop.reqs
             if loop.w0 + lo * span <= q.due < loop.w0 + hi * span
             and q.first is not None]
        out.append(float(np.median(t)) * 1e3 if t else float("inf"))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    harness.enable_compile_cache()
    harness.check_device(cell.chips)
    import jax
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    cfg = cell.cfg
    model = build_model(harness.model_config(cfg))
    params = reference_module(cfg).make_params(cfg,
                                               harness.jax_key(args.seed))
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate_per_s"] = rate
        scfg, _ = harness.serve_config(cfg, mix)
        eng = ServeEngine(model, params, scfg)
        harness.warm_shapes(eng, scfg, cfg["vocab_size"], args.seed)
        warm = float(mix["warm_s"])
        items = schedule(mix, args.seed, vocab=cfg["vocab_size"],
                         context=cfg["max_position_embeddings"],
                         stretches=[warm, warm + args.seconds,
                                    warm + args.seconds + 90])
        loop = driver.drive(eng, items, mix, seconds=args.seconds,
                            warm_s=warm, chunk=scfg.prefill_chunk,
                            grace_s=0.0)
        first, last = thirds(loop)
        due = sum(loop.w0 <= q.due < loop.w1 for q in loop.reqs)
        print(json.dumps({
            "rate": rate, "due_per_s": due / (loop.w1 - loop.w0),
            "output_tok_per_s": driver.window_tokens(loop)
            / (loop.w1 - loop.w0),
            "backlog_open": backlog(loop, loop.w0),
            "backlog_close": backlog(loop, loop.w1),
            "ttft_p50_ms_first_third": first,
            "ttft_p50_ms_last_third": last}), flush=True)
        del eng
        gc.collect()
        jax.block_until_ready(params)
    return 0


if __name__ == "__main__":
    sys.exit(main())
