"""One-cell perf measurement for the S.Perf hypothesis->change->measure loop.

Usage:
  PYTHONPATH=src python -m benchmarks.perf_cell --arch granite-8b \
      --shape train_4k [--mesh single] [--tag variant-name]

Prints the three roofline terms, the per-type collective breakdown, and the
top collective shapes - the 'profile' the iteration loop reads.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion")
os.environ["REPRO_MIXED_DOTS"] = "1"

import argparse
import re
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--tag", default="V?")
    ap.add_argument("--top-collectives", type=int, default=8)
    args = ap.parse_args()

    from repro.configs import SHAPES, get_config
    from repro.configs.base import TrainConfig
    from repro.launch.dryrun import run_cell
    from repro.launch.hlo_cost import analyze, parse_hlo, _trip_count
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import prefill_cell, serve_cell, train_cell

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tcfg = TrainConfig(global_batch=shape.global_batch,
                               seq_len=shape.seq_len, remat="full")
            step, cargs, shardings = train_cell(cfg, shape, mesh, tcfg)
        elif shape.kind == "prefill":
            step, cargs, shardings = prefill_cell(cfg, shape, mesh)
        else:
            step, cargs, shardings = serve_cell(cfg, shape, mesh)
        compiled = jax.jit(step, in_shardings=shardings).lower(*cargs).compile()
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()

    corr = analyze(hlo)
    t_c = corr["flops"] / PEAK_FLOPS
    t_m = corr["bytes"] / HBM_BW
    t_x = corr.get("collective_bytes_tpu", corr["collective_bytes"]) / ICI_BW
    mem_gib = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes) / 2 ** 30
    dom = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
              key=lambda kv: kv[1])[0]
    print(f"[{args.tag}] {args.arch}/{args.shape}/{args.mesh}")
    print(f"  mem/dev {mem_gib:6.2f} GiB | tc {t_c:.3e} s | tm {t_m:.3e} s "
          f"| tx {t_x:.3e} s | dominant={dom} "
          f"| roofline_frac={t_c/max(t_c,t_m,t_x):.2f}")
    for k, v in corr["collectives"].items():
        if v["bytes"]:
            print(f"  {k:20s} count {v['count']:10.0f}  "
                  f"{v['bytes']/2**30:9.2f} GiB raw | "
                  f"{v.get('bytes_tpu', v['bytes'])/2**30:9.2f} GiB tpu-equiv")

    # top individual collective shapes (weighted by loop multiplicity)
    comps = parse_hlo(hlo)
    from collections import defaultdict
    mult = defaultdict(float)

    def visit(name, m, depth=0):
        if depth > 64 or name not in comps:
            return
        mult[name] += m
        c = comps[name]
        for body, cond in c.while_edges:
            t = _trip_count(comps[cond]) if cond in comps else 1
            visit(body, m * t, depth + 1)
            visit(cond, m * (t + 1), depth + 1)
        for bg in c.branch_groups:
            for b in bg:
                visit(b, m / len(bg), depth + 1)
        for cal in c.callees:
            visit(cal, m, depth + 1)

    visit(comps["__entry__"].name, 1.0)
    from repro.launch.hlo_cost import COLLECTIVE_OPS, _nbytes
    rows = Counter()
    for name, m in mult.items():
        if name == "__entry__" or name not in comps:
            continue
        for ins in comps[name].instrs:
            base = ins.op.replace("-start", "")
            if base in COLLECTIVE_OPS:
                ts = ins.rhs.split(ins.op + "(")[0]
                shape_m = re.search(r"\w+\[[\d,]*\]", ts)
                rows[(base, shape_m.group(0) if shape_m else "?")] += \
                    m * _nbytes(ts)
    for (op, sh), b in rows.most_common(args.top_collectives):
        print(f"    {b/2**30:8.2f} GiB  {op:20s} {sh}")


if __name__ == "__main__":
    main()
