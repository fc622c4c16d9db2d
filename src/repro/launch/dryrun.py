import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # keep remat-saved scan stacks in bf16: WLICM otherwise hoists the
    # backward loop's per-step fp32 converts into a whole-stack fp32 copy
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion")
os.environ["REPRO_MIXED_DOTS"] = "1"  # compile-only: native mixed-precision dots

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell
against the production meshes, with ShapeDtypeStruct stand-ins (no device
allocation), and record memory / cost / collective analysis for the
roofline.

The two XLA_FLAGS lines above MUST precede every other import (jax locks
the device count at first init).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out experiments/dryrun
"""
import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from ..configs import ASSIGNED_ARCHS, SHAPES, get_config
from ..configs.base import TrainConfig
from .hlo_cost import analyze as hlo_analyze
from .mesh import make_production_mesh
from .specs import prefill_cell, serve_cell, train_cell

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Bytes of one HLO shape string like 'bf16[16,128,4096]' (or a tuple)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in (per-partition) HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # result-type = op-name(...)  -- match '= <collective>(' occurrences
        for op in COLLECTIVE_OPS:
            marker = f" {op}("
            alt = f" {op}-start("
            if marker in stripped or alt in stripped:
                lhs = stripped.split("=", 1)
                if len(lhs) != 2:
                    continue
                # result type precedes '=' after the '%name ' prefix:
                #   %x = bf16[2,4]{1,0} all-reduce(...)
                rhs = lhs[1].strip()
                type_part = rhs.split(op)[0]
                b = _shape_bytes(type_part)
                out[op]["count"] += 1
                out[op]["bytes"] += b
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             skip_execute: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "mesh_shape": list(mesh.devices.shape),
           "n_devices": int(mesh.devices.size)}

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tcfg = TrainConfig(global_batch=shape.global_batch,
                               seq_len=shape.seq_len, remat="full")
            step, args, shardings = train_cell(cfg, shape, mesh, tcfg)
            fn = jax.jit(step, in_shardings=shardings)
        elif shape.kind == "prefill":
            step, args, shardings = prefill_cell(cfg, shape, mesh)
            fn = jax.jit(step, in_shardings=shardings)
        else:
            step, args, shardings = serve_cell(cfg, shape, mesh)
            fn = jax.jit(step, in_shardings=shardings)

        lowered = fn.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_size_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_size_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_size_bytes":
                int(getattr(mem, "generated_code_size_in_bytes", 0)),
        }
        rec["memory"]["total_per_device_bytes"] = (
            rec["memory"]["argument_size_bytes"]
            + rec["memory"]["output_size_bytes"]
            + rec["memory"]["temp_size_bytes"])

        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        rec["cost"] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        }
        hlo = compiled.as_text()
        rec["collectives"] = parse_collectives(hlo)
        rec["hlo_lines"] = hlo.count("\n")
        # corrected static analysis: while-loop (scan) bodies weighted by
        # their trip counts (XLA's cost_analysis counts them once)
        corr = hlo_analyze(hlo)
        rec["corrected"] = {
            "flops": corr["flops"],
            "bytes_proxy": corr["bytes"],
            "transcendentals": corr["transcendentals"],
            "collective_bytes": corr["collective_bytes"],
            "collectives": corr["collectives"],
            "while_trip_counts": corr["while_trip_counts"],
        }
    return rec


def all_cells():
    """Applicable (arch, shape) cells.  long_500k only for sub-quadratic
    archs (see DESIGN.md S.Arch-applicability)."""
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape_name in SHAPES:
            if shape_name == "long_500k" and not cfg.subquadratic:
                continue
            yield arch, shape_name


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
            out_path = outdir / f"{tag}.json"
            if out_path.exists():
                print(f"[skip] {tag} (exists)", flush=True)
                continue
            print(f"[run ] {tag}", flush=True)
            try:
                rec = run_cell(arch, shape_name, mp)
                out_path.write_text(json.dumps(rec, indent=1))
                print(f"[ ok ] {tag}: compile {rec['compile_s']}s, "
                      f"mem/dev {rec['memory']['total_per_device_bytes']/2**30:.2f} GiB, "
                      f"flops {rec['cost']['flops']:.3e}, "
                      f"coll {rec['collectives']['total_bytes']/2**20:.1f} MiB",
                      flush=True)
            except Exception as e:
                failures += 1
                err = {"arch": arch, "shape": shape_name,
                       "mesh": "multi" if mp else "single",
                       "error": repr(e),
                       "traceback": traceback.format_exc()}
                (outdir / f"{tag}.error.json").write_text(
                    json.dumps(err, indent=1))
                print(f"[FAIL] {tag}: {e!r}", flush=True)
    print(f"done; {failures} failures", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
