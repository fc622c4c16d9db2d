"""One run of one cell: load, warm up, measure, check, report.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in `setup_s`, from process start to the window's first
tick): JAX's device check, the weights made on the device from the seed in
one jitted call, the engine, one pass of warm-up requests that compiles or
loads from the persistent cache every program shape the cell uses (the
fused decode at max_batch and the chunk-row buckets up to chunks_per_tick),
and `warm_s` seconds of the cell's own load, so the batch is at its steady
occupancy when the window opens.

After the window: the peak device memory is read, the engine is freed, and
a seeded sample of finished requests is compared with the plain reference
(bench/check.py).  With --trace 1 the profiler records the first
`TRACE_S` seconds of the window and the per-layer metrics are read over
that traced part.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are also the last lines of
standard error and the result's last key, `checks`.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

from .spec import Cell, load_cell, reference_module

TRACE_S = 6.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(SystemExit):
    pass


def check_device(chips: int) -> dict:
    """The chip this run measures.  No TPU, or fewer chips than the cell
    asks for, ends the run with no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found platform {d.platform!r}; the "
                       f"benchmark runs only on the chip")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def enable_compile_cache():
    """JAX's persistent compilation cache, where the program keeps it
    ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), for every
    program however quickly it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def jax_key(seed: int):
    import jax
    import numpy as np
    word = np.random.SeedSequence(seed % 2 ** 64).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def model_config(cfg: dict):
    """The program's ModelConfig from the file's `program` block, held to
    the file's published widths."""
    from repro.configs.base import ModelConfig
    p = cfg["program"]
    widths = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
              "n_heads": "num_attention_heads",
              "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
              "vocab_size": "vocab_size",
              "max_seq": "max_position_embeddings"}
    for k, src in widths.items():
        if p[k] != cfg[src]:
            raise ValueError(f"{cfg['name']}: program {k}={p[k]} but "
                             f"{src}={cfg[src]}")
    if p["d_ff"] != cfg.get("intermediate_size", cfg.get("ffn_dim")):
        raise ValueError(f"{cfg['name']}: program d_ff differs")
    return ModelConfig(**p)


def serve_config(cfg: dict, mix: dict):
    """(ServeConfig, clients) for a configuration under a mix."""
    from repro.configs.base import ServeConfig
    from .loadgen import n_clients
    s = cfg["serve"]
    ctx = cfg["max_position_embeddings"]
    clients = 0
    if mix["loop"] == "closed":
        clients = n_clients(mix, s["num_pages"], s["page_size"], ctx)
        max_batch = clients
    else:
        max_batch = int(mix["max_batch"])
    k = int(s["chunks_per_tick"])
    scfg = ServeConfig(paged=True, chunked=True, batched=True,
                       page_size=s["page_size"], max_batch=max_batch,
                       max_seq=ctx, prefill_chunk=s["prefill_chunk"],
                       max_new_tokens=1,
                       tick_token_budget=max_batch + k * s["prefill_chunk"],
                       max_chunks_per_tick=k, num_pages=s["num_pages"],
                       temperature=0.0)
    return scfg, clients


def warm_shapes(eng, scfg, vocab: int, seed: int):
    """Requests of one chunk each, max_new_tokens 2, in groups of
    chunks_per_tick, then half as many, down to one: each group runs one
    chunk launch at its row bucket and then the fused decode."""
    from .loadgen import seed_rng
    rng = seed_rng(seed + 2)
    k = scfg.max_chunks_per_tick
    while k >= 1:
        for _ in range(k):
            eng.submit(rng.integers(1, vocab, size=scfg.prefill_chunk)
                       .tolist(), max_new_tokens=2)
        while eng.queue or any(s is not None for s in eng.slots):
            eng.tick()
        k //= 2


@dataclass
class Record:
    """What the metric readers read (bench/metrics/<name>.py)."""
    loop: object                   # driver.Loop
    trace: object                  # trace_reduce.Trace or None
    counts: dict                   # reference_module.counts(cfg)
    flops: object                  # work.ModelFlops
    peak: dict                     # work.peaks(device_kind)
    setup_s: float


def _read(metrics, rec) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        v = m.read(rec)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def _trace_ctl(tmp: Path) -> dict:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return {"start": lambda: jax.profiler.start_trace(
                str(tmp), profiler_options=opts),
            "stop": jax.profiler.stop_trace, "seconds": TRACE_S}


def _breakdown(trace) -> dict:
    from . import trace_reduce as tr
    ops = tr.top_ops(trace.ops[0], trace.window, 10)
    idle = sorted(tr.idle_by_span(trace, 0).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, ns / 1e9] for n, ns in idle[:10]]}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, *, device: dict, peak: Optional[dict] = None,
        model=None, control: bool = False,
        log: Callable[[str], None] = print) -> dict:
    """One run of `cell`.  `device` is what check_device returned; `peak`
    defaults to the peaks table's row for its kind (tests that drive the
    harness without a chip pass both).  `model` reuses a built model's
    compiled steps across runs in one process; `control` adds the float8
    control's reading on the same sample (bench/calibrate.py)."""
    import jax
    import numpy as np
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    from . import check, driver, trace_reduce, work
    from .loadgen import schedule

    cfg, mix = cell.cfg, cell.mix
    ref = reference_module(cfg)
    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs)
        if ev == COMPILE_EVENT else None)
    model = model or build_model(model_config(cfg))
    params = ref.make_params(cfg, jax_key(seed))
    jax.block_until_ready(params)
    scfg, clients = serve_config(cfg, mix)
    eng = ServeEngine(model, params, scfg)
    warm_shapes(eng, scfg, cfg["vocab_size"], seed)
    log(f"set-up compiles: {len(compiles)} taking {sum(compiles)!r} s; "
        f"max_batch {scfg.max_batch}, pages {scfg.num_pages}, "
        f"clients {clients}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device memory after warm-up: in use "
        f"{stats.get('bytes_in_use')}, peak {stats.get('peak_bytes_in_use')}"
        f", limit {stats.get('bytes_limit')}")
    warm_s = float(mix["warm_s"])
    items = schedule(mix, seed, vocab=cfg["vocab_size"],
                     context=cfg["max_position_embeddings"],
                     stretches=[warm_s, warm_s + seconds,
                                warm_s + seconds + driver.GRACE_S + 30.0],
                     clients=clients)
    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
    try:
        loop = driver.drive(eng, items, mix, seconds=seconds, warm_s=warm_s,
                            chunk=scfg.prefill_chunk,
                            min_finished=int(mix["sample"]["requests"]),
                            trace=_trace_ctl(tmp) if trace else None,
                            compile_count=lambda: len(compiles))
        tr = None
        if trace:
            found = sorted(tmp.rglob("*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no trace")
            tr = trace_reduce.read_xspace(found[-1], cell.chips)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    devs = jax.devices()[:cell.chips]
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      0)) for d in devs)
    setup_s = loop.w0 - t_start
    counts = ref.counts(cfg)
    rec = Record(loop, tr, counts, work.ModelFlops(counts),
                 peak or work.peaks(device["kind"]), setup_s)
    metrics = _read(cell.per_layer if trace else cell.end_to_end, rec)
    ttft = driver.ttfts(loop)
    gaps = driver.tbt_gaps(loop)
    lateness = np.asarray([q.submit - q.due for q in loop.reqs
                           if loop.w0 <= q.due < loop.w1])
    # attempted: requests due in the window, and requests the window served
    # (a closed loop's long requests may be sent before it opens)
    attempted = sum(loop.w0 <= q.due < loop.w1
                    or any(loop.w0 < t <= loop.w1 for t in q.stamps)
                    for q in loop.reqs)
    failed = int(np.isinf(ttft).sum())
    log(f"window {loop.w1 - loop.w0!r} s, ticks {loop.k1 - loop.k0}, "
        f"tokens {driver.window_tokens(loop)}, attempted {attempted}, "
        f"compiles in window {loop.compiles_in_window}")
    if len(ttft):
        log(f"ttft ms p50 {float(np.median(ttft)) * 1e3!r} max "
            f"{float(np.max(ttft)) * 1e3!r}; generator lateness ms p50 "
            f"{float(np.median(lateness)) * 1e3!r} max "
            f"{float(np.max(lateness)) * 1e3!r}")
        log("ttft ms by percentile " + json.dumps(
            {p: float(np.percentile(ttft, p)) * 1e3
             for p in (50, 80, 90, 95, 99)}))
    if len(gaps):
        log(f"tbt ms p50 {float(np.median(gaps)) * 1e3!r} over "
            f"{len(gaps)} gaps; by percentile " + json.dumps(
                {p: float(np.percentile(gaps, p)) * 1e3
                 for p in (50, 90, 95, 99)}))
    finished = [q.r for q in loop.reqs if q.done_at is not None]
    short = sum(len(r.out_tokens) != r.max_new_tokens for r in finished)
    del eng
    gc.collect()
    picked = check.sample(finished, int(mix["sample"]["requests"]), seed)
    t_ref = time.perf_counter()
    got = check.compare(ref, cfg, params, picked, control=control)
    log(f"reference: {len(picked)} requests, {got['positions']} served "
        f"positions, {time.perf_counter() - t_ref!r} s")
    checks = {
        "max_gap_sigma": {"value": got["max_gap_sigma"],
                          "limit": cell.limits["max_gap_sigma"]},
        "unserved_requests": {"value": failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
    }
    if not got["positions"]:
        checks["max_gap_sigma"]["value"] = float("inf")   # nothing compared
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = dict(device)
    dev["memory_peak_bytes"] = peak_bytes
    if tr is not None:
        dev["busy_s"] = sum(trace_reduce.busy_ns(o, tr.window)
                            for o in tr.ops) / len(tr.ops) / 1e9
        dev["window_s"] = tr.window_s
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if tr is not None:
        out["breakdown"] = _breakdown(tr)
    if control:
        out["control_max_gap_sigma"] = got["control_max_gap_sigma"]
    out["checks"] = checks
    return out


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    enable_compile_cache()
    device = check_device(cell.chips)
    res = run(cell, args.seed, args.seconds, bool(args.trace), t_start,
              device=device,
              log=lambda s: print(s, flush=True))
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0
