#!/usr/bin/env python3
"""Run the paged serving path once on a TPU, at granite-3-2b's published widths.

    python chip_smoke.py            # one chip: phases (a), (b), (c)
    python chip_smoke.py --tp 4     # four chips: phase (c) at tp 4 vs tp 1

Phases, each of which must pass (none is caught and skipped):

  (a) device check: JAX must find a TPU.  There is no CPU fallback.
  (b) kernel parity at full width: the Pallas paged decode and batched
      chunk-prefill kernels against their jnp references (impl="ref"),
      on a shuffled block table, within two bf16 ulps at unit scale; the
      page write equal to the scatter; the copy-on-write page copy in
      place.
  (c) the engine: ServeEngine (paged + chunked + batched, Pallas kernels)
      serves 12 requests with seeded prompt lengths of 64-1536 tokens
      through granite-3-2b (40 layers, d_model 2048, 32 heads / 8 KV,
      d_ff 8192, vocab 49155, bf16; random weights from --seed).  Every
      request must finish with in-vocabulary tokens, the engine's
      invariants must hold after every tick, and the greedy tokens of the
      two shortest requests must be greedy under a teacher-forced
      reference forward pass.

With --tp 4 only phase (c)'s requests run, at tp_degree 4 and at
tp_degree 1 in this one process: the greedy outputs must be bit-identical
and the per-shard KV byte accounting must balance.

The last line of standard output is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Without a TPU, or without the rest of the repository next to it, the
script exits non-zero and prints no such line.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ServeConfig  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402
from repro.serve.scheduler import RequestState  # noqa: E402
from repro.serve.serve_step import copy_page  # noqa: E402

ARCH = "granite-3-2b"
SERVE = dict(paged=True, chunked=True, batched=True, page_size=128,
             max_batch=8, max_seq=2048, prefill_chunk=512, max_new_tokens=32,
             tick_token_budget=8 + 4 * 512)
N_REQUESTS, MIN_PROMPT, MAX_PROMPT = 12, 64, 1536
MAX_TICKS = 2000
# kernel parity: |pallas - ref| <= ATOL + RTOL * |ref|, two bf16 ulps at
# unit scale (both paths accumulate in float32 and round once to bf16)
ATOL = RTOL = 2.0 ** -6
# teacher-forced reference: an emitted token may sit at most this many
# standard deviations of its logit row below the row's maximum (bf16
# rounding differs between the paged chunked path and one dense forward;
# corrupted KV moves a token by several standard deviations)
REF_GAP_SIGMAS = 0.25
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def require(ok: bool, what: str):
    if not ok:
        raise RuntimeError(what)


def check_device() -> dict:
    """Phase (a)."""
    devs = jax.devices()
    d = devs[0]
    print(f"[a] device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"no TPU: JAX found platform {d.platform!r}; this script "
                 f"runs only on the chip")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _agree(name: str, got, want):
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    require(got.shape == want.shape, f"{name}: shape {got.shape} vs "
                                     f"{want.shape}")
    require(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    err = np.abs(got - want)
    worst = float(err.max())
    print(f"[b] {name}: shape {got.shape}, max |pallas - ref| = {worst!r} "
          f"(bound {ATOL!r} + {RTOL!r} * |ref|)")
    require(bool((err <= ATOL + RTOL * np.abs(want)).all()),
            f"{name}: Pallas and reference disagree by up to {worst!r}")


def kernel_parity(cfg, seed: int):
    """Phase (b): both paged kernels, compiled, against the references on
    the engine's own pool geometry with a shuffled block table."""
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ps, batch = SERVE["page_size"], SERVE["max_batch"]
    n_max = SERVE["max_seq"] // ps
    n_pages = batch * n_max + 1                  # page 0 is the null page
    kq, kp, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    # a two-layer stacked pool (K and V pages per layer), read at layer 1
    pages = [jax.random.normal(k, (hkv, n_pages, ps, d), jnp.bfloat16)
             for k in jax.random.split(kp, 4)]
    pool = jnp.stack([ops.pack_kv(pages[0], pages[1], 2 * d),
                      ops.pack_kv(pages[2], pages[3], 2 * d)])
    rng = np.random.default_rng(seed)
    tables = rng.permutation(np.arange(1, n_pages)).reshape(batch, n_max)
    tables = tables.astype(np.int32)
    lens = rng.integers(1, SERVE["max_seq"] + 1, size=batch).astype(np.int32)
    lens[:2] = (SERVE["max_seq"], 1)
    q = jax.random.normal(kq, (batch, 1, hq, d), jnp.bfloat16)
    args = (q, pool, 1, jnp.asarray(tables), jnp.asarray(lens))
    _agree("paged_flash_decode", ops.paged_flash_decode(*args, impl="pallas"),
           ops.paged_flash_decode(*args, impl="ref"))

    # four chunk rows: page-aligned, a later chunk, a ragged mid-page
    # chunk, and a dead padding row (all-null table, exactly zero out)
    chunk = SERVE["prefill_chunk"]
    offs = np.array([0, chunk, 1300, 0], np.int32)
    true_lens = np.array([chunk, 2 * chunk, 1500, 0], np.int32)
    rows = tables[:4].copy()
    rows[3] = 0
    qc = jax.random.normal(kc, (4, chunk, hq, d), jnp.bfloat16)
    args = (qc, pool, 1, jnp.asarray(rows), jnp.asarray(offs),
            jnp.asarray(true_lens))
    got = ops.batched_paged_prefill_attention(*args, impl="pallas")
    _agree("batched_paged_prefill_attention", got,
           ops.batched_paged_prefill_attention(*args, impl="ref"))
    require(not bool(jnp.any(got[3])), "dead chunk row is not exactly zero")

    # the page write, compiled, must store exactly what the scatter
    # stores: decode rows (the last one idle, on the null page 0) and
    # chunk rows of which two belong to one sequence and share the page
    # where the first ends mid-page
    k, v = (jax.random.normal(kk, (batch, 1, hkv, d), jnp.bfloat16)
            for kk in jax.random.split(kc))
    dec = (k, v, jnp.asarray(tables), jnp.asarray(lens - 1),
           jnp.asarray(lens), 1)
    rows = np.stack([tables[0], tables[1], tables[0], np.zeros(n_max)])
    starts = np.array([0, 1300, 300, 0], np.int32)
    ends = np.array([300, 1500, 300 + chunk, 0], np.int32)
    k, v = (jax.random.normal(kk, (4, chunk, hkv, d), jnp.bfloat16)
            for kk in jax.random.split(kq))
    chunks = (k, v, jnp.asarray(rows, jnp.int32), jnp.asarray(starts),
              jnp.asarray(ends), chunk)
    for name, (k, v, *where) in (("decode", dec), ("chunks", chunks)):
        got, want = (ops.kv_page_write(
            pool, 1, k, v, ops.kv_write_plan(*where, ps, impl=impl),
            impl=impl) for impl in ("pallas", "ref"))
        require(bool(jnp.array_equal(got, want)),
                f"kv_page_write ({name}) differs from the scatter")
        print(f"[b] kv_page_write ({name}): equal to the scatter")

    # the copy-on-write page copy as the engine runs it (pool donated):
    # in place, with at most the copied page as a temporary
    src, dst = int(tables[0, 0]), int(tables[1, 0])
    page = np.asarray(pool[:, :, src])
    copy = jax.jit(copy_page, donate_argnums=(0,))
    mem = copy.lower(pool, src, dst).compile().memory_analysis()
    require(mem.alias_size_in_bytes == pool.nbytes
            and mem.temp_size_in_bytes <= pool.nbytes // n_pages,
            f"copy_page is not in place: {mem}")
    pool = copy(pool, src, dst)
    require(bool((np.asarray(pool[:, :, dst]) == page).all()),
            "copy_page did not copy the page")
    print(f"[b] copy_page: in place, temporaries "
          f"{mem.temp_size_in_bytes} B")


def prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(MIN_PROMPT, MAX_PROMPT + 1, size=N_REQUESTS)
    return [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
            for n in lengths]


def serve(model, params, reqs, tp_degree: int):
    """Submit every request, tick to completion checking the engine's
    invariants after every tick, and wait for the device."""
    eng = ServeEngine(model, params, ServeConfig(**SERVE,
                                                 tp_degree=tp_degree))
    for p in reqs:
        eng.submit(p)
    done, ticks = [], 0
    t0 = time.perf_counter()
    while eng.queue or any(s is not None for s in eng.slots):
        require(ticks < MAX_TICKS, f"engine still busy after {ticks} ticks")
        done.extend(eng.tick())
        eng.check_invariants()
        ticks += 1
    jax.block_until_ready((eng.cache, eng.tokens, eng.lens))
    wall = time.perf_counter() - t0
    require(len(done) == len(reqs), f"{len(done)} of {len(reqs)} finished")
    for r in done:
        require(r.state == RequestState.DONE, f"request {r.uid}: {r.state}")
        require(len(r.out_tokens) == SERVE["max_new_tokens"],
                f"request {r.uid}: {len(r.out_tokens)} tokens")
        require(all(0 <= t < model.cfg.vocab_size for t in r.out_tokens),
                f"request {r.uid}: token outside the vocabulary")
    return eng, sorted(done, key=lambda r: r.uid), ticks, wall


def report(tag: str, eng, done, ticks: int, wall: float, compile_s: list):
    peak = max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.devices()[:eng.scfg.tp_degree])
    print(f"[{tag}] tokens served: {sum(len(r.out_tokens) for r in done)}; "
          f"prompt tokens: {sum(len(r.prompt) for r in done)}; "
          f"ticks: {ticks}")
    print(f"[{tag}] peak device bytes in use: {peak}")
    print(f"[{tag}] compile seconds so far: {sum(compile_s)!r}")
    print(f"[{tag}] wall seconds after block_until_ready (informational, "
          f"compiles included; not a metric): {wall!r}")


def check_reference(model, params, done):
    """Teacher-force the two shortest requests through one plain forward
    pass (jnp reference attention) and require every emitted token to be
    greedy up to REF_GAP_SIGMAS."""
    forward = jax.jit(lambda p, t: model.forward(p, {"tokens": t},
                                                 impl="ref")[0])
    worst = 0.0
    for r in sorted(done, key=lambda r: len(r.prompt))[:2]:
        seq = jnp.asarray([r.prompt + r.out_tokens[:-1]], jnp.int32)
        logits = np.asarray(forward(params, seq)[0, len(r.prompt) - 1:])
        require(bool(np.isfinite(logits).all()), "non-finite reference")
        rows = np.arange(len(r.out_tokens))
        gaps = (logits.max(-1) - logits[rows, r.out_tokens]) \
            / logits.std(-1)
        worst = max(worst, float(gaps.max()))
        require(bool((gaps <= REF_GAP_SIGMAS).all()),
                f"request {r.uid}: emitted token {float(gaps.max())!r} "
                f"standard deviations below the reference argmax")
    print(f"[c] reference: worst emitted-token gap {worst!r} sigma "
          f"(bound {REF_GAP_SIGMAS})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4),
                    help="4: compare tensor-parallel serving on four chips "
                         "with one chip, and run nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    device = check_device()
    require(device["count"] >= args.tp,
            f"--tp {args.tp} needs {args.tp} chips, JAX sees "
            f"{device['count']}")
    print(f"compilation cache: {cache_dir}")
    compile_s: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == COMPILE_EVENT else None)

    cfg = get_config(ARCH)
    if args.tp == 1:
        kernel_parity(cfg, args.seed)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    reqs = prompts(cfg, args.seed)
    if args.tp == 1:
        eng, done, ticks, wall = serve(model, params, reqs, 1)
        report("c", eng, done, ticks, wall, compile_s)
        check_reference(model, params, done)
    else:
        eng_tp, done_tp, ticks, wall = serve(model, params, reqs, args.tp)
        report(f"tp{args.tp}", eng_tp, done_tp, ticks, wall, compile_s)
        stats_tp = eng_tp.tp_stats()
        del eng_tp
        eng_1, done_1, ticks, wall = serve(model, params, reqs, 1)
        report("tp1", eng_1, done_1, ticks, wall, compile_s)
        stats_1 = eng_1.tp_stats()
        same = [a.out_tokens == b.out_tokens for a, b in zip(done_tp,
                                                             done_1)]
        print(f"[tp] bit-identical greedy outputs: {sum(same)} of "
              f"{len(same)} requests")
        require(all(same), "tp_degree 4 and 1 emitted different tokens")
        print(f"[tp] tp_stats at {args.tp}: {stats_tp}")
        require(stats_tp["kv_pages_read"] == stats_1["kv_pages_read"],
                "the two engines read different KV page counts")
        require(stats_tp["shard_kv_bytes_read"] * args.tp
                == stats_tp["kv_pages_read"] * stats_tp["page_bytes"],
                "per-shard KV bytes x tp != pages read x page bytes")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
