"""Serving benchmark: dense vs paged KV cache, prefix caching, and
token-budget chunked prefill.

    PYTHONPATH=src python benchmarks/serve_bench.py
    PYTHONPATH=src python benchmarks/serve_bench.py --quick   # CI-sized
    PYTHONPATH=src python benchmarks/serve_bench.py --prefix-trace \
        --json serve_prefix_bench.json
    PYTHONPATH=src python benchmarks/serve_bench.py --chunked \
        --json serve_chunked_bench.json

Default mode serves the same mixed-length request trace (short / medium /
long prompts, default 128 / 1024 / 3968 with max_seq=4096) through the
dense and the paged engine and reports tokens/s and KV-cache memory: the
dense engine preallocates max_batch * max_seq KV whether requests need it
or not; the paged pool is sized to the traffic, so peak KV bytes drop
while throughput holds (admission backpressure, never a mid-flight
failure).

--prefix-trace serves a SHARED-PREFIX trace (the shape of real traffic:
shared system prompts / few-shot templates with per-request tails)
through the paged engine with prefix caching off and on.  One warmup
request per prefix publishes its prompt pages into the radix tree; the
followers then run concurrently, attach the cached pages, and prefill
only their tails.  Reported: prefix hit rate, prefill tokens computed /
saved, and peak working-set pages - with bitwise-identical greedy outputs
cache-on vs cache-off (asserted).

--chunked serves the mixed trace through the paged engine with monolithic
admission-time prefill vs the token-budget scheduler (chunked prefill
mixed into decode ticks, docs/scheduling.md).  Reported: p50/p95 TTFT and
time-between-tokens, in wall seconds and in deterministic WORK-CLOCK
tokens (total prefill + decode tokens executed between two events - the
exact size of a scheduling bubble), plus dispatch accounting (jitted
launches and device->host transfers per tick, recompile count, host-loop
wall time).  Asserted: byte-identical greedy outputs, a hard per-tick
budget ceiling, and lower p95 work-clock TTFT and TBT for chunked
(decodes no longer stall behind whole-prompt prefills).

--chunked --batched additionally runs the sequential per-chunk oracle
(ServeConfig.batched=False) and pins the ONE-LAUNCH TICK: the batched
engine must serve a steady-state tick - K prefill chunks + M decodes in
flight - with exactly one batched ragged prefill launch, one fused
decode launch, and one device->host transfer, with greedy outputs
bit-identical to the sequential path and strictly fewer total launches.

--speculative serves a shared-prefix LONG-GENERATION trace through the
paged chunked batched engine with self-speculative decoding off vs on
(draft by n-gram lookup over each request's own history, verify the
chain in one batched chunk launch, roll back rejects by lens -
docs/speculative.md).  Asserted, never eyeballed: bit-identical greedy
outputs, equal work-clock totals, nonzero acceptance, and generated
tokens per decode launch > 1.5x the non-speculative baseline (tokens
per KV page read reported alongside).

--fleet serves the shared-prefix trace (one warmup per prefix, then the
followers) through a FleetRouter (serve/router.py, docs/routing.md)
sweeping replica counts (default 1/2/4) under the cache-hit-weighted
affinity policy, with round-robin at the same replica counts as the
control.  Affinity peeks every replica's radix tree per submit and lands
each follower on the replica that already caches its prefix; round-robin
scatters them.  Asserted, never eyeballed: bit-identical greedy outputs
across EVERY fleet size and policy (replicas share the jitted steps),
per-replica page conservation after the drain, and strictly fewer
prefill tokens computed under affinity than round-robin at every n > 1
(the prefill-tokens-saved curve is the headline artifact,
BENCH_fleet.json).

--tp serves the mixed trace through the paged chunked batched engine at
tp_degree=1 and tp_degree=N (--tp-degree, default 2): the KV page pool
and paged attention kernels shard across devices on the head axis with
the block table replicated (docs/tensor_parallel.md).  Asserted, never
eyeballed: bit-identical greedy outputs, equal work clocks and page
reads, and per-device KV read bytes <= single-device bytes / N + the
block-table replication overhead (the headline artifact, BENCH_tp.json).
Needs >= N devices (on CPU,
XLA_FLAGS=--xla_force_host_platform_device_count=N).

--preempt-trace exercises decode-priority budget shaping and victim
preemption (docs/scheduling.md): in-flight decodes' p95 work-clock TBT
under a long-prompt prefill burst must be strictly lower with
`decode_priority` on (the prefill share of every tick is capped), and a
high-priority burst against a capacity cap (ServeConfig.usable_pages)
must shed, park, and resume low-priority victims with greedy outputs
bit-identical to the same trace served uncapped.

Output: CSV rows per mode; --json additionally writes the full metrics
dict (CI uploads it as a workflow artifact).

--emit-trace PATH / --emit-metrics PATH (any mode, composable with the
flags above) write the engine-telemetry artifacts from the LAST engine
the selected mode ran - the interesting one in every comparison (paged,
prefix-on, chunked-batched, spec-on, preempted): a Chrome trace-event
JSON openable in Perfetto (docs/observability.md) and a metrics snapshot
+ per-launch-kind data-movement breakdown (HBM/SRAM bytes, energy,
padding overhead).  The emitted per-launch KV-page counts are asserted
to match the engine's PageAllocator-derived accounting before the file
is written.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import (FleetConfig, FleetRouter, dense_kv_bytes,
                         paged_kv_bytes, pages_needed)
from repro.serve.engine import ServeEngine

# --emit-trace / --emit-metrics plumbing: every mode builds engines
# through make_engine, which turns span tracing on when a trace was
# requested and remembers the most recent engine so emit_artifacts can
# export from the mode's final (always the telemetry-interesting) run
_EMIT = {"trace": "", "metrics": "", "eng": None}


def make_engine(model, params, scfg):
    if _EMIT["trace"]:
        scfg = dataclasses.replace(scfg, telemetry=True)
    eng = ServeEngine(model, params, scfg)
    _EMIT["eng"] = eng
    return eng


def emit_artifacts():
    eng = _EMIT["eng"]
    if eng is None:
        return
    if _EMIT["trace"]:
        eng.export_trace(_EMIT["trace"])
        print(f"# wrote {_EMIT['trace']} (open in Perfetto / "
              f"chrome://tracing)")
    if _EMIT["metrics"]:
        movement = eng.movement_stats()
        recs = eng.launch_records()
        # the attribution must agree with the allocator: per-launch page
        # counts come from block-table rows, the legacy counter from the
        # analytic ceil - both sides of the same accounting
        pages_rec = sum(r.kv_pages_read for r in recs
                        if r.kind in ("decode", "spec_verify"))
        assert pages_rec == eng.kv_pages_read, \
            f"launch-record KV pages {pages_rec} != engine counter " \
            f"{eng.kv_pages_read}"
        Path(_EMIT["metrics"]).write_text(json.dumps(
            {"metrics": eng.metrics_snapshot(), "movement": movement,
             "launches": len(recs)}, indent=2))
        tot = movement.get("total", {})
        print(f"# wrote {_EMIT['metrics']}: launches={len(recs)} "
              f"hbm={tot.get('hbm_bytes', 0):.3e}B "
              f"sram={tot.get('sram_bytes', 0):.3e}B "
              f"energy={tot.get('energy_j', 0):.3e}J "
              f"padding_overhead={tot.get('padding_overhead', 0):.3f}")


def run_mode(model, params, scfg, prompts, max_new):
    eng = make_engine(model, params, scfg)
    t0 = time.time()
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    done = eng.run_until_done(max_ticks=100_000)
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    assert len(done) == len(prompts), (len(done), len(prompts))
    return {"requests": len(done), "tokens": toks, "seconds": dt,
            "tok_per_s": toks / max(dt, 1e-9),
            "kv_bytes": eng.kv_cache_bytes(),
            "peak_pages": eng.peak_pages,
            "pool_pages": scfg.pool_pages() if scfg.paged else 0}


# ===========================================================================
# chunked-prefill trace (monolithic vs token-budget scheduler)
# ===========================================================================

def make_wave_trace(rng, vocab, lens, waves):
    """`waves` arrival waves, each [longest, *shorter lens] submitted the
    same tick - the bubble-inducing shape: every wave's long prompt lands
    at the head of the FIFO queue while earlier waves are mid-decode and
    this wave's short prompts queue behind it."""
    order = sorted(lens, reverse=True)
    arrivals = []
    for w in range(waves):
        for n in order:
            arrivals.append((w * 4, rng.integers(1, vocab,
                                                 size=n).tolist()))
    return arrivals


def run_latency_mode(model, params, scfg, arrivals, max_new, short_len):
    """Serve a timed-arrival trace and report latency stats: p50/p95 TTFT,
    time-between-tokens, and per-token tick-work stalls (deterministic
    bubble sizes - see docs/scheduling.md), wall-clock and work-clock."""
    eng = make_engine(model, params, scfg)
    pending = list(arrivals)
    uids_short = []
    t0 = time.time()
    tick = 0
    done = []
    while pending or eng.queue or any(s is not None for s in eng.slots):
        while pending and pending[0][0] <= tick:
            _, prompt = pending.pop(0)
            uid = eng.submit(prompt, max_new_tokens=max_new)
            if len(prompt) <= short_len:
                uids_short.append(uid)
        done.extend(eng.tick())
        tick += 1
        assert tick < 500_000, "trace did not drain"
    dt = time.time() - t0
    assert len(done) == len(arrivals), (len(done), len(arrivals))
    outs = {r.uid: r.out_tokens for r in done}
    st = eng.stats()
    # TTFT of the interactive class: short prompts that queued behind a
    # long prefill - the requests chunking is supposed to protect
    short_reqs = [r for r in done if r.uid in uids_short]
    short_ttft = [r.ttft_work() for r in short_reqs]
    toks = sum(len(t) for t in outs.values())
    row = {"requests": len(done), "tokens": toks, "seconds": dt,
           "tok_per_s": toks / max(dt, 1e-9),
           "prefill_tokens": st["prefill_tokens"],
           "tick_token_budget": st["tick_token_budget"],
           "short_ttft_work_p95": float(np.percentile(short_ttft, 95))}
    row.update({k: st[k] for k in (
        "ticks", "chunks_run", "packs_run", "max_tick_tokens",
        "ttft_wall_p50", "ttft_wall_p95", "tbt_wall_p50", "tbt_wall_p95",
        "ttft_work_p50", "ttft_work_p95", "tbt_work_p50", "tbt_work_p95",
        "stall_work_p50", "stall_work_p95", "stall_work_max",
        # dispatch accounting: jitted launches, device->host transfers,
        # recompiles, and per-tick host-loop wall time
        "jit_calls", "host_syncs", "compile_count",
        "jit_calls_per_tick_max", "jit_calls_per_tick_mean",
        "jit_calls_per_busy_tick_max", "host_syncs_per_tick_max",
        "tick_host_wall_p50", "tick_host_wall_p95")})
    return outs, row


def run_chunked_trace(args, out_json):
    """Mixed 128/1k/4k wave trace through the paged engine: monolithic
    admission-time prefill vs chunked prefill under a per-tick token
    budget.  Asserted: byte-identical greedy outputs; tick_token_budget a
    hard per-tick ceiling the monolithic engine blows through; lower p95
    tick-work stalls (time-between-tokens for in-flight decodes) and
    lower p95 TTFT for short prompts queued behind long prefills."""
    # float32 keeps greedy argmax ties out of the comparison
    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    waves = max(args.requests // len(args.lens), 2)
    arrivals = make_wave_trace(rng, cfg.vocab_size, args.lens, waves)
    short_len = sorted(args.lens)[-2]          # everything but the longest
    per_req = pages_needed(max(args.lens) + args.max_new, args.page_size)
    # a latency trace, so no slot or page contention: every request admits
    # the tick it arrives and the measured TTFT/TBT gaps are pure PREFILL
    # SCHEDULING (the default trace exercises backpressure instead)
    max_batch = len(arrivals)
    num_pages = len(arrivals) * per_req + 1
    # room for the oldest request's guaranteed chunk PLUS a
    # shortest-remaining-first chunk every tick (serve/scheduler.py)
    budget = args.tick_budget or max_batch + 2 * args.prefill_chunk
    base = dict(max_batch=max_batch, max_seq=args.max_seq,
                max_new_tokens=args.max_new, paged=True,
                page_size=args.page_size, num_pages=num_pages)
    chunk_kw = dict(chunked=True, prefill_chunk=args.prefill_chunk,
                    tick_token_budget=budget)
    cfg_mono = ServeConfig(**base)
    cfg_chunk = ServeConfig(**base, **chunk_kw)            # batched (default)
    modes = [("monolithic", cfg_mono)]
    if args.batched:
        # the sequential per-chunk oracle the one-launch tick is held to
        modes.append(("chunked_seq",
                      ServeConfig(**base, **chunk_kw, batched=False)))
    modes.append(("chunked", cfg_chunk))

    print(f"# arch={cfg.name} max_batch={max_batch} lens={args.lens} "
          f"waves={waves} max_new={args.max_new} "
          f"page={args.page_size} chunk={args.prefill_chunk} "
          f"budget={budget}")
    print("mode,requests,tokens,seconds,tok_per_s,ticks,chunks_run,"
          "max_tick_tokens,stall_work_p95,short_ttft_work_p95,"
          "tbt_wall_p95,ttft_wall_p95,jit_calls,busy_tick_jit_max,"
          "sync_max,compiles")
    rows, outs = {}, {}
    for mode, scfg in modes:
        outs[mode], r = run_latency_mode(model, params, scfg, arrivals,
                                         args.max_new, short_len)
        rows[mode] = r
        print(f"{mode},{r['requests']},{r['tokens']},{r['seconds']:.2f},"
              f"{r['tok_per_s']:.1f},{r['ticks']},{r['chunks_run']},"
              f"{r['max_tick_tokens']},{r['stall_work_p95']:.0f},"
              f"{r['short_ttft_work_p95']:.0f},"
              f"{r['tbt_wall_p95'] * 1e3:.1f}ms,"
              f"{r['ttft_wall_p95'] * 1e3:.1f}ms,"
              f"{r['jit_calls']},{r['jit_calls_per_busy_tick_max']},"
              f"{r['host_syncs_per_tick_max']},{r['compile_count']}")

    mono, chunk = rows["monolithic"], rows["chunked"]
    if args.batched:
        seq = rows["chunked_seq"]
        print(f"# one-launch ticks: busy-tick jit calls "
              f"{chunk['jit_calls_per_busy_tick_max']} vs "
              f"{seq['jit_calls_per_busy_tick_max']} sequential, total "
              f"launches {chunk['jit_calls']} vs {seq['jit_calls']}, "
              f"syncs {chunk['host_syncs']} vs {seq['host_syncs']}, "
              f"compiles {chunk['compile_count']} vs "
              f"{seq['compile_count']}")
        assert outs["chunked"] == outs["chunked_seq"], \
            "batched chunk execution changed greedy outputs"
        # the acceptance criterion: a steady-state tick with prefill AND
        # decode in flight is one batched prefill launch + one decode
        # launch; no tick ever syncs more than once
        assert chunk["jit_calls_per_busy_tick_max"] == 2, \
            f"batched busy tick ran {chunk['jit_calls_per_busy_tick_max']}" \
            f" jitted calls (want exactly 2)"
        assert chunk["jit_calls_per_tick_max"] <= 2
        assert chunk["host_syncs_per_tick_max"] <= 1
        assert chunk["jit_calls"] < seq["jit_calls"], \
            "batched path must issue fewer launches than sequential"
        rows["savings_batched"] = {
            "jit_calls_ratio": chunk["jit_calls"] / max(seq["jit_calls"], 1),
            "host_syncs_ratio": chunk["host_syncs"]
            / max(seq["host_syncs"], 1),
            "identical_greedy_outputs": True,
        }
    print(f"# p95 tick-work stall {chunk['stall_work_p95']:.0f} vs "
          f"{mono['stall_work_p95']:.0f} tokens, short-prompt p95 TTFT "
          f"{chunk['short_ttft_work_p95']:.0f} vs "
          f"{mono['short_ttft_work_p95']:.0f} work-tokens, max tick "
          f"{chunk['max_tick_tokens']} vs {mono['max_tick_tokens']}")
    assert outs["chunked"] == outs["monolithic"], \
        "chunked scheduling changed greedy outputs"
    assert chunk["max_tick_tokens"] <= budget, \
        "tick_token_budget exceeded"
    assert mono["max_tick_tokens"] > budget, \
        "monolithic trace never exceeded the budget - trace too easy to " \
        "show a scheduling bubble"
    assert chunk["stall_work_p95"] < mono["stall_work_p95"], \
        "chunked scheduling must lower p95 decode stalls (TBT)"
    assert chunk["short_ttft_work_p95"] < mono["short_ttft_work_p95"], \
        "chunked scheduling must lower p95 TTFT for short prompts"
    rows["savings"] = {
        "stall_work_p95_ratio": chunk["stall_work_p95"]
        / max(mono["stall_work_p95"], 1e-9),
        "short_ttft_work_p95_ratio": chunk["short_ttft_work_p95"]
        / max(mono["short_ttft_work_p95"], 1e-9),
        "identical_greedy_outputs": True,
    }
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


# ===========================================================================
# shared-prefix trace (prefix caching on vs off)
# ===========================================================================

def make_prefix_trace(rng, vocab, groups, followers, shared_len, tail_len):
    """One warmup + `followers` follower prompts per shared prefix."""
    warm, follow = [], []
    for _ in range(groups):
        shared = rng.integers(1, vocab, size=shared_len).tolist()
        warm.append(shared + rng.integers(1, vocab, size=tail_len).tolist())
        for _ in range(followers):
            follow.append(shared
                          + rng.integers(1, vocab, size=tail_len).tolist())
    return warm, follow


def run_prefix_mode(model, params, scfg, warm, follow, max_new):
    eng = make_engine(model, params, scfg)
    out = {}
    t0 = time.time()
    # warmups run to completion first so their prompt pages are published
    # before any follower is admitted; followers then run concurrently
    for wave in (warm, follow):
        for p in wave:
            eng.submit(p, max_new_tokens=max_new)
        for r in eng.run_until_done(max_ticks=100_000):
            out[r.uid] = r.out_tokens
    dt = time.time() - t0
    assert len(out) == len(warm) + len(follow)
    stats = eng.prefix_stats()
    toks = sum(len(t) for t in out.values())
    return out, {
        "requests": len(out), "tokens": toks, "seconds": dt,
        "tok_per_s": toks / max(dt, 1e-9),
        "prefill_tokens": stats["prefill_tokens"],
        "prefix_hit_tokens": stats["prefix_hit_tokens"],
        "prompt_tokens": stats["prompt_tokens"],
        "hit_rate": stats["prefix_hit_tokens"]
        / max(stats["prompt_tokens"], 1),
        "cow_copies": stats["cow_copies"],
        "cached_pages": stats["cached_pages"],
        "peak_pages": stats["peak_pages"],
        "peak_live_pages": stats["peak_live_pages"],
        "pool_pages": scfg.pool_pages(),
    }


def run_prefix_trace(args, out_json):
    # float32 keeps greedy argmax ties out of the cache-on/off comparison
    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    warm, follow = make_prefix_trace(rng, cfg.vocab_size, args.groups,
                                     args.followers, args.shared_len,
                                     args.tail_len)
    per_req = pages_needed(args.shared_len + args.tail_len + args.max_new,
                           args.page_size)
    num_pages = (args.groups * pages_needed(args.shared_len, args.page_size)
                 + args.max_batch * per_req + 1)
    base = dict(max_batch=args.max_batch, max_seq=args.max_seq,
                max_new_tokens=args.max_new, paged=True,
                page_size=args.page_size, num_pages=num_pages)
    cfg_off = ServeConfig(**base)
    cfg_on = ServeConfig(**base, prefix_cache=True)

    print(f"# arch={cfg.name} groups={args.groups} "
          f"followers={args.followers} shared={args.shared_len} "
          f"tail={args.tail_len} max_new={args.max_new} "
          f"page={args.page_size} pool={num_pages}")
    print("mode,requests,tokens,seconds,tok_per_s,prefill_tokens,"
          "hit_rate,peak_live_pages,peak_pages,cached_pages,cow_copies")
    rows = {}
    outs = {}
    for mode, scfg in (("prefix_off", cfg_off), ("prefix_on", cfg_on)):
        outs[mode], r = run_prefix_mode(model, params, scfg, warm, follow,
                                        args.max_new)
        rows[mode] = r
        print(f"{mode},{r['requests']},{r['tokens']},{r['seconds']:.2f},"
              f"{r['tok_per_s']:.1f},{r['prefill_tokens']},"
              f"{r['hit_rate']:.2f},{r['peak_live_pages']},"
              f"{r['peak_pages']},{r['cached_pages']},{r['cow_copies']}")

    off, on = rows["prefix_off"], rows["prefix_on"]
    saved = 1 - on["prefill_tokens"] / max(off["prefill_tokens"], 1)
    print(f"# prefill tokens {on['prefill_tokens']} vs "
          f"{off['prefill_tokens']} ({saved:.0%} saved), peak live pages "
          f"{on['peak_live_pages']} vs {off['peak_live_pages']}")
    assert outs["prefix_on"] == outs["prefix_off"], \
        "prefix caching changed greedy outputs"
    assert saved >= 0.40, f"prefill savings {saved:.0%} < 40%"
    assert on["peak_live_pages"] < off["peak_live_pages"], \
        "prefix caching must shrink the peak working set"
    rows["savings"] = {"prefill_tokens_saved_frac": saved,
                       "identical_greedy_outputs": True}
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


# ===========================================================================
# fleet routing (prefix-aware affinity vs round-robin, 1/2/4 replicas)
# ===========================================================================

def run_fleet_mode(model, params, scfg, fcfg, warm, follow, max_new):
    """Serve the warm-then-followers shared-prefix trace through one
    router configuration.  Warmups drain first so every shared prefix is
    published on SOME replica before the followers are scored against the
    fleet; the followers then run concurrently."""
    router = FleetRouter(model, params, scfg, fcfg)
    out = {}
    t0 = time.time()
    for wave in (warm, follow):
        for p in wave:
            router.submit(p, max_new_tokens=max_new)
        for r in router.run_until_done(max_ticks=100_000):
            out[r.fleet_uid] = r.out_tokens
    dt = time.time() - t0
    assert len(out) == len(warm) + len(follow)
    router.check_invariants()
    st = router.fleet_stats()
    toks = sum(len(t) for t in out.values())
    row = {"n_replicas": st["n_replicas"], "policy": st["policy"],
           "requests": st["requests"], "tokens": toks, "seconds": dt,
           "tok_per_s": toks / max(dt, 1e-9),
           "prefill_tokens": st["prefill_tokens"],
           "prefix_hit_tokens": st["prefix_hit_tokens"],
           "hit_rate": st["prefix_hit_tokens"]
           / max(st["prompt_tokens"], 1),
           "ticks": st["ticks"], "dispatch": st["dispatch"],
           "spills": st["spills"], "affinity_hits": st["affinity_hits"],
           "affinity_hit_tokens": st["affinity_hit_tokens"]}
    return out, row, router


def run_fleet_trace(args, out_json):
    """Replica-count sweep of the fleet router on the shared-prefix trace:
    affinity at every count in --replicas, round-robin at the same counts
    as the control.  The affinity policy must (a) reproduce the 1-replica
    outputs bit-identically at every fleet size (shared jitted steps) and
    (b) strictly beat round-robin on prefill tokens computed at every
    n > 1 - a follower routed off its cached prefix recomputes the whole
    shared prefix, and that recompute is exactly what prefix-aware
    dispatch exists to avoid."""
    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    warm, follow = make_prefix_trace(rng, cfg.vocab_size, args.groups,
                                     args.followers, args.shared_len,
                                     args.tail_len)
    per_req = pages_needed(args.shared_len + args.tail_len + args.max_new,
                           args.page_size)
    num_pages = (args.groups * pages_needed(args.shared_len, args.page_size)
                 + args.max_batch * per_req + 1)
    scfg = ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                       max_new_tokens=args.max_new, paged=True,
                       page_size=args.page_size, num_pages=num_pages,
                       prefix_cache=True,
                       telemetry=bool(args.emit_trace))
    sweep = [("affinity", n) for n in args.replicas]
    sweep += [("round_robin", n) for n in args.replicas if n > 1]

    print(f"# arch={cfg.name} groups={args.groups} "
          f"followers={args.followers} shared={args.shared_len} "
          f"tail={args.tail_len} max_new={args.max_new} "
          f"pool={num_pages}/replica replicas={args.replicas}")
    print("mode,replicas,requests,tokens,seconds,tok_per_s,"
          "prefill_tokens,hit_rate,affinity_hit_tokens,spills,dispatch")
    rows, outs = {}, {}
    router = None
    for policy, n in sweep:
        key = f"{policy}_n{n}"
        outs[key], rows[key], router = run_fleet_mode(
            model, params, scfg,
            FleetConfig(n_replicas=n, policy=policy),
            warm, follow, args.max_new)
        r = rows[key]
        print(f"{policy},{n},{r['requests']},{r['tokens']},"
              f"{r['seconds']:.2f},{r['tok_per_s']:.1f},"
              f"{r['prefill_tokens']},{r['hit_rate']:.2f},"
              f"{r['affinity_hit_tokens']},{r['spills']},"
              f"\"{r['dispatch']}\"")
    if args.emit_trace and router is not None:
        router.export_trace(args.emit_trace, clock="work")
        print(f"# wrote {args.emit_trace} (merged fleet trace, one track "
              f"group per replica; open in Perfetto)")

    base_key = f"affinity_n{args.replicas[0]}"
    for key, out in outs.items():
        assert out == outs[base_key], \
            f"{key} changed greedy outputs vs {base_key}"
    curve = {n: rows[f"affinity_n{n}"]["prefill_tokens"]
             for n in args.replicas}
    print(f"# affinity prefill-token curve over replicas: {curve}")
    savings = {}
    for n in args.replicas:
        if n <= 1 or f"round_robin_n{n}" not in rows:
            continue
        aff = rows[f"affinity_n{n}"]
        rr = rows[f"round_robin_n{n}"]
        saved = 1 - aff["prefill_tokens"] / max(rr["prefill_tokens"], 1)
        print(f"# n={n}: affinity prefill {aff['prefill_tokens']} vs "
              f"round-robin {rr['prefill_tokens']} ({saved:.0%} saved)")
        assert aff["prefill_tokens"] < rr["prefill_tokens"], \
            f"affinity routing saved no prefill over round-robin at n={n}"
        assert aff["affinity_hit_tokens"] > 0, \
            f"affinity never matched a cached prefix at n={n}"
        savings[f"n{n}"] = {"prefill_tokens_saved_frac": saved,
                            "affinity_hit_tokens":
                            aff["affinity_hit_tokens"]}
    rows["savings_fleet"] = dict(savings,
                                 prefill_curve={str(n): curve[n]
                                                for n in args.replicas},
                                 identical_greedy_outputs=True)
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


# ===========================================================================
# self-speculative decoding (draft/verify vs plain decode)
# ===========================================================================

def run_spec_trace(args, out_json):
    """Shared-prefix, LONG-GENERATION trace through the paged chunked
    batched engine with ServeConfig.speculative off vs on.  Long greedy
    generations on the smoke models settle into repeating patterns - the
    traffic shape self-drafting (prompt-lookup over the request's own
    history, serve/drafting.py) is built for, standing in for the
    copy/paraphrase structure of real retrieval and code traffic.

    Asserted, not eyeballed: bit-identical greedy outputs spec-on vs
    spec-off, equal work-clock totals (the work clock counts ACCEPTED
    tokens only), nonzero acceptance, and the headline speedup -
    generated tokens per decode-path launch > 1.5x the baseline's, with
    tokens per KV page read (the memory-traffic side of the same win)
    reported alongside."""
    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # calibrated trace: 48-token shared prefix, short tails, LONG greedy
    # generations (the drafter's acceptance comes from the repeating
    # patterns long generations settle into - short runs never get there)
    shared_len, tails = 48, (8, 16, 24, 4)
    shared = rng.integers(1, cfg.vocab_size, size=shared_len).tolist()
    prompts = [shared + rng.integers(1, cfg.vocab_size, size=t).tolist()
               for t in tails]
    max_new = args.spec_max_new
    base = dict(max_batch=len(tails), max_seq=2048, max_new_tokens=max_new,
                paged=True, page_size=16, chunked=True, prefill_chunk=32,
                tick_token_budget=128, batched=True, prefix_cache=True,
                spec_k=args.spec_k)

    print(f"# arch={cfg.name} shared={shared_len} tails={tails} "
          f"max_new={max_new} spec_k={args.spec_k}")
    print("mode,requests,tokens,seconds,tok_per_s,ticks,launches,"
          "tokens_per_launch,tokens_per_kv_page,drafted,accepted,"
          "rejected,accept_rate,chain_accept_mean")
    rows, outs = {}, {}
    for mode, spec in (("spec_off", False), ("spec_on", True)):
        eng = make_engine(model, params,
                          ServeConfig(speculative=spec, **base))
        t0 = time.time()
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        done = eng.run_until_done(max_ticks=100_000)
        dt = time.time() - t0
        assert len(done) == len(prompts)
        outs[mode] = {r.uid: r.out_tokens for r in done}
        st = eng.stats()
        rows[mode] = {"requests": len(done),
                      "tokens": st["gen_tokens"], "seconds": dt,
                      "tok_per_s": st["gen_tokens"] / max(dt, 1e-9),
                      "work_tokens": st["work_tokens"]}
        rows[mode].update({k: st[k] for k in (
            "ticks", "jit_calls", "decode_launches", "kv_pages_read",
            "tokens_per_launch", "tokens_per_kv_page", "spec_drafted",
            "spec_accepted", "spec_rejected", "spec_acceptance_rate",
            "spec_chain_accept_mean", "host_syncs", "compile_count")})
        r = rows[mode]
        print(f"{mode},{r['requests']},{r['tokens']},{r['seconds']:.2f},"
              f"{r['tok_per_s']:.1f},{r['ticks']},{r['decode_launches']},"
              f"{r['tokens_per_launch']:.2f},{r['tokens_per_kv_page']:.4f},"
              f"{r['spec_drafted']},{r['spec_accepted']},"
              f"{r['spec_rejected']},{r['spec_acceptance_rate']:.2f},"
              f"{r['spec_chain_accept_mean']:.2f}")

    off, on = rows["spec_off"], rows["spec_on"]
    launch_ratio = on["tokens_per_launch"] / max(off["tokens_per_launch"],
                                                 1e-9)
    page_ratio = on["tokens_per_kv_page"] / max(off["tokens_per_kv_page"],
                                                1e-9)
    print(f"# tokens/launch {on['tokens_per_launch']:.2f} vs "
          f"{off['tokens_per_launch']:.2f} ({launch_ratio:.2f}x), "
          f"tokens/KV-page {on['tokens_per_kv_page']:.4f} vs "
          f"{off['tokens_per_kv_page']:.4f} ({page_ratio:.2f}x), "
          f"acceptance {on['spec_acceptance_rate']:.2f} "
          f"(drafted {on['spec_drafted']} accepted {on['spec_accepted']} "
          f"rejected {on['spec_rejected']}, per-chain mean "
          f"{on['spec_chain_accept_mean']:.2f})")
    assert outs["spec_on"] == outs["spec_off"], \
        "speculative decoding changed greedy outputs"
    assert on["work_tokens"] == off["work_tokens"], \
        "the work clock must count accepted tokens only"
    assert on["spec_accepted"] > 0, "no draft token was ever accepted"
    assert launch_ratio > 1.5, \
        f"tokens-per-launch speedup {launch_ratio:.2f}x <= 1.5x"
    rows["savings_speculative"] = {
        "tokens_per_launch_ratio": launch_ratio,
        "tokens_per_kv_page_ratio": page_ratio,
        "acceptance_rate": on["spec_acceptance_rate"],
        "identical_greedy_outputs": True,
    }
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


# ===========================================================================
# preemption + decode-priority trace (budget shaping and load shedding)
# ===========================================================================

def run_preempt_replay(model, params, scfg, arrivals):
    """Serve a timed-arrival (tick, prompt, max_new, priority) trace."""
    eng = make_engine(model, params, scfg)
    pending = list(arrivals)
    tick, done = 0, []
    t0 = time.time()
    while pending or eng.queue or any(s is not None for s in eng.slots):
        while pending and pending[0][0] <= tick:
            _, prompt, max_new, prio = pending.pop(0)
            eng.submit(prompt, max_new_tokens=max_new, priority=prio)
        done.extend(eng.tick())
        tick += 1
        assert tick < 500_000, "trace did not drain"
    dt = time.time() - t0
    return done, eng, dt


def _decode_tbt_p95(done, uids):
    tbt = [d for r in done if r.uid in uids for d in r.tbt_work()]
    return float(np.percentile(tbt, 95)) if tbt else 0.0


def run_preempt_trace(args, out_json):
    """Two-part trace for the preemption/shaping acceptance criteria.

    Part 1 - decode-priority budget shaping: short interactive requests
    decode while a burst of long prompts floods the prefill queue; with
    `decode_priority` ON the prefill share of every tick is capped, so
    the in-flight decodes' p95 work-clock TBT must be STRICTLY lower
    than with shaping off (asserted), at identical request completion.

    Part 2 - preemption: low-priority background requests fill a capacity
    cap (ServeConfig.usable_pages - same pool shape, fewer grantable
    pages); a high-priority burst then preempts victims, which park
    QUEUED->RESUMING and resume through the chunk path.  Greedy outputs
    must be bit-identical to the same trace served WITHOUT the capacity
    cap (the uninterrupted oracle), and preemptions/resumes/
    pages_reclaimed are reported (asserted > 0)."""
    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    short_len, long_len = sorted(args.lens)[0], max(args.lens)
    n_short, n_long = 3, 4
    shorts = [(0, rng.integers(1, cfg.vocab_size, size=short_len).tolist(),
               args.max_new * 2, 0) for _ in range(n_short)]
    burst = [(4, rng.integers(1, cfg.vocab_size, size=long_len).tolist(),
              args.max_new, 0) for _ in range(n_long)]
    arrivals = sorted(shorts + burst)
    max_batch = n_short + n_long
    per_req = pages_needed(long_len + 2 * args.max_new, args.page_size)
    chunk = args.prefill_chunk
    budget = max_batch + 4 * chunk         # room for several chunks/tick
    base = dict(max_batch=max_batch, max_seq=args.max_seq,
                max_new_tokens=args.max_new, paged=True,
                page_size=args.page_size,
                num_pages=max_batch * per_req + 1, chunked=True,
                prefill_chunk=chunk, tick_token_budget=budget)
    short_uids = set(range(1, n_short + 1))

    print(f"# arch={cfg.name} shorts={n_short}x{short_len} "
          f"burst={n_long}x{long_len} chunk={chunk} budget={budget} "
          f"max_prefill_fraction=0.25")
    print("mode,requests,seconds,decode_tbt_work_p95,max_tick_tokens,"
          "preemptions,resumes,pages_reclaimed")
    rows = {}
    for mode, extra in (("shaping_off", {}),
                        ("shaping_on", dict(decode_priority=True,
                                            max_prefill_fraction=0.25))):
        done, eng, dt = run_preempt_replay(
            model, params, ServeConfig(**base, **extra), arrivals)
        st = eng.stats()
        rows[mode] = {"requests": len(done), "seconds": dt,
                      "decode_tbt_work_p95": _decode_tbt_p95(done,
                                                             short_uids),
                      "max_tick_tokens": st["max_tick_tokens"],
                      "preemptions": st["preemptions"],
                      "resumes": st["resumes"],
                      "pages_reclaimed": st["pages_reclaimed"]}
        r = rows[mode]
        print(f"{mode},{r['requests']},{r['seconds']:.2f},"
              f"{r['decode_tbt_work_p95']:.0f},{r['max_tick_tokens']},"
              f"{r['preemptions']},{r['resumes']},{r['pages_reclaimed']}")

    off, on = rows["shaping_off"], rows["shaping_on"]
    print(f"# decode p95 TBT (work-clock): {on['decode_tbt_work_p95']:.0f} "
          f"shaped vs {off['decode_tbt_work_p95']:.0f} unshaped")
    assert on["decode_tbt_work_p95"] < off["decode_tbt_work_p95"], \
        "decode-priority shaping must lower decode p95 work-clock TBT " \
        "under a prefill burst"

    # ---- part 2: preemption against a capacity cap --------------------
    lo = [(0, rng.integers(1, cfg.vocab_size, size=long_len).tolist(),
           args.max_new, 0) for _ in range(2)]
    hi = [(6, rng.integers(1, cfg.vocab_size, size=short_len * 2).tolist(),
           args.max_new, 5)]
    trace = sorted(lo + hi)
    pre_base = dict(base, max_batch=3, preemption=True,
                    max_chunks_per_tick=1,
                    tick_token_budget=3 + chunk)
    cap = 2 * per_req + 2                  # fits the background, not the burst
    done_o, eng_o, _ = run_preempt_replay(model, params,
                                          ServeConfig(**pre_base), trace)
    done_p, eng_p, _ = run_preempt_replay(
        model, params, ServeConfig(**pre_base, usable_pages=cap), trace)
    st = eng_p.stats()
    outs_o = {r.uid: r.out_tokens for r in done_o}
    outs_p = {r.uid: r.out_tokens for r in done_p}
    print(f"# preemption leg: preemptions={st['preemptions']} "
          f"resumes={st['resumes']} pages_reclaimed={st['pages_reclaimed']} "
          f"(capacity cap {cap} of {pre_base['num_pages']} pages)")
    assert st["preemptions"] >= 1 and st["resumes"] >= 1, \
        "capacity cap never forced a preemption - trace too easy"
    assert outs_p == outs_o, \
        "preempt/resume changed greedy outputs vs the uninterrupted run"
    rows["preemption"] = {"preemptions": st["preemptions"],
                          "resumes": st["resumes"],
                          "pages_reclaimed": st["pages_reclaimed"],
                          "identical_greedy_outputs": True,
                          "usable_pages": cap,
                          "tbt_shaping_ratio":
                          on["decode_tbt_work_p95"]
                          / max(off["decode_tbt_work_p95"], 1e-9)}
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


def run_default_trace(args, out_json):
    """Mixed-length trace through the dense vs the paged engine."""
    cfg = get_smoke_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=args.lens[i % len(args.lens)]).tolist()
               for i in range(args.requests)]

    num_pages = args.num_pages
    if num_pages == 0:
        # size the pool to the trace: the longest request fully resident on
        # every slot would be dense-equivalent; halving it is what paging
        # buys on a mixed trace (short requests hold few pages)
        per_req = pages_needed(max(args.lens) + args.max_new, args.page_size)
        num_pages = max(args.max_batch * per_req // 2,
                        2 * per_req) + 1

    dense_cfg = ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                            max_new_tokens=args.max_new)
    paged_cfg = ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                            max_new_tokens=args.max_new, paged=True,
                            page_size=args.page_size, num_pages=num_pages)

    print(f"# arch={cfg.name} max_batch={args.max_batch} "
          f"max_seq={args.max_seq} lens={args.lens} "
          f"requests={args.requests} max_new={args.max_new}")
    print(f"# capacity math: dense {dense_kv_bytes(cfg, dense_cfg)} B, "
          f"paged pool {paged_kv_bytes(cfg, paged_cfg, num_pages)} B "
          f"({num_pages} pages x {args.page_size} tok)")
    print("mode,requests,tokens,seconds,tok_per_s,kv_bytes,"
          "peak_pages,pool_pages")
    rows = {}
    for mode, scfg in (("dense", dense_cfg), ("paged", paged_cfg)):
        r = run_mode(model, params, scfg, prompts, args.max_new)
        rows[mode] = r
        print(f"{mode},{r['requests']},{r['tokens']},{r['seconds']:.2f},"
              f"{r['tok_per_s']:.1f},{r['kv_bytes']},{r['peak_pages']},"
              f"{r['pool_pages']}")
    saved = 1 - rows["paged"]["kv_bytes"] / rows["dense"]["kv_bytes"]
    print(f"# paged peak KV bytes {rows['paged']['kv_bytes']} "
          f"vs dense {rows['dense']['kv_bytes']} "
          f"({saved:.0%} smaller)")
    assert rows["paged"]["kv_bytes"] < rows["dense"]["kv_bytes"], \
        "paged pool must be strictly smaller than the dense cache"
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


# ===========================================================================
# chaos trace (kill 1 of N replicas mid-trace; latency cost of recovery)
# ===========================================================================

def make_chaos_trace(rng, vocab, lens, requests, spread):
    """Timed-arrival mixed trace: `requests` prompts cycling `lens`,
    arrival ticks spread over [0, spread]."""
    arrivals = []
    for i in range(requests):
        n = lens[i % len(lens)]
        arrivals.append((int(rng.integers(0, spread + 1)),
                         rng.integers(1, vocab, size=n).tolist()))
    arrivals.sort(key=lambda a: a[0])
    return arrivals


def run_chaos_mode(model, params, scfg, fcfg, arrivals, max_new,
                   kill_tick=None, victim=None):
    """Serve a timed-arrival trace through the fleet, optionally killing
    `victim` at fleet tick `kill_tick`.  TTFT is measured in FLEET TICKS
    (first-token tick minus submit tick) - the one clock that spans a
    redispatch, since per-engine work clocks restart on the survivor.
    Asserts router invariants every tick and that every request
    completes."""
    router = FleetRouter(model, params, scfg, fcfg)
    pending = list(arrivals)
    submit_tick, first_tok = {}, {}
    done = []
    t0 = time.time()
    tick = 0
    while pending or not router.idle:
        if kill_tick is not None and tick == kill_tick:
            router.fail(victim)
        while pending and pending[0][0] <= tick:
            _, prompt = pending.pop(0)
            uid = router.submit(prompt, max_new_tokens=max_new)
            submit_tick[uid] = tick
        done.extend(router.tick())
        router.check_invariants()
        for uid, req in router.requests.items():
            if uid not in first_tok and req.out_tokens:
                first_tok[uid] = tick
        tick += 1
        assert tick < 500_000, "chaos trace did not drain"
    dt = time.time() - t0
    statuses = router.statuses()
    assert set(statuses.values()) == {"done"}, \
        f"chaos trace left non-done requests: {statuses}"
    assert len(done) == len(arrivals), (len(done), len(arrivals))
    ttft = sorted(first_tok[u] - submit_tick[u] for u in submit_tick)
    st = router.fleet_stats()
    outs = {u: list(r.out_tokens) for u, r in router.requests.items()}
    row = {"requests": len(done), "ticks": tick, "seconds": dt,
           "ttft_ticks_p50": float(np.percentile(ttft, 50)),
           "ttft_ticks_p95": float(np.percentile(ttft, 95)),
           "redispatches": st["redispatches"],
           "failures": st["failures"],
           "replica_states": st["replica_states"],
           "dispatch": st["dispatch"]}
    return outs, row, router


def run_chaos_trace(args, out_json):
    """Kill 1 of N replicas mid-trace and price the recovery: the same
    timed-arrival trace runs fault-free and with a kill at --kill-tick,
    and the bench asserts (a) every request still completes, (b) greedy
    outputs are bit-identical to the fault-free run - replica death is
    invisible in the tokens - and (c) the p95 first-token latency (in
    fleet ticks, the clock that spans a redispatch) inflates by at most
    --chaos-ttft-bound x.  The latency cost of fault tolerance is the
    headline number; the conformance is the contract."""
    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n = args.chaos_replicas
    rng = np.random.default_rng(0)
    arrivals = make_chaos_trace(rng, cfg.vocab_size, args.lens,
                                args.requests * 2, spread=4)
    scfg = ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                       max_new_tokens=args.max_new, paged=True,
                       page_size=args.page_size, chunked=True,
                       batched=True, prefix_cache=True,
                       prefill_chunk=args.prefill_chunk,
                       tick_token_budget=args.tick_budget
                       or args.max_batch + 2 * args.prefill_chunk)
    fcfg = FleetConfig(n_replicas=n)
    print(f"# arch={cfg.name} replicas={n} requests={len(arrivals)} "
          f"lens={args.lens} max_new={args.max_new} "
          f"kill_tick={args.kill_tick} victim={args.victim}")
    print("mode,requests,ticks,ttft_ticks_p50,ttft_ticks_p95,"
          "redispatches,dispatch")
    rows = {}
    base_out, rows["fault_free"], _ = run_chaos_mode(
        model, params, scfg, fcfg, arrivals, args.max_new)
    chaos_out, rows["kill_one"], router = run_chaos_mode(
        model, params, scfg, fcfg, arrivals, args.max_new,
        kill_tick=args.kill_tick, victim=args.victim)
    for key in ("fault_free", "kill_one"):
        r = rows[key]
        print(f"{key},{r['requests']},{r['ticks']},"
              f"{r['ttft_ticks_p50']:.1f},{r['ttft_ticks_p95']:.1f},"
              f"{r['redispatches']},\"{r['dispatch']}\"")
    assert chaos_out == base_out, \
        "kill-one run changed greedy outputs vs the fault-free run"
    assert rows["kill_one"]["failures"] == 1
    assert rows["kill_one"]["redispatches"] > 0, \
        "the kill moved no requests - pick an earlier --kill-tick"
    p95_base = max(rows["fault_free"]["ttft_ticks_p95"], 1.0)
    p95_chaos = rows["kill_one"]["ttft_ticks_p95"]
    inflation = p95_chaos / p95_base
    bound = args.chaos_ttft_bound
    print(f"# p95 first-token latency: {p95_base:.1f} -> {p95_chaos:.1f} "
          f"ticks ({inflation:.2f}x, bound {bound:.1f}x); "
          f"{rows['kill_one']['redispatches']} requests redispatched")
    assert inflation <= bound, \
        f"p95 TTFT inflated {inflation:.2f}x > bound {bound:.1f}x"
    rows["chaos_summary"] = {
        "identical_greedy_outputs": True,
        "all_requests_completed": True,
        "ttft_ticks_p95_inflation": inflation,
        "ttft_ticks_p95_bound": bound,
        "redispatches": rows["kill_one"]["redispatches"],
        "kill_tick": args.kill_tick, "victim": args.victim,
        "n_replicas": n}
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


# ===========================================================================
# tensor-parallel trace (tp=1 vs tp=N: per-device data movement)
# ===========================================================================

def run_tp_mode(model, params, scfg, prompts, max_new):
    """Serve the trace and report the TP accounting alongside run_mode's
    throughput row: per-device KV bytes read, block-table replication
    bytes, and the movement breakdown's per_device section."""
    eng = make_engine(model, params, scfg)
    t0 = time.time()
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    done = eng.run_until_done(max_ticks=100_000)
    dt = time.time() - t0
    eng.check_invariants()
    assert len(done) == len(prompts), (len(done), len(prompts))
    outs = {r.uid: list(r.out_tokens) for r in done}
    toks = sum(len(t) for t in outs.values())
    tp = eng.tp_stats()
    row = {"tp_degree": tp["tp_degree"], "requests": len(done),
           "tokens": toks, "seconds": dt,
           "tok_per_s": toks / max(dt, 1e-9),
           "work_tokens": eng.stats()["work_tokens"],
           "kv_pages_read": tp["kv_pages_read"],
           "page_bytes": tp["page_bytes"],
           "shard_page_bytes": tp["shard_page_bytes"],
           "shard_kv_bytes_read": tp["shard_kv_bytes_read"],
           "table_bytes_replicated": tp["table_bytes_replicated"]}
    mv = eng.movement_stats()
    if "per_device" in mv:
        row["per_device_movement"] = mv["per_device"]
    return outs, row


def run_tp_trace(args, out_json):
    """The same mixed trace through the paged chunked batched engine at
    tp_degree=1 and tp_degree=--tp-degree (head-sharded KV pool + kernels,
    docs/tensor_parallel.md).  Asserted, never eyeballed: bit-identical
    greedy outputs (the all-gather restores the tp=1 summation order),
    equal work clocks and page reads, and the headline inequality - each
    device at tp=N streams at most 1/N of the single-device KV read bytes
    plus the block-table replication overhead.  Requires >= --tp-degree
    devices (on CPU: XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=args.lens[i % len(args.lens)]).tolist()
               for i in range(args.requests)]

    def scfg(tp):
        return ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                           max_new_tokens=args.max_new, paged=True,
                           page_size=args.page_size, chunked=True,
                           batched=True, prefill_chunk=args.prefill_chunk,
                           tick_token_budget=args.tick_budget
                           or args.max_batch + 2 * args.prefill_chunk,
                           tp_degree=tp)

    n = args.tp_degree
    print(f"# arch={cfg.name} tp_degree=1 vs {n} requests={len(prompts)} "
          f"lens={args.lens} max_new={args.max_new} "
          f"devices={jax.device_count()}")
    print("mode,requests,tokens,tok_per_s,kv_pages_read,"
          "shard_kv_bytes_read,table_bytes_replicated")
    rows = {}
    base_out, rows["tp1"] = run_tp_mode(model, params, scfg(1), prompts,
                                        args.max_new)
    tp_out, rows[f"tp{n}"] = run_tp_mode(model, params, scfg(n), prompts,
                                         args.max_new)
    for key in ("tp1", f"tp{n}"):
        r = rows[key]
        print(f"{key},{r['requests']},{r['tokens']},{r['tok_per_s']:.1f},"
              f"{r['kv_pages_read']},{r['shard_kv_bytes_read']},"
              f"{r['table_bytes_replicated']}")
    assert tp_out == base_out, \
        f"tp={n} changed greedy outputs vs single-device"
    assert rows["tp1"]["work_tokens"] == rows[f"tp{n}"]["work_tokens"]
    assert rows["tp1"]["kv_pages_read"] == rows[f"tp{n}"]["kv_pages_read"], \
        "sharding must not change which pages decode reads"
    # the headline: per-device KV reads divide by the degree, and the
    # price is only the replicated scalar-prefetch state (block table)
    per_dev = rows[f"tp{n}"]["shard_kv_bytes_read"]
    single = rows["tp1"]["shard_kv_bytes_read"]
    overhead = rows[f"tp{n}"]["table_bytes_replicated"]
    assert per_dev <= single / n + overhead, \
        (f"per-device KV bytes {per_dev} > single-device/{n} "
         f"({single / n:.0f}) + table replication ({overhead})")
    ratio = per_dev / max(single, 1)
    print(f"# per-device KV read bytes: {single} -> {per_dev} "
          f"({ratio:.3f}x, ideal {1 / n:.3f}x); table replication "
          f"overhead {overhead} B; outputs bit-identical")
    rows["tp_summary"] = {
        "identical_greedy_outputs": True,
        "tp_degree": n,
        "per_device_kv_read_ratio": ratio,
        "ideal_ratio": 1.0 / n,
        "table_replication_bytes": overhead}
    if out_json:
        Path(out_json).write_text(json.dumps(rows, indent=2))
        print(f"# wrote {out_json}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=4096)
    ap.add_argument("--lens", type=int, nargs="+", default=[128, 1024, 3968],
                    help="mixed prompt lengths (cycled)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged pool size (0 = sized to the trace: "
                         "max_batch * pages(longest request) / 2 + slack)")
    ap.add_argument("--prefix-trace", action="store_true",
                    help="shared-prefix trace: paged serving with prefix "
                         "caching off vs on")
    ap.add_argument("--chunked", action="store_true",
                    help="mixed trace: monolithic admission prefill vs the "
                         "token-budget chunked-prefill scheduler, with "
                         "p50/p95 TTFT and time-between-tokens")
    ap.add_argument("--speculative", action="store_true",
                    help="shared-prefix long-generation trace with self-"
                         "speculative decoding off vs on: bit-identical "
                         "greedy outputs, equal work clocks, and tokens-"
                         "per-launch speedup > 1.5x, all asserted")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="speculative trace: max drafted tokens per "
                         "request per tick")
    ap.add_argument("--spec-max-new", type=int, default=512,
                    help="speculative trace: generation length (long "
                         "enough for self-drafting to engage)")
    ap.add_argument("--fleet", action="store_true",
                    help="shared-prefix trace through the fleet router: "
                         "replica-count sweep (--replicas) of prefix-aware "
                         "affinity dispatch vs round-robin; bit-identical "
                         "outputs across every size and strictly fewer "
                         "prefill tokens than round-robin, both asserted")
    ap.add_argument("--replicas", type=int, nargs="+", default=[1, 2, 4],
                    help="fleet trace: replica counts to sweep")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-tolerance trace: the same timed-arrival "
                         "mixed trace through an N-replica fleet fault-"
                         "free and with 1 replica killed mid-trace; "
                         "asserts every request completes, outputs are "
                         "bit-identical to the fault-free run, and p95 "
                         "first-token latency inflates by at most "
                         "--chaos-ttft-bound x")
    ap.add_argument("--chaos-replicas", type=int, default=4,
                    help="chaos trace: fleet size (1 replica dies)")
    ap.add_argument("--kill-tick", type=int, default=4,
                    help="chaos trace: fleet tick at which the victim "
                         "replica is killed")
    ap.add_argument("--victim", type=int, default=1,
                    help="chaos trace: replica index to kill")
    ap.add_argument("--chaos-ttft-bound", type=float, default=3.0,
                    help="chaos trace: max allowed p95 first-token "
                         "latency inflation (kill-one / fault-free)")
    ap.add_argument("--tp", action="store_true",
                    help="tensor-parallel trace: the mixed trace at "
                         "tp_degree 1 vs --tp-degree (head-sharded KV "
                         "pool + kernels); asserts bit-identical greedy "
                         "outputs, equal work clocks, and per-device KV "
                         "read bytes <= single-device/N + block-table "
                         "replication overhead (needs >= N devices; on "
                         "CPU set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--tp-degree", type=int, default=2,
                    help="tp trace: tensor-parallel degree to compare "
                         "against single-device")
    ap.add_argument("--preempt-trace", action="store_true",
                    help="decode-priority shaping (decode p95 TBT with vs "
                         "without the prefill-share cap under a prefill "
                         "burst, asserted lower) + preemption under a "
                         "capacity cap (bit-identical outputs to the "
                         "uninterrupted run, preempt/resume counters)")
    ap.add_argument("--batched", action="store_true",
                    help="with --chunked: additionally run the sequential "
                         "per-chunk oracle and assert the one-launch tick "
                         "(exactly 2 jitted calls + 1 device->host "
                         "transfer per steady-state tick, identical greedy "
                         "outputs, fewer total launches)")
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="chunked trace: tokens per prefill chunk (page "
                         "multiple)")
    ap.add_argument("--tick-budget", type=int, default=0,
                    help="chunked trace: tokens of work per tick "
                         "(0 = max_batch + 2 * prefill_chunk)")
    ap.add_argument("--groups", type=int, default=2,
                    help="prefix trace: distinct shared prefixes")
    ap.add_argument("--followers", type=int, default=3,
                    help="prefix trace: follower requests per prefix")
    ap.add_argument("--shared-len", type=int, default=256)
    ap.add_argument("--tail-len", type=int, default=64)
    ap.add_argument("--json", default="",
                    help="also write the metrics dict to this path")
    ap.add_argument("--emit-trace", default="",
                    help="write a Chrome trace-event JSON (open in "
                         "Perfetto) of the mode's final engine run; "
                         "enables ServeConfig.telemetry for the run")
    ap.add_argument("--emit-metrics", default="",
                    help="write the final engine's metrics snapshot + "
                         "per-launch-kind data-movement breakdown "
                         "(HBM/SRAM bytes, energy) to this path")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (max_seq=512, lens 64/128/448)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.quick:
        args.max_seq, args.lens = 512, [64, 128, 448]
        args.max_new, args.page_size = 16, 16
        args.shared_len, args.tail_len = 128, 32
        args.prefill_chunk = 64

    _EMIT["trace"], _EMIT["metrics"] = args.emit_trace, args.emit_metrics
    _EMIT["eng"] = None

    if args.prefix_trace:
        rows = run_prefix_trace(args, args.json)
    elif args.chunked:
        rows = run_chunked_trace(args, args.json)
    elif args.fleet:
        rows = run_fleet_trace(args, args.json)
    elif args.chaos:
        rows = run_chaos_trace(args, args.json)
    elif args.tp:
        rows = run_tp_trace(args, args.json)
    elif args.speculative:
        rows = run_spec_trace(args, args.json)
    elif args.preempt_trace:
        rows = run_preempt_trace(args, args.json)
    else:
        rows = run_default_trace(args, args.json)
    emit_artifacts()
    return rows


if __name__ == "__main__":
    main()
