"""Differential conformance layer for speculative decoding.

Extends the traffic-replay harness (tests/traffic.py) with the spec-on /
spec-off differential: every registered trace replays through TWO engines
that differ ONLY in ServeConfig.speculative, and the checks assert the
speculative engine is observationally identical to the baseline -

  greedy outputs       bit-identical (fast path), tolerating only genuine
                       fp argmax near-ties via the teacher-forced fallback
                       (traffic.assert_greedy_equivalent)
  sampled outputs      every emitted token lies in the support of the
                       target's OWN filtered distribution at that position
                       (teacher-forced through model.forward with the same
                       temperature / top-k / top-p stack), and a fixed
                       seed reproduces the trace exactly
  work clock           equal work_tokens totals: the work clock advances
                       only for ACCEPTED tokens, so drafting never skews
                       work-clock TTFT/TBT between the two runs
  page accounting      refcount conservation across rejection rollbacks -
                       replay() runs ServeEngine.check_invariants() after
                       EVERY tick, and after the trace drains every page
                       is back in the pool (or parked, refcounted, in the
                       prefix tree)

The registry deliberately covers every traffic shape the serve suites
use: mixed lengths, shared prefixes (the prefix-cache + high-acceptance
shape), waves (pipeline-bubble shape), and priority bursts (preemption
interleaved with speculation).
"""
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from repro.configs.base import ServeConfig
from repro.serve import ServeEngine
from repro.serve.scheduler import Request
from traffic import (TrafficItem, assert_greedy_equivalent, mixed_prompts,
                     priority_burst, replay, shared_prefix_prompts,
                     wave_arrivals)

# smoke-scale engine shape every conformance trace shares (speculation
# needs the paged engine; overrides per trace below)
BASE_SCFG = dict(max_batch=4, max_seq=512, page_size=16, prefill_chunk=32,
                 tick_token_budget=64, max_new_tokens=24, paged=True,
                 spec_k=6, spec_ngram=3)


@dataclass(frozen=True)
class TraceSpec:
    """One registered conformance trace: a seeded item builder plus the
    ServeConfig overrides the shape needs (pool pressure, prefix cache,
    preemption)."""
    name: str
    build: Callable[[int], List[TrafficItem]]
    scfg_kw: Dict[str, Any] = field(default_factory=dict)


def _mixed_items(vocab: int) -> List[TrafficItem]:
    return [TrafficItem(0, p) for p in
            mixed_prompts(vocab, lens=(16, 64, 224, 9, 130, 40))]


def _shared_prefix_items(vocab: int) -> List[TrafficItem]:
    return [TrafficItem(0, p) for p in
            shared_prefix_prompts(vocab, 48, (8, 16, 24, 4))]


def _wave_items(vocab: int) -> List[TrafficItem]:
    return wave_arrivals(vocab, (120, 24, 16), waves=3, period=4)


def _priority_burst_items(vocab: int) -> List[TrafficItem]:
    return priority_burst(vocab, (96, 96), (64,), burst_tick=3,
                          burst_priority=5, seed=1)


TRACES: Dict[str, TraceSpec] = {t.name: t for t in [
    TraceSpec("mixed", _mixed_items),
    TraceSpec("shared_prefix", _shared_prefix_items,
              {"prefix_cache": True}),
    TraceSpec("wave", _wave_items),
    # usable_pages squeezed so the burst actually preempts: preemption's
    # lens-rollback bookkeeping must stay consistent with speculation's
    TraceSpec("priority_burst", _priority_burst_items,
              {"preemption": True, "usable_pages": 28,
               "max_chunks_per_tick": 1, "max_batch": 3}),
]}


def make_scfg(trace: TraceSpec, speculative: bool,
              **extra) -> ServeConfig:
    kw = dict(BASE_SCFG)
    kw.update(trace.scfg_kw)
    kw.update(extra)
    return ServeConfig(speculative=speculative, **kw)


def replay_trace(model, params, trace: TraceSpec, speculative: bool,
                 **scfg_extra) -> Tuple[Dict[int, List[int]], ServeEngine]:
    """Replay one registered trace (fresh items - replay() stamps uids)
    with per-tick engine invariant checks.  Returns ({uid: out}, engine)."""
    eng = ServeEngine(model, params,
                      make_scfg(trace, speculative, **scfg_extra))
    items = trace.build(model.cfg.vocab_size)
    out, _ = replay(eng, items, check=True)
    return out, eng


def assert_pages_conserved(eng: ServeEngine):
    """After a drained trace every page is accounted for: back in the
    free pool, or parked in the prefix tree with a live refcount.  A
    speculative rollback that leaked (or double-freed) a page fails
    here - and per-tick, in replay()'s check_invariants sweeps."""
    if not eng.paged:
        return
    assert all(s is None for s in eng.slots)
    if eng.prefix is not None:
        eng.prefix.check_invariants()
        assert eng.allocator.used_pages == eng.prefix.cached_pages, \
            (eng.allocator.used_pages, eng.prefix.cached_pages)
    else:
        assert eng.allocator.used_pages == 0, eng.allocator.used_pages
        assert (eng.allocator.table == 0).all()


def assert_spec_conformance(model, params, trace: TraceSpec,
                            **scfg_extra):
    """The greedy differential: replay `trace` spec-off and spec-on and
    assert bit-identical outputs (teacher-forced near-tie fallback),
    equal work-clock totals, page conservation on both engines, and -
    on traces long enough to draft - that speculation actually engaged.
    Returns (baseline engine, speculative engine) for extra checks."""
    base_out, eng_off = replay_trace(model, params, trace, False,
                                     **scfg_extra)
    spec_out, eng_on = replay_trace(model, params, trace, True,
                                    **scfg_extra)
    assert base_out.keys() == spec_out.keys()
    if spec_out != base_out:
        assert_greedy_equivalent(model, params, eng_on.sched.finished,
                                 base_out)
    s_off, s_on = eng_off.stats(), eng_on.stats()
    assert s_off["work_tokens"] == s_on["work_tokens"], \
        (s_off["work_tokens"], s_on["work_tokens"])
    assert s_off["gen_tokens"] == s_on["gen_tokens"]
    assert_pages_conserved(eng_off)
    assert_pages_conserved(eng_on)
    assert s_on["spec_drafted"] > 0, "speculation never engaged"
    return eng_off, eng_on


def assert_tp_shard_accounting(eng: ServeEngine):
    """Per-shard KV-byte accounting cross-checked against the allocator's
    page counter: every page the decode path read was read once per
    device, each device streaming exactly its head shard of the page -
    so shard bytes x tp_degree must equal pages x full page bytes, with
    no rounding (head counts divide tp_degree by construction).  With
    tp_degree > 1 the block table is replicated onto every shard, so
    replication bytes must have accrued."""
    t = eng.tp_stats()
    tp = t["tp_degree"]
    assert t["shard_page_bytes"] * tp == t["page_bytes"], t
    assert t["shard_kv_bytes_read"] * tp \
        == t["kv_pages_read"] * t["page_bytes"], t
    if tp > 1 and t["kv_pages_read"] > 0:
        assert t["table_bytes_replicated"] > 0, t


def assert_tp_conformance(model, params, trace: TraceSpec,
                          tp_degree: int = 2, speculative: bool = False,
                          **scfg_extra):
    """The tensor-parallel differential: replay `trace` through a
    single-device engine and a head-sharded tp=`tp_degree` engine that
    differ ONLY in ServeConfig.tp_degree, and assert the sharded engine
    is observationally identical - bit-identical greedy outputs (the
    all-gather inside the sharded kernels restores the tp=1 float
    summation order, so this is exact equality, with the teacher-forced
    near-tie fallback kept only for belt and braces), equal work-clock
    and generated-token totals, page conservation on both engines, and
    the per-shard byte cross-check above.  Returns (tp=1 engine,
    tp=`tp_degree` engine) for extra checks."""
    base_out, eng_1 = replay_trace(model, params, trace, speculative,
                                   **scfg_extra)
    tp_out, eng_tp = replay_trace(model, params, trace, speculative,
                                  tp_degree=tp_degree, **scfg_extra)
    assert base_out.keys() == tp_out.keys()
    if tp_out != base_out:
        assert_greedy_equivalent(model, params, eng_tp.sched.finished,
                                 base_out)
    s_1, s_tp = eng_1.stats(), eng_tp.stats()
    assert s_1["work_tokens"] == s_tp["work_tokens"], \
        (s_1["work_tokens"], s_tp["work_tokens"])
    assert s_1["gen_tokens"] == s_tp["gen_tokens"]
    assert s_1["kv_pages_read"] == s_tp["kv_pages_read"], \
        "sharding must not change WHICH pages decode reads, only how " \
        "much of each page every device streams"
    assert_pages_conserved(eng_1)
    assert_pages_conserved(eng_tp)
    assert_tp_shard_accounting(eng_1)
    assert_tp_shard_accounting(eng_tp)
    return eng_1, eng_tp


def assert_sampled_support(model, params, scfg: ServeConfig,
                           done: List[Request], slack: float = 1e-3):
    """Teacher-force every finished request's emitted trace through
    model.forward and assert each generated token survives the SAME
    temperature -> top-k -> top-p filter stack the engine sampled it
    with: its logit sits at or above the filter thresholds (within
    `slack`, for kernel-vs-forward rounding wobble).  A speculative
    acceptance path that emitted a token the target could never have
    sampled fails loudly here."""
    import jax.numpy as jnp

    for req in done:
        seq = req.prompt + req.out_tokens
        out = model.forward(params, {"tokens": jnp.asarray([seq],
                                                           jnp.int32)})
        logits = np.asarray(out[0] if isinstance(out, tuple) else out,
                            np.float64)[0]
        V = logits.shape[-1]
        for i, tok in enumerate(req.out_tokens):
            row = logits[len(req.prompt) - 1 + i]
            if scfg.temperature <= 0.0:
                assert row[tok] >= row.max() - slack
                continue
            scaled = row / scfg.temperature
            if 0 < scfg.top_k < V:
                kth = np.sort(scaled)[V - scfg.top_k]
                assert scaled[tok] >= kth - slack, \
                    f"uid {req.uid} token {i}: outside top-k"
            if scfg.top_p < 1.0:
                order = np.argsort(-scaled)
                probs = np.exp(scaled - scaled.max())
                probs /= probs.sum()
                cum = np.cumsum(probs[order])
                keep = (cum - probs[order]) < scfg.top_p
                kept = set(order[keep].tolist())
                # slack: admit tokens tied (within fp wobble) with the
                # last kept logit
                floor = scaled[order[keep]].min()
                assert tok in kept or scaled[tok] >= floor - slack, \
                    f"uid {req.uid} token {i}: outside top-p nucleus"
