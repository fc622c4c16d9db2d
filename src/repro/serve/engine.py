"""Continuous-batching serve engine (dense or paged KV cache).

Slot-based scheduler: up to `max_batch` concurrent sequences share one
batched KV cache; every tick runs one batched decode step for all decoding
slots; finished sequences free their slot immediately (no head-of-line
blocking).  Queueing, admission policy (FIFO / shortest-prompt-first),
chunk planning, and latency accounting live in the token-budget scheduler
(serve/scheduler.py); this module owns all device state and page
bookkeeping.

Two cache modes (ServeConfig.paged), each with its one tick:

  paged  a global page pool + block table (serve/paged_cache.py): a request
         holds ceil((prompt + max_new) / page_size) pages from admission to
         completion and returns them the tick it finishes, so mixed-length
         traffic fits far more concurrent sequences in the same KV bytes.
         Admission reserves the worst case up front; when the free list
         cannot cover it the request simply stays queued (backpressure) -
         nothing mid-flight can run out of pages.
         Each tick gets ServeConfig.tick_token_budget tokens of work:
         decoding slots consume 1 each, and the remainder is filled with
         prompt chunks (multiples of ServeConfig.prefill_chunk) for
         PREFILLING slots through the offset-causal block-table kernel
         (kernels/paged_prefill.py) - decode latency stays flat while
         long prompts stream in.  A slot that is still prefilling keeps
         lens == 0 and a zeroed row in the DEVICE block table, so the
         batched decode step's write lane for it lands in the reserved
         null page, never in its half-filled pages.  A tick costs ONE
         ragged batched prefill launch + ONE fused decode launch + ONE
         device->host transfer regardless of how many requests are in
         flight: the scheduler packs every planned chunk into a K-row
         batch (serve/scheduler.py pack_chunks, power-of-two bucketed),
         final-chunk tokens are sampled device-side, and per-slot
         bookkeeping collapses into vectorized masked updates.
  dense  one (L, max_batch, max_seq, Hkv, D) strip per K and V - every slot
         reserves max_seq worth of KV whether it needs it or not.  The
         whole prompt prefills in ONE pass at admission (token by token
         for the recurrent and audio families), then one fused decode
         launch per tick.  It is the independent reference the paged
         engine's outputs are tested against, and the only engine for
         the non-attention families.

Decode-priority shaping + preemption (paged mode):

  ServeConfig.decode_priority caps the prefill share of every tick at
  max_prefill_fraction * tick_token_budget after decode slots take their
  token each, so a burst of queued prefills can never inflate per-tick
  work - and with it every in-flight decode's work-clock TBT - up to the
  full budget.
  ServeConfig.preemption lets admission SHED lower-priority running
  requests (submit(priority=...), higher wins) when the page pool or the
  slot table cannot place a higher-priority candidate: the victim's
  non-shared pages return to the pool (prefix-cache pages survive via
  refcounts), it parks QUEUED->RESUMING, and on re-admission the prefix
  cache re-matches whatever pages survived while only the lost remainder
  re-prefills through the chunk path.  A mid-decode victim resumes from
  prompt + generated-so-far (Request.target), so greedy outputs are
  bit-identical to an uninterrupted run.  Equal priorities never preempt.

Prefix caching (ServeConfig.prefix_cache, paged mode only): finished
requests publish their prompt pages into a radix tree
(serve/prefix_cache.py); admission matches the longest cached prefix,
attaches those pages refcounted, and prefills ONLY the uncached remainder
as budgeted chunks (the request's prefill cursor simply starts at the
cached-prefix boundary).

Self-speculative decoding (ServeConfig.speculative, paged mode only):
each tick, every DECODING slot may propose a draft chain by n-gram
lookup over its own token history (serve/drafting.py - no second model),
capped by spec_k, the remaining generation budget, and the
tick's token budget (drafted tokens consume budget exactly like prefill
chunks).  All chains verify in ONE extra ragged launch through the same
batched chunk kernel decode already uses (serve/serve_step.py
make_spec_verify_step): row r scores [pending, d_1..d_m] at
offset = lens, the target's token is sampled at every position, and a
draft token is accepted iff it matches - so the emitted stream is
distributed exactly as non-speculative decoding (bit-identical under
greedy), and every chain nets n_acc + 1 >= 1 tokens for one launch.
Rejection rollback is free: the device sets lens = offset + n_acc + 1
and everything past it is dead - masked by the offset-causal kernel,
overwritten by the slot's next write - while the pages stay reserved
(admission sized them for max_new_tokens up front).  The work clock
advances only for ACCEPTED tokens, so work-clock latency and the final
work_tokens total are directly comparable spec-on vs spec-off.

Requests finish on length (max_new_tokens) or on a stop token
(submit(..., stop_tokens=...) / ServeConfig.eos_id), freeing or
publishing their pages the same tick.  Sampling runs the device-side
stack in serve/sampling.py: greedy at temperature 0; otherwise
temperature -> top-k -> top-p -> categorical through a PRNG key seeded
from ServeConfig.seed and threaded on the engine, so runs are
reproducible.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, ServeConfig
from ..models import Model, build_model
from .paged_cache import (PageAllocator, pages_needed, page_kv_bytes,
                          shard_page_kv_bytes)
from .prefix_cache import RadixPrefixCache
from .scheduler import (ChunkTask, DraftTask, Request, RequestState,
                        SpecBatch, TokenBudgetScheduler)
from .serve_step import (copy_page, make_chunk_batch_step,
                         make_fused_decode_step, make_prefill_step,
                         make_serve_step, make_spec_verify_step,
                         sample_token)
from .telemetry import (TRACK_ENGINE, TRACK_QUEUE, LaunchRecord,
                        MetricsRegistry, SpanTracer, Telemetry, TickRecord,
                        export_chrome_trace, movement_breakdown)

# the dense engine pads attention-family prompts to a multiple of this
# before the whole-prompt prefill, bounding jit recompiles to one per bucket
PREFILL_BUCKET = 16

# Jitted serve steps are SHARED across every engine built on the same model
# (and sampling knobs): the steps close over nothing but the model and the
# static sampling configuration (temperature, top_k, top_p), so two engines
# can execute the very same compiled executables.  That eliminates per-engine recompiles (constructing an
# engine is free once the first one warmed up) and - just as important -
# keeps greedy outputs bit-identical ACROSS engine instances: near-tie
# argmaxes are sensitive to last-ulp rounding differences between separate
# compilations of the same program, so parity comparisons between two
# engines (paged vs dense, preempted vs uninterrupted oracle) are
# only exact when both run the same executables.
#
# The cache lives for the PROCESS: the step closures capture the model, so
# an entry pins its model (and compiled variants) for as long as the
# process runs.  That is the point - deliberate, bounded by the number of
# distinct models built, and cheap next to the recompiles it saves.  (A
# weak-keyed mapping would be a lie here: value -> model -> key is a
# strong cycle, so nothing would ever actually be evicted.)
_STEP_CACHE: Dict[int, Any] = {}


def _shared_steps(model: Model, temperature: float, top_k: int = 0,
                  top_p: float = 1.0, tp_mesh=None) -> Dict[str, Any]:
    # keyed by object identity WITH the model pinned in the entry, so an
    # id can never be recycled for a different model
    entry = _STEP_CACHE.get(id(model))
    if entry is None or entry[0] is not model:
        entry = (model, {})
        _STEP_CACHE[id(model)] = entry
    per_model = entry[1]
    # the tp mesh keys structurally (jax.sharding.Mesh equality is devices
    # + axis names), so two TP replicas at the same degree share the SAME
    # jitted steps - the cross-replica bit-identity the fleet differential
    # tests rely on extends to TP fleets unchanged
    knobs = (float(temperature), int(top_k), float(top_p), tp_mesh)
    steps = per_model.get(knobs)
    if steps is None:
        # donate the cache through the jit boundary so a tick updates the
        # KV pool in place instead of transiently doubling it (donation is
        # unimplemented on CPU - skip there to avoid per-call warnings)
        def _jit_donating_cache(fn, cache_argnum):
            if jax.default_backend() == "cpu":
                return jax.jit(fn)
            return jax.jit(fn, donate_argnums=(cache_argnum,))

        steps = {
            "decode": _jit_donating_cache(make_serve_step(model), 1),
            # sampling + masked token/length updates fused into the decode
            # launch: the whole decode phase of a tick is one jitted call
            # and the sampled tokens come back in ONE device_get at tick end
            "decode_fused": _jit_donating_cache(
                make_fused_decode_step(model, temperature=temperature,
                                       top_k=top_k, top_p=top_p,
                                       tp_mesh=tp_mesh), 1),
            "prefill": _jit_donating_cache(make_prefill_step(model), 2),
        }
        if model.prefill_chunks is not None:
            # the one-launch tick: every chunk planned this tick runs as
            # one ragged batch, final-chunk tokens sampled device-side
            steps["prefill_chunks"] = _jit_donating_cache(
                make_chunk_batch_step(model, temperature=temperature,
                                      top_k=top_k, top_p=top_p,
                                      tp_mesh=tp_mesh), 2)
            steps["copy_page"] = _jit_donating_cache(copy_page, 0)
        if model.verify_chunks is not None:
            # the speculative verify launch: one ragged batch scores every
            # draft chain and folds acceptance into tokens/lens device-side
            steps["spec_verify"] = _jit_donating_cache(
                make_spec_verify_step(model, temperature=temperature,
                                      top_k=top_k, top_p=top_p,
                                      tp_mesh=tp_mesh), 2)
        per_model[knobs] = steps
    return steps


def _registry_counter(name: str):
    """Class-level compatibility view over a registry counter (the engine
    analogue of the scheduler's): reads and `self.x += n` writes on the
    old attribute names go through the MetricsRegistry, so the registry is
    the one source of truth while every call site keeps its spelling."""
    def fget(self):
        return int(self.tm.registry.get(name).value)

    def fset(self, v):
        self.tm.registry.get(name).set_total(v)

    return property(fget, fset)


def _registry_gauge(name: str):
    def fget(self):
        return int(self.tm.registry.get(name).value)

    def fset(self, v):
        self.tm.registry.get(name).set(v)

    return property(fget, fset)


class ServeEngine:
    def __init__(self, model: Model, params, scfg: ServeConfig):
        self.model = model
        self.params = params
        self.scfg = scfg.validate()
        cfg = model.cfg
        B = scfg.max_batch
        self.paged = scfg.paged
        self.speculative = scfg.speculative
        self._attention_family = cfg.family in ("dense", "moe", "vlm")
        if self.speculative and model.verify_chunks is None:
            raise ValueError(f"speculative serving needs an attention "
                             f"family, got {cfg.family}")
        # telemetry FIRST: one metrics registry per engine (the typed
        # backing store of every counter below - the scheduler, allocator,
        # and prefix cache all register into it), plus the optional span
        # tracer (ServeConfig.telemetry; host-side only - zero jitted
        # calls, zero device->host syncs, bit-identical outputs on or off)
        tracer = SpanTracer(scfg.telemetry_spans) if scfg.telemetry else None
        self.tm = Telemetry(registry=MetricsRegistry(), tracer=tracer,
                            clock=self._clock)
        m = self.tm.registry
        m.counter("serve_jit_calls_total",
                  "Jitted model-step launches dispatched")
        m.counter("serve_host_syncs_total",
                  "Device->host transfers (token fetches and admission "
                  "samples)")
        m.counter("serve_prefill_tokens_total",
                  "Prompt tokens actually computed by prefill")
        m.counter("serve_prefix_hit_tokens_total",
                  "Prompt tokens served from the prefix cache instead of "
                  "being recomputed")
        m.counter("serve_cow_copies_total",
                  "Device-side copy-on-write page copies")
        m.counter("serve_gen_tokens_total", "Generation tokens emitted")
        m.counter("serve_decode_launches_total",
                  "Token-emitting launches (fused decode + spec verify)")
        m.counter("serve_decode_ctx_tokens_total",
                  "Context lengths of the live rows of token-emitting "
                  "launches, summed (positions each row attends)")
        m.counter("serve_kv_pages_read_total",
                  "KV pages read by token-emitting launches (analytic "
                  "host-side count, not a device counter)")
        m.counter("serve_requests_submitted_total",
                  "Requests accepted by submit()")
        m.counter("serve_requests_finished_total",
                  "Requests finished (length or stop token)")
        m.histogram("serve_queue_wait_seconds",
                    "Seconds from submit() to a request's first slot and "
                    "page reservation (resumes not observed)",
                    buckets=(0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1,
                             2.5, 5, 10))
        m.gauge("serve_peak_pages",
                "High-water mark of pool pages in use (cached included)")
        m.gauge("serve_peak_live_pages",
                "High-water mark of distinct pages referenced by slots")
        m.gauge("serve_outstanding_work_tokens",
                "Queued + in-flight work tokens (prompt remaining plus "
                "unspent generation budget) - the load signal load_stats() "
                "publishes for the fleet router")
        m.gauge("serve_tp_degree",
                "Tensor-parallel degree of this engine (devices the "
                "head-sharded KV page pool spans; 1 = single-device)")
        m.counter("serve_tp_shard_kv_bytes_read_total",
                  "KV bytes read PER DEVICE by token-emitting launches "
                  "(kv_pages_read converted through the head-sharded "
                  "per-shard page bytes; equals the full page bytes at "
                  "tp_degree 1)")
        m.counter("serve_tp_table_bytes_replicated_total",
                  "Block-table bytes uploaded times tp_degree - the "
                  "replication overhead of keeping the table as scalar-"
                  "prefetch state on every shard")
        self.prefix: Optional[RadixPrefixCache] = None
        # tensor parallelism: the mesh is built (and the pools committed
        # head-sharded) inside the paged branch below; tp_degree > 1
        # without paged mode is rejected by ServeConfig.validate()
        self.tp_mesh = None
        m.get("serve_tp_degree").set(scfg.tp_degree)
        # per-device bytes of one page (full page bytes at tp_degree 1);
        # indivisible head/tp combos fail with the clear error below, so
        # fall back to tp=1 math here rather than raising twice
        self._shard_page_bytes = shard_page_kv_bytes(
            cfg, scfg.page_size,
            scfg.tp_degree if cfg.n_kv_heads % scfg.tp_degree == 0 else 1)
        if scfg.prefix_cache and not scfg.paged:
            raise ValueError("prefix_cache requires paged=True")
        if self.paged:
            if model.prefill_chunks is None:
                raise ValueError(f"paged serving needs an attention family, "
                                 f"got {cfg.family}")
            if scfg.max_seq % scfg.page_size:
                # the block table covers max_seq in whole pages
                raise ValueError(
                    f"max_seq ({scfg.max_seq}) must be a multiple of "
                    f"page_size ({scfg.page_size})")
            num_pages = scfg.pool_pages()
            self.allocator = PageAllocator(num_pages, scfg.page_size, B,
                                           scfg.max_seq,
                                           usable_pages=scfg.usable_pages,
                                           metrics=m)
            self.cache = model.init_cache(B, scfg.max_seq,
                                          page_size=scfg.page_size,
                                          num_pages=num_pages)
            if scfg.tp_degree > 1:
                if cfg.n_kv_heads % scfg.tp_degree:
                    raise ValueError(
                        f"ServeConfig.tp_degree ({scfg.tp_degree}) must "
                        f"divide n_kv_heads ({cfg.n_kv_heads}): the KV "
                        f"page pool shards on the head axis, so every "
                        f"device needs a whole number of KV heads (GQA "
                        f"query heads follow their KV head's shard)")
                from jax.sharding import NamedSharding, PartitionSpec
                from ..launch.mesh import make_serve_mesh
                self.tp_mesh = make_serve_mesh(scfg.tp_degree)
                # commit placement ONCE at construction: the (L, Hkv, P,
                # ps, W) pool head-sharded, params fully replicated, so
                # every jitted step compiles against stable shardings and
                # per-tick uploads (block table, chunk packs, tokens) stay
                # small uncommitted host arrays jit re-shards for free
                hs = NamedSharding(self.tp_mesh,
                                   PartitionSpec(None, "model", None, None,
                                                 None))
                rep = NamedSharding(self.tp_mesh, PartitionSpec())
                self.cache = {
                    "kv_pages": jax.device_put(self.cache["kv_pages"], hs),
                    "block_table": jax.device_put(
                        self.cache["block_table"], rep),
                }
                self.params = jax.device_put(self.params, rep)
            if scfg.prefix_cache:
                self.prefix = RadixPrefixCache(self.allocator,
                                               scfg.page_size, metrics=m)
                self.prefix.event_cb = self._prefix_event
        else:
            self.allocator = None
            self.cache = model.init_cache(B, scfg.max_seq,
                                          enc_len=scfg.max_seq)
        self.lens = jnp.zeros((B,), jnp.int32)
        self.slots: List[Optional[Request]] = [None] * B
        self.tokens = jnp.zeros((B, 1), jnp.int32)
        self.sched = TokenBudgetScheduler(self.scfg, metrics=m)
        self._uid = 0
        self._admit_seq = 0          # monotone admission stamp (victim order)
        self._key = jax.random.PRNGKey(scfg.seed)
        self._dummy_key = jax.random.PRNGKey(0)   # greedy: key arg unused
        self._finished_this_tick: List[Request] = []
        # set the first time a submit carries a deadline: the per-tick
        # expiry sweep is a no-op until then (deadline-free traffic pays
        # one boolean check per tick)
        self._deadlines_active = False
        self._table_dirty = False    # device block table behind the host's
        # host mirror of `lens`: every host-side decision that needs
        # lengths (COW guard, bookkeeping) reads this instead of syncing
        # the device array - lengths are fully determined by scheduling
        self._lens_np = np.zeros((B,), np.int64)
        # dispatch / throughput counters (jit_calls, host_syncs,
        # gen_tokens, decode_launches, kv_pages_read, ...) live in the
        # telemetry registry; the attribute names below the class body are
        # registry-backed properties, and per-tick dispatch accounting is
        # the typed TickRecords in self.tm.ticks
        # n_acc array of the tick's verify launch, fetched WITH tokens
        self._spec_nacc: Optional[jax.Array] = None

        # jitted steps come from the model-level shared cache: every engine
        # on this model (at these sampling knobs) runs the SAME executables
        # - no per-engine recompiles, and bit-identical numerics across
        # engine instances (see _shared_steps)
        steps = _shared_steps(model, scfg.temperature, scfg.top_k,
                              scfg.top_p, tp_mesh=self.tp_mesh)
        self._decode = steps["decode"]
        self._decode_fused = steps["decode_fused"]
        self._prefill = steps["prefill"]
        if self.paged:
            self._prefill_chunks = steps["prefill_chunks"]
            self._copy_page_step = steps["copy_page"]
        if self.speculative:
            self._spec_verify = steps["spec_verify"]

    # registry-backed compatibility views (one source of truth: the
    # telemetry registry; `eng.jit_calls += 1` et al. keep working)
    jit_calls = _registry_counter("serve_jit_calls_total")
    host_syncs = _registry_counter("serve_host_syncs_total")
    prefill_tokens = _registry_counter("serve_prefill_tokens_total")
    prefix_hit_tokens = _registry_counter("serve_prefix_hit_tokens_total")
    cow_copies = _registry_counter("serve_cow_copies_total")
    gen_tokens = _registry_counter("serve_gen_tokens_total")
    decode_launches = _registry_counter("serve_decode_launches_total")
    decode_ctx_tokens = _registry_counter("serve_decode_ctx_tokens_total")
    kv_pages_read = _registry_counter("serve_kv_pages_read_total")
    peak_pages = _registry_gauge("serve_peak_pages")
    peak_live_pages = _registry_gauge("serve_peak_live_pages")

    # ------------------------------------------------------------------
    # telemetry surface
    # ------------------------------------------------------------------
    def export_trace(self, path, clock: str = "wall"):
        """Write the span tracer's records as Chrome trace-event JSON
        (open in Perfetto / chrome://tracing): request lifecycle spans on
        per-slot tracks, nested engine phases on the engine track.
        clock="wall" for the human view, "work" for the
        deterministic work-clock view.  Returns the trace dict."""
        if self.tm.tracer is None:
            raise ValueError(
                "span tracing is off: build the engine with "
                "ServeConfig(telemetry=True) to record spans")
        return export_chrome_trace(path, self.tm.tracer,
                                   self.scfg.max_batch, clock=clock)

    def metrics_snapshot(self) -> Dict[str, Any]:
        """JSON-ready snapshot of every registered metric."""
        return self.tm.registry.snapshot()

    def prometheus_metrics(self) -> str:
        """Every registered metric in Prometheus text exposition format."""
        return self.tm.registry.prometheus_text()

    def launch_records(self) -> List[LaunchRecord]:
        """Per-launch data-movement attribution records, launch order."""
        return list(self.tm.launches)

    def movement_stats(self) -> Dict[str, Dict[str, float]]:
        """Paper-style (Fig. 6) data-movement breakdown per launch kind:
        estimated HBM / SRAM bytes and energy folded from the per-launch
        records through core/energy.py (see telemetry.movement_breakdown)."""
        return movement_breakdown(self.tm.launches, self.model.cfg,
                                  self.scfg, tp_degree=self.scfg.tp_degree)

    def tp_stats(self) -> Dict[str, int]:
        """Tensor-parallel accounting snapshot: the per-device KV bytes
        the token-emitting launches read, the block-table bytes paid to
        replication, and the per-shard page-byte unit - everything the
        conformance cross-check (shard_bytes * tp == pages_read *
        page_bytes) consumes."""
        g = self.tm.registry.get
        return {
            "tp_degree": int(self.scfg.tp_degree),
            "shard_kv_bytes_read":
                int(g("serve_tp_shard_kv_bytes_read_total").value),
            "table_bytes_replicated":
                int(g("serve_tp_table_bytes_replicated_total").value),
            "shard_page_bytes": int(self._shard_page_bytes),
            "page_bytes": int(page_kv_bytes(self.model.cfg,
                                            self.scfg.page_size)),
            "kv_pages_read": int(self.kv_pages_read),
        }

    def _prefix_event(self, name: str, **args):
        """Prefix-cache hit/publish/evict instants onto the engine track
        (wired as RadixPrefixCache.event_cb; no-op with the tracer off)."""
        tr = self.tm.tracer
        if tr is not None:
            tr.add_event(name, "prefix", TRACK_ENGINE, self.sched.ticks,
                         self.sched.work_clock, tr.now(), **args)

    def _note_launch(self, kind: str, rows: int, live_rows: int,
                     true_tokens: int, padded_tokens: int,
                     kv_pages_read: int, kv_pages_written: int,
                     new_kv_tokens: int, ctx_tokens: int = 0):
        """Record one kernel launch's data-movement attribution."""
        self.tm.launches.append(LaunchRecord(
            tick=self.sched.ticks, kind=kind, rows=rows,
            live_rows=live_rows, true_tokens=true_tokens,
            padded_tokens=padded_tokens, kv_pages_read=kv_pages_read,
            kv_pages_written=kv_pages_written,
            new_kv_tokens=new_kv_tokens,
            work_clock=self.sched.work_clock, ctx_tokens=ctx_tokens))

    def _note_decode_ctx(self, rows) -> int:
        """Count a token-emitting launch's attended context: the sum of
        its live rows' context lengths (serve_decode_ctx_tokens_total)."""
        n = int(sum(rows))
        self.decode_ctx_tokens += n
        return n

    def _note_kv_pages_read(self, n_pages: int):
        """Count pages a token-emitting launch read, in BOTH units: pool
        pages (the historical serve_kv_pages_read_total) and per-device
        bytes (pages x the head-sharded per-shard page bytes) - every
        shard walks the same replicated block table over the same page
        ids, so per-shard reads are exactly total reads / tp_degree."""
        n = int(n_pages)
        self.kv_pages_read += n
        self.tm.registry.get("serve_tp_shard_kv_bytes_read_total").inc(
            n * self._shard_page_bytes)

    def _note_table_upload(self, nbytes: int):
        """Count one block-table upload's replication cost: the table is
        scalar-prefetch state on every shard, so the bytes multiply by
        tp_degree instead of dividing."""
        self.tm.registry.get("serve_tp_table_bytes_replicated_total").inc(
            int(nbytes) * int(self.scfg.tp_degree))

    def _row_pages(self, slot: int, true_len: int) -> int:
        """KV pages slot's attention READS at KV length `true_len`:
        counted from the allocator's block-table row (the PageAllocator's
        accounting IS the source of truth - tests cross-check this count
        against the analytic ceil(true_len / page_size))."""
        n = -(-int(true_len) // self.scfg.page_size)
        return int(np.count_nonzero(self.allocator.table[slot, :n]))

    def _span_pages(self, start: int, end: int) -> int:
        """Pages the K/V writes of token positions [start, end) touch."""
        if end <= start:
            return 0
        ps = self.scfg.page_size
        return end // ps - start // ps + (1 if end % ps else 0)

    def _clock(self):
        """(tick index, work clock): the stamps of the phase spans."""
        return self.sched.ticks, self.sched.work_clock

    def _phase(self, req: Request, phase: str, track: int, **args):
        """Request-lifecycle phase transition onto the tracer (no-op off)."""
        self.tm.request_phase(req.uid, phase, track, self.sched.ticks,
                              self.sched.work_clock, **args)

    def _event(self, req: Request, name: str, track: int, **args):
        self.tm.request_event(req.uid, name, track, self.sched.ticks,
                              self.sched.work_clock, **args)

    # ------------------------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        """Requests waiting for admission (owned by the scheduler)."""
        return self.sched.queue

    @property
    def tick_log(self):
        """Per-tick (decode_tokens, prefill_tokens) budget accounting."""
        return self.sched.tick_log

    def submit(self, prompt: List[int],
               max_new_tokens: Optional[int] = None,
               stop_tokens: Optional[Sequence[int]] = None,
               priority: int = 0,
               deadline: Optional[int] = None,
               max_retries: Optional[int] = None) -> int:
        """Enqueue a request.  Everything that can never be served -
        empty prompt, zero generation budget, overflowing max_seq, a page
        reservation larger than the engine can ever grant, a deadline the
        prompt's own prefill would already blow - fails HERE with a clear
        error instead of deep inside prefill or the allocator.
        `stop_tokens` (merged with ServeConfig.eos_id) end generation
        early the tick one is produced.  Higher `priority` admits first
        and - with ServeConfig.preemption - may preempt running
        lower-priority requests when the page pool runs dry.  `deadline`
        is a per-request work-clock deadline in tokens (default:
        ServeConfig.default_deadline_tokens; 0/None = none): once the
        engine has executed that much work since the submit the request
        expires with a TIMEOUT status, pages freed the same tick.
        `max_retries` caps how many times a fleet router may redispatch
        the request off a failed replica (None = unbounded)."""
        n_new = self.scfg.max_new_tokens if max_new_tokens is None \
            else max_new_tokens
        if not prompt:
            raise ValueError("empty prompt")
        if n_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n_new}")
        if deadline is None:
            deadline = self.scfg.default_deadline_tokens or None
        if deadline is not None:
            if deadline <= 0:
                raise ValueError(f"deadline must be >= 1 work token, "
                                 f"got {deadline}")
            if deadline <= len(prompt):
                # the prompt alone costs len(prompt) work tokens of
                # prefill before the first token can exist: a smaller
                # deadline is a guaranteed timeout - reject it at submit
                raise ValueError(
                    f"deadline ({deadline}) is not above the prompt's "
                    f"minimum prefill work ({len(prompt)} tokens): the "
                    f"request could never produce a token in time")
        if max_retries is not None and max_retries < 0:
            raise ValueError(f"max_retries must be >= 0 (None = "
                             f"unbounded), got {max_retries}")
        # the model's own max_seq bounds it too: a learned position table
        # has no rows past it
        max_seq = min(self.scfg.max_seq, self.model.cfg.max_seq)
        if len(prompt) + n_new > max_seq:
            raise ValueError(
                f"request does not fit: {len(prompt)} prompt + {n_new} new "
                f"tokens > max_seq {max_seq}")
        if self.paged:
            need = pages_needed(len(prompt) + n_new, self.scfg.page_size)
            usable = min(self.allocator.max_pages_per_seq,
                         self.allocator.usable_pages)
            if need > usable:
                # backpressure cannot help a reservation larger than the
                # whole pool - fail fast instead of queueing forever
                raise ValueError(
                    f"request needs {need} pages; the engine can grant at "
                    f"most {usable} (pool {self.allocator.num_pages}, "
                    f"max_seq {self.scfg.max_seq}, page "
                    f"{self.scfg.page_size})")
        stops = frozenset(stop_tokens or ())
        if self.scfg.eos_id is not None:
            stops = stops | {self.scfg.eos_id}
        self._uid += 1
        req = Request(self._uid, list(prompt), n_new, stop_tokens=stops,
                      priority=int(priority), deadline_tokens=deadline,
                      max_retries=max_retries)
        if deadline is not None:
            self._deadlines_active = True
        self.sched.submit(req)
        self.tm.registry.get("serve_requests_submitted_total").inc()
        self._phase(req, "QUEUED", TRACK_QUEUE,
                    prompt_tokens=len(prompt), priority=int(priority))
        return self._uid

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def prefix_stats(self) -> Dict[str, int]:
        """Prefill / prefix-cache counters (zeros when caching is off)."""
        total = self.prefill_tokens + self.prefix_hit_tokens
        return {"prefill_tokens": self.prefill_tokens,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prompt_tokens": total,
                "cow_copies": self.cow_copies,
                "cached_pages": self.prefix.cached_pages
                if self.prefix is not None else 0,
                "peak_pages": self.peak_pages,
                "peak_live_pages": self.peak_live_pages}

    def load_stats(self) -> Dict[str, int]:
        """Cheap occupancy view for dispatch decisions (the fleet router
        reads this once per replica per submit): queue depth, in-flight
        requests, outstanding work tokens, and page headroom.  Pure
        host-side bookkeeping reads - no device sync, no LRU or refcount
        effects - and the work-token total is published to the
        registry gauge `serve_outstanding_work_tokens`, so the load
        signal the router acted on is visible in metrics snapshots."""
        inflight = [r for r in self.slots if r is not None]
        work = sum(r.prompt_remaining + r.remaining_new
                   for r in inflight)
        work += sum(r.prompt_remaining + r.remaining_new
                    for r in self.queue)
        self.tm.registry.get("serve_outstanding_work_tokens").set(work)
        free = int(self.allocator.free_pages) if self.paged \
            else 1 << 30                # dense KV never backpressures
        evictable = self.prefix.evictable_pages() \
            if self.prefix is not None else 0
        return {"queue_depth": len(self.queue),
                "inflight": len(inflight),
                "free_slots": sum(s is None for s in self.slots),
                "outstanding_work_tokens": work,
                "free_pages": free,
                "evictable_pages": evictable}

    def stats(self) -> Dict[str, float]:
        """Engine stats API: scheduler latency aggregates (p50/p95 TTFT
        and time-between-tokens, wall-clock and work-clock), per-tick
        budget accounting, the prefill / prefix-cache counters, and
        dispatch accounting (jitted launches, device->host transfers, and
        host-loop wall time per tick)."""
        out: Dict[str, float] = dict(self.sched.stats())
        out.update(self.prefix_stats())
        out["tick_token_budget"] = self.scfg.tick_token_budget
        out["jit_calls"] = self.jit_calls
        out["host_syncs"] = self.host_syncs
        out["compile_count"] = self.compile_cache_size()
        out["speculative"] = self.speculative
        out["telemetry"] = self.tm.enabled
        out["gen_tokens"] = self.gen_tokens
        out["decode_launches"] = self.decode_launches
        out["kv_pages_read"] = self.kv_pages_read
        out["tp_degree"] = self.scfg.tp_degree
        out["tokens_per_launch"] = (self.gen_tokens / self.decode_launches
                                    if self.decode_launches else 0.0)
        out["tokens_per_kv_page"] = (self.gen_tokens / self.kv_pages_read
                                     if self.kv_pages_read else 0.0)
        ticks = self.tm.ticks
        if ticks:
            calls = [t.jit_calls for t in ticks]
            syncs = [t.host_syncs for t in ticks]
            walls = [t.host_wall_s for t in ticks]
            # "busy" = the steady-state shape of the acceptance criterion:
            # prefill chunks AND decodes in the same tick
            busy = [t.jit_calls for t in ticks
                    if t.n_chunk_tasks and t.n_decode]
            out["jit_calls_per_tick_max"] = max(calls)
            out["jit_calls_per_tick_mean"] = float(np.mean(calls))
            out["jit_calls_per_busy_tick_max"] = max(busy) if busy else 0
            out["host_syncs_per_tick_max"] = max(syncs)
            out["tick_host_wall_p50"] = float(np.percentile(walls, 50))
            out["tick_host_wall_p95"] = float(np.percentile(walls, 95))
        return out

    def check_invariants(self):
        """Debug hook for serve-path test fixtures (tests/traffic.py calls
        it after every tick): allocator refcount conservation + block-table
        mirroring (PageAllocator.check_invariants), prefix-tree consistency
        when caching is on, and the engine's own host-side bookkeeping -
        slot back-references, queue states, and the lens mirror.  Pure
        host-side: never touches a device array, so calling it cannot
        perturb the launch/sync accounting under test."""
        if self.paged:
            if self.prefix is not None:
                self.prefix.check_invariants()
            else:
                self.allocator.check_invariants()
            # per-shard byte accounting tracks the page counter exactly
            # (every read is noted through _note_kv_pages_read, in pages
            # AND per-device bytes, off one shard_page_kv_bytes unit)
            shard_bytes = int(self.tm.registry.get(
                "serve_tp_shard_kv_bytes_read_total").value)
            assert shard_bytes == self.kv_pages_read \
                * self._shard_page_bytes, \
                (f"per-shard KV byte accounting drifted: {shard_bytes} != "
                 f"{self.kv_pages_read} pages x {self._shard_page_bytes} "
                 f"bytes/shard-page")
        for i, r in enumerate(self.slots):
            if r is None:
                if self.paged:
                    assert not self.allocator.table[i].any(), \
                        f"slot {i} empty but its table row is live"
                assert self._lens_np[i] == 0, \
                    f"slot {i} empty but lens mirror {self._lens_np[i]}"
            else:
                assert r.slot == i, f"slot {i} back-reference broken"
                assert r.state in (RequestState.PREFILLING,
                                   RequestState.DECODING), \
                    f"slot {i} holds a {r.state} request"
        for r in self.queue:
            assert r.state in (RequestState.QUEUED, RequestState.RESUMING)
            assert r.slot is None, \
                f"queued request {r.uid} still holds slot {r.slot}"
            assert r.remaining_new >= 1

    def compile_cache_size(self) -> int:
        """Total compiled-variant count across the engine's jitted steps
        (jax pjit cache sizes) - the recompile-count metric benchmarks
        record and the steady-state guard tests pin down.  Steps are
        shared across engines of the same model (_shared_steps), so the
        absolute count spans every sibling engine in the process; deltas
        within one engine's run still measure that run's recompiles."""
        fns = [self._decode, self._decode_fused, self._prefill,
               getattr(self, "_prefill_chunks", None),
               getattr(self, "_spec_verify", None)]
        return sum(fn._cache_size() for fn in fns
                   if fn is not None and hasattr(fn, "_cache_size"))

    def kv_cache_bytes(self) -> int:
        """Allocated cache bytes, every leaf: KV strips or pages, block
        table, and recurrent state for ssm/hybrid/audio families.  Caches
        are preallocated, so allocated == peak."""
        return sum(int(np.prod(leaf.shape))
                   * jnp.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree_util.tree_leaves(self.cache))

    # ------------------------------------------------------------------
    # sampling / emission / completion (shared by all schedules)
    # ------------------------------------------------------------------
    def _sample(self, logits) -> jax.Array:
        """(B, 1, V) logits -> (B, 1) tokens through the device-side
        sampling stack (serve/sampling.py).  Greedy at temperature 0;
        otherwise temperature -> top-k -> top-p -> categorical through
        the engine's threaded PRNG key (one split per call, so a fixed
        ServeConfig.seed reproduces the whole trace)."""
        if self.scfg.temperature <= 0.0:
            return sample_token(logits)
        self._key, sub = jax.random.split(self._key)
        return sample_token(logits, temperature=self.scfg.temperature,
                            top_k=self.scfg.top_k, top_p=self.scfg.top_p,
                            key=sub)

    def _next_key(self) -> jax.Array:
        """PRNG key for a fused (device-side sampling) step: a fixed dummy
        at temperature 0 (the step ignores it - no per-tick split work),
        one split per launch otherwise."""
        if self.scfg.temperature <= 0.0:
            return self._dummy_key
        self._key, sub = jax.random.split(self._key)
        return sub

    def _fetch_tokens(self) -> np.ndarray:
        """THE tick's device->host transfer: the (B, 1) token array after
        the fused steps wrote every lane's sampled token into it."""
        self.host_syncs += 1
        return np.asarray(jax.device_get(self.tokens))

    def _emit(self, req: Request, tok: int,
              work: Optional[int] = None) -> bool:
        """Record one generated token; True when the request is finished
        (stop token or length budget).  `work` back-stamps the token's
        work clock (paged tick: a final chunk's token is emitted after the
        decode launch, but stamped at its place in the chunk plan)."""
        req.out_tokens.append(tok)
        self.gen_tokens += 1
        self.sched.note_token(req, time.perf_counter(), work=work)
        if tok in req.stop_tokens:
            req.finish_reason = "stop"
            return True
        if len(req.out_tokens) >= req.max_new_tokens:
            req.finish_reason = "length"
            return True
        return False

    def _finish(self, req: Request):
        """Free the request's slot; pages go back to the pool (or publish
        into the prefix cache) the same tick."""
        i = req.slot
        req.state = RequestState.DONE
        req.done = True
        self.slots[i] = None
        self.lens = self.lens.at[i].set(0)
        self._lens_np[i] = 0
        if self.prefix is not None:
            # prompt pages go into the radix tree; the partial tail page
            # and generation pages return to the pool
            self.prefix.release(i, req.prompt)
        elif self.paged:
            self.allocator.free_slot(i)
        if self.paged:
            self._table_dirty = True     # zero the slot's device row
        self.sched.note_finished(req)
        self.tm.registry.get("serve_requests_finished_total").inc()
        self._phase(req, "DONE", i, reason=req.finish_reason,
                    out_tokens=len(req.out_tokens))
        self._finished_this_tick.append(req)

    def _expire(self, req: Request):
        """Deadline timeout: take the request out of the engine - queued,
        prefilling, or decoding - and free everything it held THE SAME
        TICK.  A slot-holding request frees exactly like a preemption
        victim (only fully-valid positions publish into the prefix tree:
        prefill_pos while prefilling, the lens mirror while decoding;
        without a prefix cache the slot's pages simply return to the
        pool), so an expired request can never strand capacity or corrupt
        page accounting.  Finishes with state TIMEOUT / finish_reason
        "timeout" and surfaces through the tick's finished list like any
        completion - a deadline bounds latency, it never hangs."""
        i = req.slot
        if i is not None:
            if self.prefix is not None:
                if req.state is RequestState.PREFILLING:
                    n_valid = req.prefill_pos
                    seq = list(req.target)
                else:
                    seq = req.prompt + list(req.out_tokens)
                    n_valid = int(self._lens_np[i])
                self.prefix.release(i, seq[:n_valid])
            elif self.paged:
                self.allocator.free_slot(i)
            self.slots[i] = None
            self.lens = self.lens.at[i].set(0)
            self._lens_np[i] = 0
            req.slot = None
            if self.paged:
                self._table_dirty = True
        else:
            self.sched.queue.remove(req)
        req.state = RequestState.TIMEOUT
        req.done = True
        req.finish_reason = "timeout"
        self.sched.timeouts += 1
        self.sched.note_finished(req)
        self._phase(req, "TIMEOUT", i if i is not None else TRACK_QUEUE,
                    out_tokens=len(req.out_tokens))
        self._finished_this_tick.append(req)

    def _expire_deadlines(self):
        """Top-of-tick deadline sweep (both tick flavors): expire every
        request - queued or in flight - whose work-clock age reached its
        deadline.  The scheduler owns the predicate (sched.expired); the
        engine owns the page/slot consequences.  Sweeping BEFORE admission
        and planning means a request never does work in the tick it
        expires, and the pages it frees are immediately admissible."""
        if not self._deadlines_active:
            return
        expired = [r for r in self.sched.queue if self.sched.expired(r)]
        expired += [r for r in self.slots
                    if r is not None and self.sched.expired(r)]
        for r in expired:
            self._expire(r)

    def request_statuses(self) -> Dict[int, str]:
        """{uid: state} for every request this engine has ever accepted:
        terminal ("done" / "timeout" / "failed") or still-live ("queued" /
        "prefilling" / "decoding" / "resuming").  Built from the three
        places a request can be - finished list, admission queue, slots -
        so nothing is ever silently dropped (the exhaustion-reporting and
        chaos suites assert on exactly this view)."""
        out: Dict[int, str] = {}
        for r in self.sched.finished:
            out[r.uid] = r.state.value
        for r in self.queue:
            out[r.uid] = r.state.value
        for r in self.slots:
            if r is not None:
                out[r.uid] = r.state.value
        return out

    def _sync_table(self):
        """Upload the block table, MASKING rows of slots that are not yet
        decoding: a PREFILLING slot keeps lens == 0, so the batched decode
        step's write lane for it must land in the reserved null page - not
        in the pages its chunks are filling.  The host table is ALWAYS
        copied before upload: jnp.asarray of an aligned numpy array can be
        zero-copy on CPU, and the allocator mutates this table in place on
        every alloc/free/preempt - an aliased upload would let those host
        writes retarget the device table under an in-flight tick."""
        with self.tm.phase("engine.table_upload"):
            tbl = self.allocator.table.copy()
            masked = [i for i, r in enumerate(self.slots)
                      if r is not None
                      and r.state is not RequestState.DECODING]
            if masked:
                tbl[masked] = 0
            self.cache["block_table"] = jnp.asarray(tbl)
            self._note_table_upload(tbl.nbytes)
            self._table_dirty = False

    # ------------------------------------------------------------------
    # dense admission (whole-prompt prefill)
    # ------------------------------------------------------------------
    def _admit(self):
        """Prefill queued requests into free slots, whole prompts at once.
        Admission order follows ServeConfig.admission_policy; stops when
        no slot is free."""
        while True:
            req = self.sched.peek()
            if req is None:
                return
            slot = self._free_slot()
            if slot is None:
                return
            if self._attention_family:
                self._admit_prefill(slot, req)
            else:
                self._admit_stepwise(slot, req)

    def _admit_prefill(self, slot: int, req: Request):
        """Dense cache, attention family: one batched prefill into a
        sub-cache sized to the padded prompt, scattered into the slot row;
        the first generated token is sampled from the prompt's last logits
        (a stop token here finishes the request immediately)."""
        self._pop_admitted(req)
        self._phase(req, "PREFILLING", slot)
        s_real = len(req.prompt)
        s_pad = min(-(-s_real // PREFILL_BUCKET) * PREFILL_BUCKET,
                    self.scfg.max_seq)
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :s_real] = req.prompt
        sub = self.model.init_cache(1, s_pad)
        batch = {"tokens": jnp.asarray(toks),
                 "true_lens": jnp.asarray([s_real])}
        self.jit_calls += 1
        with self.tm.phase("engine.prefill_launch", true_tokens=s_real):
            logits, sub, _ = self._prefill(self.params, batch, sub)
        self._note_launch("prefill", rows=1, live_rows=1,
                          true_tokens=s_real, padded_tokens=s_pad,
                          kv_pages_read=0, kv_pages_written=0,
                          new_kv_tokens=s_real)
        self.cache["k"] = self.cache["k"].at[:, slot, :s_pad].set(
            sub["k"][:, 0])
        self.cache["v"] = self.cache["v"].at[:, slot, :s_pad].set(
            sub["v"][:, 0])
        self.prefill_tokens += s_real
        self.sched.note_work(s_real)
        self.lens = self.lens.at[slot].set(s_real)
        self._lens_np[slot] = s_real
        self.host_syncs += 1
        nxt = int(self._sample(logits)[0, 0])
        self.tokens = self.tokens.at[slot, 0].set(nxt)
        self.slots[slot] = req
        req.slot = slot
        req.prefill_pos = s_real
        req.state = RequestState.DECODING
        self._stamp_admit(req)
        self._phase(req, "DECODING", slot)
        if self._emit(req, nxt):
            self._finish(req)

    def _note_alloc(self):
        self.peak_pages = max(self.peak_pages, self.allocator.used_pages)
        self.peak_live_pages = max(self.peak_live_pages,
                                   self.allocator.live_pages())

    def _ensure_free(self, n: int, protect=frozenset()) -> bool:
        """True if n pages are (or can be made) free.  With the prefix
        cache, LRU-evicts unreferenced cached pages - never `protect`
        (pages about to be attached) or anything a slot references."""
        if self.allocator.can_alloc(n):
            return True
        if self.prefix is not None:
            self.prefix.evict(n - self.allocator.free_pages,
                              protect=frozenset(protect))
        return self.allocator.can_alloc(n)

    def _copy_page(self, src: int, dst: int):
        """Device-side copy of one page across every layer's K and V (the
        data half of copy-on-write; the allocator did the bookkeeping), in
        place in the donated pool (serve_step.copy_page)."""
        self.cache["kv_pages"] = self._copy_page_step(self.cache["kv_pages"],
                                                      src, dst)

    def _reserve_prefix(self, slot: int, req: Request) -> Optional[int]:
        """Shared prefix-cached reservation: attach the longest cached
        prefix, allocate the rest of the worst case, COW the final cached
        page when the whole prompt is covered.  Returns the prompt
        position computation must start from (the prefill cursor), or
        None when out of pages even after eviction.  A RESUMING request's
        target is prompt + pre-preemption output: the match re-finds
        whatever pages survived the preemption (the tree's references kept
        them alive) and only the lost remainder re-prefills."""
        scfg = self.scfg
        ps = scfg.page_size
        target = req.target
        P = len(target)
        matched = self.prefix.match(target)
        # a fully cached prompt still recomputes its LAST token (we need
        # its logits to start decoding); that token's K/V write lands in
        # the final cached page, which therefore gets a private
        # copy-on-write copy instead of being attached
        full_cover = bool(matched) and len(matched) * ps >= P
        shared = matched[:-1] if full_cover else matched
        need_total = pages_needed(P + req.remaining_new, ps)
        n_fresh = need_total - len(shared)
        if not self._ensure_free(n_fresh, protect=matched):
            return None
        if shared:
            self.allocator.attach(slot, shared)
        owned = self.allocator.alloc(slot, n_fresh)
        if full_cover:
            self._copy_page(matched[-1], owned[len(shared)])
            self.cow_copies += 1
        self._note_alloc()
        start = P - 1 if full_cover else len(shared) * ps
        self.prefix_hit_tokens += start
        return start

    def _stamp_admit(self, req: Request):
        """Monotone admission stamp: the preemption policy sheds the most
        recently admitted PREFILLING victim first (it has the least sunk
        prefill work and the longest road ahead)."""
        req.admit_seq = self._admit_seq
        self._admit_seq += 1

    def _pop_admitted(self, req: Request):
        """Take a request whose slot (and pages) were just reserved off the
        queue.  Its first admission stamps t_admit and observes its queue
        wait; a resume after preemption keeps both."""
        self.sched.pop(req)
        if req.t_admit is None:
            req.t_admit = time.perf_counter()
            self.tm.registry.get("serve_queue_wait_seconds").observe(
                req.t_admit - req.t_submit)

    def _admit_stepwise(self, slot: int, req: Request):
        """Token-by-token prefill through decode_step (exact for every
        architecture family, including recurrent state caches)."""
        self._pop_admitted(req)
        self._phase(req, "PREFILLING", slot)
        lens = self.lens
        cache = self.cache
        last_logits = None
        with self.tm.phase("engine.prefill_launch",
                           true_tokens=len(req.prompt)):
            for t in req.prompt:
                tok = self.tokens.at[slot, 0].set(t)
                pos = lens
                self.jit_calls += 1
                logits, cache = self._decode(self.params, cache, tok, pos)
                lens = lens.at[slot].add(1)
                last_logits = logits
        # one aggregated record for the whole token-by-token sweep
        self._note_launch("stepwise", rows=1, live_rows=1,
                          true_tokens=len(req.prompt),
                          padded_tokens=len(req.prompt),
                          kv_pages_read=0, kv_pages_written=0,
                          new_kv_tokens=len(req.prompt))
        self.cache, self.lens = cache, lens
        self._lens_np[slot] = len(req.prompt)
        self.prefill_tokens += len(req.prompt)
        self.sched.note_work(len(req.prompt))
        self.host_syncs += 1
        nxt = int(self._sample(last_logits)[slot, 0]) \
            if last_logits is not None else 0
        self.tokens = self.tokens.at[slot, 0].set(nxt)
        self.slots[slot] = req
        req.slot = slot
        req.prefill_pos = len(req.prompt)
        req.state = RequestState.DECODING
        self._phase(req, "DECODING", slot)
        if self._emit(req, nxt):
            self._finish(req)

    # ------------------------------------------------------------------
    # paged admission and chunked prefill (token-budget schedule)
    # ------------------------------------------------------------------
    def _reserve_chunked(self, slot: int, req: Request) -> bool:
        """Paged admission: reserve pages (through the prefix cache when
        enabled) and mark the request PREFILLING with its cursor at the
        cached-prefix boundary - no prompt computation happens here; the
        scheduler streams chunks in over the coming ticks."""
        if self.prefix is not None:
            start = self._reserve_prefix(slot, req)
            if start is None:
                return False
        else:
            need = pages_needed(len(req.target) + req.remaining_new,
                                self.scfg.page_size)
            if not self.allocator.can_alloc(need):
                return False
            self.allocator.alloc(slot, need)
            self._note_alloc()
            start = 0
        self.slots[slot] = req
        req.slot = slot
        req.prefill_pos = start
        req.state = RequestState.PREFILLING
        self._stamp_admit(req)
        self._phase(req, "PREFILLING", slot, cached_tokens=start)
        return True

    def _run_chunk_batch(self, tasks: List[ChunkTask]):
        """Execute EVERY prefill chunk planned this tick in ONE jitted
        launch: the scheduler packs the tasks into a ragged K-row batch
        (power-of-two bucketed, dead rows padded to the null page like
        the masked decode table), each row carrying its own offset /
        cursor / block-table row; final-chunk first tokens are sampled
        device-side inside the launch and land in the engine's tokens /
        lens via masked scatters.  Returns the final rows' deferred
        emissions [(req, slot, work-clock stamp)] - their token VALUES
        surface in the tick's single device_get after the decode launch.

        Host accounting walks the tasks in plan order: each final row's
        first token is stamped with the work clock as it stands after its
        own chunk, not after the whole pack."""
        with self.tm.phase("engine.chunk_launch") as args:
            pack = self.sched.pack_chunks(tasks)
            finals = []
            for t in tasks:
                t.req.prefill_pos = t.start + t.length
                self.prefill_tokens += t.length
                self.sched.note_work(t.length)
                self.sched.chunks_run += 1
                if t.req.prefill_pos >= len(t.req.target):
                    t.req.state = RequestState.DECODING
                    self._phase(t.req, "DECODING", t.slot)
                    self._table_dirty = True    # unmask the slot's row
                    self._lens_np[t.slot] = len(t.req.target)
                    finals.append((t.req, t.slot, self.sched.work_clock))
            # per-row block-table rows from the host allocator (dead rows
            # keep the all-null table so every page walk lands on the null
            # page)
            rows, width = pack.tokens.shape
            tables = np.zeros((rows, self.allocator.table.shape[1]),
                              np.int32)
            live = pack.row_slots >= 0
            tables[live] = self.allocator.table[pack.row_slots[live]]
            batch = {"tokens": jnp.asarray(pack.tokens),
                     "offset": jnp.asarray(pack.offsets),
                     "true_lens": jnp.asarray(pack.true_lens),
                     "final_slot": jnp.asarray(pack.final_slots)}
            self.jit_calls += 1
            self.sched.packs_run += 1
            self.cache, self.tokens, self.lens = self._prefill_chunks(
                self.params, batch, self.cache, jnp.asarray(tables),
                self.tokens, self.lens, self._next_key())
            n_true = sum(t.length for t in tasks)
            args.update(rows=rows, live_rows=len(tasks), true_tokens=n_true)
            self._note_launch(
                "chunk_batch", padded_tokens=rows * width,
                kv_pages_read=sum(self._row_pages(t.slot,
                                                  t.start + t.length)
                                  for t in tasks),
                kv_pages_written=sum(self._span_pages(t.start,
                                                      t.start + t.length)
                                     for t in tasks),
                new_kv_tokens=n_true, **args)
        return finals

    def _run_spec_verify(self, tasks: List[DraftTask]) -> SpecBatch:
        """Execute EVERY draft chain planned this tick in ONE jitted
        launch through the batched chunk kernel: the scheduler packs the
        chains into a ragged verify batch (pack_drafts) with per-row
        block-table rows from the host allocator, the device samples the
        target's token at every chain position, accepts the matching
        draft prefix, writes the bonus token into the engine's tokens and
        the new KV frontier into lens (rejected positions past it are
        dead - rollback is free), and leaves the per-row acceptance
        counts in _spec_nacc for the host to fetch WITH the tick's
        tokens - no extra device->host sync."""
        with self.tm.phase("engine.verify_launch") as args:
            pack = self.sched.pack_drafts(tasks, self._lens_np)
            # per-row block-table rows from the host allocator (dead rows
            # keep the all-null table, copied - never aliased - like
            # _run_chunk_batch)
            rows, width = pack.tokens.shape
            tables = np.zeros((rows, self.allocator.table.shape[1]),
                              np.int32)
            live = pack.row_slots < self.scfg.max_batch
            tables[live] = self.allocator.table[pack.row_slots[live]]
            batch = {"tokens": jnp.asarray(pack.tokens),
                     "offset": jnp.asarray(pack.offsets),
                     "true_lens": jnp.asarray(pack.true_lens),
                     "q_lens": jnp.asarray(pack.q_lens),
                     "draft_lens": jnp.asarray(pack.draft_lens),
                     "row_slot": jnp.asarray(pack.row_slots)}
            self.jit_calls += 1
            self.decode_launches += 1
            ps = self.scfg.page_size
            self._note_kv_pages_read(sum(-(-int(t) // ps)
                                         for t in pack.true_lens[live]))
            self.cache, self.tokens, self.lens, self._spec_nacc = \
                self._spec_verify(self.params, batch, self.cache,
                                  jnp.asarray(tables), self.tokens,
                                  self.lens, self._next_key())
            n_q = sum(1 + len(t.draft) for t in pack.tasks)
            args.update(rows=rows, live_rows=len(pack.tasks),
                        true_tokens=n_q,
                        ctx_tokens=self._note_decode_ctx(
                            t.offset + 1 + len(t.draft)
                            for t in pack.tasks))
            self._note_launch(
                "spec_verify", padded_tokens=rows * width,
                kv_pages_read=sum(self._row_pages(
                    t.slot, t.offset + 1 + len(t.draft))
                    for t in pack.tasks),
                kv_pages_written=sum(
                    self._span_pages(t.offset, t.offset + 1 + len(t.draft))
                    for t in pack.tasks),
                new_kv_tokens=n_q, **args)
        return pack

    # ------------------------------------------------------------------
    # preemption (ServeConfig.preemption): shed low-priority load when the
    # page pool - or the slot table - cannot place a higher-priority
    # admission candidate
    # ------------------------------------------------------------------
    def _next_victim(self, cand: Request) -> Optional[Request]:
        """Victim policy: only requests CAND strictly outranks are
        eligible (equal priority never preempts - the priority-inversion
        guard, and what keeps all-default-priority traffic preemption
        free).  PREFILLING victims go first - lowest priority, most
        recently admitted first (least sunk prefill work) - then DECODING
        victims, lowest priority, longest remaining generation first
        (shedding the one that would hold its pages longest)."""
        best, best_key = None, None
        for r in self.slots:
            if r is None or r.priority >= cand.priority:
                continue
            if r.state is RequestState.PREFILLING:
                key = (0, r.priority, -r.admit_seq)
            elif r.state is RequestState.DECODING:
                key = (1, r.priority, -r.remaining_new, -r.admit_seq)
            else:
                continue
            if best_key is None or key < best_key:
                best, best_key = r, key
        return best

    def _preempt(self, victim: Request):
        """Shed one running request: drop the slot's reference on every
        page it holds - private pages return to the pool, prefix-cache
        pages survive through the tree's refcount - zero its lane, and
        park it back in the queue as RESUMING.  A mid-decode victim
        snapshots prompt + generated-so-far as its resume target
        (Request.target): the chunk path rebuilds that KV on resume and
        the final resume chunk's logits sample the NEXT token exactly as
        the uninterrupted decode would have.

        Publish-on-preempt (prefix cache on): instead of freeing, the
        victim's fully-written pages PARK in the radix tree keyed by the
        tokens whose KV they hold - on resume the prefix match re-attaches
        them and only the lost tail re-prefills; under continued pressure
        they are ordinary evictable cache.  Only fully-VALID positions
        publish: prefill_pos for a PREFILLING victim, the lens mirror for
        a DECODING one (the pending token's KV is unwritten, and any
        speculative garbage past lens must never enter the tree)."""
        slot = victim.slot
        free0 = self.allocator.free_pages
        if self.prefix is not None:
            if victim.state is RequestState.PREFILLING:
                n_valid = victim.prefill_pos
                seq = list(victim.target)
            else:
                seq = victim.prompt + list(victim.out_tokens)
                n_valid = int(self._lens_np[slot])
            cached0 = self.prefix.cached_pages
            self.prefix.release(slot, seq[:n_valid])
            self.sched.pages_parked += self.prefix.cached_pages - cached0
        else:
            self.allocator.free_slot(slot)
        self.sched.pages_reclaimed += self.allocator.free_pages - free0
        self.sched.preemptions += 1
        victim.n_preemptions += 1
        self._event(victim, "PREEMPT", slot,
                    pages_reclaimed=self.allocator.free_pages - free0)
        self._phase(victim, "RESUMING", TRACK_QUEUE)
        self.slots[slot] = None
        self.lens = self.lens.at[slot].set(0)
        self._lens_np[slot] = 0
        victim.slot = None
        victim.prefill_pos = 0
        if victim.out_tokens:
            victim.resume_tokens = victim.prompt + list(victim.out_tokens)
        victim.state = RequestState.RESUMING
        self.sched.requeue(victim)
        self._table_dirty = True     # lane must mask to the null page

    def _try_preempt(self, cand: Request) -> bool:
        """Shed ONE victim toward placing CAND (the admission loop retries
        the reservation after each).  False when preemption is off, no
        eligible victim exists, or shedding every eligible victim plus
        evicting the whole prefix cache could still not cover CAND's
        worst-case reservation - then backpressure is the right answer
        and shedding would only waste the victims' work."""
        if not self.scfg.preemption:
            return False
        victim = self._next_victim(cand)
        if victim is None:
            return False
        need = pages_needed(len(cand.target) + cand.remaining_new,
                            self.scfg.page_size)
        headroom = self.allocator.free_pages
        if self.prefix is not None:
            headroom += self.prefix.evictable_pages()
        # DISTINCT pages across eligible victims: a page shared by two
        # victim slots (or with the prefix tree) frees - or becomes
        # evictable - only once, so counting per slot would overstate the
        # reclaim and shed victims for nothing
        victim_pages = {p for r in self.slots
                        if r is not None and r.priority < cand.priority
                        for p in self.allocator.slot_pages(r.slot)}
        headroom += len(victim_pages)
        if need > headroom:
            return False
        self._preempt(victim)
        return True

    def _tick_paged(self) -> List[Request]:
        """One budgeted iteration: admit, fill the budget with prefill
        chunks, run one batched decode step for the slots that were
        already decoding.  Total work never exceeds tick_token_budget.

        The tick is ONE batched ragged prefill launch + ONE fused decode
        launch + ONE device->host transfer, whatever the traffic: all
        sampling happens device-side and token values surface in a
        single fetch at the end, so the host loop carries no per-chunk
        or per-slot round-trips (the serving analogue of the paper's
        bubble-free vertical dataflow - fine-grained chunking only wins
        once per-step dispatch overhead is gone)."""
        w0 = self.sched.work_clock
        # admission FIRST (it can preempt: a decoding victim shed here
        # must not join this tick's decode batch): reserve slots + pages
        # for as many queued requests as the policy head allows (no
        # prompt computation yet).  When the head cannot be placed and
        # outranks a running request, shed victims one at a time and
        # retry; otherwise head-of-line backpressure as before.
        with self.tm.phase("engine.admit"):
            while True:
                req = self.sched.peek()
                if req is None:
                    break
                resuming = req.state is RequestState.RESUMING
                placed = False
                while True:
                    slot = self._free_slot()
                    if slot is not None \
                            and self._reserve_chunked(slot, req):
                        placed = True
                        break
                    if not self._try_preempt(req):
                        break
                if not placed:
                    break
                self._pop_admitted(req)
                if resuming:
                    self.sched.resumes += 1
                    req.n_resumes += 1
                    self._event(req, "RESUME", req.slot)
        if self._table_dirty:
            # a preemption zeroed a lane (or freed pages that admission
            # just re-allocated): the device table must mask it to the
            # null page BEFORE this tick's launches touch the pool
            self._sync_table()
        with self.tm.phase("engine.plan") as plan:
            decode_slots = [i for i, r in enumerate(self.slots)
                            if r is not None
                            and r.state is RequestState.DECODING]
            prefilling = [(i, r) for i, r in enumerate(self.slots)
                          if r is not None
                          and r.state is RequestState.PREFILLING]
            # speculative drafting: DECODING slots may propose chains out
            # of the budget left after every decode slot took its
            # guaranteed token; prefill planning gets what remains after
            # drafts, so the tick's total work stays bounded by
            # tick_token_budget
            spec_tasks: List[DraftTask] = []
            spec_tokens = 0
            if self.speculative and decode_slots:
                room = self.scfg.tick_token_budget - len(decode_slots)
                spec_tasks = self.sched.plan_drafts(
                    [(i, self.slots[i]) for i in decode_slots], room)
                spec_tokens = sum(len(t.draft) for t in spec_tasks)
            budget = self.sched.prefill_budget(len(decode_slots)
                                               + spec_tokens)
            chunks = self.sched.plan_chunks(prefilling, budget)
            plan.update(n_chunks=len(chunks), n_decode=len(decode_slots),
                        n_drafts=len(spec_tasks))
        self._tick_profile = (len(chunks), len(decode_slots))
        finals = self._run_chunk_batch(chunks) if chunks else []
        # drafted slots verify their whole chain in the spec launch; the
        # rest take their one token through the fused decode as before
        spec_slots = {t.slot for t in spec_tasks}
        plain_slots = [i for i in decode_slots if i not in spec_slots]
        if decode_slots and self.prefix is not None:
            self._cow_guard({t.slot: len(t.draft) for t in spec_tasks})
        spec_pack = None
        if spec_tasks:
            spec_pack = self._run_spec_verify(spec_tasks)
        if plain_slots:
            with self.tm.phase("engine.decode_launch",
                               live_rows=len(plain_slots)) as args:
                live = np.zeros((len(self.slots),), bool)
                live[plain_slots] = True
                self.jit_calls += 1
                self.decode_launches += 1
                args["ctx_tokens"] = self._note_decode_ctx(
                    int(self._lens_np[i]) + 1 for i in plain_slots)
                self._note_kv_pages_read(sum(
                    -(-(int(self._lens_np[i]) + 1) // self.scfg.page_size)
                    for i in plain_slots))
                pages_read = sum(self._row_pages(i,
                                                 int(self._lens_np[i]) + 1)
                                 for i in plain_slots)
                self.cache, self.tokens, self.lens = self._decode_fused(
                    self.params, self.cache, self.tokens, self.lens,
                    jnp.asarray(live), self._next_key())
                self._note_launch("decode", rows=len(self.slots),
                                  live_rows=len(plain_slots),
                                  true_tokens=len(plain_slots),
                                  padded_tokens=len(self.slots),
                                  kv_pages_read=pages_read,
                                  kv_pages_written=len(plain_slots),
                                  new_kv_tokens=len(plain_slots),
                                  ctx_tokens=args["ctx_tokens"])
            self.sched.note_work(len(plain_slots))
            self._lens_np[plain_slots] += 1
        gen_work = len(plain_slots)
        if finals or plain_slots or spec_pack is not None:
            # THE device->host transfer: every sampled token of the tick
            # (plus, speculating, every chain's acceptance count)
            with self.tm.phase("engine.device_get"):
                if spec_pack is not None:
                    self.host_syncs += 1
                    toks, naccs = (np.asarray(x) for x in jax.device_get(
                        (self.tokens, self._spec_nacc)))
                else:
                    toks = self._fetch_tokens()
        # (nothing was fetched only when there is nothing to emit)
        with self.tm.phase("engine.emit"):
            for req, slot, work in finals:
                if self._emit(req, int(toks[slot, 0]), work=work):
                    self._finish(req)
            if spec_pack is not None:
                for r, t in enumerate(spec_pack.tasks):
                    n = int(naccs[r])
                    self.sched.note_spec(len(t.draft), n)
                    self._event(t.req, "SPEC_VERIFY", t.slot,
                                drafted=len(t.draft), accepted=n)
                    self._lens_np[t.slot] = t.offset + n + 1
                    # accepted draft prefix + the target's bonus token;
                    # work-clock advances per ACCEPTED token only, so
                    # work_tokens match a non-speculative run exactly
                    chain = list(t.draft[:n]) + [int(toks[t.slot, 0])]
                    for tok in chain:
                        self.sched.note_work(1)
                        gen_work += 1
                        if self._emit(t.req, tok):
                            self._finish(t.req)
                            break
            for i in plain_slots:
                req = self.slots[i]
                if self._emit(req, int(toks[i, 0])):
                    self._finish(req)
            self.sched.note_tick(gen_work,
                                 self.sched.work_clock - w0 - gen_work)
            if self._finished_this_tick:
                self._maybe_evict_watermark()
            if self._table_dirty:
                self._sync_table()
        return self._finished_this_tick

    # ------------------------------------------------------------------
    def _cow_guard(self, spec_spans: Dict[int, int]):
        """Give any decoding slot about to WRITE into a shared page a
        private copy first.  By construction generation pages are private
        (the one structural COW happens at admission), so this is a cheap
        defensive sweep - but it makes 'decode never corrupts a cached
        page' an invariant of the tick loop rather than of the admission
        math.  Slots still prefilling are skipped: their decode write lane
        is masked to the null page, not to table[lens // page_size].
        `spec_spans` maps slots with a planned draft chain to its length
        m: the verify launch writes positions lens .. lens + m, so every
        page that range touches gets the same guard."""
        ps = self.scfg.page_size
        lens = self._lens_np          # host mirror: no device->host sync
        dirty = False
        for i, req in enumerate(self.slots):
            if req is None or req.state is not RequestState.DECODING:
                continue
            lo = int(lens[i]) // ps
            hi = (int(lens[i]) + spec_spans.get(i, 0)) // ps
            for idx in range(lo, hi + 1):
                page = int(self.allocator.table[i, idx])
                if self.allocator.refcount(page) > 1:
                    src, dst = self.allocator.cow(i, idx)
                    self._copy_page(src, dst)
                    self.cow_copies += 1
                    dirty = True
        if dirty:
            self._sync_table()

    def _maybe_evict_watermark(self):
        if self.prefix is not None and self.scfg.prefix_evict_watermark > 0:
            usable = self.allocator.usable_pages
            target = math.ceil(self.scfg.prefix_evict_watermark * usable)
            short = target - self.allocator.free_pages
            if short > 0:
                self.prefix.evict(short)

    def tick(self) -> List[Request]:
        """One engine iteration.  Paged: one token-budgeted round of decode
        + prefill chunks.  Dense: admit (whole-prompt prefills) + one
        batched decode step.  Returns requests that finished this tick.
        Every tick appends its dispatch accounting to self.tm.ticks and
        runs inside the engine.tick phase (Telemetry.phase)."""
        with self.tm.phase("engine.tick") as args:
            self._finished_this_tick = []
            self._tick_profile = (0, 0)
            self._expire_deadlines()
            j0, s0 = self.jit_calls, self.host_syncs
            t0 = time.perf_counter()
            out = self._tick_paged() if self.paged else self._tick_dense()
            rec = TickRecord(self.jit_calls - j0, self.host_syncs - s0,
                             time.perf_counter() - t0, *self._tick_profile)
            self.tm.ticks.append(rec)
            args.update(jit_calls=rec.jit_calls, host_syncs=rec.host_syncs,
                        n_chunks=rec.n_chunk_tasks, n_decode=rec.n_decode)
        return out

    def _tick_dense(self) -> List[Request]:
        w0 = self.sched.work_clock
        with self.tm.phase("engine.admit"):
            self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            if self.sched.work_clock > w0:      # admissions that finished
                self.sched.note_tick(0, self.sched.work_clock - w0)
            return self._finished_this_tick
        self._tick_profile = (0, len(active))
        # one fused launch: decode + device-side sampling + vectorized
        # masked token/length updates, then ONE device->host transfer for
        # every lane's sampled token
        with self.tm.phase("engine.decode_launch",
                           live_rows=len(active)) as args:
            live = np.zeros((len(self.slots),), bool)
            live[active] = True
            self.jit_calls += 1
            self.decode_launches += 1
            args["ctx_tokens"] = self._note_decode_ctx(
                int(self._lens_np[i]) + 1 for i in active)
            self.cache, self.tokens, self.lens = self._decode_fused(
                self.params, self.cache, self.tokens, self.lens,
                jnp.asarray(live), self._next_key())
            self._note_launch("decode", rows=len(self.slots),
                              live_rows=len(active), true_tokens=len(active),
                              padded_tokens=len(self.slots),
                              kv_pages_read=0, kv_pages_written=0,
                              new_kv_tokens=len(active),
                              ctx_tokens=args["ctx_tokens"])
        self.sched.note_work(len(active))
        self._lens_np[active] += 1
        with self.tm.phase("engine.device_get"):
            toks = self._fetch_tokens()
        with self.tm.phase("engine.emit"):
            for i in active:
                req = self.slots[i]
                if self._emit(req, int(toks[i, 0])):
                    self._finish(req)
            self.sched.note_tick(len(active),
                                 self.sched.work_clock - w0 - len(active))
        return self._finished_this_tick

    def run_until_done(self, max_ticks: int = 10_000,
                       on_exhaust: str = "raise") -> List[Request]:
        """Tick until queue and slots drain.  If `max_ticks` runs out with
        work still pending the engine RAISES (on_exhaust="raise", default)
        so a hung scheduler cannot masquerade as a completed trace; pass
        on_exhaust="return" to get the partial results back instead."""
        done: List[Request] = []
        for _ in range(max_ticks):
            done.extend(self.tick())
            if not self.queue and all(s is None for s in self.slots):
                return done
        n_flight = sum(s is not None for s in self.slots)
        if not self.queue and n_flight == 0:
            return done
        msg = (f"run_until_done: {max_ticks} ticks exhausted with "
               f"{len(self.queue)} queued and {n_flight} in-flight "
               f"requests still pending ({len(done)} finished)")
        if on_exhaust == "raise":
            raise RuntimeError(msg)
        import warnings
        warnings.warn(msg)
        return done
