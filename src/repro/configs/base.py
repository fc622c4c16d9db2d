"""Config system: model / mesh / train / serve configuration dataclasses.

Every assigned architecture provides `get_config()` returning the exact
published configuration, and `get_smoke_config()` returning a reduced config
of the same family for CPU smoke tests.  The full configs are exercised only
through the dry-run (ShapeDtypeStruct lowering, no allocation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# OPT's learned position table (OPTLearnedPositionalEmbedding): position p
# reads row p + 2 of a table of max_seq + 2 rows
LEARNED_POS_OFFSET = 2


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // n_heads

    # --- numerics / layers -------------------------------------------------
    norm: str = "rmsnorm"       # rmsnorm | layernorm | nonparam_ln
    norm_eps: float = 1e-6
    act: str = "silu"           # silu (gated) | gelu | relu
    bias: bool = False          # biases on q/k/v/out and the MLP's in/out
    dtype: str = "bfloat16"
    tie_embeddings: bool = True
    qk_norm: bool = False       # gemma3-style per-head RMS norm of q and k

    # --- position / attention pattern --------------------------------------
    rope_theta: float = 10_000.0
    rope_scaling: float = 1.0   # dynamic RoPE scaling (paper's long-seq trick)
    sliding_window: int = 0     # 0 = full attention
    global_every: int = 0       # gemma3: every Nth layer is global, rest local
    attn_logit_softcap: float = 0.0
    use_rope: bool = True       # False: absolute positions (OPT, whisper)
    # absolute positions added to the token embedding where use_rope is off:
    # "sinusoidal" (computed per position) or "learned" (OPT's table of
    # max_seq + LEARNED_POS_OFFSET rows, position p at row p + the offset)
    abs_positions: str = "sinusoidal"

    # --- MoE ----------------------------------------------------------------
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_capacity_factor: float = 1.25

    # --- SSM (Mamba2) --------------------------------------------------------
    ssm_state: int = 0
    ssm_heads: int = 0          # 0 -> derived
    ssm_expand: int = 2
    ssm_conv: int = 4

    # --- hybrid (zamba2) ------------------------------------------------------
    shared_attn_every: int = 0  # one shared-weight attn block every k ssm blocks

    # --- RWKV ------------------------------------------------------------------
    rwkv: bool = False

    # --- encoder-decoder (whisper) ----------------------------------------------
    encoder_layers: int = 0     # > 0 => enc-dec

    # --- modality frontend stubs ---------------------------------------------
    frontend: str = ""          # "" | "vision" | "audio"
    frontend_tokens: int = 0    # vision: patch embeddings prepended

    max_seq: int = 524_288

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        assert self.n_heads % max(self.n_kv_heads, 1) == 0, \
            f"{self.name}: n_heads must be divisible by n_kv_heads"
        assert self.act in ("silu", "gelu", "relu"), self.act
        assert self.abs_positions in ("sinusoidal", "learned"), \
            self.abs_positions

    @property
    def learned_positions(self) -> bool:
        """Absolute positions read from a learned table (OPT)."""
        return not self.use_rope and self.abs_positions == "learned"

    # ---- derived -----------------------------------------------------------
    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def is_attention_free(self) -> bool:
        return self.rwkv or (self.family == "ssm" and not self.rwkv)

    @property
    def subquadratic(self) -> bool:
        """True if decode-time state does not grow O(seq) with full attention
        (SSM / hybrid / linear attention) - gates the long_500k shape."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Approximate parameter count (embedding + blocks), for 6ND math."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.head_dim
        attn = d * (self.n_heads * hd) * 2 + d * (self.n_kv_heads * hd) * 2
        n_ffn_mats = 3 if self.act == "silu" else 2
        if self.moe_experts:
            ffn = n_ffn_mats * d * f * self.moe_experts + d * self.moe_experts
        else:
            ffn = n_ffn_mats * d * f
        if self.bias:
            attn += (self.n_heads + 2 * self.n_kv_heads) * hd + d
            ffn += 0 if self.moe_experts else f + d
        if self.rwkv:
            per_layer = d * d * 4 + d * f * 2 + 10 * d
        elif self.family in ("ssm", "hybrid"):
            # Mamba2 block: in_proj (z, x, B, C, dt), depthwise conv, out_proj.
            # zamba2-style hybrids put the MLP only in the shared attn block.
            d_in = self.ssm_expand * d
            ssm = d * (2 * d_in + 2 * self.ssm_state) + d_in * d
            per_layer = ssm
        else:
            per_layer = attn + ffn
        total = self.n_layers * per_layer + v * d * (1 if self.tie_embeddings else 2)
        if self.learned_positions:
            total += (self.max_seq + LEARNED_POS_OFFSET) * d
        if self.shared_attn_every:
            total += attn + n_ffn_mats * d * f
        if self.encoder_layers:
            total += self.encoder_layers * (attn + ffn) + attn * self.n_layers
        return int(total)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k of the experts)."""
        if not self.moe_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        n_ffn_mats = 3 if self.act == "silu" else 2
        dense_ffn_all = self.n_layers * n_ffn_mats * d * f * self.moe_experts
        dense_ffn_active = self.n_layers * n_ffn_mats * d * f * self.moe_top_k
        return int(self.param_count() - dense_ffn_all + dense_ffn_active)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class MeshConfig:
    """Production mesh description (see launch/mesh.py)."""
    multi_pod: bool = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 256
    seq_len: int = 4096
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    remat: str = "none"         # none | full | dots
    grad_compression: str = ""  # "" | int8
    seed: int = 0
    log_every: int = 10


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold n_tokens (ceil-div, >= 1).  THE page math -
    ServeConfig, the model cache init, the allocator and the capacity
    helpers all route through here."""
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    return max(1, -(-n_tokens // page_size))


def dense_equivalent_pages(batch: int, max_len: int, page_size: int) -> int:
    """Pool size matching dense capacity, plus the reserved null page 0."""
    return batch * pages_for_tokens(max_len, page_size) + 1


@dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_seq: int = 4096
    prefill_chunk: int = 512
    max_new_tokens: int = 64
    temperature: float = 0.0    # 0 = greedy
    seed: int = 0               # PRNG seed for temperature > 0 sampling
    # device-side sampling filters (serve/sampling.py), applied in the
    # standard order logits / temperature -> top-k -> top-p -> categorical;
    # both are no-ops at temperature 0 (greedy bypasses the filters)
    top_k: int = 0              # keep only the k highest logits (0 = off)
    top_p: float = 1.0          # nucleus filter mass (1.0 = off)
    # finishing a request before max_new_tokens: eos_id (engine-wide) and/or
    # per-request submit(..., stop_tokens=...) end generation the tick the
    # token is produced, freeing its pages immediately
    eos_id: Optional[int] = None

    # --- token-budget scheduler (serve/scheduler.py) ------------------------
    # The paged engine runs Sarathi-style chunked prefill mixed into decode
    # ticks: every tick gets `tick_token_budget` tokens of work; each
    # decoding slot consumes 1 and the remainder is filled with prompt
    # chunks (multiples of `prefill_chunk`), so decode latency stays flat
    # while long prompts stream in (chunks prefill through the
    # offset-causal block-table kernel, kernels/paged_prefill.py).
    # chunked and batched choose nothing any more: the paged engine has one
    # path (chunked prefill, one batched chunk launch per tick), and
    # validate() rejects False.  They stay declared until the benchmark
    # harness stops passing them.
    chunked: bool = True
    # tokens of work (decode + prefill) per tick; 0 on a paged engine
    # resolves to max_batch + prefill_chunk (validate())
    tick_token_budget: int = 0
    admission_policy: str = "fifo"   # fifo | sjf (shortest prompt first)
    # cap on prefill chunks planned per tick (0 = budget-limited only).
    # Bounds the ragged chunk-batch width - and, at 1, pins every pack to
    # the K=1 kernel bucket, which makes replays bit-stable across
    # different schedules (the deterministic-replay mode the preemption
    # parity tests and --preempt-trace bench run in).
    max_chunks_per_tick: int = 0
    batched: bool = True        # see `chunked`

    # --- decode-priority budget shaping (serve/scheduler.py) ----------------
    # decode_priority=True caps the prefill share of every tick at
    # max_prefill_fraction * tick_token_budget AFTER decode slots have taken
    # their token each, so a burst of queued long prefills can never inflate
    # the per-tick work (and therefore the work-clock TBT of every in-flight
    # decode) up to the full budget: steady-state decode TBT is bounded by
    # n_decode + floor(max_prefill_fraction * budget) instead of budget.
    # Paged mode only.
    decode_priority: bool = False
    max_prefill_fraction: float = 0.5   # of tick_token_budget, (0, 1]

    # --- preemption (serve/engine.py) ---------------------------------------
    # preemption=True lets admission SHED lower-priority load when the page
    # pool runs dry instead of merely backpressuring: a queued request that
    # outranks a running one (submit(priority=...), higher wins) may preempt
    # it - the victim's non-shared pages return to the pool (prefix-cache
    # pages survive via refcounts), the victim parks QUEUED->RESUMING, and
    # on re-admission the prefix cache re-matches whatever pages survived
    # while only the lost remainder is re-prefilled through the chunk path.
    # Victim order: lowest-priority first; PREFILLING (most recently
    # admitted first) before DECODING (longest-remaining first).  Requires
    # paged=True (the resume path is the chunk path).  Equal-priority
    # requests never preempt each other, so all-default-priority traffic
    # behaves exactly like preemption=False.
    preemption: bool = False

    # --- SLO-driven priority aging (serve/scheduler.py) ---------------------
    # priority_aging=True lets queued (and preempted/parked) requests age
    # into higher ADMISSION priority: every priority_age_tokens of
    # work-clock age adds +1 effective priority, so a low-priority request
    # outranks a priority-P stream after at most (P + 1) *
    # priority_age_tokens tokens of engine work - a deterministic
    # starvation bound.  Aging affects queue ordering only; preemption
    # keeps using base priority (an aged request never evicts running
    # work, which rules out preempt/re-preempt cycles).
    priority_aging: bool = False
    priority_age_tokens: int = 256   # work tokens of age per +1 priority

    # --- self-speculative decoding (serve/engine.py + serve/drafting.py) ----
    # speculative=True drafts up to spec_k tokens per decoding request per
    # tick by prompt-lookup over the request's OWN token history (n-gram
    # match, no second model) and verifies the whole chain in one launch
    # through the batched chunk kernel: accepted tokens emit together, the
    # first mismatch emits the target model's own token instead, so every
    # verify launch nets >= 1 token and greedy outputs stay equivalent to
    # non-speculative decoding.  Rejected positions simply fall past the
    # new `lens` frontier - the causal mask hides them and later writes
    # overwrite them, so rollback costs nothing and page reservations are
    # untouched (admission already reserved the worst case).  Drafted
    # tokens consume tick budget like prefill tokens; the work clock
    # advances only for ACCEPTED tokens so TTFT/TBT stay comparable with
    # speculation on or off.  Requires paged=True (the verify path is the
    # batched chunk path).
    speculative: bool = False
    spec_k: int = 4             # max drafted tokens per request per tick
    spec_ngram: int = 3         # longest n-gram the drafter matches on

    # --- paged KV cache (serve/paged_cache.py) ------------------------------
    # paged=True stores K/V in a global page pool indexed through a block
    # table instead of one dense (max_batch, max_seq) strip per slot; only
    # attention families (dense / moe / vlm) support it.  max_seq must be a
    # multiple of page_size (enforced by ServeEngine).
    paged: bool = False
    page_size: int = 16         # tokens per page (TPU wants >= 128 in prod)
    num_pages: int = 0          # 0 = dense-equivalent capacity (+ null page)
    # soft capacity cap: the allocator exposes only this many pages to
    # admission while the DEVICE pool stays num_pages, so capacity pressure
    # (backpressure, preemption) can be dialed without changing any array
    # shape - no recompiles between a pressured run and a full-capacity
    # oracle, and both execute the very same compiled steps (which is what
    # keeps their greedy outputs bit-comparable).  0 = the whole pool.
    usable_pages: int = 0

    # --- prefix cache (serve/prefix_cache.py) -------------------------------
    # prefix_cache=True keeps finished requests' prompt pages in a radix
    # tree keyed by page-sized token blocks; new requests reuse the longest
    # cached prefix (refcounted, copy-on-write) and prefill only the
    # uncached suffix.  Paged mode only.
    prefix_cache: bool = False
    # keep at least this fraction of the pool free by LRU-evicting
    # unreferenced cached pages after completions (0 = evict only when an
    # admission would otherwise run out of pages)
    prefix_evict_watermark: float = 0.0

    # --- request deadlines (serve/scheduler.py + serve/engine.py) -----------
    # Default per-request deadline in WORK-CLOCK tokens (0 = no deadline).
    # A request whose work-clock age (engine work executed since its
    # submit) reaches its deadline before it finishes is expired with a
    # TIMEOUT status at the top of the next tick: its slot and pages are
    # freed (valid prefix pages publish into the prefix cache, exactly
    # like preemption) the same tick, so an expired request can never hang
    # the engine or strand capacity.  Per-request submit(deadline=...)
    # overrides this default; deadlines are deterministic because the work
    # clock is.
    default_deadline_tokens: int = 0

    # --- telemetry (serve/telemetry.py) -------------------------------------
    # The metrics registry is ALWAYS on - it is the typed backing store of
    # engine.stats() / scheduler.stats() and costs a handful of host-side
    # counter writes per tick.  telemetry=True additionally turns on the
    # SPAN TRACER: per-request lifecycle spans and per-tick engine/launch
    # spans in a bounded ring buffer (telemetry_spans records), exportable
    # as Chrome trace-event JSON via engine.export_trace() for Perfetto.
    # Tracing is host-side only: it adds zero jitted calls and zero
    # device->host syncs, and outputs stay bit-identical either way.
    telemetry: bool = False
    telemetry_spans: int = 65536

    # --- tensor parallelism (launch/mesh.py + kernels/ops.py) ---------------
    # tp_degree > 1 shards the engine across devices on the HEAD axis: the
    # KV page pool is head-sharded (one Hkv/tp slice per device), the paged
    # flash-decode and batched-chunk/verify kernels run under shard_map with
    # the block table replicated as scalar-prefetch state, and attention
    # outputs all-gather back to replicated before the output projection -
    # so every other op (projections, FFN/MoE, sampling) computes on
    # replicated values with the same float summation order as tp=1, which
    # is what keeps greedy outputs bit-identical to the single-device
    # engine.  Requires paged=True (the paged tick's launches are the
    # sharded paths), n_kv_heads divisible by
    # tp_degree (checked by ServeEngine against the model config), and at
    # least tp_degree JAX devices (use
    # XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU).
    tp_degree: int = 1

    def validate(self) -> "ServeConfig":
        """Scheduler-level config validation (called by ServeEngine).
        Returns the config to run: on a paged engine a tick_token_budget of
        0 resolves to max_batch + prefill_chunk.

        Degenerate knob combinations fail HERE with a clear error instead
        of hanging the tick loop: a paged engine whose budget cannot fit
        one decode sweep plus one prefill chunk would starve prefill
        forever (decode slots consume the whole budget every tick)."""
        for flag in ("chunked", "batched"):
            if not getattr(self, flag):
                raise ValueError(
                    f"{flag}=False is no longer supported: the monolithic "
                    f"and sequential-chunk serving paths were removed (the "
                    f"paged engine always runs chunked prefill in one "
                    f"batched launch per tick; paged=False is the dense "
                    f"engine)")
        budget = self.max_batch + self.prefill_chunk
        if self.paged and self.tick_token_budget == 0 and budget > 0:
            return dataclasses.replace(
                self, tick_token_budget=budget).validate()
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), "
                             f"got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.admission_policy not in ("fifo", "sjf"):
            raise ValueError(f"admission_policy must be 'fifo' or 'sjf', "
                             f"got {self.admission_policy!r}")
        if self.paged:
            if self.prefill_chunk < 1 or self.prefill_chunk % self.page_size:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a "
                    f"positive multiple of page_size ({self.page_size}) so "
                    f"every chunk starts on a page boundary")
            if self.tick_token_budget < self.max_batch + self.prefill_chunk:
                raise ValueError(
                    f"tick_token_budget ({self.tick_token_budget}) must be "
                    f">= max_batch + prefill_chunk "
                    f"({self.max_batch} + {self.prefill_chunk}) or prefill "
                    f"can starve behind a full decode batch")
        if self.decode_priority:
            if not self.paged:
                raise ValueError("decode_priority shaping requires "
                                 "paged=True (it caps the per-tick "
                                 "prefill share)")
            if not 0.0 < self.max_prefill_fraction <= 1.0:
                raise ValueError(
                    f"max_prefill_fraction must be in (0, 1], got "
                    f"{self.max_prefill_fraction}")
            if int(self.max_prefill_fraction
                   * self.tick_token_budget) < self.prefill_chunk:
                raise ValueError(
                    f"max_prefill_fraction * tick_token_budget "
                    f"({self.max_prefill_fraction} * "
                    f"{self.tick_token_budget}) must fit at least one "
                    f"prefill_chunk ({self.prefill_chunk}) or prefill "
                    f"starves forever")
        if self.max_chunks_per_tick < 0:
            raise ValueError(f"max_chunks_per_tick must be >= 0, got "
                             f"{self.max_chunks_per_tick}")
        if self.speculative:
            if not self.paged:
                raise ValueError(
                    "speculative decoding requires paged=True (draft "
                    "chains verify through the batched chunk path)")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
            if self.spec_ngram < 1:
                raise ValueError(f"spec_ngram must be >= 1, "
                                 f"got {self.spec_ngram}")
        if self.default_deadline_tokens < 0:
            raise ValueError(
                f"default_deadline_tokens must be >= 0 (0 = no deadline), "
                f"got {self.default_deadline_tokens}")
        if self.telemetry_spans < 1:
            raise ValueError(f"telemetry_spans must be >= 1, "
                             f"got {self.telemetry_spans}")
        if self.preemption and not self.paged:
            raise ValueError("preemption requires paged=True (a preempted "
                             "request resumes through the chunked prefill "
                             "path)")
        if self.priority_aging and self.priority_age_tokens < 1:
            raise ValueError(
                f"priority_age_tokens must be >= 1 when priority_aging is "
                f"on, got {self.priority_age_tokens}")
        if self.tp_degree < 1:
            raise ValueError(f"tp_degree must be >= 1, got {self.tp_degree}")
        if self.tp_degree > 1 and not self.paged:
            raise ValueError(
                f"tp_degree={self.tp_degree} requires paged=True (tensor "
                f"parallelism shards the paged tick's launches)")
        if self.usable_pages:
            if not self.paged:
                raise ValueError("usable_pages requires paged=True")
            if not 1 <= self.usable_pages <= self.pool_pages() - 1:
                raise ValueError(
                    f"usable_pages ({self.usable_pages}) must be in "
                    f"[1, {self.pool_pages() - 1}] (pool "
                    f"{self.pool_pages()} incl. the null page)")
        return self

    def pages_per_seq(self) -> int:
        return pages_for_tokens(self.max_seq, self.page_size)

    def pool_pages(self) -> int:
        """Actual pool size: configured, or dense-equivalent + null page."""
        return self.num_pages or dense_equivalent_pages(
            self.max_batch, self.max_seq, self.page_size)


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}
