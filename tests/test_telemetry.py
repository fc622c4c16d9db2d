"""Engine-wide telemetry: metrics registry, span tracer, per-launch
data-movement attribution, and the Chrome-trace exporter.

Covers: registry semantics (exactly-once registration with a mandatory
help string, counter monotonicity, cumulative histogram buckets, labeled
children, JSON snapshot and Prometheus text exposition), the doc-coverage
check (every metric an engine registers must be documented in
docs/observability.md), tracer determinism (two replays of the same
seeded trace produce bit-identical work-clock span sequences), the
zero-overhead guarantee (telemetry on vs off: bit-identical greedy
outputs and identical per-tick jit-call / host-sync dispatch accounting),
Chrome trace-event schema validation for both the wall and the work
clock, launch-record KV-page accounting against the PageAllocator (the
block-table-derived per-launch counts must sum exactly to the engine's
analytic kv_pages_read counter), the movement-breakdown byte model,
preempt/resume lifecycle instants, the speculative counters, the
per-tick dispatch records and stats() views, and the engine's phases in
a jax.profiler trace plus the admission stamps and queue-wait histogram.
"""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.models import build_model
from repro.serve import ServeEngine
from repro.serve.telemetry import (TRACK_ENGINE, TRACK_QUEUE, Counter,
                                   Gauge, Histogram, LaunchRecord,
                                   MetricError, MetricsRegistry, Span,
                                   SpanTracer, Telemetry, TickRecord,
                                   TraceEvent, export_chrome_trace,
                                   movement_breakdown)

from traffic import mixed_prompts, priority_burst, replay, serve_all

PAGE = 8
DOCS = Path(__file__).resolve().parent.parent / "docs"


@pytest.fixture(scope="module")
def model_f32():
    # float32 keeps greedy argmax ties out of the parity comparisons
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _base(**over):
    base = dict(max_batch=3, max_seq=256, max_new_tokens=6, paged=True,
                page_size=PAGE, num_pages=3 * 29 + 1,
                prefill_chunk=16, tick_token_budget=32,
                prefix_cache=True)
    base.update(over)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def traced_run(model_f32):
    """One mixed trace served with span tracing ON - shared by the
    schema / accounting / catalog tests (read-only for all of them)."""
    model, params = model_f32
    prompts = mixed_prompts(model.cfg.vocab_size)
    outs, eng = serve_all(model, params, _base(telemetry=True), prompts,
                          check=True)
    return eng, outs, prompts


# ===========================================================================
# metrics registry semantics
# ===========================================================================

def test_registry_exactly_once_and_help_required():
    reg = MetricsRegistry()
    reg.counter("a_total", "help text")
    with pytest.raises(MetricError):
        reg.counter("a_total", "again")            # duplicate name
    with pytest.raises(MetricError):
        reg.gauge("a_total", "kind change is still a duplicate")
    with pytest.raises(MetricError):
        reg.counter("b_total", "")                 # help is mandatory
    with pytest.raises(MetricError):
        reg.counter("b_total", "   ")
    with pytest.raises(MetricError):
        reg.counter("bad-name!", "punctuation is not a metric name")
    assert "a_total" in reg and "b_total" not in reg


def test_counter_is_monotone():
    c = Counter("c_total", "h")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(MetricError):
        c.inc(-1)
    c.set_total(9)                                 # legacy write-through
    with pytest.raises(MetricError):
        c.set_total(3)                             # never backwards
    assert c.value == 9


def test_gauge_set_and_watermark():
    g = Gauge("g", "h")
    g.set(7)
    g.set(3)
    assert g.value == 3
    g.max_update(10)
    g.max_update(4)
    assert g.value == 10


def test_histogram_cumulative_buckets_and_mean():
    h = Histogram("h", "h", buckets=(1, 4, 16))
    for v in (0.5, 2, 3, 20, 100):
        h.observe(v)
    assert h.bucket_counts == [1, 2, 0, 2]         # per-bucket (+Inf last)
    assert h.count == 5
    assert h.sum == pytest.approx(125.5)
    assert h.mean == pytest.approx(125.5 / 5)
    with pytest.raises(MetricError):
        Histogram("e", "h", buckets=())


def test_labeled_children():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth by priority",
                  labelnames=("priority",))
    g.labels(0).set(3)
    g.labels(5).set(1)
    g.labels(0).set(4)                             # same child, updated
    assert {k: c.value for k, c in g.label_items()} == \
        {("0",): 4, ("5",): 1}
    with pytest.raises(MetricError):
        g.labels(0, "extra")                       # label-arity mismatch


def test_snapshot_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "Requests served").inc(3)
    reg.gauge("depth", "Queue depth", labelnames=("prio",)).labels(2).set(7)
    h = reg.histogram("lat", "Latency", buckets=(1, 2))
    h.observe(0.5)
    h.observe(5)
    snap = reg.snapshot()
    assert snap["reqs_total"] == {"kind": "counter",
                                  "help": "Requests served", "value": 3}
    assert snap["depth"]["value"] == {"prio=2": 7} or \
        snap["depth"]["value"] == {"2": 7}
    assert snap["lat"]["value"]["count"] == 2
    text = reg.prometheus_text()
    assert "# HELP reqs_total Requests served" in text
    assert "# TYPE reqs_total counter" in text
    assert "reqs_total 3" in text
    assert 'depth{prio="2"} 7' in text
    # histogram buckets are CUMULATIVE and close with +Inf == count
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="2"} 1' in text
    assert 'lat_bucket{le="+Inf"} 2' in text
    assert "lat_count 2" in text
    assert text.endswith("\n")
    assert reg.catalog() == {"depth": "Queue depth", "lat": "Latency",
                             "reqs_total": "Requests served"}


def test_tracer_is_a_bounded_ring():
    tr = SpanTracer(capacity=4)
    for i in range(10):
        tr.add_event(f"e{i}", "request", 0, i, i, float(i))
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e.name for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    with pytest.raises(ValueError):
        SpanTracer(capacity=0)


def test_telemetry_facade_noop_without_tracer():
    tm = Telemetry()                               # registry only
    assert not tm.enabled
    tm.request_phase(1, "QUEUED", TRACK_QUEUE, 0, 0)
    tm.request_event(1, "PREEMPT", 0, 0, 0)
    assert tm.open_phases() == {}


# ===========================================================================
# engine registry: exactly-once registration + doc coverage
# ===========================================================================

def test_engine_registers_every_metric_once_with_help(traced_run):
    eng, _, _ = traced_run
    cat = eng.tm.registry.catalog()
    assert len(cat) >= 30
    for name, help_ in cat.items():
        assert help_.strip(), f"metric {name} has an empty help string"
    # one shared registry per engine: every component's prefix shows up
    prefixes = {n.split("_")[0] for n in cat}
    assert {"serve", "sched", "pool", "prefix"} <= prefixes
    # registration is exactly-once by construction - a second engine must
    # be able to build its own registry without tripping the guard
    names = eng.tm.registry.names()
    assert names == sorted(set(names))


def test_every_metric_is_documented(traced_run):
    """Doc-coverage check: docs/observability.md must name every metric
    the engine registers (the catalog is the source of truth, so adding
    a metric without documenting it fails here)."""
    eng, _, _ = traced_run
    text = (DOCS / "observability.md").read_text()
    missing = [n for n in eng.tm.registry.catalog() if f"`{n}`" not in text]
    assert not missing, \
        f"metrics missing from docs/observability.md: {missing}"


def test_standalone_components_get_private_registries():
    """A scheduler / allocator / prefix cache built without an engine must
    each self-register into a private registry (unit tests construct them
    directly) - twice, without duplicate-registration errors."""
    from repro.serve import (PageAllocator, RadixPrefixCache,
                             TokenBudgetScheduler)
    for _ in range(2):
        sched = TokenBudgetScheduler(_base())
        alloc = PageAllocator(16, PAGE, 2, 64)
        cache = RadixPrefixCache(alloc, PAGE)
        assert "sched_ticks_total" in sched.metrics
        assert "pool_free_pages" in alloc.metrics
        assert "prefix_lookups_total" in cache.metrics
        assert cache.metrics is not alloc.metrics is not sched.metrics


# ===========================================================================
# determinism and zero overhead
# ===========================================================================

def test_work_clock_trace_is_deterministic(model_f32):
    """Two replays of the same seeded trace must record bit-identical
    work-clock span sequences (wall stamps excluded by construction)."""
    model, params = model_f32
    prompts = mixed_prompts(model.cfg.vocab_size)
    traces = []
    for _ in range(2):
        _, eng = serve_all(model, params, _base(telemetry=True), prompts)
        traces.append(eng.tm.tracer.deterministic_trace())
    assert traces[0], "tracer recorded nothing"
    assert traces[0] == traces[1]


def test_telemetry_off_is_bit_identical_and_free(model_f32):
    """Span tracing must be observer-only: greedy outputs bit-identical
    and the dispatch accounting (jitted calls and device->host syncs,
    per tick) EXACTLY unchanged with telemetry on vs off."""
    model, params = model_f32
    prompts = mixed_prompts(model.cfg.vocab_size)
    outs_off, eng_off = serve_all(model, params, _base(), prompts)
    outs_on, eng_on = serve_all(model, params, _base(telemetry=True),
                                prompts)
    assert outs_on == outs_off
    # the per-tick dispatch records, everything but the wall-time field
    def dispatch(eng):
        return [(t.jit_calls, t.host_syncs, t.n_chunk_tasks, t.n_decode)
                for t in eng.tm.ticks]
    assert dispatch(eng_on) == dispatch(eng_off)
    assert eng_on.jit_calls == eng_off.jit_calls
    assert eng_on.host_syncs == eng_off.host_syncs
    # the off engine records no spans and refuses to export a trace
    assert eng_off.tm.tracer is None
    assert not eng_off.scfg.telemetry
    with pytest.raises(ValueError):
        eng_off.export_trace("/dev/null")


# ===========================================================================
# request lifecycle spans
# ===========================================================================

def test_request_lifecycle_spans(traced_run):
    eng, outs, prompts = traced_run
    tr = eng.tm.tracer
    assert eng.tm.open_phases() == {}, "drained trace left open spans"
    spans = tr.spans()
    phases = {}
    for s in spans:
        if s.cat == "request":
            args = dict(s.args)
            phases.setdefault(args["uid"], []).append(args["phase"])
    assert set(phases) == set(outs)
    for uid, seq in phases.items():
        assert seq[0] == "QUEUED", f"uid {uid} did not start QUEUED"
        assert "PREFILLING" in seq and "DECODING" in seq
        # work-clock stamps are monotone within a request's lifecycle
    done_events = [e for e in tr.events() if e.name.endswith(":DONE")]
    assert len(done_events) == len(outs)
    # every span is work-clock-consistent and stamped with its tick
    for s in spans:
        assert s.work1 >= s.work0 >= 0
        assert s.wall1 >= s.wall0 >= 0.0
        assert s.tick >= 0


def test_preempt_resume_events(model_f32):
    """A capacity-capped priority burst must land PREEMPT and RESUME
    instants (and a RESUMING phase span) on the trace."""
    model, params = model_f32
    items = priority_burst(model.cfg.vocab_size, background_lens=(96, 96),
                           burst_lens=(64,), burst_tick=2)
    scfg = ServeConfig(max_batch=3, max_seq=256, max_new_tokens=8,
                       paged=True, page_size=PAGE, num_pages=200,
                       prefill_chunk=16,
                       tick_token_budget=24, preemption=True,
                       max_chunks_per_tick=1, usable_pages=28,
                       telemetry=True)
    eng = ServeEngine(model, params, scfg)
    replay(eng, items)
    assert eng.sched.preemptions >= 1 and eng.sched.resumes >= 1
    names = {e.name.split(":", 1)[1] for e in eng.tm.tracer.events()
             if ":" in e.name}
    assert "PREEMPT" in names and "RESUME" in names
    resuming = [s for s in eng.tm.tracer.spans()
                if s.cat == "request" and dict(s.args).get("phase") ==
                "RESUMING"]
    assert resuming and all(s.track == TRACK_QUEUE for s in resuming)
    assert eng.tm.open_phases() == {}


# ===========================================================================
# Chrome trace-event export (Perfetto)
# ===========================================================================

def _validate_chrome_trace(trace, n_slots):
    assert set(trace) == {"traceEvents", "displayTimeUnit", "otherData"}
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    for ev in events:
        assert ev["ph"] in ("X", "i", "M"), ev
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str) and ev["name"]
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert isinstance(ev["args"]["name"], str)
            continue
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        else:
            assert ev["s"] in ("t", "p", "g")      # instant scope
    # metadata must name the engine + requests processes and every track
    meta = {(e["pid"], e["tid"], e["args"]["name"])
            for e in events if e["ph"] == "M"}
    assert (0, 0, "engine") in meta and (1, 0, "requests") in meta
    for slot in range(n_slots):
        assert (1, slot, f"slot{slot}") in meta
    assert (1, n_slots, "queue") in meta


def test_export_trace_is_valid_chrome_json(traced_run, tmp_path):
    eng, _, _ = traced_run
    path = tmp_path / "trace.json"
    returned = eng.export_trace(path)
    on_disk = json.loads(path.read_text())         # must round-trip as JSON
    assert on_disk == json.loads(json.dumps(returned))
    _validate_chrome_trace(on_disk, eng.scfg.max_batch)
    assert on_disk["otherData"]["clock"] == "wall"
    assert on_disk["otherData"]["dropped_records"] == 0


def test_export_trace_work_clock(traced_run, tmp_path):
    """The work-clock export is the deterministic view: every timestamp
    is an integer number of work tokens (1 token == 1 us)."""
    eng, _, _ = traced_run
    path = tmp_path / "trace_work.json"
    trace = eng.export_trace(path, clock="work")
    _validate_chrome_trace(trace, eng.scfg.max_batch)
    assert trace["otherData"]["clock"] == "work"
    for ev in trace["traceEvents"]:
        if ev["ph"] in ("X", "i"):
            assert float(ev["ts"]).is_integer()
    with pytest.raises(ValueError):
        eng.export_trace(path, clock="sundial")


# ===========================================================================
# per-launch movement attribution
# ===========================================================================

KNOWN_KINDS = {"prefill", "chunk_batch", "decode", "spec_verify",
               "stepwise"}


def test_launch_records_match_page_allocator_accounting(traced_run):
    """The acceptance cross-check: per-launch KV-page counts are derived
    from PageAllocator block-table rows, the engine's kv_pages_read
    counter from the analytic ceil(len / page_size) - the two views of
    the same accounting must agree EXACTLY over the whole trace."""
    eng, _, _ = traced_run
    recs = eng.launch_records()
    assert recs, "no launch records"
    for r in recs:
        assert r.kind in KNOWN_KINDS
        assert 0 <= r.live_rows <= r.rows
        assert 0 <= r.true_tokens <= r.padded_tokens
        assert r.kv_pages_read >= 0 and r.kv_pages_written >= 0
        assert r.tick >= 0 and r.work_clock >= 0
    from_records = sum(r.kv_pages_read for r in recs
                       if r.kind in ("decode", "spec_verify"))
    assert from_records == eng.kv_pages_read, \
        (from_records, eng.kv_pages_read)


def test_decode_ctx_tokens_fill_the_pages_read(traced_run):
    """serve_decode_ctx_tokens_total is the sum of the token-emitting
    launches' ctx_tokens, and a launch's attended context fills the pages
    it reads: ctx <= pages x page_size < ctx + live rows x page_size."""
    eng, _, _ = traced_run
    recs = [r for r in eng.launch_records()
            if r.kind in ("decode", "spec_verify")]
    assert recs
    assert sum(r.ctx_tokens for r in recs) == eng.decode_ctx_tokens > 0
    for r in recs:
        assert r.live_rows <= r.ctx_tokens <= r.kv_pages_read * PAGE \
            < r.ctx_tokens + r.live_rows * PAGE, r


def test_movement_breakdown_byte_model(model_f32):
    """Synthetic launch records through the exact byte model: KV pages
    stream page_size tokens of K+V, weights stream once per launch,
    activations move per padded token, SRAM is 2x HBM (single-pass flash
    staging), and energy folds through core/energy.py."""
    import jax.numpy as jnp
    model, _ = model_f32
    cfg, scfg = model.cfg, _base()
    it = jnp.dtype(cfg.dtype).itemsize
    kv_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * it
    rec = LaunchRecord(tick=0, kind="decode", rows=4, live_rows=2,
                       true_tokens=2, padded_tokens=4, kv_pages_read=5,
                       kv_pages_written=2, new_kv_tokens=2, work_clock=9)
    out = movement_breakdown([rec], cfg, scfg)
    d = out["decode"]
    assert d["kv_read_bytes"] == 5 * PAGE * kv_tok
    assert d["kv_write_bytes"] == 2 * kv_tok
    assert d["weight_bytes"] == cfg.active_param_count() * it
    assert d["act_bytes"] == 4 * 2 * cfg.n_layers * cfg.d_model * it
    assert d["hbm_bytes"] == (d["kv_read_bytes"] + d["kv_write_bytes"]
                              + d["weight_bytes"] + d["act_bytes"])
    assert d["sram_bytes"] == 2 * d["hbm_bytes"]
    assert d["energy_j"] > 0
    assert d["padding_overhead"] == pytest.approx(0.5)
    assert d["hbm_share"] == pytest.approx(1.0)
    assert out["total"]["hbm_bytes"] == d["hbm_bytes"]
    assert movement_breakdown([], cfg, scfg)["total"]["launches"] == 0


def test_movement_stats_over_trace(traced_run):
    eng, _, _ = traced_run
    mv = eng.movement_stats()
    total = mv.pop("total")
    assert total["hbm_bytes"] > 0
    assert total["sram_bytes"] == pytest.approx(2 * total["hbm_bytes"])
    assert 0 <= total["padding_overhead"] < 1
    assert sum(row["hbm_share"] for row in mv.values()) == \
        pytest.approx(1.0)
    assert sum(row["launches"] for row in mv.values()) == \
        total["launches"] == len(eng.launch_records())


# ===========================================================================
# speculative counters
# ===========================================================================

def test_spec_counters_reach_registry(model_f32):
    """drafted == accepted + rejected, the acceptance-ratio histogram
    sees one observation per verified chain, and the registry values
    back the stats() keys the bench prints."""
    model, params = model_f32
    rng = np.random.default_rng(11)
    base = rng.integers(1, model.cfg.vocab_size, size=4).tolist()
    prompts = [base * 6, base * 5]                 # repetitive by design
    scfg = ServeConfig(max_batch=2, max_seq=256, max_new_tokens=48,
                       paged=True, page_size=16,
                       prefill_chunk=16, tick_token_budget=32,
                       speculative=True, spec_k=4, telemetry=True)
    outs, eng = serve_all(model, params, scfg, prompts)
    st = eng.stats()
    reg = eng.tm.registry
    assert st["spec_drafted"] > 0, "drafter never engaged"
    assert st["spec_drafted"] == st["spec_accepted"] + st["spec_rejected"]
    assert st["spec_drafted"] == reg.get("sched_spec_drafted_total").value
    assert st["spec_rejected"] == reg.get("sched_spec_rejected_total").value
    hist = reg.get("sched_spec_chain_accept_ratio")
    assert hist.count > 0
    assert 0.0 <= st["spec_chain_accept_mean"] <= 1.0
    assert st["spec_chain_accept_mean"] == pytest.approx(hist.mean)
    # verify instants carry the per-chain outcome onto the trace
    spec_events = [e for e in eng.tm.tracer.events()
                   if e.name.endswith(":SPEC_VERIFY")]
    assert len(spec_events) == hist.count
    drafted = sum(dict(e.args)["drafted"] for e in spec_events)
    assert drafted == st["spec_drafted"]


# ===========================================================================
# legacy compatibility views
# ===========================================================================

def test_launch_log_and_stats_compat(traced_run):
    """The typed per-tick TickRecords account for every jitted call and
    sync, and stats() keeps its flat keys - both computed from the
    registry and the TickRecords."""
    eng, outs, _ = traced_run
    ticks = eng.tm.ticks
    assert ticks and all(isinstance(t, TickRecord) for t in ticks)
    assert sum(t.jit_calls for t in ticks) == eng.jit_calls
    assert sum(t.host_syncs for t in ticks) == eng.host_syncs
    assert all(t.host_wall_s >= 0.0 for t in ticks)
    st = eng.stats()
    for key in ("jit_calls", "host_syncs", "prefill_tokens", "gen_tokens",
                "ticks", "chunks_run", "preemptions", "resumes",
                "spec_drafted", "spec_accepted", "spec_rejected",
                "queue_depth", "max_tick_tokens", "compile_count",
                "tbt_work_p95", "telemetry"):
        assert key in st, f"stats() lost key {key}"
    assert st["telemetry"] is True
    assert st["jit_calls"] == eng.jit_calls
    assert st["gen_tokens"] == sum(len(t) for t in outs.values())
    # legacy attribute writes still route through the registry
    reg = eng.tm.registry
    assert eng.jit_calls == reg.get("serve_jit_calls_total").value
    assert eng.peak_pages == reg.get("serve_peak_pages").value
    snap = eng.metrics_snapshot()
    assert snap["serve_jit_calls_total"]["value"] == eng.jit_calls
    prom = eng.prometheus_metrics()
    assert f"serve_jit_calls_total {eng.jit_calls}" in prom


# ===========================================================================
# engine phases on the profiler's clock, admission stamps
# ===========================================================================

PAGED_PHASES = {"engine.tick", "engine.admit", "engine.table_upload",
                  "engine.plan", "engine.chunk_launch",
                  "engine.decode_launch", "engine.device_get",
                  "engine.emit"}
DENSE_PHASES = {"engine.tick", "engine.admit", "engine.prefill_launch",
                "engine.decode_launch", "engine.device_get",
                "engine.emit"}


@pytest.mark.parametrize("paged", [True, False])
def test_engine_phases_in_profiler_trace(model_f32, tmp_path, paged):
    """One tick that admits a prompt while another request decodes,
    recorded by jax.profiler: every phase of the tick is in the
    .xplane.pb, nested in engine.tick, and the launches carry their
    args.  The tracer is off: the annotations do not depend on it."""
    from jax.profiler import ProfileData
    model, params = model_f32
    eng = ServeEngine(model, params, _base(paged=paged,
                                           prefix_cache=False,
                                           max_new_tokens=4))
    eng.submit(list(range(1, 12)))
    while not any(r is not None and r.out_tokens for r in eng.slots):
        eng.tick()
    eng.submit(list(range(3, 17)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.tick()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("engine.")]
    names = {e.name for e in events}
    assert names == (PAGED_PHASES if paged else DENSE_PHASES)
    (tick,) = [e for e in events if e.name == "engine.tick"]
    t0, t1 = tick.start_ns, tick.start_ns + tick.duration_ns
    for e in events:
        assert t0 <= e.start_ns and e.start_ns + e.duration_ns <= t1, e.name
    stats = {e.name: dict(e.stats) for e in events}
    # a dense admission prefills whole, so the newcomer decodes too.
    # Context attended: paged, 11 prompt positions + the pending token;
    # dense, the first request's 12 + 1 and the newcomer's 14 + 1
    n_decode, ctx = (1, 12) if paged else (2, 28)
    assert stats["engine.decode_launch"] == {"live_rows": n_decode,
                                             "ctx_tokens": ctx}
    assert stats["engine.tick"]["n_decode"] == n_decode
    if paged:
        assert stats["engine.chunk_launch"] == {"rows": 1, "live_rows": 1,
                                                "true_tokens": 14}
    else:
        assert stats["engine.prefill_launch"] == {"true_tokens": 14}
    assert eng.tm.tracer is None


@pytest.fixture(scope="module")
def preempt_run(model_f32):
    """The capacity-capped priority burst of test_preempt_resume_events:
    background requests are preempted and resumed."""
    model, params = model_f32
    items = priority_burst(model.cfg.vocab_size, background_lens=(96, 96),
                           burst_lens=(64,), burst_tick=2)
    scfg = ServeConfig(max_batch=3, max_seq=256, max_new_tokens=8,
                       paged=True, page_size=PAGE, num_pages=200,
                       prefill_chunk=16,
                       tick_token_budget=24, preemption=True,
                       max_chunks_per_tick=1, usable_pages=28,
                       telemetry=True)
    eng = ServeEngine(model, params, scfg)
    _, done = replay(eng, items)
    assert eng.sched.resumes >= 1
    return eng, done


def test_admit_stamp_survives_preempt_resume(preempt_run):
    """t_admit is the first slot and page reservation: set once, on the
    clock of t_submit and token_wall, and kept through preempt -> resume
    (a resume that reset it would stamp it after the RESUME instant)."""
    eng, done = preempt_run
    for r in done:
        assert r.t_submit <= r.t_admit <= r.token_wall[0]
    tr = eng.tm.tracer
    resumed = {dict(e.args)["uid"]: e.wall for e in tr.events()
               if e.name.endswith(":RESUME")}
    assert resumed
    by_uid = {r.uid: r for r in done}
    for uid, wall in resumed.items():
        assert by_uid[uid].n_preemptions >= 1
        assert by_uid[uid].t_admit < tr.epoch + wall


def test_queue_wait_histogram_counts_first_admissions(preempt_run):
    """serve_queue_wait_seconds observes each request's first admission
    only: its count is the number of requests, not of admissions."""
    eng, done = preempt_run
    hist = eng.tm.registry.get("serve_queue_wait_seconds")
    assert hist.count == len(done) == eng.stats()["requests"]
    assert hist.sum == pytest.approx(sum(r.t_admit - r.t_submit
                                         for r in done))
    assert eng.sched.resumes >= 1       # admissions outnumber requests
