"""One-launch ticks: the batched ragged prefill-chunk kernel and the
host-overhead-free serve loop.

Covers: batched-vs-single-row kernel parity (ref + pallas interpret,
ragged rows, shuffled tables, dead rows, sliding windows), the
scheduler's pack step (power-of-two bucketing, sentinel slots), engine
parity against the dense engine (mixed traffic, prefix cache on/off,
windowed gemma3 models, the K=1 degenerate case), plan-order work-clock
stamps, a hypothesis property over random chunk packings / bucket
sizes, the one-launch dispatch
accounting (one batched prefill launch + one decode launch + one
device->host transfer per busy tick), and a recompile guard: a
steady-state tick triggers ZERO new XLA compilations (jax.log_compiles)."""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.kernels import ops
from repro.models import build_model
from repro.serve import ServeEngine, RequestState, TokenBudgetScheduler
from repro.serve.scheduler import ChunkTask, Request, bucket_rows

# shared traffic-replay harness (tests/traffic.py)
from pools import LAYER, stack_pool
from traffic import MIXED_LENS, mixed_prompts as _mixed_prompts, \
    serve_all as _serve


@pytest.fixture(scope="module")
def model_f32():
    # float32 keeps greedy argmax ties out of the parity comparisons
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _base(**over):
    base = dict(max_batch=3, max_seq=256, max_new_tokens=6, paged=True,
                page_size=8, num_pages=3 * 29 + 1,
                prefill_chunk=16, tick_token_budget=32)
    base.update(over)
    return ServeConfig(**base)


# ===========================================================================
# kernel level: batched ragged rows == single-row launches, row by row
# ===========================================================================

@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("window", [0, 12])
def test_batched_kernel_matches_single_rows(impl, window, rng):
    """Each row of one batched launch must equal its own single-row
    launch - different offsets, ragged true lengths, shuffled per-row
    tables, and a dead padding row returning exactly zero."""
    S, Hq, Hkv, D, ps, n_pages, n_max = 8, 4, 2, 16, 4, 24, 8
    ks = jax.random.split(rng, 3)
    pool = stack_pool(jax.random.normal(ks[0], (Hkv, n_pages, ps, D)),
                      jax.random.normal(ks[1], (Hkv, n_pages, ps, D)))
    q = jax.random.normal(ks[2], (4, S, Hq, D))
    perm = np.random.default_rng(0).permutation(
        np.arange(1, n_pages)).astype(np.int32)
    tables = np.zeros((4, n_max), np.int32)
    tables[0, :6] = perm[:6]
    tables[1, :8] = perm[6:14]
    tables[2, :4] = perm[14:18]
    offs = np.array([4, 17, 0, 0], np.int32)
    # rows 0/2 full chunks, row 1 ragged (3 real tokens), row 3 DEAD
    tls = np.array([4 + S, 17 + 3, 0 + S, 0], np.int32)
    got = ops.batched_paged_prefill_attention(
        q, pool, LAYER, jnp.asarray(tables), jnp.asarray(offs),
        jnp.asarray(tls), window=window, impl=impl)
    for r in range(3):
        want = ops.paged_prefill_attention(
            q[r:r + 1], pool, LAYER, jnp.asarray(tables[r]),
            int(offs[r]), window=window, impl=impl)
        n_real = int(tls[r] - offs[r])
        err = float(jnp.abs(got[r, :n_real] - want[0, :n_real]).max())
        assert err <= 1e-5, (r, err)
    assert float(jnp.abs(got[3]).max()) == 0.0      # dead row: exact zero


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_same_sequence_two_chunks_one_launch(impl, rng):
    """Two chunks of the SAME sequence packed into one batch (ordered
    offsets) must together equal the rows of one monolithic causal
    attention - the property that lets the engine fold a whole tick's
    plan, including multi-chunk requests, into one launch."""
    S, Hq, Hkv, D, ps = 32, 4, 2, 16, 8
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, S, Hq, D))
    k = jax.random.normal(ks[1], (1, S, Hkv, D))
    v = jax.random.normal(ks[2], (1, S, Hkv, D))
    want = ops.flash_attention(q, k, v, causal=True, impl="ref")
    n_pages = S // ps
    k_pages = jnp.zeros((n_pages + 1, ps, Hkv, D))
    v_pages = jnp.zeros((n_pages + 1, ps, Hkv, D))
    for j in range(n_pages):
        k_pages = k_pages.at[j + 1].set(k[0, j * ps:(j + 1) * ps])
        v_pages = v_pages.at[j + 1].set(v[0, j * ps:(j + 1) * ps])
    # the pages are head-major: (Hkv, P, ps, D)
    pool = stack_pool(jnp.moveaxis(k_pages, 2, 0), jnp.moveaxis(v_pages, 2, 0))
    row = np.arange(1, n_pages + 1, dtype=np.int32)
    tables = np.stack([row, row])
    half = S // 2
    qb = jnp.stack([q[0, :half], q[0, half:]])
    got = ops.batched_paged_prefill_attention(
        qb, pool, LAYER, jnp.asarray(tables),
        jnp.asarray([0, half], jnp.int32),
        jnp.asarray([half, S], jnp.int32), impl=impl)
    err = float(jnp.abs(jnp.concatenate([got[0], got[1]])[None] - want).max())
    assert err <= 1e-5


# ===========================================================================
# the pack step
# ===========================================================================

def test_bucket_rows_powers_of_two():
    assert [bucket_rows(k) for k in (1, 2, 3, 4, 5, 7, 8, 9)] \
        == [1, 2, 4, 4, 8, 8, 8, 16]


def test_pack_chunks_layout():
    scfg = ServeConfig(max_batch=4, prefill_chunk=8, tick_token_budget=64,
                      paged=True, page_size=8)
    sched = TokenBudgetScheduler(scfg)
    a = Request(1, list(range(100, 120)), 4)   # 20 tokens
    b = Request(2, list(range(200, 209)), 4)   # 9 tokens
    tasks = [ChunkTask(a, 0, 0, 8), ChunkTask(b, 1, 0, 8),
             ChunkTask(b, 1, 8, 1)]            # b's final 1-token tail
    pack = sched.pack_chunks(tasks)
    assert pack.k_real == 3
    assert pack.tokens.shape == (4, 8)         # 3 tasks -> bucket of 4
    assert pack.tokens[0].tolist() == list(range(100, 108))
    assert pack.tokens[2].tolist() == [208, 0, 0, 0, 0, 0, 0, 0]
    assert pack.offsets.tolist() == [0, 0, 8, 0]
    assert pack.true_lens.tolist() == [8, 8, 9, 0]
    # only b's tail COMPLETES a prompt; everything else is the sentinel
    assert pack.final_slots.tolist() == [4, 4, 1, 4]
    assert pack.row_slots.tolist() == [0, 1, 1, -1]


# ===========================================================================
# engine parity: the batched one-launch tick == the dense engine
# ===========================================================================

@pytest.mark.parametrize("prefix_cache", [False, True])
def test_batched_matches_sequential_mixed_traffic(prefix_cache, model_f32):
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size)
    dense, _ = _serve(m, params, _base(paged=False), prompts)
    bat, eng = _serve(m, params, _base(prefix_cache=prefix_cache), prompts)
    assert bat == dense
    st = eng.stats()
    assert st["packs_run"] > 0
    assert st["chunks_run"] > st["packs_run"]   # batching actually batched
    assert st["max_tick_tokens"] <= 32
    assert st["jit_calls_per_tick_max"] <= 2
    assert st["host_syncs_per_tick_max"] <= 1


def test_batched_matches_sequential_windowed_model(rng):
    """Local/global sliding-window layers (gemma3 pattern): the per-row
    window mask must survive the batching."""
    cfg = get_smoke_config("gemma3-4b").replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(rng)
    prompts = _mixed_prompts(cfg.vocab_size, lens=(40, 9, 100))
    dense, _ = _serve(m, params, _base(max_batch=2, paged=False), prompts)
    bat, _ = _serve(m, params, _base(max_batch=2), prompts)
    assert bat == dense


def test_k1_degenerate_case(model_f32):
    """One slot, one request: the batched path runs K=1 packs and must
    still match the dense engine."""
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(70,))
    dense, _ = _serve(m, params, _base(max_batch=1, paged=False), prompts)
    # budget 17 = max_batch + prefill_chunk: exactly one chunk per tick
    bat, eng = _serve(m, params, _base(max_batch=1, tick_token_budget=17),
                      prompts)
    assert bat == dense
    assert eng.stats()["packs_run"] == eng.stats()["chunks_run"]  # all K=1


def test_batched_stop_tokens_and_temperature(model_f32):
    """Stop tokens finish the same tick through the deferred emission, and
    seeded temperature sampling stays reproducible through the fused
    device-side sampler."""
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(20, 33))
    ref, _ = _serve(m, params, _base(max_new_tokens=12), prompts)
    stop = ref[min(ref)][4]
    out, eng = _serve(m, params, _base(max_new_tokens=12), prompts,
                      stop_tokens=[stop])
    for uid, toks in out.items():
        full = ref[uid]
        if stop in full:
            assert toks == full[:full.index(stop) + 1]
        else:
            assert toks == full
    assert eng.allocator.used_pages == 0
    kw = dict(temperature=0.7, seed=11, max_new_tokens=10)
    t1, _ = _serve(m, params, _base(**kw), prompts)
    t2, _ = _serve(m, params, _base(**kw), prompts)
    assert t1 == t2
    assert t1 != ref    # sampling actually happened


def test_work_clock_stats_match_sequential(model_f32):
    """Deferred emission must not shift the work-clock accounting: a
    request's first token is stamped with the work clock as it stands
    right after its OWN final chunk in plan order (what one launch per
    chunk would stamp), not after the whole pack; every later token with
    the clock at the end of the tick that emitted it."""
    m, params = model_f32
    eng = ServeEngine(m, params, _base())
    first, early_finals = {}, 0
    run_pack = eng._run_chunk_batch

    def spy(tasks):
        nonlocal early_finals
        w = eng.sched.work_clock
        for k, t in enumerate(tasks):
            w += t.length
            if t.start + t.length >= len(t.req.target):
                first[t.req.uid] = w
                early_finals += k < len(tasks) - 1
        return run_pack(tasks)

    eng._run_chunk_batch = spy
    for p in _mixed_prompts(m.cfg.vocab_size):
        eng.submit(p)
    tick_end = []
    while eng.queue or any(s is not None for s in eng.slots):
        eng.tick()
        tick_end.append(eng.sched.work_clock)
    done = eng.sched.finished
    assert len(done) == len(MIXED_LENS)
    assert {r.uid: r.token_work[0] for r in done} == first
    assert early_finals >= 1     # a final chunk with work after it
    for r in done:
        assert r.token_work[1:] == [tick_end[t] for t in r.token_tick[1:]]


# ===========================================================================
# dispatch accounting: the acceptance criterion
# ===========================================================================

def test_one_launch_per_busy_tick(model_f32):
    """A steady-state tick with K prefilling + M decoding requests issues
    exactly ONE batched prefill launch + ONE decode launch + ONE
    device->host transfer; no tick ever exceeds that."""
    m, params = model_f32
    eng = ServeEngine(m, params, _base(max_batch=3, max_new_tokens=40,
                                       tick_token_budget=35))
    eng.submit([5, 7, 11, 13])
    while not any(r is not None and r.state is RequestState.DECODING
                  for r in eng.slots):
        eng.tick()
    eng.submit(list(range(1, 161)))            # 10 chunks of 16
    eng.submit(list(range(1, 81)))             # 5 chunks of 16
    busy = 0
    while eng.queue or any(r is not None
                           and r.state is RequestState.PREFILLING
                           for r in eng.slots):
        eng.tick()
        t = eng.tm.ticks[-1]
        if t.n_chunk_tasks and t.n_decode:
            busy += 1
            assert t.jit_calls == 2, t
            assert t.host_syncs == 1, t
        assert t.jit_calls <= 2 and t.host_syncs <= 1
    assert busy >= 3          # the steady-state shape really occurred
    eng.run_until_done(max_ticks=10_000)
    assert all(t.jit_calls <= 2 and t.host_syncs <= 1 for t in eng.tm.ticks)


def test_monolithic_tick_single_sync(model_f32):
    """The dense engine's tick: its decode phase is one fused launch
    + one device->host transfer, not per-slot int() syncs."""
    m, params = model_f32
    eng = ServeEngine(m, params, _base(paged=False, max_new_tokens=12))
    for p in _mixed_prompts(m.cfg.vocab_size, lens=(12, 20, 9)):
        eng.submit(p)
    eng.tick()                                  # admissions + first decode
    for _ in range(4):                          # pure decode ticks
        eng.tick()
        t = eng.tm.ticks[-1]
        assert t.n_decode == 3
        assert t.jit_calls == 1 and t.host_syncs == 1, t
    eng.run_until_done(max_ticks=10_000)


# ===========================================================================
# recompile guard: steady-state ticks compile nothing
# ===========================================================================

class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.compiles = []

    def emit(self, record):
        msg = record.getMessage()
        if "Finished XLA compilation" in msg:
            self.compiles.append(msg)


def test_steady_state_tick_zero_recompiles(model_f32):
    """With jax.log_compiles on, warmed-up ticks (same K bucket, same
    shapes) must trigger ZERO new XLA compilations - the compile-cache
    guard that keeps the one-launch tick actually one launch."""
    m, params = model_f32
    eng = ServeEngine(m, params, _base(max_batch=2, max_new_tokens=60,
                                       tick_token_budget=18))
    eng.submit([5, 7, 11, 13])
    while not any(r is not None and r.state is RequestState.DECODING
                  for r in eng.slots):
        eng.tick()
    eng.submit(list(range(1, 193)))            # 12 chunks of 16
    for _ in range(4):                         # warm the K=1 pack + decode
        eng.tick()
    assert any(r is not None and r.state is RequestState.PREFILLING
               for r in eng.slots)             # still mid-prefill: steady
    handler = _CompileCounter()
    loggers = [logging.getLogger("jax._src.dispatch"),
               logging.getLogger("jax._src.interpreters.pxla")]
    cache0 = eng.compile_cache_size()
    for lg in loggers:
        lg.addHandler(handler)
    try:
        with jax.log_compiles(True):
            for _ in range(5):                 # steady-state ticks
                eng.tick()
    finally:
        for lg in loggers:
            lg.removeHandler(handler)
    assert handler.compiles == []
    assert eng.compile_cache_size() == cache0
    assert all(r is not None for r in eng.slots)   # nothing finished: the
    eng.run_until_done(max_ticks=10_000)           # ticks were truly steady


# ===========================================================================
# hypothesis: batched == dense over random packings / bucket sizes
# ===========================================================================

def test_property_random_packings(model_f32):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(28, 9, 60))
    dense, _ = _serve(m, params, _base(max_batch=2, paged=False), prompts)

    @settings(max_examples=8, deadline=None)
    @given(chunk_mult=st.integers(1, 4), extra=st.integers(0, 40),
           policy=st.sampled_from(["fifo", "sjf"]))
    def check(chunk_mult, extra, policy):
        chunk = 8 * chunk_mult
        budget = 2 + chunk + extra
        out, eng = _serve(
            m, params,
            _base(max_batch=2, prefill_chunk=chunk,
                  tick_token_budget=budget, admission_policy=policy),
            prompts)
        assert out == dense
        st_ = eng.stats()
        assert st_["max_tick_tokens"] <= budget
        assert st_["jit_calls_per_tick_max"] <= 2
        assert st_["host_syncs_per_tick_max"] <= 1
        assert eng.prefill_tokens == sum(len(p) for p in prompts)
        assert eng.allocator.used_pages == 0

    check()
