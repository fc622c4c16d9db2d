"""Token-budget scheduler: paged chunked prefill against the dense engine
on mixed traffic, budget accounting invariants, decode starvation,
lifecycle, admission policy, temperature plumbing, stop tokens, mid-prompt
chunk kernel parity, and a hypothesis property test over random budgets /
chunk sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.kernels import ops
from repro.models import build_model
from repro.serve import ServeEngine, RequestState, TokenBudgetScheduler
from repro.serve.scheduler import Request

# shared traffic-replay harness (tests/traffic.py): seeded generators +
# the serve loop; MIXED_LENS is the acceptance-shape mixed traffic
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
                page_size=8, num_pages=3 * 29 + 1)
    base.update(over)
    return ServeConfig(**base)


# ===========================================================================
# parity: chunked scheduling must produce byte-identical greedy outputs
# to the dense engine (the independent reference)
# ===========================================================================

@pytest.mark.parametrize("prefix_cache", [False, True])
def test_chunked_matches_monolithic_mixed_traffic(prefix_cache, model_f32):
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size)
    dense, _ = _serve(m, params, _base(paged=False), prompts)
    chunked, eng = _serve(
        m, params, _base(prefix_cache=prefix_cache,
                         prefill_chunk=16, tick_token_budget=32), prompts)
    assert dense == chunked
    assert eng.allocator.used_pages == 0 if not prefix_cache \
        else eng.allocator.live_pages() == 0
    st = eng.stats()
    assert st["chunks_run"] > len(prompts)        # long prompts chunked
    assert st["max_tick_tokens"] <= 32            # budget is a hard ceiling


def test_chunked_matches_monolithic_windowed_model(rng):
    """Local/global sliding-window layers (gemma3 pattern) through the
    chunked path: the offset-causal kernel's window mask must compose
    across chunk boundaries."""
    cfg = get_smoke_config("gemma3-4b").replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(rng)
    prompts = _mixed_prompts(cfg.vocab_size, lens=(40, 9, 100))
    dense, _ = _serve(m, params, _base(max_batch=2, paged=False), prompts)
    chunked, _ = _serve(m, params,
                        _base(max_batch=2, prefill_chunk=16,
                              tick_token_budget=32), prompts)
    assert dense == chunked


def test_prefix_cache_composes_with_chunking(model_f32):
    """Warm request publishes its prompt pages; followers skip the cached
    prefix and chunk-prefill only the remainder."""
    m, params = model_f32
    rng = np.random.default_rng(3)
    shared = rng.integers(1, m.cfg.vocab_size, size=64).tolist()
    tails = [rng.integers(1, m.cfg.vocab_size, size=24).tolist()
             for _ in range(3)]
    prompts = [shared + t for t in tails] + [shared]   # last: full cover
    scfg_off = _base(prefill_chunk=16, tick_token_budget=32)
    scfg_on = _base(prefix_cache=True, prefill_chunk=16,
                    tick_token_budget=32)

    def run(scfg):
        eng = ServeEngine(m, params, scfg)
        out = {}
        for wave in ([prompts[0]], prompts[1:]):   # warmup, then followers
            for p in wave:
                eng.submit(p)
            for r in eng.run_until_done(max_ticks=50_000):
                out[r.uid] = r.out_tokens
        return out, eng

    out_off, _ = run(scfg_off)
    out_on, eng = run(scfg_on)
    assert out_on == out_off
    assert eng.prefix_hit_tokens > 0
    assert eng.prefill_tokens < sum(len(p) for p in prompts)
    eng.prefix.check_invariants()


# ===========================================================================
# budget accounting + starvation
# ===========================================================================

def test_budget_accounting_invariants(model_f32):
    """No tick may exceed tick_token_budget, decode slots always consume
    their token, and prefill chunks are governed by prefill_chunk."""
    m, params = model_f32
    budget, chunk = 24, 8
    eng = ServeEngine(m, params, _base(prefill_chunk=chunk,
                                       tick_token_budget=budget))
    for p in _mixed_prompts(m.cfg.vocab_size):
        eng.submit(p)
    eng.run_until_done(max_ticks=50_000)
    assert eng.tick_log, "no ticks recorded"
    for decode_toks, prefill_toks in eng.tick_log:
        assert decode_toks + prefill_toks <= budget
        assert 0 <= decode_toks <= eng.scfg.max_batch
    # every prompt longer than one chunk was split into multiple chunks
    n_long = sum(1 for n in MIXED_LENS if n > chunk)
    assert eng.sched.chunks_run >= n_long + sum(
        1 for n in MIXED_LENS if n <= chunk)
    # total work conserved: every prompt token computed exactly once
    assert eng.prefill_tokens == sum(MIXED_LENS)


def test_decode_never_starves_behind_long_prefill(model_f32):
    """The acceptance property: while a long prompt streams in chunk by
    chunk, every already-decoding slot still produces exactly one token
    per tick (no request-level pipeline bubble)."""
    m, params = model_f32
    eng = ServeEngine(m, params,
                      _base(max_batch=2, prefill_chunk=8,
                            tick_token_budget=16, max_new_tokens=40))
    short = eng.submit([5, 7, 11, 13])
    # let the short request reach DECODING
    while not any(r is not None and r.state is RequestState.DECODING
                  for r in eng.slots):
        eng.tick()
    long_uid = eng.submit(list(range(1, 161)))     # 20 chunks of 8
    saw_prefilling = 0
    while True:
        long_req = next((r for r in list(eng.slots) + eng.queue
                         if r is not None and r.uid == long_uid), None)
        short_req = next((r for r in eng.slots
                          if r is not None and r.uid == short), None)
        if long_req is None or long_req.state is not RequestState.PREFILLING:
            if saw_prefilling:
                break
        if short_req is None:
            break
        before = len(short_req.out_tokens)
        eng.tick()
        if long_req is not None \
                and long_req.state is RequestState.PREFILLING:
            saw_prefilling += 1
            assert len(short_req.out_tokens) == before + 1, \
                "decode slot stalled behind a streaming prefill"
    assert saw_prefilling >= 5    # the long prompt really did stream in


def test_long_prefill_never_starved_by_short_stream(model_f32):
    """The other side of shortest-remaining-first: a sustained stream of
    short newcomers must not stop a long prompt from advancing - the
    oldest prefilling request is guaranteed one chunk every tick."""
    m, params = model_f32
    eng = ServeEngine(m, params,
                      _base(max_batch=4, prefill_chunk=8,
                            tick_token_budget=20, max_new_tokens=2))
    long_uid = eng.submit(list(range(1, 129)))     # 16 chunks of 8
    eng.tick()
    long_req = next(r for r in eng.slots if r is not None)
    while long_req.state is RequestState.PREFILLING:
        eng.submit([1, 2, 3, 4, 5])                # newcomer every tick
        before = long_req.prefill_pos
        eng.tick()
        assert long_req.prefill_pos > before, \
            "oldest prefilling request starved by newcomers"
    assert long_req.uid == long_uid
    eng.run_until_done(max_ticks=10_000)


def test_lifecycle_states(model_f32):
    m, params = model_f32
    eng = ServeEngine(m, params,
                      _base(max_batch=1, prefill_chunk=8,
                            tick_token_budget=9, max_new_tokens=2))
    uid = eng.submit(list(range(1, 33)))           # 4 chunks
    req = eng.queue[0]
    assert req.state is RequestState.QUEUED and req.uid == uid
    eng.tick()
    assert req.state is RequestState.PREFILLING
    assert 0 < req.prefill_pos < len(req.prompt)
    while req.state is RequestState.PREFILLING:
        eng.tick()
    assert req.state is RequestState.DECODING
    assert req.out_tokens and req.prefill_pos == len(req.prompt)
    done = eng.run_until_done()
    assert req.state is RequestState.DONE and req.done
    assert req in done and req.finish_reason == "length"
    # latency accounting recorded for every emitted token
    assert len(req.token_work) == len(req.out_tokens)
    assert req.ttft_work() > 0 and len(req.tbt_work()) == 1


def test_chunked_lowers_stalls_and_short_ttft(model_f32):
    """The acceptance criterion at test scale: on a wave trace (a long
    prompt arriving at the head of each wave with shorts behind it while
    earlier requests decode), 16-token chunks at budget 40 lower the p95
    per-token tick-work stall (the deterministic TBT bubble) and the p95
    TTFT of short prompts against whole-prompt chunks - with
    byte-identical greedy outputs."""
    m, params = model_f32
    rng = np.random.default_rng(1)
    lens = (224, 32, 16)                 # each wave: long first, shorts behind
    arrivals = []
    for w in range(2):
        for n in lens:
            arrivals.append((w * 3, rng.integers(
                1, m.cfg.vocab_size, size=n).tolist()))

    def run(scfg):
        eng = ServeEngine(m, params, scfg)
        pending = list(arrivals)
        tick, done = 0, []
        while pending or eng.queue or any(s is not None for s in eng.slots):
            while pending and pending[0][0] <= tick:
                eng.submit(pending.pop(0)[1])
            done.extend(eng.tick())
            tick += 1
            assert tick < 10_000
        outs = {r.uid: r.out_tokens for r in done}
        shorts = [r.ttft_work() for r in done if len(r.prompt) < max(lens)]
        st = eng.stats()
        return outs, st["stall_work_p95"], float(np.percentile(shorts, 95))

    base = dict(max_batch=6, max_seq=256, max_new_tokens=8, paged=True,
                page_size=8, num_pages=6 * 29 + 1)
    # whole-prompt chunks: the longest prompt is one chunk, and the budget
    # resolves to max_batch + prefill_chunk
    whole_out, whole_stall, whole_ttft = run(
        ServeConfig(**base, prefill_chunk=max(lens)))
    # budget fits the oldest request's guaranteed chunk plus a
    # shortest-remaining-first chunk, so shorts drain past the long
    chunk_out, chunk_stall, chunk_ttft = run(
        ServeConfig(**base, prefill_chunk=16, tick_token_budget=40))
    assert chunk_out == whole_out
    assert chunk_stall <= 40 < whole_stall
    assert chunk_stall < whole_stall
    assert chunk_ttft < whole_ttft


# ===========================================================================
# admission policy
# ===========================================================================

@pytest.mark.parametrize("policy,first", [("fifo", "long"),
                                          ("sjf", "short")])
def test_admission_policy_order(policy, first, model_f32):
    m, params = model_f32
    eng = ServeEngine(m, params,
                      _base(max_batch=1, admission_policy=policy))
    uid_long = eng.submit(list(range(1, 100)))
    uid_short = eng.submit([3, 1, 4])
    done = eng.run_until_done()
    order = [r.uid for r in done]
    expect = [uid_long, uid_short] if first == "long" \
        else [uid_short, uid_long]
    assert order == expect


def test_scheduler_plan_chunks_unit():
    """Pure planning: shortest-remaining-first chunk fill under the
    budget, round-robin passes until the budget is spent."""
    scfg = ServeConfig(max_batch=2, prefill_chunk=8, tick_token_budget=64,
                       paged=True, page_size=8)
    sched = TokenBudgetScheduler(scfg)
    a = Request(1, list(range(20)), 4)   # 20 tokens: chunks 8, 8, 4
    b = Request(2, list(range(9)), 4)    # 9 tokens: chunks 8, 1
    tasks = sched.plan_chunks([(0, a), (1, b)], budget=25)
    # a is OLDEST (guaranteed floor chunk), then shortest-remaining-first:
    # pass 1: a[0:8], b[0:8]; pass 2: a[8:16], b's 1-token tail fits last
    got = [(t.req.uid, t.start, t.length) for t in tasks]
    assert got == [(1, 0, 8), (2, 0, 8), (1, 8, 8), (2, 8, 1)]
    assert sum(t.length for t in tasks) == 25
    # a budget too small for any whole chunk schedules nothing
    assert sched.plan_chunks([(0, Request(3, list(range(20)), 4))], 7) == []


def test_config_validation():
    bad = [dict(paged=True, tick_token_budget=512,
                prefill_chunk=13, page_size=8),                # misaligned
           dict(paged=True, prefill_chunk=8, page_size=8,
                max_batch=8, tick_token_budget=8),             # starves
           dict(admission_policy="lifo"),
           dict(temperature=-1.0)]
    for kw in bad:
        with pytest.raises(ValueError):
            ServeConfig(**kw).validate()
    # the monolithic and sequential-chunk paths are gone
    for flag in ("chunked", "batched"):
        for paged in (True, False):
            with pytest.raises(ValueError, match=f"{flag}=False .*removed"):
                ServeConfig(paged=paged, **{flag: False}).validate()
    ServeConfig(paged=True, page_size=8, prefill_chunk=16,
                max_batch=4, tick_token_budget=20).validate()
    # a paged budget of 0 resolves to the least budget that validates
    got = ServeConfig(paged=True, page_size=8, prefill_chunk=16,
                      max_batch=4).validate()
    assert got.tick_token_budget == 4 + 16
    assert ServeConfig().validate().tick_token_budget == 0     # dense


# ===========================================================================
# SLO-driven priority aging
# ===========================================================================

def test_priority_aging_reorders_admission_unit():
    """Pure queue ordering: with priority_aging on, a queued request gains
    +1 effective priority per priority_age_tokens of work-clock age, so an
    old low-priority request outranks a freshly submitted higher class -
    and the boost is counted exactly when the aged admission happens."""
    def fresh(aging):
        return TokenBudgetScheduler(ServeConfig(
            max_batch=1, paged=True, page_size=8,
            priority_aging=aging, priority_age_tokens=10))

    s = fresh(True)
    lo = Request(1, list(range(8)), 2, priority=0)
    s.submit(lo)
    s.note_work(60)                     # lo ages: effective 0 + 60//10 = 6
    hi = Request(2, list(range(8)), 2, priority=5)
    s.submit(hi)                        # fresh: age 0, effective 5
    assert s.effective_priority(lo) == 6
    assert s.effective_priority(hi) == 5
    assert s.peek() is lo
    s.pop(lo)
    assert s.priority_boosts == 1       # admitted above its base class
    s.pop(hi)
    assert s.priority_boosts == 1       # hi admitted at base priority
    # same shape with aging off: the higher class wins, nothing boosts
    s = fresh(False)
    lo = Request(1, list(range(8)), 2, priority=0)
    s.submit(lo)
    s.note_work(60)
    hi = Request(2, list(range(8)), 2, priority=5)
    s.submit(hi)
    assert s.effective_priority(lo) == 0
    assert s.peek() is hi
    s.pop(hi)
    assert s.priority_boosts == 0


def test_priority_aging_bounds_starvation(model_f32):
    """The SLO property end to end: under a sustained stream of fresh
    high-priority arrivals (each starting at age 0 - simultaneous
    submissions age in lockstep, so only a STAGGERED stream exposes
    starvation), a low-priority request's work-clock TTFT is bounded by
    gap * priority_age_tokens plus a couple of service times.  With aging
    off the same trace serves the low request dead last."""
    m, params = model_f32
    rng = np.random.default_rng(9)
    V = m.cfg.vocab_size
    # each request is 16 prompt + 2 generated = 18 work tokens and takes
    # 2 ticks at max_batch=1; one high arrival per 2 ticks saturates the
    # engine, so the low request waits on priority alone
    arrivals = [0, 0] + [2 * i for i in range(1, 23)]      # 24 highs

    def run(priority_aging):
        eng = ServeEngine(m, params, _base(
            max_batch=1, prefill_chunk=16,
            tick_token_budget=17, max_new_tokens=2,
            priority_aging=priority_aging, priority_age_tokens=32))
        low = eng.submit(rng.integers(1, V, size=16).tolist(), priority=0)
        pending = list(arrivals)
        tick, done = 0, []
        while pending or eng.queue or any(s is not None for s in eng.slots):
            while pending and pending[0] <= tick:
                pending.pop(0)
                eng.submit(rng.integers(1, V, size=16).tolist(), priority=5)
            done.extend(eng.tick())
            tick += 1
            assert tick < 10_000
        order = [r.uid for r in done]
        low_req = next(r for r in done if r.uid == low)
        return order, low_req.ttft_work(), eng

    gap, age_tokens, per_req_work = 5, 32, 18
    bound = gap * age_tokens + 3 * per_req_work    # admission + drain slack
    order_on, ttft_on, eng_on = run(True)
    order_off, ttft_off, eng_off = run(False)
    # aging off: every high class request is served first - unbounded wait
    assert order_off[-1] == min(order_off)         # low (uid 1) dead last
    assert ttft_off > bound
    assert eng_off.sched.priority_boosts == 0
    # aging on: the low request jumps the stream inside the bound
    assert order_on.index(min(order_on)) < len(order_on) - 8
    assert ttft_on <= bound, (ttft_on, bound)
    assert eng_on.sched.priority_boosts >= 1
    assert eng_on.stats()["priority_boosts"] >= 1


# ===========================================================================
# temperature plumbing (bugfix: ServeConfig.temperature was ignored)
# ===========================================================================

def test_temperature_zero_stays_greedy(model_f32):
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(12, 30))
    greedy, _ = _serve(m, params, _base(max_batch=2), prompts)
    explicit, _ = _serve(m, params, _base(max_batch=2, temperature=0.0),
                         prompts)
    assert greedy == explicit


def test_temperature_seeded_is_reproducible(model_f32):
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(12, 30))
    kw = dict(max_batch=2, max_new_tokens=16, temperature=0.9)
    out1, _ = _serve(m, params, _base(seed=7, **kw), prompts)
    out2, _ = _serve(m, params, _base(seed=7, **kw), prompts)
    assert out1 == out2                       # same seed, same trace
    out3, _ = _serve(m, params, _base(seed=8, **kw), prompts)
    assert out1 != out3                       # sampling actually happens
    greedy, _ = _serve(m, params, _base(max_batch=2, max_new_tokens=16),
                       prompts)
    assert out1 != greedy


def test_temperature_chunked_reproducible(model_f32):
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(40, 9))
    kw = dict(max_batch=2, temperature=0.7, seed=11,
              prefill_chunk=8, tick_token_budget=16)
    out1, _ = _serve(m, params, _base(**kw), prompts)
    out2, _ = _serve(m, params, _base(**kw), prompts)
    assert out1 == out2


# ===========================================================================
# stop tokens
# ===========================================================================

def test_stop_tokens_finish_early(model_f32):
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(20, 33))
    kw = dict(max_batch=2, max_new_tokens=12)
    ref, _ = _serve(m, params, _base(**kw), prompts)
    # pick a token the first request actually generates mid-stream
    uid0 = min(ref)
    stop = ref[uid0][4]
    out, eng = _serve(m, params, _base(**kw), prompts, stop_tokens=[stop])
    for uid, toks in out.items():
        full = ref[uid]
        if stop in full:
            cut = full.index(stop) + 1
            assert toks == full[:cut]          # truncated AT the stop token
        else:
            assert toks == full
    assert any(r.finish_reason == "stop" for r in eng.sched.finished)
    assert eng.allocator.used_pages == 0       # pages freed on early finish


def test_eos_id_config_equivalent_to_stop_tokens(model_f32):
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size, lens=(20,))
    ref, _ = _serve(m, params, _base(max_new_tokens=12), prompts)
    stop = ref[min(ref)][2]
    via_cfg, _ = _serve(m, params, _base(max_new_tokens=12, eos_id=stop),
                        prompts)
    via_submit, _ = _serve(m, params, _base(max_new_tokens=12), prompts,
                           stop_tokens=[stop])
    assert via_cfg == via_submit


def test_stop_tokens_publish_prefix_pages(model_f32):
    """A stop-token finish must still publish prompt pages into the
    prefix cache that tick (not leak or skip them)."""
    m, params = model_f32
    eng = ServeEngine(m, params, _base(prefix_cache=True, max_new_tokens=12))
    prompt = list(range(1, 25))
    eng.submit(prompt)
    ref = eng.run_until_done()
    stop = ref[0].out_tokens[1]
    eng2 = ServeEngine(m, params, _base(prefix_cache=True, eos_id=stop,
                                        max_new_tokens=12))
    eng2.submit(prompt)
    done = eng2.run_until_done()
    assert done[0].finish_reason == "stop"
    assert eng2.prefix.cached_pages == len(prompt) // 8
    assert eng2.prefix.match(prompt)           # prefix reusable immediately
    eng2.prefix.check_invariants()


def test_finish_at_admission_does_not_corrupt_published_pages(model_f32):
    """Regression: a request that finishes AT admission (its first sampled
    token is a stop token / max_new_tokens == 1) publishes its prompt
    pages the same tick; the batched decode that follows must not write
    its lane's garbage K/V into the just-published page through a stale
    device block table.  A follower matching the prefix must match the
    cache-off reference exactly."""
    m, params = model_f32
    prompt = list(range(1, 33))                    # 4 full pages of 8
    follower = prompt + [7, 3]

    def run(prefix_cache, first_max_new):
        eng = ServeEngine(m, params, _base(prefix_cache=prefix_cache,
                                           max_batch=2, max_new_tokens=8))
        eng.submit([9, 8, 7])                      # keeps a decode in flight
        eng.tick()
        eng.submit(prompt, max_new_tokens=first_max_new)
        eng.tick()      # publisher admits (and may finish) as the LAST
        eng.tick()      # admission of its tick, then the batched decode runs
        uid = eng.submit(follower)
        done = {r.uid: r.out_tokens for r in eng.run_until_done()}
        return done[uid]

    want = run(False, 1)
    assert run(True, 1) == want                    # finish-at-admission
    assert run(True, 8) == want                    # finish during decode

def test_run_until_done_raises_on_exhaustion(model_f32):
    m, params = model_f32
    eng = ServeEngine(m, params, _base(max_new_tokens=30))
    eng.submit(list(range(1, 40)))
    with pytest.raises(RuntimeError, match="in-flight"):
        eng.run_until_done(max_ticks=3)
    # the lenient mode warns and returns the partial trace instead
    eng2 = ServeEngine(m, params, _base(max_new_tokens=30))
    eng2.submit(list(range(1, 40)))
    with pytest.warns(UserWarning, match="exhausted"):
        done = eng2.run_until_done(max_ticks=3, on_exhaust="return")
    assert done == []


# ===========================================================================
# mid-prompt chunk kernel: pallas (interpret) vs ref oracle vs monolithic
# ===========================================================================

@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("off,s_chunk", [(0, 16), (8, 16), (20, 8),
                                         (28, 4)])
def test_chunk_attention_matches_monolithic(impl, off, s_chunk, rng):
    """A chunk's attention through the block table must equal the same
    rows of one monolithic causal attention - for page-aligned AND
    mid-page chunk starts."""
    S, Hq, Hkv, D, ps = 48, 4, 2, 16, 8
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (1, S, Hq, D))
    k = jax.random.normal(ks[1], (1, S, Hkv, D))
    v = jax.random.normal(ks[2], (1, S, Hkv, D))
    want = ops.flash_attention(q, k, v, causal=True,
                               impl="ref")[:, off:off + s_chunk]
    # scatter ALL K/V (prefix + chunk) into a shuffled page pool
    n_pages = S // ps
    perm = np.random.default_rng(0).permutation(np.arange(1, n_pages + 1))
    k_pages = jnp.zeros((n_pages + 1, ps, Hkv, D))
    v_pages = jnp.zeros((n_pages + 1, ps, Hkv, D))
    for j in range(n_pages):
        k_pages = k_pages.at[perm[j]].set(k[0, j * ps:(j + 1) * ps])
        v_pages = v_pages.at[perm[j]].set(v[0, j * ps:(j + 1) * ps])
    # the pages are head-major: (Hkv, P, ps, D)
    pool = stack_pool(jnp.moveaxis(k_pages, 2, 0), jnp.moveaxis(v_pages, 2, 0))
    got = ops.paged_prefill_attention(
        q[:, off:off + s_chunk], pool, LAYER,
        jnp.asarray(perm, jnp.int32), off, impl=impl)
    assert float(jnp.abs(got - want).max()) <= 1e-5


def test_model_chunked_prefill_composes_exactly(model_f32):
    """Composing K=1 Model.prefill_chunks calls left to right must
    reproduce one whole-prompt row: identical final logits, identical
    decode continuation."""
    m, params = model_f32
    toks = np.random.default_rng(5).integers(
        1, m.cfg.vocab_size, size=40).tolist()
    ps, n_pages = 8, 8
    row = np.zeros(8, np.int32)                      # 40 tokens = 5 pages
    row[:5] = np.arange(1, 6)
    page_rows = jnp.asarray([row])

    def fresh_cache():
        c = m.init_cache(1, 64, page_size=ps, num_pages=n_pages)
        return dict(c, block_table=page_rows)

    def chunk(start, n):
        return {"tokens": jnp.asarray([toks[start:start + n]], jnp.int32),
                "offset": jnp.asarray([start], jnp.int32),
                "true_lens": jnp.asarray([start + n], jnp.int32)}

    logits_whole, cache_whole, _ = m.prefill_chunks(
        params, chunk(0, 40), fresh_cache(), page_rows)
    cache = fresh_cache()
    for start, n in ((0, 16), (16, 16), (32, 8)):
        logits, cache, cursor = m.prefill_chunks(params, chunk(start, n),
                                                 cache, page_rows)
        assert int(cursor[0]) == start + n
    assert float(jnp.abs(logits - logits_whole).max()) <= 1e-4
    assert float(jnp.abs(cache["kv_pages"]
                         - cache_whole["kv_pages"]).max()) <= 1e-4
    d1, _ = m.decode_step(params, jnp.asarray([[7]]), jnp.asarray([40]),
                          cache_whole)
    d2, _ = m.decode_step(params, jnp.asarray([[7]]), jnp.asarray([40]),
                          cache)
    assert float(jnp.abs(d1 - d2).max()) <= 1e-4


# ===========================================================================
# hypothesis: parity + budget invariants over random budgets / chunk sizes
# ===========================================================================

def _hypothesis_or_skip():
    return pytest.importorskip("hypothesis")


def test_property_random_budget_and_chunk(model_f32):
    _hypothesis_or_skip()
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
        assert eng.stats()["max_tick_tokens"] <= budget
        assert eng.prefill_tokens == sum(len(p) for p in prompts)
        assert eng.allocator.used_pages == 0

    check()


# ===========================================================================
# deadlines: submit-time validation and work-clock expiry
# ===========================================================================

def test_submit_deadline_and_retry_validation(model_f32):
    """Every never-servable deadline/retry combination fails AT SUBMIT
    with a clear error - not deep inside prefill or the allocator."""
    m, params = model_f32
    eng = ServeEngine(m, params, _base())
    with pytest.raises(ValueError, match="deadline"):
        eng.submit([1, 2, 3], deadline=0)
    with pytest.raises(ValueError, match="deadline"):
        eng.submit([1, 2, 3], deadline=-5)
    with pytest.raises(ValueError, match="minimum prefill work"):
        # the prompt alone costs 3 work tokens of prefill: a deadline at
        # or below that is a guaranteed timeout
        eng.submit([1, 2, 3], deadline=3)
    with pytest.raises(ValueError, match="max_retries"):
        eng.submit([1, 2, 3], max_retries=-1)
    # the boundary case is accepted: one token CAN land in time
    uid = eng.submit([1, 2, 3], deadline=4, max_retries=0)
    assert eng.sched.queue[-1].uid == uid
    assert eng.sched.queue[-1].deadline_tokens == 4


def test_default_deadline_tokens_config(model_f32):
    """ServeConfig.default_deadline_tokens stamps every submit that does
    not bring its own deadline; 0 means none; negatives are rejected at
    config validation."""
    m, params = model_f32
    with pytest.raises(ValueError, match="default_deadline_tokens"):
        _base(default_deadline_tokens=-1).validate()
    eng = ServeEngine(m, params, _base(default_deadline_tokens=64))
    eng.submit([1, 2, 3])
    assert eng.sched.queue[-1].deadline_tokens == 64
    eng.submit([1, 2, 3], deadline=32)
    assert eng.sched.queue[-1].deadline_tokens == 32
    eng = ServeEngine(m, params, _base())        # default 0 = no deadline
    eng.submit([1, 2, 3])
    assert eng.sched.queue[-1].deadline_tokens is None


def test_deadline_expiry_frees_pages_same_tick(model_f32):
    """A request whose work-clock deadline lands mid-flight goes
    terminal TIMEOUT the very tick it expires - slot and pages freed
    immediately (conservation checked per tick), unrelated traffic
    unharmed, and the engine never hangs."""
    m, params = model_f32
    scfg = _base(prefill_chunk=16, tick_token_budget=32,
                 max_new_tokens=8)
    eng = ServeEngine(m, params, scfg)
    # 100-token prompt, deadline 101: barely above the submit-time floor,
    # but chunked prefill at 32 tokens/tick crosses 101 work tokens long
    # before the first token - a mid-prefill expiry
    doomed = eng.submit(list(range(1, 101)), deadline=101)
    fine = eng.submit(list(range(200, 210)))
    done = eng.run_until_done(max_ticks=1000)
    by_uid = {r.uid: r for r in done}
    assert by_uid[doomed].state is RequestState.TIMEOUT
    assert by_uid[doomed].finish_reason == "timeout"
    assert by_uid[doomed].slot is None
    assert by_uid[fine].state is RequestState.DONE
    assert len(by_uid[fine].out_tokens) == 8
    assert eng.stats()["timeouts"] == 1
    assert eng.allocator.used_pages == 0         # every page came home
    eng.check_invariants()


def test_deadline_expiry_in_queue_never_admits(model_f32):
    """A request that expires while still QUEUED times out from the
    queue - it must never be admitted, never touch a slot or a page."""
    m, params = model_f32
    scfg = _base(max_batch=1, prefill_chunk=16,
                 tick_token_budget=32, max_new_tokens=4)
    eng = ServeEngine(m, params, scfg)
    hog = eng.submit(list(range(1, 80)))          # owns the only slot
    starved = eng.submit(list(range(100, 140)), deadline=41)
    done = eng.run_until_done(max_ticks=1000)
    by_uid = {r.uid: r for r in done}
    assert by_uid[hog].state is RequestState.DONE
    assert by_uid[starved].state is RequestState.TIMEOUT
    assert by_uid[starved].slot is None
    assert by_uid[starved].out_tokens == []
    eng.check_invariants()
    assert eng.allocator.used_pages == 0


def test_deadline_met_is_untouched(model_f32):
    """A generous deadline changes nothing: same outputs as the
    deadline-free run (the sweep is pure bookkeeping until an expiry)."""
    m, params = model_f32
    prompts = _mixed_prompts(m.cfg.vocab_size)
    base_out, _ = _serve(m, params, _base(), prompts)
    eng = ServeEngine(m, params, _base())
    for p in prompts:
        eng.submit(p, deadline=100_000)
    done = eng.run_until_done()
    assert {r.uid: r.out_tokens for r in done} == base_out
    assert eng.stats()["timeouts"] == 0
