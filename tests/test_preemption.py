"""Preemptive, decode-priority scheduling under page-pool pressure.

Covers the tentpole of ISSUE 5: decode-priority budget shaping (the
prefill share of a tick is capped so queued prefill depth cannot inflate
decode TBT), victim preemption when the page pool cannot place a
higher-priority admission (PREFILLING most-recently-admitted first, then
DECODING longest-remaining; equal priority NEVER preempts), the
QUEUED->RESUMING park/resume lifecycle, page-refcount conservation across
preempt/resume, resume-via-prefix-cache page survival, monotone TTFT/TBT
work-clock stamps across a preemption, and the preemption counters in
stats().

Parity methodology: greedy outputs of a preempted run are compared
bit-for-bit against an uninterrupted full-capacity oracle run in the SAME
process.  Three pins make that comparison structural rather than lucky:
engines share jitted steps per model (serve/engine.py _shared_steps);
oracle and pressured runs share num_pages (pressure comes from the
usable_pages capacity cap) so array shapes and compiled executables
match; and max_chunks_per_tick=1 keeps every chunk pack in the K=1
kernel bucket across schedules.  traffic.assert_greedy_equivalent backs
the bit comparison with an epsilon-greedy teacher-forced check, so a
genuine last-ulp argmax tie cannot flake the suite while real KV
corruption (which shifts logits by orders of magnitude more) still fails.
The soak exercises random arrival traffic (mixed lengths, shared
prefixes, priorities, bursts - tests/traffic.py) against a deliberately
tiny usable-page cap, asserting the engine never deadlocks, allocator
invariants hold after EVERY tick, and final outputs bit-match the
full-capacity oracle.
"""
import copy

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.models import build_model
from repro.serve import ServeEngine, RequestState, TokenBudgetScheduler
from repro.serve.scheduler import Request

from traffic import (assert_greedy_equivalent, priority_burst,
                     random_arrivals, replay)

PAGE = 8


@pytest.fixture(scope="module")
def model_f32():
    # float32 keeps greedy argmax ties out of the parity comparisons
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def _cfg(**over):
    # deterministic-replay configuration: num_pages stays FIXED across
    # every engine in this file and pressure comes from usable_pages (a
    # host-side capacity cap), so a pressured run and its full-capacity
    # oracle share identical array shapes - and therefore identical
    # compiled executables; max_chunks_per_tick=1 additionally pins every
    # pack to the K=1 kernel bucket, so every schedule (oracle, pressured,
    # resumed) rebuilds KV through the same executable.  Under those two
    # pins, bit-parity with the oracle is structural, not luck.
    base = dict(max_batch=3, max_seq=256, max_new_tokens=8, paged=True,
                page_size=PAGE, num_pages=200,
                prefill_chunk=16, tick_token_budget=24, preemption=True,
                max_chunks_per_tick=1)
    base.update(over)
    return ServeConfig(**base)


def _replay(model, params, scfg, items):
    eng = ServeEngine(model, params, scfg)
    out, done = replay(eng, copy.deepcopy(items))
    return out, eng


def _assert_parity(model, params, eng, out, oracle):
    """Bit equality against the oracle, with the epsilon-greedy fallback
    for genuine fp near-ties (traffic.assert_greedy_equivalent)."""
    if out != oracle:
        assert_greedy_equivalent(model, params, eng.sched.finished, oracle)


# ===========================================================================
# budget shaper
# ===========================================================================

def test_prefill_budget_unit():
    sched = TokenBudgetScheduler(_cfg(tick_token_budget=40))
    assert sched.prefill_budget(0) == 40
    assert sched.prefill_budget(3) == 37
    shaped = TokenBudgetScheduler(_cfg(tick_token_budget=40,
                                       decode_priority=True,
                                       max_prefill_fraction=0.5))
    assert shaped.prefill_budget(0) == 20          # capped at 0.5 * budget
    assert shaped.prefill_budget(3) == 20
    assert shaped.prefill_budget(39) == 1          # decode always fits first
    assert shaped.prefill_budget(40) == 0


def test_decode_priority_validation():
    with pytest.raises(ValueError, match="decode_priority"):
        ServeConfig(decode_priority=True).validate()
    with pytest.raises(ValueError, match="max_prefill_fraction"):
        _cfg(decode_priority=True, max_prefill_fraction=1.5).validate()
    with pytest.raises(ValueError, match="prefill_chunk"):
        _cfg(decode_priority=True, max_prefill_fraction=0.1,
             tick_token_budget=40).validate()
    with pytest.raises(ValueError, match="preemption"):
        ServeConfig(preemption=True).validate()
    _cfg(decode_priority=True, max_prefill_fraction=0.7).validate()


def test_decode_priority_bounds_decode_tbt(model_f32):
    """The tentpole property at test scale: under a burst of queued long
    prefills, decode-priority shaping caps per-tick work, so the
    work-clock TBT of an in-flight decode is strictly lower than with
    shaping off - with byte-identical greedy outputs."""
    m, params = model_f32
    rng = np.random.default_rng(0)
    short = rng.integers(1, m.cfg.vocab_size, size=8).tolist()
    longs = [rng.integers(1, m.cfg.vocab_size, size=96).tolist()
             for _ in range(3)]

    def run(shaped):
        scfg = _cfg(max_batch=4, max_new_tokens=24, tick_token_budget=52,
                    preemption=False, decode_priority=shaped,
                    max_prefill_fraction=0.5, max_chunks_per_tick=0)
        eng = ServeEngine(m, params, scfg)
        uid = eng.submit(short)
        while not any(r is not None and r.state is RequestState.DECODING
                      for r in eng.slots):
            eng.tick()
        for p in longs:                            # the prefill burst
            eng.submit(p)
        done = eng.run_until_done(max_ticks=10_000)
        eng.check_invariants()
        req = next(r for r in done if r.uid == uid)
        tbt = req.tbt_work()
        outs = {r.uid: r.out_tokens for r in done}
        return outs, max(tbt), float(np.percentile(tbt, 95)), eng

    out_off, max_off, p95_off, _ = run(False)
    out_on, max_on, p95_on, eng = run(True)
    # same requests complete with full budgets either way (shaping changes
    # the schedule, not completion; bit-parity is asserted in the K=1
    # matched-bucket scenarios below)
    assert {u: len(t) for u, t in out_on.items()} \
        == {u: len(t) for u, t in out_off.items()}
    # shaped: a tick carries at most n_decode + 0.5 * budget work
    assert max_on <= 4 + 26
    assert max_off > max_on                        # burst inflated unshaped TBT
    assert p95_on < p95_off
    for d, p in eng.tick_log:
        assert p <= 26                             # prefill share hard-capped


def test_chunk_floor_goes_to_highest_priority_class():
    """The guaranteed-progress chunk goes to the oldest request OF THE
    HIGHEST PRESENT PRIORITY: a high-priority admission (e.g. one that
    just preempted its way in) must not wait out a lower-priority
    neighbor's prefill, while within a class the oldest still wins."""
    sched = TokenBudgetScheduler(_cfg())
    lo = Request(1, list(range(96)), 4, priority=0)
    hi = Request(2, list(range(64)), 4, priority=5)
    tasks = sched.plan_chunks([(0, lo), (1, hi)], budget=16)
    assert [(t.req.uid, t.length) for t in tasks] == [(2, 16)]
    # equal priority: oldest keeps the floor (anti-starvation unchanged)
    hi0 = Request(3, list(range(64)), 4, priority=0)
    tasks = sched.plan_chunks([(0, lo), (1, hi0)], budget=16)
    assert [(t.req.uid, t.length) for t in tasks] == [(1, 16)]


# ===========================================================================
# victim selection + lifecycle
# ===========================================================================

def test_preempt_victim_choice_prefilling_most_recent(model_f32):
    """Two low-priority requests mid-prefill; a high-priority arrival that
    does not fit must shed the MOST RECENTLY admitted one."""
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(usable_pages=28))
    a = eng.submit(list(range(1, 97)))             # 13 pages
    b = eng.submit(list(range(1, 97)))             # 13 pages (3 free left)
    eng.tick()                                     # both admitted, prefilling
    reqs = {r.uid: r for r in eng.slots if r is not None}
    assert reqs[a].state is reqs[b].state is RequestState.PREFILLING
    hi = eng.submit(list(range(1, 65)), priority=5)
    eng.tick()
    assert reqs[b].state is RequestState.RESUMING  # newer admission shed
    assert reqs[a].state is RequestState.PREFILLING
    assert reqs[b].slot is None and reqs[b] in eng.queue
    assert reqs[b].n_preemptions == 1
    hi_req = next(r for r in eng.slots if r is not None and r.uid == hi)
    assert hi_req.priority == 5
    st = eng.stats()
    assert st["preemptions"] == 1 and st["pages_reclaimed"] == 13
    eng.check_invariants()
    done = eng.run_until_done(max_ticks=20_000)
    assert sorted(r.uid for r in done) == [a, b, hi]
    assert reqs[b].n_resumes == 1
    assert eng.stats()["resumes"] == 1


def test_preempt_victim_choice_decoding_longest_remaining(model_f32):
    """With only DECODING candidates, the victim is the one with the most
    generation budget left (it would hold its pages longest)."""
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(max_batch=2, usable_pages=18))
    a = eng.submit(list(range(1, 49)), max_new_tokens=12)   # 8 pages
    b = eng.submit(list(range(1, 49)), max_new_tokens=24)   # 9 pages
    for _ in range(40):
        eng.tick()
        reqs = {r.uid: r for r in eng.slots if r is not None}
        if len(reqs) == 2 and all(r.state is RequestState.DECODING
                                  for r in reqs.values()):
            break
    else:
        pytest.fail("background requests never reached DECODING")
    hi = eng.submit(list(range(1, 49)), priority=1)          # 7 pages
    eng.tick()
    assert reqs[b].state is RequestState.RESUMING            # longest remaining
    assert reqs[a].state is RequestState.DECODING
    # a mid-decode victim resumes from prompt + generated-so-far
    assert reqs[b].resume_tokens == reqs[b].prompt + reqs[b].out_tokens
    eng.check_invariants()
    done = eng.run_until_done(max_ticks=20_000)
    assert sorted(r.uid for r in done) == [a, b, hi]


def test_equal_priority_never_preempts(model_f32):
    """The priority-inversion guard, half one: equal-priority pressure
    backpressures exactly like preemption=False - nothing is shed."""
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(max_batch=2, usable_pages=15))
    for _ in range(3):
        eng.submit(list(range(1, 81)))             # 11 pages each, pool 15
    done = eng.run_until_done(max_ticks=20_000)
    assert len(done) == 3
    st = eng.stats()
    assert st["preemptions"] == 0 and st["resumes"] == 0
    assert st["pages_reclaimed"] == 0


def test_lower_priority_never_preempts_higher(model_f32):
    """The priority-inversion guard, half two: a queued low-priority
    request must wait out a running high-priority one."""
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(max_batch=2, usable_pages=15))
    hi = eng.submit(list(range(1, 81)), priority=5)
    eng.tick()
    lo = eng.submit(list(range(1, 81)), priority=0)
    done = eng.run_until_done(max_ticks=20_000)
    assert eng.stats()["preemptions"] == 0
    assert [r.uid for r in done] == [hi, lo]       # hi ran to completion first


def test_preempt_headroom_guard(model_f32):
    """A candidate that could not fit even after shedding every eligible
    victim must NOT shed anyone (backpressure, work preserved)."""
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(max_batch=3, usable_pages=19))
    anchor = eng.submit(list(range(1, 57)), priority=9)        # 8 pages, pinned
    lo = eng.submit(list(range(1, 41)), priority=0)            # 6 pages
    eng.tick()
    # mid needs 13 pages; free = 19 - 14 = 5; only lo (6 pages) is
    # sheddable (anchor outranks mid): 5 + 6 = 11 < 13 -> refuse, park
    mid = eng.submit(list(range(1, 89)), priority=5,
                     max_new_tokens=16)                        # 13 pages
    eng.tick()
    assert eng.stats()["preemptions"] == 0        # shedding lo would not
    done = eng.run_until_done(max_ticks=20_000)   # cover mid's 13 pages
    assert sorted(r.uid for r in done) == [anchor, lo, mid]


# ===========================================================================
# preempt/resume parity vs an uninterrupted large-pool oracle
# ===========================================================================

# max_chunks_per_tick 1 pins every pack to K=1 (structural bit-parity);
# 0 lets the ticks pack several chunks, and parity may fall back to the
# epsilon-greedy check at genuine near-ties
@pytest.mark.parametrize("max_chunks", [0, 1])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_resume_parity_prefilling_victim(max_chunks, prefix_cache,
                                         model_f32):
    """A victim shed mid-PREFILL re-prefills from its cursor (or the
    surviving cached prefix) and must produce byte-identical greedy
    outputs to a run that was never preempted.  The oracle shares the
    victim run's full configuration (only the capacity cap differs), so
    both runs execute the same code paths on the same executables."""
    m, params = model_f32
    items = priority_burst(m.cfg.vocab_size, (96, 96), (64,), 1,
                           burst_priority=5, seed=1)
    kw = dict(max_chunks_per_tick=max_chunks, prefix_cache=prefix_cache)
    oracle, _ = _replay(m, params, _cfg(**kw), items)
    out, eng = _replay(m, params, _cfg(usable_pages=28, **kw), items)
    _assert_parity(m, params, eng, out, oracle)
    st = eng.stats()
    assert st["preemptions"] >= 1 and st["resumes"] >= 1
    assert st["pages_reclaimed"] >= 1


@pytest.mark.parametrize("max_chunks", [0, 1])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_resume_parity_decoding_victim(max_chunks, prefix_cache, model_f32):
    """A victim shed MID-DECODE re-prefills prompt + generated-so-far; the
    final resume chunk's logits sample the next token exactly as the
    uninterrupted decode would - byte-identical outputs, monotone
    work-clock stamps."""
    m, params = model_f32
    items = priority_burst(m.cfg.vocab_size, (96,), (64,), 9,
                           burst_priority=5, seed=0)
    kw = dict(max_batch=2, max_chunks_per_tick=max_chunks,
              prefix_cache=prefix_cache)
    oracle, _ = _replay(m, params, _cfg(**kw), items)
    out, eng = _replay(m, params, _cfg(usable_pages=15, **kw), items)
    _assert_parity(m, params, eng, out, oracle)
    victim = next(r for r in eng.sched.finished if r.n_preemptions)
    assert victim.resume_tokens is not None       # preempted while decoding
    assert len(victim.out_tokens) > len(victim.resume_tokens) \
        - len(victim.prompt)                      # kept generating after
    # TTFT/TBT accounting stays monotone across the preempt/resume: stamps
    # are carried, never reset
    assert victim.token_work == sorted(victim.token_work)
    assert victim.ttft_work() > 0
    assert all(d >= 0 for d in victim.tbt_work())
    assert eng.stats()["preemptions"] >= 1


def test_resume_via_prefix_cache_reuses_survivors(model_f32):
    """Pages the tree references survive a preemption (refcounts), so a
    resuming victim re-matches them and only re-prefills the remainder."""
    m, params = model_f32
    prompt = list(range(1, 97))                    # 12 full pages
    eng = ServeEngine(m, params, _cfg(max_batch=2,
                                      prefix_cache=True))
    # a finished warmup publishes the prompt's pages into the tree
    eng.submit(prompt, max_new_tokens=1)
    eng.run_until_done(max_ticks=10_000)
    published = eng.prefix.cached_pages
    assert published == 12
    # the same prompt re-admits (attaching cached pages) and gets shed
    uid = eng.submit(prompt)
    eng.tick()
    req = next(r for r in eng.slots if r is not None and r.uid == uid)
    prefill_before = eng.prefill_tokens
    eng._preempt(req)
    eng.check_invariants()
    # the attached pages survived the shed: still cached, refcount back to 1
    assert eng.prefix.cached_pages == published
    survivors = eng.prefix.cached_prefix_len(req.target)
    assert survivors >= 88                         # all but the COW'd tail
    done = eng.run_until_done(max_ticks=10_000)
    assert done[0].uid == uid and done[0].n_resumes == 1
    # the resume recomputed at most the non-surviving remainder per pass
    assert eng.prefill_tokens - prefill_before \
        <= 2 * (len(prompt) - survivors + PAGE)
    eng.check_invariants()


def test_preempt_publishes_victim_pages(model_f32):
    """Publish-on-preempt: shedding a victim with a prefix cache active
    PARKS its computed KV pages in the tree (refcounted) instead of
    discarding them, so the resume re-attaches them as cache hits and
    only recomputes the unparked tail.  Refcounts conserve throughout:
    right after the shed the tree is the sole owner of every parked
    page, and the drained engine balances used == cached."""
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(max_batch=2, prefix_cache=True))
    uid = eng.submit(list(range(1, 97)), max_new_tokens=8)   # 12 pages
    req = None
    for _ in range(12):                            # prefill -> decoding
        eng.tick()
        req = next((r for r in eng.slots if r is not None), None)
        if req is not None and req.state is RequestState.DECODING:
            break
    assert req is not None and req.state is RequestState.DECODING
    parked0, cached0 = eng.sched.pages_parked, eng.prefix.cached_pages
    eng._preempt(req)
    eng.check_invariants()
    parked = eng.sched.pages_parked - parked0
    assert parked >= 12                            # whole prompt parked
    assert eng.prefix.cached_pages - cached0 == parked
    # the tree is now the sole owner: nothing else maps pages
    assert eng.allocator.used_pages == eng.prefix.cached_pages
    assert eng.stats()["pages_parked"] == eng.sched.pages_parked
    # the resume re-attaches the parked pages as prefix hits
    hit0 = eng.prefill_tokens
    done = eng.run_until_done(max_ticks=10_000)
    assert done[-1].uid == uid and done[-1].n_resumes == 1
    assert len(done[-1].out_tokens) == 8
    assert eng.prefix_hit_tokens >= parked * PAGE - PAGE   # COW'd tail
    assert eng.prefill_tokens - hit0 <= len(req.target) + 2 * PAGE \
        - eng.prefix_hit_tokens + req.max_new_tokens
    eng.check_invariants()
    assert eng.allocator.used_pages == eng.prefix.cached_pages


def test_refcount_conservation_across_preempt_cycles(model_f32):
    """Repeated forced preempt/resume cycles conserve page accounting:
    after every cycle the allocator balances and no page leaks."""
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(max_batch=2,
                                      prefix_cache=True))
    uid = eng.submit(list(range(1, 81)), max_new_tokens=12)
    free0 = None
    for cycle in range(3):
        for _ in range(3):
            eng.tick()
        req = next((r for r in eng.slots if r is not None), None)
        if req is None:
            break
        if free0 is None:
            free0 = eng.allocator.free_pages + len(
                eng.allocator.slot_pages(req.slot))
        eng._preempt(req)
        eng.check_invariants()
        assert req.state is RequestState.RESUMING
    done = eng.run_until_done(max_ticks=20_000)
    assert done and done[-1].uid == uid
    eng.check_invariants()
    assert eng.allocator.live_pages() == 0         # nothing left mapped
    st = eng.stats()
    assert st["preemptions"] == st["resumes"] >= 2


# ===========================================================================
# stats / gauges
# ===========================================================================

def test_priority_queue_depth_gauges(model_f32):
    m, params = model_f32
    eng = ServeEngine(m, params, _cfg(max_batch=1))
    eng.submit([1, 2, 3])                          # admits immediately
    eng.tick()
    eng.submit([4, 5, 6], priority=2)
    eng.submit([7, 8, 9], priority=2)
    eng.submit([1, 1, 1], priority=-1)
    st = eng.stats()
    assert st["queue_depth"] == 3
    assert st["queue_depth_by_priority"] == {"2": 2, "-1": 1}
    # higher priority admits first even under FIFO
    done = eng.run_until_done(max_ticks=20_000)
    uid_order = [r.uid for r in done]
    assert uid_order.index(2) < uid_order.index(4)
    assert uid_order.index(3) < uid_order.index(4)


def test_stats_expose_preemption_counters(model_f32):
    m, params = model_f32
    out, eng = _replay(m, params, _cfg(usable_pages=28),
                       priority_burst(m.cfg.vocab_size, (96, 96), (64,), 1,
                                      burst_priority=5, seed=3))
    st = eng.stats()
    for key in ("preemptions", "resumes", "pages_reclaimed",
                "queue_depth", "queue_depth_by_priority"):
        assert key in st
    assert st["preemptions"] >= 1
    assert st["resumes"] == st["preemptions"]      # everything resumed
    assert st["queue_depth"] == 0                  # drained


# ===========================================================================
# soak: random traffic against a tiny pool
# ===========================================================================

def _soak_case(model, params, seed: int):
    """One soak example: random arrivals (mixed lengths, shared prefixes,
    priorities, bursts) against a deliberately tiny page pool, with
    invariants checked after EVERY tick (traffic.replay).  The engine must
    drain without deadlock and bit-match the large-pool oracle."""
    items = random_arrivals(model.cfg.vocab_size, 10, seed)
    for prefix_cache in (False, True):
        # oracle and pressured run share the FULL configuration (only the
        # capacity cap differs): same code paths, same executables
        oracle, _ = _replay(model, params,
                            _cfg(max_new_tokens=4,
                                 prefix_cache=prefix_cache), items)
        out, eng = _replay(model, params,
                           _cfg(max_new_tokens=4, usable_pages=17,
                                prefix_cache=prefix_cache), items)
        _assert_parity(model, params, eng, out, oracle)
        assert len(out) == len(items)
        st = eng.stats()
        assert st["queue_depth"] == 0
        assert eng.allocator.live_pages() == 0
    return st


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_soak_fixed_seeds(seed, model_f32):
    """The CI fixed-seed soak profile: always runs, no hypothesis
    dependency - the same cases the hypothesis property starts from."""
    m, params = model_f32
    _soak_case(m, params, seed)


def test_soak_preemptions_actually_occur(model_f32):
    """The soak pool is genuinely tiny: across the fixed seed profile the
    preemption path fires (otherwise the soak proves nothing)."""
    m, params = model_f32
    total = 0
    for seed in (0, 1, 2):
        items = random_arrivals(m.cfg.vocab_size, 10, seed)
        _, eng = _replay(m, params,
                         _cfg(max_new_tokens=4, usable_pages=17), items)
        total += eng.stats()["preemptions"]
    assert total >= 1


def test_soak_hypothesis_random_traffic(model_f32):
    """Property: ANY random arrival trace against the tiny pool drains
    without deadlock, keeps allocator invariants after every tick, and
    bit-matches the large-pool oracle.  Derandomized so CI runs a fixed
    example set."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    m, params = model_f32

    @settings(max_examples=6, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 19))
    def check(seed):
        _soak_case(m, params, seed)

    check()
