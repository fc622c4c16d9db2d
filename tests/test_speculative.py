"""Self-speculative decoding units: the drafter, config validation, and
engine-level drafting behavior (budget consumption, counters, rollback
bookkeeping).  Full differential conformance lives in
tests/test_conformance.py; this file tests the pieces in isolation."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.models import build_model
from repro.serve import ServeEngine, ngram_draft
from repro.serve.scheduler import Request, TokenBudgetScheduler

# ===========================================================================
# ngram_draft: suffix-shift prompt-lookup
# ===========================================================================


def test_draft_constant_run():
    """A constant tail is a period-1 cycle: the draft repeats it for the
    full max_draft, regardless of history length."""
    assert ngram_draft([1, 2, 5, 5, 5, 5], 4, 3) == [5, 5, 5, 5]
    assert ngram_draft([7, 7], 6, 3) == [7] * 6


def test_draft_period_cycle():
    """A period-p tail predicts cyclically - including past the end of
    recorded history (token[t] = token[t - p] wraps through the draft)."""
    h = [9, 1, 2, 3, 1, 2, 3, 1, 2]
    assert ngram_draft(h, 5, 3) == [3, 1, 2, 3, 1]


def test_draft_most_recent_match_wins():
    """Two occurrences of the trailing n-gram: the MOST RECENT one sets
    the period, so the freshest local pattern is continued."""
    #     [1, 2, X, ..., 1, 2, Y, ..., 1, 2] -> predicts Y (recent), not X
    h = [1, 2, 8, 0, 1, 2, 5, 0, 1, 2]
    assert ngram_draft(h, 1, 2)[0] == 5


def test_draft_longer_ngram_preferred():
    """When a longer suffix match exists it wins over a shorter one that
    would predict differently."""
    #  trailing 3-gram [4, 1, 2] occurs earlier followed by 9;
    #  the trailing 1-gram [2] also occurs at index 2 followed by 7
    h = [4, 1, 2, 7, 4, 1, 2, 9, 4, 1, 2]
    assert ngram_draft(h, 1, 3)[0] == 9


def test_draft_no_repetition_is_empty():
    assert ngram_draft([1, 2, 3, 4, 5, 6], 4, 3) == []


def test_draft_degenerate_inputs():
    assert ngram_draft([1, 1, 1], 0, 3) == []     # no room
    assert ngram_draft([5], 4, 3) == []           # too short to match
    assert ngram_draft([], 4, 3) == []


# ===========================================================================
# config validation + family gating
# ===========================================================================

_SPEC_KW = dict(max_batch=2, max_seq=128, page_size=16, paged=True,
                prefill_chunk=16, tick_token_budget=32, max_new_tokens=8,
                speculative=True)


def test_speculative_requires_chunked_batched():
    with pytest.raises(ValueError, match="speculative decoding requires "
                                         "paged=True"):
        ServeConfig(**{**_SPEC_KW, "paged": False}).validate()
    for flag in ("chunked", "batched"):
        with pytest.raises(ValueError, match=f"{flag}=False is no longer"):
            ServeConfig(**{**_SPEC_KW, flag: False}).validate()
    with pytest.raises(ValueError, match="spec_k"):
        ServeConfig(**{**_SPEC_KW, "spec_k": 0}).validate()
    with pytest.raises(ValueError, match="spec_ngram"):
        ServeConfig(**{**_SPEC_KW, "spec_ngram": 0}).validate()


def test_speculative_rejects_non_attention_family():
    """Speculation verifies through the batched paged chunk kernel; an
    attention-free family has no such path and must fail loudly at
    engine construction, not at the first tick."""
    cfg = get_smoke_config("rwkv6-1.6b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="attention"):
        ServeEngine(m, params, ServeConfig(**_SPEC_KW))


# ===========================================================================
# scheduler drafting policy (host-side, no device work)
# ===========================================================================

def _req(uid, prompt, out, max_new=32):
    r = Request(uid=uid, prompt=list(prompt), max_new_tokens=max_new)
    for t in out:
        r.out_tokens.append(t)
    return r


def _sched(**kw):
    return TokenBudgetScheduler(ServeConfig(**{**_SPEC_KW, **kw}))


def test_plan_drafts_consumes_room():
    """Draft lengths are capped by the shared room: once the tick's
    leftover budget is spent, later slots draft nothing."""
    s = _sched(spec_k=6)
    reqs = [(i, _req(i, [3, 3, 3, 3], [3, 3])) for i in range(2)]
    tasks = s.plan_drafts(reqs, room=8)
    assert [len(t.draft) for t in tasks] == [6, 2]
    assert s.plan_drafts(reqs, room=0) == []


def test_plan_drafts_caps_at_remaining_new():
    """A request one token from its generation cap never drafts (the
    guaranteed token IS its last); nearly-done requests draft at most
    remaining_new - 1 so chain + bonus can't overrun the reservation."""
    s = _sched(spec_k=6)
    nearly = _req(0, [4, 4, 4, 4], [4, 4], max_new=4)   # 2 remaining
    done1 = _req(1, [4, 4, 4, 4], [4, 4, 4], max_new=4)  # 1 remaining
    tasks = s.plan_drafts([(0, nearly), (1, done1)], room=32)
    assert [(t.slot, len(t.draft)) for t in tasks] == [(0, 1)]


def test_plan_drafts_skips_non_repeating_history():
    s = _sched()
    tasks = s.plan_drafts([(0, _req(0, [1, 2, 3, 4], [5, 6]))], room=32)
    assert tasks == []


def test_pack_drafts_rows():
    """The packed verify batch: row = [pending, draft...] at the slot's
    current lens, true_len = offset + 1 + m, sentinel rows dead."""
    s = _sched(spec_k=6)
    req = _req(0, [9, 9, 9], [9, 9])
    (task,) = s.plan_drafts([(1, req)], room=32)
    lens = np.array([0, 5], np.int32)
    pack = s.pack_drafts([task], lens)
    assert pack.tokens[0, 0] == 9                 # pending = last emitted
    assert list(pack.tokens[0, 1:1 + 6]) == [9] * 6
    assert pack.offsets[0] == 5
    assert pack.true_lens[0] == 5 + 1 + 6
    assert pack.q_lens[0] == 7
    assert pack.draft_lens[0] == 6
    assert pack.row_slots[0] == 1
    # padding rows (bucketing) carry the max_batch sentinel slot
    assert all(r == s.scfg.max_batch for r in pack.row_slots[1:])


# ===========================================================================
# engine: drafting engages and stays within budget on a live run
# ===========================================================================

@pytest.fixture(scope="module")
def model_f32():
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def test_engine_spec_counters_and_budget(model_f32):
    """A live speculative run on a repetitive prompt: drafting engages,
    acceptance is recorded, every tick stays within the token budget,
    and the emitted stream matches the non-speculative engine's."""
    m, params = model_f32
    scfg = dict(max_batch=2, max_seq=256, page_size=16, paged=True,
                prefill_chunk=16, tick_token_budget=32, max_new_tokens=48,
                spec_k=4)
    rng = np.random.default_rng(11)
    base = rng.integers(1, m.cfg.vocab_size, size=4).tolist()
    prompt = base * 6                              # repetitive by design

    def run(speculative):
        eng = ServeEngine(m, params,
                          ServeConfig(speculative=speculative, **scfg))
        eng.submit(prompt)
        eng.run_until_done()
        return eng

    eng_off, eng_on = run(False), run(True)
    s = eng_on.stats()
    assert s["spec_drafted"] > 0 and s["spec_accepted"] >= 0
    assert [r.out_tokens for r in eng_on.sched.finished] == \
        [r.out_tokens for r in eng_off.sched.finished]
    budget = eng_on.scfg.tick_token_budget
    for d, p in eng_on.sched.tick_log:
        assert d + p <= budget
    s_off = eng_off.stats()
    assert s["ticks"] <= s_off["ticks"]
    # the work clock counts accepted tokens only, and accepted drafts
    # raise the tokens each token-emitting launch yields
    assert s["work_tokens"] == s_off["work_tokens"]
    assert s["spec_accepted"] > 0
    assert s["tokens_per_launch"] > 1.5 * s_off["tokens_per_launch"], \
        (s["tokens_per_launch"], s_off["tokens_per_launch"])
