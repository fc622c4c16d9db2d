"""Prefix cache: radix tree, refcount/COW correctness, eviction churn,
allocator invariants, and greedy parity cache-on vs cache-off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.models import build_model
from repro.serve import PageAllocator, RadixPrefixCache, ServeEngine


def _tree(num_pages=33, ps=4, max_batch=4, max_seq=64):
    alloc = PageAllocator(num_pages, ps, max_batch, max_seq)
    return alloc, RadixPrefixCache(alloc, ps)


# ===========================================================================
# radix tree: match / publish / split / dedupe
# ===========================================================================

def test_radix_match_publish_and_split():
    alloc, tree = _tree(ps=2)
    toks = [1, 2, 3, 4, 5, 6]
    pages = alloc.alloc(0, 3)
    tree.release(0, toks)                       # publish all 3 full pages
    assert tree.match(toks) == pages
    assert tree.match([1, 2, 3, 4, 9, 9]) == pages[:2]   # mid-edge partial
    assert tree.match([9] * 6) == []
    assert tree.match([1]) == []                # shorter than one page
    # a divergent prompt splits the edge; shared pages are deduped
    toks2 = [1, 2, 3, 4, 7, 8]
    pages2 = alloc.alloc(1, 3)
    free_before = alloc.free_pages
    tree.release(1, toks2)
    assert alloc.free_pages == free_before + 2  # 2 duplicate pages freed
    m = tree.match(toks2)
    assert m[:2] == pages[:2] and m[2] == pages2[2]
    assert tree.match(toks) == pages            # original path intact
    assert tree.cached_pages == 4
    tree.check_invariants()


def test_radix_publish_identical_prompt_dedupes():
    """Two requests that computed the same prefix independently (both in
    flight before either finished) publish once; the loser's pages free."""
    alloc, tree = _tree(ps=2)
    toks = [5, 6, 7, 8]
    pa = alloc.alloc(0, 2)
    pb = alloc.alloc(1, 2)
    tree.release(0, toks)
    tree.release(1, toks)
    assert tree.match(toks) == pa
    assert set(pb).issubset(set(alloc._free))   # duplicates returned
    assert tree.cached_pages == 2
    tree.check_invariants()


def test_radix_partial_tail_page_not_published():
    """Only FULL prompt pages enter the tree; the partial tail page (which
    decode keeps writing into) is freed on completion."""
    alloc, tree = _tree(ps=4)
    pages = alloc.alloc(0, 3)                   # 9 prompt + gen reservation
    tree.release(0, list(range(9)))             # 9 tokens -> 2 full pages
    assert tree.cached_pages == 2
    assert tree.match(list(range(9))) == pages[:2]
    assert alloc.refcount(pages[2]) == 0
    tree.check_invariants()


# ===========================================================================
# refcounts + copy-on-write
# ===========================================================================

def test_refcount_attach_release_interleaved_divergent():
    """Two live requests share cached prefix pages (refcount 3: tree + two
    slots); divergent tails stay private; releases unwind cleanly."""
    alloc, tree = _tree(ps=2)
    base = [1, 2, 3, 4]
    seed = alloc.alloc(0, 2)
    tree.release(0, base)                       # tree now owns the prefix
    shared = tree.match(base + [7, 8])
    assert shared == seed
    alloc.attach(1, shared)
    alloc.alloc(1, 2)                           # slot 1 tail
    alloc.attach(2, tree.match(base + [9, 9]))
    alloc.alloc(2, 2)                           # slot 2 divergent tail
    for p in shared:
        assert alloc.refcount(p) == 3           # tree + slot 1 + slot 2
    tree.check_invariants()
    tree.release(1, base + [7, 8])
    for p in shared:
        assert alloc.refcount(p) == 2
    tree.release(2, base + [9, 9])
    for p in shared:
        assert alloc.refcount(p) == 1           # only the tree
    # both 3-page prompts are now fully cached; the two tails both hang
    # off the shared prefix
    assert tree.match(base + [7, 8]) != tree.match(base + [9, 9])
    assert tree.match(base + [7, 8])[:2] == shared
    tree.check_invariants()


def test_cow_bookkeeping():
    """allocator.cow swaps in a private page and drops the shared ref;
    no page is ever both free and referenced along the way."""
    alloc, tree = _tree(ps=2)
    pages = alloc.alloc(0, 2)
    tree.release(0, [1, 2, 3, 4])
    shared = tree.match([1, 2, 3, 4])
    alloc.attach(1, shared)
    old, new = alloc.cow(1, 1)
    assert old == shared[1] and new not in shared
    assert alloc.refcount(old) == 1             # tree keeps its copy
    assert alloc.refcount(new) == 1             # slot's private copy
    assert alloc.table[1, 1] == new
    tree.check_invariants()
    alloc.free_slot(1)
    assert alloc.refcount(new) == 0
    tree.check_invariants()


def test_engine_full_cover_prompt_cows_and_matches(rng):
    """A prompt that is ENTIRELY cached recomputes only its last token,
    COWs the final shared page, and still produces cache-off tokens."""
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(rng)
    base = list(range(1, 17))                   # exactly 2 pages of 8

    def run(prefix):
        eng = ServeEngine(m, params,
                          ServeConfig(max_batch=2, max_seq=64, paged=True,
                                      page_size=8, num_pages=33,
                                      prefix_cache=prefix))
        out = {}
        for wave in ([base], [base, base]):     # repeat => full cover twice
            for p in wave:
                eng.submit(p, max_new_tokens=5)
            for r in eng.run_until_done():
                out[r.uid] = r.out_tokens
        return out, eng

    out_off, _ = run(False)
    out_on, eng = run(True)
    assert out_off == out_on
    assert eng.cow_copies == 2
    assert eng.prefix_hit_tokens == 2 * 15      # all but the last token
    eng.prefix.check_invariants()


# ===========================================================================
# engine parity: greedy tokens identical with the cache on vs off
# ===========================================================================

def _shared_prefix_waves(vocab):
    """Two shared 32-token prefixes, one after the other: a warm request
    runs to completion (publishing its pages), then three followers that
    share its prefix run concurrently."""
    rng = np.random.default_rng(0)
    waves = []
    for _ in range(2):
        shared = rng.integers(1, vocab, size=32).tolist()
        waves += [[shared + rng.integers(1, vocab, size=8).tolist()
                   for _ in range(n)] for n in (1, 3)]
    return waves


@pytest.mark.parametrize("arch,trace", [("granite-3-2b", "mixed"),
                                        ("gemma3-4b", "mixed"),
                                        ("granite-3-2b", "shared")])
def test_engine_prefix_parity(arch, trace, rng):
    """Shared prefixes, divergence inside and across pages, full-cover
    repeats, sub-page prompts: greedy outputs must be identical with
    prefix caching on and off (gemma3 adds sliding windows + QK norm).
    On the shared-prefix trace the cache must also save at least 40% of
    the prompt tokens' prefill and shrink the peak working set."""
    cfg = get_smoke_config(arch).replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(rng)
    base = list(range(1, 17))
    waves = [[base + [30, 31, 32, 33, 34]],
             [base + [40, 41], base, [9, 9, 9, 9],
              base + [30, 31, 32, 33, 34]]]
    if trace == "shared":
        waves = _shared_prefix_waves(cfg.vocab_size)

    def run(prefix):
        eng = ServeEngine(m, params,
                          ServeConfig(max_batch=2, max_seq=64, paged=True,
                                      page_size=8, num_pages=33,
                                      prefix_cache=prefix))
        out = {}
        for wave in waves:
            for p in wave:
                eng.submit(p, max_new_tokens=5)
            for r in eng.run_until_done():
                out[r.uid] = r.out_tokens
        return out, eng

    out_off, eng_off = run(False)
    out_on, eng_on = run(True)
    assert out_off == out_on
    assert eng_on.prefix_hit_tokens > 0
    assert eng_on.prefill_tokens < eng_off.prefill_tokens
    assert eng_off.allocator.used_pages == 0
    # with the cache on, only tree pages remain in use at the end
    assert eng_on.allocator.used_pages == eng_on.prefix.cached_pages
    eng_on.prefix.check_invariants()
    if trace == "shared":
        saved = 1 - eng_on.prefill_tokens / eng_off.prefill_tokens
        assert saved >= 0.40, saved
        assert eng_on.peak_live_pages < eng_off.peak_live_pages


# ===========================================================================
# eviction: LRU churn under pool pressure never corrupts anything
# ===========================================================================

def test_evict_respects_refcounts_and_lru():
    alloc, tree = _tree(num_pages=33, ps=2)
    alloc.alloc(0, 2)
    tree.release(0, [1, 2, 3, 4])               # older
    alloc.alloc(0, 2)
    tree.release(0, [5, 6, 7, 8])               # newer
    pinned = tree.match([1, 2, 3, 4])           # bumps LRU, then pin
    alloc.attach(1, pinned)
    # evict everything evictable: only the (now older) second prompt goes
    freed = tree.evict(100)
    assert freed == 2
    assert tree.match([5, 6, 7, 8]) == []
    assert tree.match([1, 2, 3, 4]) == pinned   # pinned prefix survived
    tree.check_invariants()
    alloc.free_slot(1)
    assert tree.evict(100) == 2                 # unpinned -> evictable
    assert tree.cached_pages == 0
    assert alloc.used_pages == 0
    tree.check_invariants()


def test_evict_tail_first_keeps_valid_prefix():
    """Partial eviction trims pages off the END of a cached prompt; the
    surviving front must still match (prefix property)."""
    alloc, tree = _tree(ps=2)
    pages = alloc.alloc(0, 4)
    tree.release(0, [1, 2, 3, 4, 5, 6, 7, 8])
    assert tree.evict(1) == 1                   # trim one tail page
    assert tree.match([1, 2, 3, 4, 5, 6, 7, 8]) == pages[:3]
    tree.check_invariants()


def test_engine_eviction_churn_parity(rng):
    """A pool too small to cache every distinct prefix forces eviction
    between waves while requests are in flight; outputs still match the
    cache-off engine and invariants hold after every wave."""
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(rng)
    rng_np = np.random.default_rng(0)
    prefixes = [list(rng_np.integers(1, 200, size=16)) for _ in range(4)]
    waves = []
    for i, pre in enumerate(prefixes):          # revisit each prefix twice
        waves.append([pre + [200 + i], pre + [210 + i, 211]])
    for i, pre in enumerate(prefixes):
        waves.append([pre + [220 + i]])

    def run(prefix, watermark=0.0):
        # 16 usable pages: two live requests need 2 * 3 = 6, so at most
        # ~3 cached prefixes (2 pages each) fit - churn guaranteed
        eng = ServeEngine(m, params,
                          ServeConfig(max_batch=2, max_seq=32, paged=True,
                                      page_size=8, num_pages=17,
                                      prefix_cache=prefix,
                                      prefix_evict_watermark=watermark))
        out = {}
        for wave in waves:
            for p in wave:
                eng.submit(p, max_new_tokens=4)
            for r in eng.run_until_done():
                out[r.uid] = r.out_tokens
            if eng.prefix is not None:
                eng.prefix.check_invariants()
        return out, eng

    out_off, _ = run(False)
    out_on, eng = run(True)
    assert out_off == out_on
    assert eng.prefix_hit_tokens > 0            # some reuse survived churn
    # watermark mode proactively keeps headroom free and still matches
    out_wm, eng_wm = run(True, watermark=0.5)
    assert out_wm == out_off
    assert eng_wm.allocator.free_pages >= 8     # 50% of 16 usable


# ===========================================================================
# peek: the read-only lookup the fleet router probes with
# ===========================================================================

def _all_nodes(tree):
    out, stack = [], [tree.root]
    while stack:
        nd = stack.pop()
        out.append(nd)
        stack.extend(nd.children.values())
    return out


def test_peek_returns_match_result_without_any_side_effect():
    """peek must return exactly what match would - and leave NOTHING
    behind: no LRU stamp bumps, no tree-clock advance, no refcount
    changes, no lookup/hit counters, no events.  The fleet router peeks
    every replica per submit; a probe that perturbed LRU order or
    hit-rate accounting on the N-1 losing replicas would skew both
    eviction and metrics."""
    alloc, tree = _tree(ps=2)
    alloc.alloc(0, 2)
    tree.release(0, [1, 2, 3, 4])
    alloc.alloc(0, 2)
    tree.release(0, [5, 6, 7, 8])
    events = []
    tree.event_cb = lambda name, **kw: events.append(name)
    want = tree.match([1, 2, 3, 4])     # bump: [5,6,7,8] is now the LRU
    events.clear()
    clock0 = tree._clock
    stamps0 = [(id(nd), nd.last_used) for nd in _all_nodes(tree)]
    refs0 = {p: alloc.refcount(p) for p in tree._pages}
    metrics0 = tree.metrics.snapshot()
    # peek agrees with match on hits, partial hits, and misses...
    assert tree.peek([1, 2, 3, 4]) == want
    assert tree.peek([5, 6, 7, 8]) == tree._walk([5, 6, 7, 8], touch=False)
    assert len(tree.peek([5, 6, 7, 8])) == 2
    assert tree.peek([5, 6, 9, 9]) == tree.peek([5, 6, 7, 8])[:1]
    assert tree.peek([9, 9, 9, 9]) == []
    assert tree.peek([1]) == []         # shorter than one page
    # ... and none of it left a trace
    assert tree._clock == clock0, "peek advanced the LRU clock"
    assert [(id(nd), nd.last_used) for nd in _all_nodes(tree)] == stamps0, \
        "peek reordered LRU stamps"
    assert {p: alloc.refcount(p) for p in tree._pages} == refs0, \
        "peek touched refcounts"
    assert tree.metrics.snapshot() == metrics0, \
        "peek recorded lookup/hit metrics"
    assert events == [], "peek emitted trace events"
    tree.check_invariants()


def test_peek_does_not_change_eviction_order():
    """Hammering peek at one cached prompt must not rescue it from LRU
    eviction: evict still takes the least-recently-MATCHED prompt, even
    if it was the most-recently-peeked one."""
    alloc, tree = _tree(ps=2)
    alloc.alloc(0, 2)
    tree.release(0, [1, 2, 3, 4])
    alloc.alloc(0, 2)
    tree.release(0, [5, 6, 7, 8])
    kept = tree.match([1, 2, 3, 4])     # [5,6,7,8] is now the LRU tail
    for _ in range(25):
        assert len(tree.peek([5, 6, 7, 8])) == 2
    assert tree.evict(2) == 2
    assert tree.match([5, 6, 7, 8]) == [], \
        "peeks rescued the LRU victim - peek is not side-effect-free"
    assert tree.match([1, 2, 3, 4]) == kept
    tree.check_invariants()


# ===========================================================================
# allocator guard rails
# ===========================================================================

def test_allocator_refcount_guard_rails():
    alloc = PageAllocator(9, 4, 2, 32)
    pages = alloc.alloc(0, 2)
    with pytest.raises(ValueError):
        alloc.attach(1, [alloc._free[-1]])      # can't share a free page
    with pytest.raises(ValueError):
        alloc.unref(0)                          # null page untouchable
    alloc.attach(1, pages)
    alloc.free_slot(0)
    assert all(alloc.refcount(p) == 1 for p in pages)   # slot 1 keeps them
    alloc.free_slot(1)
    assert alloc.used_pages == 0
    alloc.check_invariants()


def test_prefix_cache_requires_paged(rng):
    cfg = get_smoke_config("granite-3-2b")
    m = build_model(cfg)
    params = m.init(rng)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(m, params, ServeConfig(prefix_cache=True))
