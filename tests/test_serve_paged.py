"""Paged KV-cache serving: kernel parity, allocator churn, backpressure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.kernels import flash_decode as fd
from repro.kernels import ops, ref
from repro.models import build_model
from repro.serve import OutOfPages, PageAllocator, ServeEngine
from repro.serve.paged_cache import (dense_kv_bytes, paged_kv_bytes,
                                     pages_needed)
from pools import LAYER, stack_pool
from traffic import mixed_prompts, serve_all


def _paged_from_dense(kc, vc, page_size, seed=0, lanes=128):
    """Scatter a dense (B, S, Hkv, D) cache into a SHUFFLED stacked page
    pool (tests/pools.py, the data at layer LAYER) and the matching block
    table (page 0 kept as the null page)."""
    B, S, Hkv, D = kc.shape
    n_max = S // page_size
    n_pool = B * n_max + 1
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, n_pool))
    bt = perm.reshape(B, n_max).astype(np.int32)
    k_pages = np.zeros((n_pool, page_size, Hkv, D), np.float32)
    v_pages = np.zeros((n_pool, page_size, Hkv, D), np.float32)
    for b in range(B):
        for j in range(n_max):
            k_pages[bt[b, j]] = np.asarray(kc[b, j*page_size:(j+1)*page_size])
            v_pages[bt[b, j]] = np.asarray(vc[b, j*page_size:(j+1)*page_size])
    return (stack_pool(jnp.asarray(np.moveaxis(k_pages, 2, 0)),
                       jnp.asarray(np.moveaxis(v_pages, 2, 0)), lanes),
            jnp.asarray(bt))


# ===========================================================================
# kernel parity: paged (ref + pallas interpret) vs dense flash decode
# ===========================================================================

@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("window", [0, 10])
@pytest.mark.parametrize("lanes", [32, 128])
def test_paged_decode_matches_dense(impl, window, lanes, rng):
    B, S, Hq, Hkv, D, ps = 3, 64, 4, 2, 16, 8
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    lens = jnp.array([S - 5, S // 2, 1])
    pool, bt = _paged_from_dense(kc, vc, ps, lanes=lanes)

    o_dense = ops.flash_decode(q, kc, vc, lens, window=window, impl="ref")
    o_paged = ops.paged_flash_decode(q, pool, LAYER, bt, lens,
                                     window=window, impl=impl)
    assert float(jnp.abs(o_paged - o_dense).max()) <= 1e-5


def test_paged_decode_gqa_single_head(rng):
    """MHA (G=1) and degenerate one-page sequences still match."""
    B, S, H, D, ps = 2, 32, 2, 8, 32          # one page per sequence
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kc = jax.random.normal(ks[1], (B, S, H, D))
    vc = jax.random.normal(ks[2], (B, S, H, D))
    lens = jnp.array([S, 3])
    pool, bt = _paged_from_dense(kc, vc, ps)
    o_dense = ops.flash_decode(q, kc, vc, lens, impl="ref")
    o_paged = ops.paged_flash_decode(q, pool, LAYER, bt, lens,
                                     impl="pallas")
    assert float(jnp.abs(o_paged - o_dense).max()) <= 1e-5


def _live_page_case(G, D, rng, hkv=2, ps=128, n_max=8):
    """A stacked f32 pool (W = 2D) and a batch whose lengths sit where the
    kernel's page walk turns: 1, one page, one page + 1, a page-block
    boundary +-1, the whole table row; then two dead rows (length 1, table
    row all the null page 0).  Every live row owns n_max distinct pages, so
    its table lists pages past its live length."""
    block = fd.pages_per_block(ps, 2 * D, 4, n_max)
    assert block < n_max, "the case needs a block boundary inside a row"
    edge = block * ps
    lens = [1, ps, ps + 1, edge - 1, edge, edge + 1, n_max * ps]
    live, dead = len(lens), 2
    n_pool = live * n_max + 1
    ks = jax.random.split(rng, 3)
    k = jax.random.normal(ks[0], (hkv, n_pool, ps, D))
    v = jax.random.normal(ks[1], (hkv, n_pool, ps, D))
    q = jax.random.normal(ks[2], (live + dead, 1, hkv * G, D))
    perm = np.random.default_rng(0).permutation(np.arange(1, n_pool))
    bt = np.zeros((live + dead, n_max), np.int32)
    bt[:live] = perm.reshape(live, n_max)
    lens = np.asarray(lens + [1] * dead, np.int32)
    return q, k, v, bt, lens


@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("mode", ["plain", "window", "softcap", "poisoned"])
def test_paged_decode_live_pages_match_ref(G, D, mode, rng):
    """The Pallas kernel (interpret mode) against ref.paged_flash_decode
    at the lengths where its live-page walk turns, with dead rows, a
    window whose first position falls mid-page inside a page block, and a
    logit softcap.  "poisoned": every page the table lists wholly past a
    row's live length, or wholly before its window, holds NaN, and the
    kernel must still equal the ref on the clean pool - no dead page
    reaches the merge."""
    ps = 128
    q, k, v, bt, lens = _live_page_case(G, D, rng, ps=ps)
    window = ps + ps // 2 + 1 if mode in ("window", "poisoned") else 0
    cap = 7.5 if mode == "softcap" else 0.0
    pool = stack_pool(k, v, 2 * D)
    want = ref.paged_flash_decode(q, pool, LAYER, bt, lens, window=window,
                                  logit_softcap=cap)
    if mode == "poisoned":
        dead_pages = [bt[r, j] for r in range(len(lens)) if bt[r].any()
                      for j in range(bt.shape[1])
                      if j * ps >= lens[r] or (j + 1) * ps <= lens[r] - window]
        assert dead_pages
        pool = pool.at[LAYER, :, np.asarray(dead_pages)].set(jnp.nan)
    got = ops.paged_flash_decode(q, pool, LAYER, bt, lens, window=window,
                                 logit_softcap=cap, impl="pallas")
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.abs(got - want).max()) <= 1e-5


# ===========================================================================
# engine parity: same trace, dense vs paged, identical greedy tokens
# ===========================================================================

@pytest.mark.parametrize("arch", ["granite-3-2b", "gemma3-4b"])
def test_engine_paged_matches_dense(arch, rng):
    cfg = get_smoke_config(arch).replace(dtype="float32")
    m = build_model(cfg)
    params = m.init(rng)
    prompts = mixed_prompts(cfg.vocab_size, lens=(3, 2, 4, 18, 2))

    def run(scfg):
        return serve_all(m, params, scfg, prompts, check=True)

    dense_out, _ = run(ServeConfig(max_batch=2, max_seq=64, max_new_tokens=5))
    paged_out, eng = run(ServeConfig(max_batch=2, max_seq=64,
                                     max_new_tokens=5, paged=True,
                                     page_size=8, num_pages=11))
    assert dense_out == paged_out
    assert eng.allocator.used_pages == 0          # everything freed
    assert eng.peak_pages > 0
    assert eng.kv_cache_bytes() < dense_kv_bytes(cfg, ServeConfig(
        max_batch=2, max_seq=64))


# ===========================================================================
# allocator: churn, free-list accounting, backpressure
# ===========================================================================

def test_allocator_churn_no_leak_no_double_alloc():
    rng = np.random.default_rng(0)
    alloc = PageAllocator(num_pages=33, page_size=8, max_batch=4,
                          max_seq=256)
    total = alloc.free_pages
    live = {}
    for step in range(200):
        slot = int(rng.integers(0, 4))
        if slot in live:
            alloc.free_slot(slot)
            del live[slot]
        else:
            n = int(rng.integers(1, 6))
            if alloc.can_alloc(n):
                pages = alloc.alloc(slot, n)
                assert 0 not in pages                 # null page never leaves
                live[slot] = pages
        # no page owned twice
        owned = [p for ps in live.values() for p in ps]
        assert len(owned) == len(set(owned))
        assert alloc.free_pages + len(owned) == total
        # block table mirrors ownership
        for s, ps in live.items():
            assert list(alloc.table[s, :len(ps)]) == ps
    for slot in list(live):
        alloc.free_slot(slot)
    assert alloc.free_pages == total
    assert (alloc.table == 0).all()


def test_allocator_out_of_pages_raises():
    alloc = PageAllocator(num_pages=5, page_size=8, max_batch=2, max_seq=256)
    alloc.alloc(0, 3)
    with pytest.raises(OutOfPages):
        alloc.alloc(1, 2)
    alloc.free_slot(0)
    assert alloc.can_alloc(4)


def test_engine_backpressure_out_of_pages(rng):
    """A pool too small for two concurrent requests serves them anyway -
    sequentially, via admission backpressure - and never errors."""
    cfg = get_smoke_config("granite-3-2b")
    m = build_model(cfg)
    params = m.init(rng)
    # each request: 8-token prompt + 4 new = 2 pages of 8; pool of 3 usable
    # pages fits ONE request at a time (2 pages) but never two
    prompts = mixed_prompts(cfg.vocab_size, lens=(8, 8, 8))
    out, eng = serve_all(m, params,
                         ServeConfig(max_batch=2, max_seq=64,
                                     max_new_tokens=4, paged=True,
                                     page_size=8, num_pages=4),
                         prompts, check=True)
    assert len(out) == 3
    assert all(len(toks) == 4 for toks in out.values())
    assert eng.peak_pages <= 3
    assert eng.allocator.used_pages == 0


def test_engine_validates_config_and_requests(rng):
    """max_seq must be a page multiple; requests must fit max_seq; the
    degenerate submissions fail at submit() with a clear error, never deep
    inside prefill or the allocator."""
    cfg = get_smoke_config("granite-3-2b")
    m = build_model(cfg)
    params = m.init(rng)
    with pytest.raises(ValueError, match="multiple of"):
        ServeEngine(m, params, ServeConfig(max_seq=60, page_size=8,
                                           paged=True))
    eng = ServeEngine(m, params,
                      ServeConfig(max_batch=2, max_seq=32, max_new_tokens=4))
    with pytest.raises(ValueError, match="does not fit"):
        eng.submit(list(range(1, 40)))
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2, 3], max_new_tokens=-1)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2, 3], max_new_tokens=0)   # 0 is NOT "use default"
    assert not eng.queue                  # nothing bad got enqueued


def test_engine_rejects_unsatisfiable_reservation(rng):
    """A reservation larger than the whole pool can never be backpressured
    into fitting - it must fail fast AT SUBMIT TIME, not queue forever or
    die inside the allocator."""
    cfg = get_smoke_config("granite-3-2b")
    m = build_model(cfg)
    params = m.init(rng)
    eng = ServeEngine(m, params,
                      ServeConfig(max_batch=2, max_seq=64, max_new_tokens=8,
                                  paged=True, page_size=8, num_pages=4))
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(1, 25)))    # needs 4 pages; pool grants 3
    assert not eng.queue


# ===========================================================================
# decode-path logit softcap: decode must match prefill (ROADMAP item)
# ===========================================================================

@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_decode_softcap_kernel_parity(impl, rng):
    """flash_decode / paged_flash_decode with softcap vs a naive oracle."""
    B, S, Hq, Hkv, D, ps, cap = 2, 32, 4, 2, 16, 8, 7.5
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    lens = jnp.array([S - 3, 5])

    G = Hq // Hkv
    qf = (q.astype(jnp.float32) / jnp.sqrt(D)).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, kc.astype(jnp.float32))
    s = cap * jnp.tanh(s / cap)
    mask = jnp.arange(S)[None, :] < lens[:, None]
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhgk,bkhd->bhgd", p, vc.astype(jnp.float32)
                      ).reshape(B, 1, Hq, D)

    got = ops.flash_decode(q, kc, vc, lens, logit_softcap=cap, impl=impl)
    assert float(jnp.abs(got - want).max()) <= 1e-5
    pool, bt = _paged_from_dense(kc, vc, ps)
    got_p = ops.paged_flash_decode(q, pool, LAYER, bt, lens,
                                   logit_softcap=cap, impl=impl)
    assert float(jnp.abs(got_p - want).max()) <= 1e-5
    # softcap must actually change the result (guard against silent no-op)
    plain = ops.flash_decode(q, kc, vc, lens, impl=impl)
    assert float(jnp.abs(got - plain).max()) > 1e-3


@pytest.mark.parametrize("paged", [False, True])
def test_decode_softcap_matches_prefill(paged, rng):
    """With attn_logit_softcap > 0, decoding token t must produce the same
    logits prefill produced at position t (dense AND paged decode path)."""
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32",
                                                   attn_logit_softcap=12.0)
    m = build_model(cfg)
    params = m.init(rng)
    toks = jnp.array([[5, 7, 11, 13, 17, 19, 23, 2]])
    logits_full, _ = m.forward(params, {"tokens": toks})
    if paged:
        cache = m.init_cache(1, 16, page_size=4, num_pages=9)
        cache["block_table"] = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        batch = {"tokens": jnp.pad(toks[:, :7], ((0, 0), (0, 1))),
                 "offset": jnp.asarray([0], jnp.int32),   # 7 tokens pad
                 "true_lens": jnp.asarray([7], jnp.int32)}  # to 8
        _, cache, lens = m.prefill_chunks(params, batch, cache,
                                          cache["block_table"])
    else:
        cache = m.init_cache(1, 16)
        _, cache, lens = m.prefill(params, {"tokens": toks[:, :7]}, cache)
    logits_dec, _ = m.decode_step(params, toks[:, 7:8], lens, cache)
    err = float(jnp.abs(logits_dec[:, 0] - logits_full[:, 7]).max())
    assert err <= 1e-4, err


def test_capacity_math_mixed_lengths():
    """The documented sizing: a paged pool covering a mixed trace is
    strictly smaller than the dense cache (the acceptance shape: 128 / 1k /
    4k prompts at max_seq = 4k)."""
    cfg = get_smoke_config("granite-3-2b")
    scfg = ServeConfig(max_batch=4, max_seq=4096, max_new_tokens=32,
                       paged=True, page_size=64)
    per_req = pages_needed(3968 + 32, 64)
    pool = scfg.max_batch * per_req // 2 + 1
    assert paged_kv_bytes(cfg, scfg, pool) < dense_kv_bytes(cfg, scfg)
    # degenerate sizing (0 = dense-equivalent) is never SMALLER than dense
    assert paged_kv_bytes(cfg, scfg, 0) >= dense_kv_bytes(cfg, scfg)
