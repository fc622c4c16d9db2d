"""Pure-jnp reference oracles for every Pallas kernel.

These are the ground truth the kernels are validated against, AND the
portable execution path used on backends without Pallas (CPU tests, the
512-device dry-run).  They are written flash-style - chunked over the KV /
time dimension with lax.scan - so they stay memory-efficient at 32K/512K
sequence lengths (no N x N materialization), mirroring the paper's
"no SRAM round-trips" structure at the XLA level.

Conventions:
  q, k, v: (batch, seq, heads, head_dim); GQA when kv heads < q heads.
  Accumulation in fp32 regardless of input dtype.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def mixed_einsum(pattern, a, b):
    """bf16 x bf16 einsum with fp32 accumulation.

    On TPU (and in the dry-run, which only compiles - REPRO_MIXED_DOTS=1)
    this is a native mixed-precision MXU dot: no fp32 copies of the operands
    are ever materialized and collectives carrying them stay bf16.  The CPU
    *runtime* cannot execute batched mixed dots (DotThunk), so tests upcast.
    """
    if jax.default_backend() == "cpu" and not os.environ.get("REPRO_MIXED_DOTS"):
        return jnp.einsum(pattern, a.astype(jnp.float32),
                          b.astype(jnp.float32))
    return jnp.einsum(pattern, a, b, preferred_element_type=jnp.float32)


def _gqa_expand(h_q: int, h_kv: int):
    assert h_q % h_kv == 0
    return h_q // h_kv


# ===========================================================================
# FlashAttention-2 forward (chunked, numerically stable)
# ===========================================================================

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: int = 0,
                    logit_softcap: float = 0.0,
                    scale: Optional[float] = None,
                    q_offset: int = 0,
                    block_kv: int = 512) -> jax.Array:
    """Chunked attention: scan over KV blocks with running (m, l, o).

    window > 0: sliding-window attention (each query attends to the last
    `window` keys, inclusive of itself).  Implies causal masking.
    q_offset: absolute position of q[0] (for chunked prefill / cross-chunk).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = _gqa_expand(Hq, Hkv)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # exp2-based exponent: exp(x) = exp2(x * log2(e)) - the paper's (and
    # hardware's) preferred form; fold the scale in once.
    LOG2E = 1.4426950408889634

    nblk = -(-Skv // block_kv)
    pad = nblk * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qf = q.reshape(B, Sq, Hkv, G, D)        # bf16; fp32 happens in the dot

    kb = jnp.moveaxis(k.reshape(B, nblk, block_kv, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nblk, block_kv, Hkv, D), 1, 0)

    q_pos = q_offset + jnp.arange(Sq)                      # (Sq,)

    def body(carry, blk):
        m, l, o = carry
        kblk, vblk, j = blk
        k_pos = j * block_kv + jnp.arange(block_kv)        # (bk,)
        s = mixed_einsum("bqhgd,bkhd->bqhgk", qf, kblk) * scale
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        mask = k_pos[None, :] <= (Skv - 1)                 # padding
        if causal or window > 0:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows (m_new == NEG_INF)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp2((s - m_safe[..., None]) * LOG2E)
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        alpha = jnp.exp2((m - m_new) * LOG2E)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = mixed_einsum("bqhgk,bkhd->bqhgd", p.astype(q.dtype), vblk)
        o_new = o * alpha[..., None] + pv
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    o0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        body, (m0, l0, o0), (kb, vb, jnp.arange(nblk)))
    o = o / jnp.maximum(l, 1e-20)[..., None]
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


# ===========================================================================
# Flash-decoding: one query token against a long KV cache
# ===========================================================================

def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 cache_len, *,
                 scale: Optional[float] = None,
                 window: int = 0,
                 logit_softcap: float = 0.0,
                 block_kv: int = 1024) -> jax.Array:
    """q: (B, 1, Hq, D); k_cache/v_cache: (B, S_max, Hkv, D); cache_len: (B,)
    valid prefix length per sequence.  Returns (B, 1, Hq, D)."""
    return _flash_decode_head_major(
        q, jnp.moveaxis(k_cache, 2, 0), jnp.moveaxis(v_cache, 2, 0),
        cache_len, scale=scale, window=window, logit_softcap=logit_softcap,
        block_kv=block_kv)


def _flash_decode_head_major(q, k, v, cache_len, *, scale, window,
                             logit_softcap, block_kv: int = 1024):
    """flash_decode over head-major (Hkv, B, S_max, D) caches.  Every
    intermediate keeps the KV head leading, so a head-sharded caller's
    slice computes exactly what the same heads compute unsharded."""
    B, Sq, Hq, D = q.shape
    assert Sq == 1
    Hkv, _, S, _ = k.shape
    G = _gqa_expand(Hq, Hkv)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    LOG2E = 1.4426950408889634
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len)

    nblk = -(-S // block_kv)
    pad = nblk * block_kv - S
    kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
    vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
    kb = jnp.moveaxis(kc.reshape(Hkv, B, nblk, block_kv, D), 2, 0)
    vb = jnp.moveaxis(vc.reshape(Hkv, B, nblk, block_kv, D), 2, 0)

    qf = jnp.moveaxis((q.astype(jnp.float32) * scale).reshape(B, Hkv, G, D),
                      1, 0)                                # (Hkv, B, G, D)

    def body(carry, blk):
        m, l, o = carry
        kblk, vblk, j = blk
        pos = j * block_kv + jnp.arange(block_kv)          # (bk,)
        # multiply-and-reduce rather than a dot: XLA picks a dot's
        # summation order from the batch shape, so a head-sharded slice
        # would round differently from the same heads unsharded
        s = jnp.sum(qf[:, :, :, None, :]
                    * kblk.astype(jnp.float32)[:, :, None], -1)
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        mask = pos[None, :] < cache_len[:, None]           # (B, bk)
        if window > 0:
            mask = mask & (pos[None, :] >= cache_len[:, None] - window)
        mask = mask[None, :, None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp2((s - m_safe[..., None]) * LOG2E)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp2((m - m_new) * LOG2E)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.sum(p[..., None] * vblk.astype(jnp.float32)[:, :, None],
                     -2)
        o_new = o * alpha[..., None] + pv
        return (m_new, l_new, o_new), None

    # varying-zero seed: under shard_map (the tensor-parallel serve path
    # wraps this kernel with the head axis sharded) the scan carry must
    # carry the same "varying manual axes" type as the body outputs, which
    # depend on the sharded cache; outside shard_map this is exactly +0.0
    vzero = jnp.sum(k[:, :, :0].astype(jnp.float32))
    m0 = jnp.full((Hkv, B, G), NEG_INF, jnp.float32) + vzero
    l0 = jnp.zeros((Hkv, B, G), jnp.float32) + vzero
    o0 = jnp.zeros((Hkv, B, G, D), jnp.float32) + vzero
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0),
                                (kb, vb, jnp.arange(nblk)))
    o = jnp.moveaxis(o / jnp.maximum(l, 1e-20)[..., None], 0, 1)
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


def _gather_pages(kv_pages, layer, tables):
    """Contiguous head-major (R, Hkv, n_max * page_size, W) strips of the
    pages each row of `tables` (R, n_max) names, at layer `layer` of the
    stacked lane-dense (L, Hkv, P, page_size, W) pool."""
    R, n_max = tables.shape
    _, Hkv, _, ps, W = kv_pages.shape
    # the head slice splits the layer and table indices, so their
    # dimensions lead: (R, n_max, Hkv, ps, W)
    kv = jnp.moveaxis(kv_pages[layer, :, tables], 2, 1)
    return kv.reshape(R, Hkv, n_max * ps, W)


def kv_page_write(kv_pages, layer, new, tables, starts, ends):
    """Write token rows into layer `layer` of the stacked page pool
    (reference of kernels/kv_write.py, an XLA scatter).

    new: (R, S, Hkv, W) packed K/V rows; row r's token i lands at
    absolute position starts[r] + i through the block-table row
    tables[r] (R, n_max); positions at or past ends[r] are not written."""
    R, S = new.shape[:2]
    _, _, P, ps, _ = kv_pages.shape
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(S)[None, :]
    page = jnp.take_along_axis(
        tables, jnp.minimum(pos // ps, tables.shape[1] - 1), axis=1)
    # out-of-range page ids are dropped by the scatter
    page = jnp.where(pos < jnp.asarray(ends, jnp.int32)[:, None], page, P)
    # the scalar layer and the (R, S) index arrays are split by the head
    # slice, so the update is (R, S, Hkv, W)
    return kv_pages.at[layer, :, page, pos % ps].set(
        new.astype(kv_pages.dtype), mode="drop")


def paged_flash_decode(q, kv_pages, layer, block_table, cache_len, *,
                       scale: Optional[float] = None,
                       window: int = 0,
                       logit_softcap: float = 0.0) -> jax.Array:
    """Decode against a paged KV cache (reference oracle).

    q: (B, 1, Hq, D); kv_pages: (L, Hkv, P, page_size, W) stacked
    lane-dense pool (K in lanes [0, D), V in [D, 2D)); layer: the layer
    read; block_table: (B, n_max) int32 page ids (position-major, unused
    entries pointing at any valid page); cache_len: (B,) or scalar.

    Gathers each sequence's pages into a contiguous head-major strip and
    runs the chunked dense decode - the ground truth the Pallas
    block-table kernel is validated against, and the portable
    paged-serving path off-TPU.
    """
    D = q.shape[-1]
    block_table = jnp.asarray(block_table, jnp.int32)
    kv = jnp.moveaxis(_gather_pages(kv_pages, layer, block_table), 1, 0)
    return _flash_decode_head_major(q, kv[..., :D], kv[..., D:2 * D],
                                    cache_len, scale=scale, window=window,
                                    logit_softcap=logit_softcap)


def batched_paged_prefill_attention(q, kv_pages, layer, page_tables,
                                    q_offsets, true_lens, q_lens=None, *,
                                    scale: Optional[float] = None,
                                    window: int = 0,
                                    logit_softcap: float = 0.0) -> jax.Array:
    """Ragged batched mid-prompt chunk-prefill attention through per-row
    block tables (reference oracle): K chunks of K different sequences,
    each at its own absolute offset, in one call.

    q: (K, S, Hq, D) chunk queries; row k sits at absolute positions
    q_offsets[k] + arange(S), zero-padded past its real length (ragged
    rows share one static S); kv_pages: (L, Hkv, P, page_size, W) stacked
    lane-dense pool, read at layer `layer`; page_tables: (K, n_max)
    per-row block-table rows (each row's
    chunk K/V already written into its pages, as is all K/V for positions
    < q_offsets[k]); true_lens: (K,) each row's prefill cursor after its
    last REAL token - columns at or past it are masked, the gather-level
    analogue of the Pallas kernel's page skip.  A dead padding row
    (true_len == 0, all-null table) returns exactly zero.  Each real
    query row attends causally over positions 0..q_offset+row - earlier
    pages and the chunk itself, so composing chunks left to right matches
    one monolithic causal prefill exactly.

    Gathers each row's pages into a contiguous strip and applies the
    offset causal mask - the ground truth the Pallas chunk kernel
    (kernels/paged_prefill.py) is validated against, and the portable
    chunked / prefix-cached serving path off-TPU.
    """
    K, S, Hq, D = q.shape
    Hkv = kv_pages.shape[1]
    G = _gqa_expand(Hq, Hkv)
    sc = scale if scale is not None else 1.0 / math.sqrt(D)
    LOG2E = 1.4426950408889634
    page_tables = jnp.asarray(page_tables, jnp.int32)
    # (K, n_max*ps, Hkv, W) strips
    kv = jnp.moveaxis(_gather_pages(kv_pages, layer, page_tables), 1, 2)
    k, v = kv[..., :D], kv[..., D:2 * D]
    Skv = k.shape[1]
    qf = (q.astype(jnp.float32) * sc).reshape(K, S, Hkv, G, D)
    s = jnp.einsum("bshgd,bkhd->bshgk", qf, k.astype(jnp.float32))
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    row = jnp.asarray(q_offsets, jnp.int32)[:, None] + jnp.arange(S)[None, :]
    col = jnp.arange(Skv)
    tl = jnp.asarray(true_lens, jnp.int32)
    mask = (col[None, None, :] <= row[:, :, None]) \
        & (col[None, None, :] < tl[:, None, None])
    if window > 0:
        mask = mask & (col[None, None, :] > row[:, :, None] - window)
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.where(mask[:, :, None, None, :],
                  jnp.exp2((s - m_safe) * LOG2E), 0.0)
    l = jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-20)
    o = jnp.einsum("bshgk,bkhd->bshgd", p / l, v.astype(jnp.float32))
    # q_lens: per-row REAL query count (speculative verify rows hold
    # 1 + m real queries).  Rows at or past it are forced to exactly
    # zero, matching the Pallas kernel's draft-length lane; the default
    # (true_lens - q_offsets) keeps the historical chunk contract.
    ql = jnp.clip(tl - jnp.asarray(q_offsets, jnp.int32), 0, S) \
        if q_lens is None else jnp.asarray(q_lens, jnp.int32)
    qpos = jnp.arange(S, dtype=jnp.int32)[None, :]
    o = jnp.where((qpos < ql[:, None])[:, :, None, None, None], o, 0.0)
    return o.reshape(K, S, Hq, D).astype(q.dtype)


def paged_prefill_attention(q, kv_pages, layer, page_row, q_offset, *,
                            scale: Optional[float] = None,
                            window: int = 0,
                            logit_softcap: float = 0.0) -> jax.Array:
    """Single-sequence mid-prompt chunk prefill (reference oracle): the
    K=1 special case of batched_paged_prefill_attention.  q: (1, S, Hq, D);
    page_row: (n_max,); every chunk position is treated as real
    (true_len = q_offset + S), the historical single-row contract."""
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    return batched_paged_prefill_attention(
        q, kv_pages, layer, jnp.asarray(page_row, jnp.int32)[None],
        off, off + q.shape[1], scale=scale, window=window,
        logit_softcap=logit_softcap)


def combine_partial_softmax(m_parts, l_parts, o_parts):
    """Merge per-shard partial (m, l, o) triples - the distributed analogue
    of the paper's tier merge, used by sequence-parallel decode.

    m_parts: (P, ...), l_parts: (P, ...), o_parts: (P, ..., D)
    """
    LOG2E = 1.4426950408889634
    m = jnp.max(m_parts, axis=0)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    alpha = jnp.exp2((m_parts - m_safe[None]) * LOG2E)
    alpha = jnp.where(m_parts <= NEG_INF / 2, 0.0, alpha)
    l = jnp.sum(l_parts * alpha, axis=0)
    o = jnp.sum(o_parts * alpha[..., None], axis=0)
    return m, l, o


# ===========================================================================
# Mamba2 (SSD) selective state space
# ===========================================================================

def mamba2_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, *, chunk: int = 0) -> jax.Array:
    """Mamba2 SSD recurrence (per-head scalar decay).

      h_t = exp(-dt_t * A) * h_{t-1} + dt_t * (B_t outer x_t)
      y_t = C_t . h_t

    x:  (B, S, H, P)   head channels
    dt: (B, S, H)      positive step sizes (already softplus'ed)
    A:  (H,)           positive per-head decay rate
    Bm: (B, S, N)      input projection (shared across heads, ngroups=1)
    Cm: (B, S, N)      output projection
    returns y: (B, S, H, P)
    """
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)

    decay = jnp.exp(-dtf * Af[None, None, :])              # (B,S,H)

    def step(h, inp):
        xt, dtt, dct, bt, ct = inp                         # (B,H,P),(B,H),(B,H),(B,N),(B,N)
        # h: (B, H, P, N)
        inject = (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        h = h * dct[..., None, None] + inject
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(decay, 1, 0), jnp.moveaxis(Bf, 1, 0),
          jnp.moveaxis(Cf, 1, 0))
    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def mamba2_step(h: jax.Array, x_t: jax.Array, dt_t: jax.Array, A: jax.Array,
                B_t: jax.Array, C_t: jax.Array):
    """Single decode step.  h: (B,H,P,N) fp32 state.  Returns (h', y_t)."""
    decay = jnp.exp(-dt_t.astype(jnp.float32) * A.astype(jnp.float32)[None])
    inject = (dt_t[..., None] * x_t.astype(jnp.float32))[..., None] \
        * B_t.astype(jnp.float32)[:, None, None, :]
    h = h * decay[..., None, None] + inject
    y = jnp.einsum("bhpn,bn->bhp", h, C_t.astype(jnp.float32))
    return h, y.astype(x_t.dtype)


# ===========================================================================
# RWKV6 (Finch) WKV recurrence with data-dependent decay
# ===========================================================================

def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array) -> jax.Array:
    """WKV6:  S_t = diag(w_t) S_{t-1} + k_t^T v_t
              y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

    r, k: (B, S, H, K); v: (B, S, H, V); w: (B, S, H, K) decay in (0,1);
    u: (H, K) bonus.  Returns (B, S, H, V).
    """
    Bsz, S, H, K = r.shape
    V = v.shape[-1]
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(Sstate, inp):
        rt, kt, vt, wt = inp                               # (B,H,K),(B,H,K),(B,H,V),(B,H,K)
        kv = kt[..., :, None] * vt[..., None, :]           # (B,H,K,V)
        y = jnp.einsum("bhk,bhkv->bhv", rt, Sstate + uf[None, :, :, None] * kv)
        Sstate = Sstate * wt[..., :, None] + kv
        return Sstate, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, wf))
    S0 = jnp.zeros((Bsz, H, K, V), jnp.float32)
    _, ys = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype)


def rwkv6_scan_chunked_state(r, k, v, w, u, *, chunk: int = 32):
    """Chunked WKV6 returning (y, final_state) - used by true prefill."""
    return _rwkv6_chunked(r, k, v, w, u, chunk=chunk)


def rwkv6_scan_chunked(r, k, v, w, u, *, chunk: int = 32):
    return _rwkv6_chunked(r, k, v, w, u, chunk=chunk)[0]


def _rwkv6_chunked(r, k, v, w, u, *, chunk: int = 32):
    """Chunked matrix-form WKV6 (same math as kernels/rwkv6_scan.py) in pure
    jnp: the backward pass only saves per-CHUNK states instead of per-step
    states, cutting training memory by ~chunk_size (the paper's fusion
    principle applied to the recurrence at the XLA level)."""
    Bsz, S, H, K = r.shape
    V = v.shape[-1]
    pad = (-S) % chunk
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        w = jnp.pad(w, zpad, constant_values=1.0)
    Sp = S + pad
    nc = Sp // chunk
    uf = u.astype(jnp.float32)

    def to_chunks(t):
        return jnp.moveaxis(t.reshape(Bsz, nc, chunk, H, t.shape[-1]), 1, 0)

    rc, kc, vc, wc = map(to_chunks, (r, k, v, w))   # (nc,B,T,H,·)

    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)

    def chunk_step(Sst, inp):
        rt, kt, vt, wt = (t.astype(jnp.float32) for t in inp)  # (B,T,H,·)
        logw = jnp.log(jnp.maximum(wt, 1e-30))
        cw = jnp.cumsum(logw, axis=1)
        cw_prev = cw - logw
        r_dec = rt * jnp.exp(cw_prev)
        k_dec = kt * jnp.exp(-cw)
        A = jnp.einsum("bthk,bshk->bhts", r_dec, k_dec) * tri[None, None]
        y = jnp.einsum("bhts,bshv->bthv", A, vt)
        diag = jnp.sum(rt * uf[None, None] * kt, -1, keepdims=True)
        y = y + diag * vt
        y = y + jnp.einsum("bthk,bhkv->bthv", r_dec, Sst)
        k_out = k_dec * jnp.exp(cw[:, -1])[:, None]
        S_new = jnp.einsum("bthk,bthv->bhkv", k_out, vt)
        Sst = jnp.exp(cw[:, -1])[..., None] * Sst + S_new
        return Sst, y.astype(r.dtype)

    S0 = jnp.zeros((Bsz, H, K, V), jnp.float32)
    S_fin, ys = jax.lax.scan(chunk_step, S0, (rc, kc, vc, wc))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, Sp, H, V)
    return y[:, :S], S_fin


def mamba2_scan_chunked_state(x, dt, A, Bm, Cm, *, chunk: int = 128):
    """Chunked SSD returning (y, final_state) - used by true prefill."""
    return _mamba2_chunked(x, dt, A, Bm, Cm, chunk=chunk)


def mamba2_scan_chunked(x, dt, A, Bm, Cm, *, chunk: int = 128):
    return _mamba2_chunked(x, dt, A, Bm, Cm, chunk=chunk)[0]


def _mamba2_chunked(x, dt, A, Bm, Cm, *, chunk: int = 128):
    """Chunked matrix-form SSD (same math as kernels/mamba2_scan.py) in pure
    jnp; backward saves per-chunk states only."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk
    Af = A.astype(jnp.float32)

    xc = jnp.moveaxis(x.reshape(Bsz, nc, chunk, H, P), 1, 0)
    dtc = jnp.moveaxis(dt.reshape(Bsz, nc, chunk, H), 1, 0)
    Bc = jnp.moveaxis(Bm.reshape(Bsz, nc, chunk, N), 1, 0)
    Cc = jnp.moveaxis(Cm.reshape(Bsz, nc, chunk, N), 1, 0)

    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))

    def chunk_step(h, inp):
        xt, dtt, bt, ct = inp
        xf = xt.astype(jnp.float32)
        dtf = dtt.astype(jnp.float32)
        bf = bt.astype(jnp.float32)
        cf = ct.astype(jnp.float32)
        log_a = -dtf * Af[None, None]                    # (B,T,H)
        csum = jnp.cumsum(log_a, axis=1)
        Mdec = jnp.exp(csum[:, :, None] - csum[:, None, :])   # (B,T,T,H)
        M = Mdec * tri[None, :, :, None]
        CB = jnp.einsum("btn,bsn->bts", cf, bf)
        xw = xf * dtf[..., None]                         # (B,T,H,P)
        y = jnp.einsum("bts,btsh,bshp->bthp", CB, M, xw)
        y = y + jnp.exp(csum)[..., None] * jnp.einsum("btn,bhpn->bthp", cf, h)
        wout = jnp.exp(csum[:, -1][:, None] - csum)[..., None] * xw
        h_new = jnp.einsum("bthp,btn->bhpn", wout, bf)
        h = jnp.exp(csum[:, -1])[..., None, None] * h + h_new
        return h, y.astype(x.dtype)

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    h_fin, ys = jax.lax.scan(chunk_step, h0, (xc, dtc, Bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, Sp, H, P)
    return y[:, :S], h_fin


def rwkv6_step(Sstate: jax.Array, r_t, k_t, v_t, w_t, u):
    """Single decode step.  Sstate: (B,H,K,V) fp32."""
    kv = k_t.astype(jnp.float32)[..., :, None] * v_t.astype(jnp.float32)[..., None, :]
    y = jnp.einsum("bhk,bhkv->bhv", r_t.astype(jnp.float32),
                   Sstate + u.astype(jnp.float32)[None, :, :, None] * kv)
    Sstate = Sstate * w_t.astype(jnp.float32)[..., :, None] + kv
    return Sstate, y.astype(r_t.dtype)


# ===========================================================================
# Naive (quadratic) attention - for small-shape cross-checks only
# ===========================================================================

def naive_attention(q, k, v, *, causal=True, window=0, logit_softcap=0.0,
                    scale=None):
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = _gqa_expand(Hq, Hkv)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qf * scale, k.astype(jnp.float32))
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal or window > 0:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)
