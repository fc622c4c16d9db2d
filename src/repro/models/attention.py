"""Attention module: MHA / GQA, RoPE, sliding-window, QK-norm, cross-attn,
KV-cache decode (incl. sequence-parallel long-context decode)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding import constrain
from .layers import dense, dense_init, pdtype, rms_head_norm, rope


def attn_init(key, cfg: ModelConfig):
    dt = pdtype(cfg)
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    ks = jax.random.split(key, 6)
    p = {"wq": dense_init(ks[0], d, nq, dt),
         "wk": dense_init(ks[1], d, nkv, dt),
         "wv": dense_init(ks[2], d, nkv, dt),
         "wo": dense_init(ks[3], nq, d, dt, scale=1.0 / math.sqrt(nq))}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _qkv(params, x, cfg: ModelConfig, kv_x=None):
    B = x.shape[0]
    kv_src = x if kv_x is None else kv_x
    q = dense(params["wq"], x).reshape(B, x.shape[1], cfg.n_heads, cfg.head_dim)
    k = dense(params["wk"], kv_src).reshape(
        B, kv_src.shape[1], cfg.n_kv_heads, cfg.head_dim)
    v = dense(params["wv"], kv_src).reshape(
        B, kv_src.shape[1], cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_head_norm(q, params["q_norm"])
        k = rms_head_norm(k, params["k_norm"])
    return q, k, v


def attn_forward(params, x, cfg: ModelConfig, *, causal: bool = True,
                 window: int = 0, positions: Optional[jax.Array] = None,
                 kv_x: Optional[jax.Array] = None,
                 impl: Optional[str] = None) -> jax.Array:
    """Full-sequence attention (training / prefill).  x: (B, S, D)."""
    q, k, v = _qkv(params, x, cfg, kv_x)
    if cfg.use_rope and kv_x is None:
        pos = positions if positions is not None \
            else jnp.arange(x.shape[1])
        q = rope(q, pos, cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, pos, cfg.rope_theta, cfg.rope_scaling)
    q = constrain(q, "bshd")
    # gather K/V across the sequence shards once, before the block scan
    k = constrain(k, "kv_rep")
    v = constrain(v, "kv_rep")
    o = ops.flash_attention(q, k, v, causal=causal, window=window,
                            logit_softcap=cfg.attn_logit_softcap, impl=impl)
    o = constrain(o, "bshd")
    B, S = x.shape[:2]
    return dense(params["wo"], o.reshape(B, S, cfg.n_heads * cfg.head_dim))


def attn_prefill(params, x, cfg: ModelConfig, cache_k, cache_v, *,
                 window: int = 0, impl: Optional[str] = None):
    """Prefill: run full attention AND fill the cache prefix.

    cache_k/v: (B, S_max, Hkv, D).  Assumes prefill starts at position 0.
    Returns (y, cache_k, cache_v)."""
    q, k, v = _qkv(params, x, cfg)
    S = x.shape[1]
    if cfg.use_rope:
        pos = jnp.arange(S)
        q = rope(q, pos, cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, pos, cfg.rope_theta, cfg.rope_scaling)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, 0, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, 0, 0, 0))
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            logit_softcap=cfg.attn_logit_softcap, impl=impl)
    B = x.shape[0]
    y = dense(params["wo"], o.reshape(B, S, cfg.n_heads * cfg.head_dim))
    return y, cache_k, cache_v


def attn_prefill_paged(params, x, cfg: ModelConfig, k_pages, v_pages,
                       page_ids, *, window: int = 0,
                       impl: Optional[str] = None):
    """Prefill one sequence's prompt into its KV pages.

    x: (1, S, D) with S a multiple of the page size (pad the prompt
    upstream; trailing pad K/V is masked by `lens` at decode time and gets
    overwritten as decode advances).  k/v_pages: (Hkv, P, page_size, D)
    head-major global pool; page_ids: (S // page_size,) pages owned by this sequence,
    position-major.  Returns (y, k_pages, v_pages)."""
    q, k, v = _qkv(params, x, cfg)
    S = x.shape[1]
    page_size = k_pages.shape[2]
    n = S // page_size
    if cfg.use_rope:
        pos = jnp.arange(S)
        q = rope(q, pos, cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, pos, cfg.rope_theta, cfg.rope_scaling)
    kp = k[0].reshape(n, page_size, cfg.n_kv_heads, cfg.head_dim)
    vp = v[0].reshape(n, page_size, cfg.n_kv_heads, cfg.head_dim)
    k_pages = k_pages.at[:, page_ids].set(
        jnp.moveaxis(kp, 2, 0).astype(k_pages.dtype))
    v_pages = v_pages.at[:, page_ids].set(
        jnp.moveaxis(vp, 2, 0).astype(v_pages.dtype))
    o = ops.flash_attention(q, k, v, causal=True, window=window,
                            logit_softcap=cfg.attn_logit_softcap, impl=impl)
    y = dense(params["wo"], o.reshape(1, S, cfg.n_heads * cfg.head_dim))
    return y, k_pages, v_pages


def _tp_pool_constrain(pages, tp_mesh):
    """Pin a KV page pool to its head-sharded layout on the serve mesh.

    The engine commits the pools head-sharded at init; this re-asserts the
    layout on the scatter output inside jit (per-layer pool slices inside
    the layer scan carry no committed sharding of their own), so the
    scatter stays a local per-shard write instead of a resharding round
    trip.  The scattered K/V values are computed from replicated
    activations, so the write is pure data movement - sharding it cannot
    change any attention result."""
    if tp_mesh is None:
        return pages
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        pages, NamedSharding(tp_mesh, P("model", None, None, None)))


def attn_decode_paged(params, x, cfg: ModelConfig, k_pages, v_pages,
                      block_table, lens, *, window: int = 0,
                      impl: Optional[str] = None, tp_mesh=None):
    """Single-token decode through the block table.

    x: (B, 1, D); k/v_pages: (Hkv, P, page_size, D) head-major global pool;
    block_table: (B, n_max) page ids; lens: (B,) current lengths (the new
    token's K/V is scattered into page lens // page_size at offset
    lens % page_size).  Idle slots (lens == 0, block-table row zeroed) write
    into the reserved null page 0, never into live pages.
    tp_mesh: head-shard the pools and the decode kernel across the serve
    mesh's "model" axis (kernels/ops.py paged_flash_decode); the attention
    output gathers back to replicated so wo and everything after run with
    tp=1 numerics.  Returns (y, k_pages, v_pages)."""
    B = x.shape[0]
    q, k, v = _qkv(params, x, cfg)
    if cfg.use_rope:
        q = rope(q, lens[:, None], cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, lens[:, None], cfg.rope_theta, cfg.rope_scaling)
    page_size = k_pages.shape[2]
    bidx = jnp.arange(B)
    page = block_table[bidx, lens // page_size]
    off = lens % page_size
    # k[:, 0] is (B, Hkv, D); the pool is indexed (Hkv, page, off)
    k_pages = k_pages.at[:, page, off].set(
        jnp.moveaxis(k[:, 0], 1, 0).astype(k_pages.dtype))
    v_pages = v_pages.at[:, page, off].set(
        jnp.moveaxis(v[:, 0], 1, 0).astype(v_pages.dtype))
    k_pages = _tp_pool_constrain(k_pages, tp_mesh)
    v_pages = _tp_pool_constrain(v_pages, tp_mesh)
    o = ops.paged_flash_decode(q, k_pages, v_pages, block_table, lens + 1,
                               window=window,
                               logit_softcap=cfg.attn_logit_softcap,
                               impl=impl, tp_mesh=tp_mesh)
    y = dense(params["wo"], o.reshape(B, 1, cfg.n_heads * cfg.head_dim))
    return y, k_pages, v_pages


def attn_prefill_chunks_paged(params, x, cfg: ModelConfig, k_pages, v_pages,
                              page_tables, offsets, true_lens, *,
                              q_lens=None, window: int = 0,
                              impl: Optional[str] = None, tp_mesh=None):
    """Prefill a RAGGED BATCH of mid-prompt chunks - K chunks of K
    different sequences, each at its own prompt position - into their
    pages, in one pass.

    x: (K, S, D); row k holds a contiguous run of prompt tokens at
    absolute positions offsets[k] + arange(S), zero-padded past its real
    length (true_lens[k] - offsets[k]).  Pages already holding K/V for
    positions < offsets[k] (cached prefix + earlier chunks) sit at the
    front of row k's block-table row page_tables[k].  Each row's chunk
    K/V is scattered token-by-token through its table row - a chunk need
    not start on a page boundary - with PAD positions redirected to the
    null page 0, so two chunks of the SAME sequence packed into one batch
    never collide (row A's pad tail would otherwise race row B's real
    writes at the same positions).  Then all rows' queries attend over
    every earlier position AND their own chunk via the offset-causal
    batched kernel (kernels/paged_prefill.py), so packing the
    scheduler's whole per-tick chunk plan into ONE launch is exact.
    Dead padding rows (true_len == 0, all-null table row) write only to
    the null page and return garbage rows the caller discards.
    Returns (y, k_pages, v_pages)."""
    q, k, v = _qkv(params, x, cfg)
    K, S = x.shape[:2]
    page_size = k_pages.shape[2]
    n_max = page_tables.shape[1]
    pos = jnp.asarray(offsets, jnp.int32)[:, None] + jnp.arange(S)[None, :]
    if cfg.use_rope:
        q = rope(q, pos, cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, pos, cfg.rope_theta, cfg.rope_scaling)
    valid = pos < jnp.asarray(true_lens, jnp.int32)[:, None]    # (K, S)
    pidx = jnp.minimum(pos // page_size, n_max - 1)
    pages = jnp.where(valid, jnp.take_along_axis(page_tables, pidx, axis=1),
                      0)
    offs = jnp.where(valid, pos % page_size, 0)
    # k is (K, S, Hkv, D); the pool is indexed (Hkv, page, off)
    k_pages = k_pages.at[:, pages, offs].set(
        jnp.moveaxis(k, 2, 0).astype(k_pages.dtype))
    v_pages = v_pages.at[:, pages, offs].set(
        jnp.moveaxis(v, 2, 0).astype(v_pages.dtype))
    k_pages = _tp_pool_constrain(k_pages, tp_mesh)
    v_pages = _tp_pool_constrain(v_pages, tp_mesh)
    o = ops.batched_paged_prefill_attention(
        q, k_pages, v_pages, page_tables, offsets, true_lens, q_lens,
        window=window, logit_softcap=cfg.attn_logit_softcap, impl=impl,
        tp_mesh=tp_mesh)
    y = dense(params["wo"], o.reshape(K, S, cfg.n_heads * cfg.head_dim))
    return y, k_pages, v_pages


def attn_prefill_chunk_paged(params, x, cfg: ModelConfig, k_pages, v_pages,
                             page_row, offset, *, window: int = 0,
                             impl: Optional[str] = None):
    """Prefill one MID-PROMPT chunk of one sequence's prompt into its
    pages: the K=1 special case of attn_prefill_chunks_paged.

    x: (1, S, D) holds a contiguous run of prompt tokens at absolute
    positions offset + arange(S) - the uncached suffix after a
    prefix-cache hit (serve/prefix_cache.py), or a single budget chunk.
    Every position of x is treated as real (true_len = offset + S): the
    historical single-row contract, where trailing pad K/V lands in the
    sequence's own reserved pages and is masked by `lens` at decode time.
    Returns (y, k_pages, v_pages)."""
    off = jnp.asarray(offset, jnp.int32).reshape(1)
    return attn_prefill_chunks_paged(
        params, x, cfg, k_pages, v_pages,
        jnp.asarray(page_row, jnp.int32)[None], off, off + x.shape[1],
        window=window, impl=impl)


# the prefix-cache suffix is the final-chunk special case
attn_prefill_suffix_paged = attn_prefill_chunk_paged


def attn_decode(params, x, cfg: ModelConfig, cache_k, cache_v, lens, *,
                window: int = 0, impl: Optional[str] = None,
                seq_parallel: bool = False, cross: bool = False):
    """Single-token decode.  x: (B, 1, D); cache: (B, S_max, Hkv, D);
    lens: (B,) current lengths (the new token is written at lens).

    cross=True: cross-attention - cache holds precomputed encoder K/V of
    length `lens`; no cache update, no RoPE.
    Returns (y, cache_k, cache_v)."""
    B = x.shape[0]
    q, k, v = _qkv(params, x, cfg)
    if not cross:
        if cfg.use_rope:
            q = rope(q, lens[:, None], cfg.rope_theta, cfg.rope_scaling)
            k = rope(k, lens[:, None], cfg.rope_theta, cfg.rope_scaling)
        # scatter the new K/V at position `lens` per sequence
        bidx = jnp.arange(B)
        cache_k = cache_k.at[bidx, lens].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[bidx, lens].set(v[:, 0].astype(cache_v.dtype))
        attend_len = lens + 1
    else:
        attend_len = lens

    if seq_parallel:
        # naive form: XLA SPMD partitions the softmax reductions over the
        # seq-sharded cache (partial-softmax merge across chips)
        o = ops.decode_attention_naive(q, cache_k, cache_v, attend_len,
                                       logit_softcap=cfg.attn_logit_softcap)
    else:
        o = ops.flash_decode(q, cache_k, cache_v, attend_len, window=window,
                             logit_softcap=cfg.attn_logit_softcap,
                             impl=impl)
    y = dense(params["wo"], o.reshape(B, 1, cfg.n_heads * cfg.head_dim))
    return y, cache_k, cache_v
