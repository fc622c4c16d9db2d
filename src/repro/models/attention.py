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
from .layers import bias_init, dense_init, linear, pdtype, rms_head_norm, rope


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
    if cfg.bias:
        kb = jax.random.split(ks[4], 4)
        for k_, (name, n) in zip(kb, (("bq", nq), ("bk", nkv), ("bv", nkv),
                                      ("bo", d))):
            p[name] = bias_init(k_, n, dt)
    return p


def _qkv(params, x, cfg: ModelConfig, kv_x=None):
    """q, k, v of x (k and v of kv_x for cross-attention), biases added
    where the config has them: before RoPE and before any cache write.

    The barrier keeps the split into heads out of the three dots.  Folded
    in, it turns each into a convolution that wants its weight as
    (heads, head_dim, d_model), so inside a layer scan the TPU compiler
    slices and transposes a copy of the layer's wq/wk/wv every step;
    kept out, each stays a plain dot that reads its layer of the stacked
    weight in place (tests/test_chip_compile.py guards this)."""
    B = x.shape[0]
    kv_src = x if kv_x is None else kv_x
    q, k, v = jax.lax.optimization_barrier(
        (linear(params, "q", x, cfg), linear(params, "k", kv_src, cfg),
         linear(params, "v", kv_src, cfg)))
    q = q.reshape(B, x.shape[1], cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, kv_src.shape[1], cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, kv_src.shape[1], cfg.n_kv_heads, cfg.head_dim)
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
    return linear(params, "o", o.reshape(B, S, cfg.n_heads * cfg.head_dim),
                  cfg)


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
    y = linear(params, "o", o.reshape(B, S, cfg.n_heads * cfg.head_dim),
               cfg)
    return y, cache_k, cache_v


def layer_window(cfg: ModelConfig, flag, attend):
    """Run `attend(window)` under the gemma3 local:global per-layer cond
    (window must be static for masking, so both paths live under lax.cond;
    flag > 0 marks a global layer)."""
    if cfg.sliding_window and cfg.global_every:
        return jax.lax.cond(flag > 0,
                            lambda: attend(0),
                            lambda: attend(cfg.sliding_window))
    return attend(cfg.sliding_window)


def attn_decode_paged(params, x, cfg: ModelConfig, kv_pages, layer, flag,
                      block_table, lens, plan, *, impl: Optional[str] = None,
                      tp_mesh=None):
    """Single-token decode through the block table.

    x: (B, 1, D); kv_pages: the stacked (L, Hkv, P, page_size, W) pool,
    written and read at layer `layer` (flag: the layer's is-global flag,
    layer_window); block_table: (B, n_max) page ids; lens: (B,) current
    lengths (the new token's K/V goes into page lens // page_size at
    offset lens % page_size; plan: ops.kv_write_plan of that write,
    computed once for all layers).  Idle slots (lens == 0, block-table
    row zeroed) write into the reserved null page 0, never into live
    pages.
    tp_mesh: head-shard the pool, the page write and the decode kernel
    across the serve mesh's "model" axis (kernels/ops.py); the attention
    output gathers back to replicated so wo and everything after run with
    tp=1 numerics.  Returns (y, kv_pages)."""
    B = x.shape[0]
    q, k, v = _qkv(params, x, cfg)
    if cfg.use_rope:
        q = rope(q, lens[:, None], cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, lens[:, None], cfg.rope_theta, cfg.rope_scaling)
    kv_pages = ops.kv_page_write(kv_pages, layer, k, v, plan, impl=impl,
                                 tp_mesh=tp_mesh)
    o = layer_window(cfg, flag, lambda w: ops.paged_flash_decode(
        q, kv_pages, layer, block_table, lens + 1, window=w,
        logit_softcap=cfg.attn_logit_softcap, impl=impl, tp_mesh=tp_mesh))
    y = linear(params, "o", o.reshape(B, 1, cfg.n_heads * cfg.head_dim),
               cfg)
    return y, kv_pages


def attn_prefill_chunks_paged(params, x, cfg: ModelConfig, kv_pages, layer,
                              flag, page_tables, offsets, true_lens, plan, *,
                              q_lens=None, impl: Optional[str] = None,
                              tp_mesh=None):
    """Prefill a RAGGED BATCH of mid-prompt chunks - K chunks of K
    different sequences, each at its own prompt position - into their
    pages, in one pass.

    x: (K, S, D); row k holds a contiguous run of prompt tokens at
    absolute positions offsets[k] + arange(S), zero-padded past its real
    length (true_lens[k] - offsets[k]).  Pages already holding K/V for
    positions < offsets[k] (cached prefix + earlier chunks) sit at the
    front of row k's block-table row page_tables[k].  Each row's chunk
    K/V goes into layer `layer` of the stacked pool through its table row
    (ops.kv_page_write, with plan the ops.kv_write_plan of the rows,
    computed once for all layers) - a chunk need not start on a page
    boundary, and
    PAD positions are not written, so two chunks of the SAME sequence
    packed into one batch never collide (row A's pad tail would otherwise
    race row B's real writes at the same positions).  Then all rows'
    queries attend over every earlier position AND their own chunk via
    the offset-causal batched kernel (kernels/paged_prefill.py), so
    packing the scheduler's whole per-tick chunk plan into ONE launch is
    exact.  Dead padding rows (true_len == 0, all-null table row) write
    nothing and return garbage rows the caller discards.  flag: the
    layer's is-global flag (layer_window).  Returns (y, kv_pages)."""
    q, k, v = _qkv(params, x, cfg)
    K, S = x.shape[:2]
    pos = jnp.asarray(offsets, jnp.int32)[:, None] + jnp.arange(S)[None, :]
    if cfg.use_rope:
        q = rope(q, pos, cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, pos, cfg.rope_theta, cfg.rope_scaling)
    kv_pages = ops.kv_page_write(kv_pages, layer, k, v, plan, impl=impl,
                                 tp_mesh=tp_mesh)
    o = layer_window(cfg, flag, lambda w: ops.batched_paged_prefill_attention(
        q, kv_pages, layer, page_tables, offsets, true_lens, q_lens,
        window=w, logit_softcap=cfg.attn_logit_softcap, impl=impl,
        tp_mesh=tp_mesh))
    y = linear(params, "o", o.reshape(K, S, cfg.n_heads * cfg.head_dim),
               cfg)
    return y, kv_pages


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
    y = linear(params, "o", o.reshape(B, 1, cfg.n_heads * cfg.head_dim),
               cfg)
    return y, cache_k, cache_v
