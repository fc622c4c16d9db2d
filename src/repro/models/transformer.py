"""Block assembly and scan-over-layers stacks for every architecture family.

Families:
  dense / moe / vlm : [norm -> attn -> res] [norm -> (mlp|moe) -> res]
                      gemma3 pattern: every `global_every`-th layer global,
                      the rest sliding-window (lax.cond on a per-layer flag)
  hybrid (zamba2)   : mamba2 blocks; after every k-th block a SHARED-weight
                      attention+MLP block (weights closed over, not scanned)
  ssm (rwkv6)       : time-mix + channel-mix
  audio (whisper)   : encoder stack (non-causal) + decoder stack (causal
                      self-attn + cross-attn)

All stacks scan over layer-stacked parameter pytrees (leading L axis), which
keeps HLO size and compile time independent of depth.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from ..sharding import constrain
from .attention import (attn_decode, attn_decode_paged, attn_forward,
                        attn_init, attn_prefill, attn_prefill_chunks_paged,
                        layer_window)
from .layers import apply_norm, grad_cast, mlp, mlp_init, norm_init, pdtype
from .mamba2 import (mamba2_decode, mamba2_forward, mamba2_init,
                     mamba2_init_state, mamba2_prefill)
from .moe import moe_ffn, moe_init
from .rwkv6 import (rwkv6_channel_mix, rwkv6_init, rwkv6_init_state,
                    rwkv6_time_mix)


@jax.custom_vjp
def optimization_barrier(x):
    """jax.lax.optimization_barrier that also keeps the barrier on the
    COTANGENT, so the backward pass gets the same hoisting protection as
    the forward."""
    return jax.lax.optimization_barrier(x)


def _barrier_fwd(x):
    return optimization_barrier(x), None


def _barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


optimization_barrier.defvjp(_barrier_fwd, _barrier_bwd)


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _layer_windows(cfg: ModelConfig):
    """Per-layer is_global flags for the gemma3 local:global pattern."""
    if cfg.sliding_window and cfg.global_every:
        return jnp.array(
            [1 if (i % cfg.global_every == cfg.global_every - 1) else 0
             for i in range(cfg.n_layers)], jnp.int32)
    return jnp.ones((cfg.n_layers,), jnp.int32)


# ===========================================================================
# generic attention+ffn block
# ===========================================================================

def block_init(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 5)
    p = {"n1": norm_init(cfg), "attn": attn_init(ks[0], cfg),
         "n2": norm_init(cfg)}
    if cross:
        p["cross_attn"] = attn_init(ks[2], cfg)
        p["n_cross"] = norm_init(cfg)
    if cfg.moe_experts:
        p["moe"] = moe_init(ks[1], cfg)
    else:
        p["mlp"] = mlp_init(ks[1], cfg)
    return p


def block_forward(p, x, cfg: ModelConfig, *, causal=True, window=0,
                  enc_out=None, impl=None):
    """Returns (x, aux_loss).

    Attention/MLP outputs are constrained back to the sequence-sharded
    layout BEFORE the residual add, so the row-parallel matmul partial sums
    lower to a reduce-scatter rather than a full all-reduce (Megatron-style
    sequence parallelism; ~16x less collective traffic per boundary)."""
    h = attn_forward(p["attn"], apply_norm(p["n1"], x, cfg), cfg,
                     causal=causal, window=window, impl=impl)
    x = x + constrain(h, "btd")
    if enc_out is not None:
        h = attn_forward(p["cross_attn"], apply_norm(p["n_cross"], x, cfg),
                         cfg, causal=False, kv_x=enc_out, impl=impl)
        x = x + constrain(h, "btd")
    aux = jnp.zeros((), jnp.float32)
    y_in = apply_norm(p["n2"], x, cfg)
    if cfg.moe_experts:
        y, aux = moe_ffn(p["moe"], y_in, cfg)
    else:
        y = mlp(p["mlp"], y_in, cfg)
    return x + constrain(y, "btd"), aux


# ===========================================================================
# decoder-only stack (dense / moe / vlm / gemma3)
# ===========================================================================

def stack_init(key, cfg: ModelConfig):
    layers = [block_init(jax.random.fold_in(key, i), cfg)
              for i in range(cfg.n_layers)]
    return _stack_trees(layers)


def _ffn_tail(p, x, cfg: ModelConfig):
    """Post-attention half of a block: norm -> (moe|mlp) -> residual."""
    y_in = apply_norm(p["n2"], x, cfg)
    if cfg.moe_experts:
        y, _ = moe_ffn(p["moe"], y_in, cfg)
    else:
        y = mlp(p["mlp"], y_in, cfg)
    return x + y


def stack_forward(params, x, cfg: ModelConfig, *, impl=None, remat=False):
    flags = _layer_windows(cfg)

    def body(carry, xs):
        x, aux = carry
        p, flag = xs
        # barrier: keep the remat-saved residual in bf16 (XLA otherwise
        # hoists the first fp32 convert of the recompute into the save);
        # grad_cast: keep the residual COTANGENT bf16 so the per-layer
        # sequence-parallel all-gather/all-reduce pair moves half the bytes
        x = grad_cast(optimization_barrier(x))
        x = constrain(x, "btd")
        if cfg.sliding_window and cfg.global_every:
            x, a = jax.lax.cond(
                flag > 0,
                lambda: block_forward(p, x, cfg, window=0, impl=impl),
                lambda: block_forward(p, x, cfg, window=cfg.sliding_window,
                                      impl=impl))
        else:
            x, a = block_forward(p, x, cfg, window=cfg.sliding_window,
                                 impl=impl)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               (params, flags))
    return x, aux


def stack_prefill(params, x, cfg: ModelConfig, cache, *, impl=None):
    """cache: {"k": (L,B,S,Hkv,D), "v": ...}.  Prefill from position 0."""
    flags = _layer_windows(cfg)

    def body(x, xs):
        p, ck, cv, flag = xs
        x = constrain(x, "btd")
        h_in = apply_norm(p["n1"], x, cfg)
        h, ck, cv = layer_window(
            cfg, flag,
            lambda w: attn_prefill(p["attn"], h_in, cfg, ck, cv, window=w,
                                   impl=impl))
        return _ffn_tail(p, x + h, cfg), (ck, cv)

    x, (ck, cv) = jax.lax.scan(body, x,
                               (params, cache["k"], cache["v"], flags))
    return x, {"k": ck, "v": cv}


def stack_decode(params, x, cfg: ModelConfig, cache, lens, *, impl=None,
                 seq_parallel=False):
    flags = _layer_windows(cfg)

    def body(x, xs):
        p, ck, cv, flag = xs
        h_in = apply_norm(p["n1"], x, cfg)
        h, ck, cv = layer_window(
            cfg, flag,
            lambda w: attn_decode(p["attn"], h_in, cfg, ck, cv, lens,
                                  window=w, impl=impl,
                                  seq_parallel=seq_parallel))
        return _ffn_tail(p, x + h, cfg), (ck, cv)

    x, (ck, cv) = jax.lax.scan(body, x,
                               (params, cache["k"], cache["v"], flags))
    return x, {"k": ck, "v": cv}


def _scan_paged(cfg: ModelConfig, params, x, kv_pages, attn):
    """Scan the decoder layers with the stacked page pool as carry: layer
    l's block runs `attn(p, h_in, kv_pages, l, flag) -> (h, kv_pages)`,
    and nothing of pool size passes through the scan's xs or ys.  The
    callers compute the write's plan (ops.kv_write_plan) before the scan:
    it is the same for every layer."""
    def body(carry, xs):
        x, kv = carry
        p, flag, l = xs
        x = constrain(x, "btd")
        h, kv = attn(p["attn"], apply_norm(p["n1"], x, cfg), kv, l, flag)
        return (_ffn_tail(p, x + h, cfg), kv), None

    (x, kv_pages), _ = jax.lax.scan(
        body, (x, kv_pages),
        (params, _layer_windows(cfg), jnp.arange(cfg.n_layers)))
    return x, kv_pages


def stack_prefill_chunks_paged(params, x, cfg: ModelConfig, cache,
                               page_tables, offsets, true_lens, *,
                               q_lens=None, impl=None, tp_mesh=None):
    """Paged prefill of a RAGGED BATCH of mid-prompt chunks - K chunks of
    K different sequences at K different prompt positions, ONE pass
    through the stack: x: (K, S, D), row k at absolute positions
    offsets[k] + arange(S) and zero-padded past true_lens[k].
    page_tables: (K, n_max) per-row block-table rows - pages already
    holding K/V (cached prefix + earlier chunks) first, then the pages
    each chunk and decode will fill.  Two chunks of the SAME sequence may
    share a batch (ordered offsets): each layer writes every row's K/V
    before its attention reads the pool, so the later chunk sees the
    earlier one exactly as if they had run back to back.  The block table
    itself is host-managed (serve/paged_cache.py) and passes through
    untouched."""
    plan = ops.kv_write_plan(page_tables, offsets, true_lens, x.shape[1],
                             cache["kv_pages"].shape[3], impl=impl)
    x, kv = _scan_paged(
        cfg, params, x, cache["kv_pages"],
        lambda p, h, kv, l, flag: attn_prefill_chunks_paged(
            p, h, cfg, kv, l, flag, page_tables, offsets, true_lens, plan,
            q_lens=q_lens, impl=impl, tp_mesh=tp_mesh))
    return x, {"kv_pages": kv, "block_table": cache["block_table"]}


def stack_decode_paged(params, x, cfg: ModelConfig, cache, lens, *,
                       impl=None, tp_mesh=None):
    """Batched single-token decode through the block table (all layers share
    one table; the stacked pool holds every layer's pages)."""
    bt = cache["block_table"]
    plan = ops.kv_write_plan(bt, lens, lens + 1, 1,
                             cache["kv_pages"].shape[3], impl=impl)
    x, kv = _scan_paged(
        cfg, params, x, cache["kv_pages"],
        lambda p, h, kv, l, flag: attn_decode_paged(
            p, h, cfg, kv, l, flag, bt, lens, plan, impl=impl,
            tp_mesh=tp_mesh))
    return x, {"kv_pages": kv, "block_table": bt}


# ===========================================================================
# hybrid stack (zamba2): mamba2 + shared attention block
# ===========================================================================

def hybrid_init(key, cfg: ModelConfig):
    layers = [mamba2_init(jax.random.fold_in(key, i), cfg)
              for i in range(cfg.n_layers)]
    shared = block_init(jax.random.fold_in(key, 10_000), cfg)
    return {"mamba": _stack_trees(layers), "shared": shared}


def n_shared_applications(cfg: ModelConfig) -> int:
    k = cfg.shared_attn_every
    return cfg.n_layers // k if k else 0


def hybrid_forward(params, x, cfg: ModelConfig, *, impl=None, remat=False):
    k = cfg.shared_attn_every
    shared = params["shared"]

    def body(x, xs):
        p, idx = xs
        x = grad_cast(optimization_barrier(x))
        x = constrain(x, "btd")
        x = x + mamba2_forward(p, x, cfg, impl=impl)
        if k:
            x = jax.lax.cond(
                (idx % k) == (k - 1),
                lambda x: block_forward(shared, x, cfg, impl=impl)[0],
                lambda x: x, x)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, (params["mamba"],
                                  jnp.arange(cfg.n_layers)))
    return x, jnp.zeros((), jnp.float32)


def hybrid_init_cache(cfg: ModelConfig, batch: int, max_len: int):
    d_in = cfg.ssm_expand * cfg.d_model
    st = mamba2_init_state(cfg, batch)
    L = cfg.n_layers
    A = max(n_shared_applications(cfg), 1)
    dt = jnp.dtype(cfg.dtype)
    return {
        "conv": jnp.zeros((L,) + st["conv"].shape, st["conv"].dtype),
        "ssm": jnp.zeros((L,) + st["ssm"].shape, st["ssm"].dtype),
        "shared_k": jnp.zeros((A, batch, max_len, cfg.n_kv_heads,
                               cfg.head_dim), dt),
        "shared_v": jnp.zeros((A, batch, max_len, cfg.n_kv_heads,
                               cfg.head_dim), dt),
    }


def hybrid_prefill(params, x, cfg: ModelConfig, cache, *, impl=None):
    """Full-sequence hybrid prefill: chunked SSD scans fill the per-layer
    conv/SSM states; the shared attention block prefills its KV caches."""
    k = cfg.shared_attn_every
    shared = params["shared"]

    def shared_prefill(x, sk_all, sv_all, app_idx):
        sk = sk_all[app_idx]
        sv = sv_all[app_idx]
        h_in = apply_norm(shared["n1"], x, cfg)
        h, sk, sv = attn_prefill(shared["attn"], h_in, cfg, sk, sv,
                                 impl=impl)
        x = x + h
        y = mlp(shared["mlp"], apply_norm(shared["n2"], x, cfg), cfg)
        sk_all = jax.lax.dynamic_update_index_in_dim(sk_all, sk, app_idx, 0)
        sv_all = jax.lax.dynamic_update_index_in_dim(sv_all, sv, app_idx, 0)
        return x + y, sk_all, sv_all

    def body(carry, xs):
        x, sk_all, sv_all = carry
        p, idx = xs
        y, st = mamba2_prefill(p, x, cfg, impl=impl)
        x = x + y
        if k:
            x, sk_all, sv_all = jax.lax.cond(
                (idx % k) == (k - 1),
                lambda x, sk, sv: shared_prefill(x, sk, sv, idx // k),
                lambda x, sk, sv: (x, sk, sv),
                x, sk_all, sv_all)
        return (x, sk_all, sv_all), (st["conv"], st["ssm"])

    (x, sk, sv), (conv, ssm) = jax.lax.scan(
        body, (x, cache["shared_k"], cache["shared_v"]),
        (params["mamba"], jnp.arange(cfg.n_layers)))
    return x, {"conv": conv, "ssm": ssm, "shared_k": sk, "shared_v": sv}


def hybrid_decode(params, x, cfg: ModelConfig, cache, lens, *, impl=None,
                  seq_parallel=False):
    k = cfg.shared_attn_every
    shared = params["shared"]

    def shared_apply(x, sk_all, sv_all, app_idx):
        sk = sk_all[app_idx]
        sv = sv_all[app_idx]
        h_in = apply_norm(shared["n1"], x, cfg)
        h, sk, sv = attn_decode(shared["attn"], h_in, cfg, sk, sv, lens,
                                impl=impl, seq_parallel=seq_parallel)
        x = x + h
        y = mlp(shared["mlp"], apply_norm(shared["n2"], x, cfg), cfg)
        sk_all = jax.lax.dynamic_update_index_in_dim(sk_all, sk, app_idx, 0)
        sv_all = jax.lax.dynamic_update_index_in_dim(sv_all, sv, app_idx, 0)
        return x + y, sk_all, sv_all

    def body(carry, xs):
        x, sk_all, sv_all = carry
        p, conv, ssm, idx = xs
        y, new_state = mamba2_decode(p, x, cfg, {"conv": conv, "ssm": ssm})
        x = x + y
        if k:
            x, sk_all, sv_all = jax.lax.cond(
                (idx % k) == (k - 1),
                lambda x, sk, sv: shared_apply(x, sk, sv, idx // k),
                lambda x, sk, sv: (x, sk, sv),
                x, sk_all, sv_all)
        return (x, sk_all, sv_all), (new_state["conv"], new_state["ssm"])

    (x, sk, sv), (conv, ssm) = jax.lax.scan(
        body, (x, cache["shared_k"], cache["shared_v"]),
        (params["mamba"], cache["conv"], cache["ssm"],
         jnp.arange(cfg.n_layers)))
    return x, {"conv": conv, "ssm": ssm, "shared_k": sk, "shared_v": sv}


# ===========================================================================
# rwkv stack
# ===========================================================================

def rwkv_init(key, cfg: ModelConfig):
    layers = []
    for i in range(cfg.n_layers):
        ki = jax.random.fold_in(key, i)
        layers.append({"n1": norm_init(cfg), "n2": norm_init(cfg),
                       "mix": rwkv6_init(ki, cfg)})
    return _stack_trees(layers)


def rwkv_forward(params, x, cfg: ModelConfig, *, impl=None, remat=False):
    def body(x, p):
        x = grad_cast(optimization_barrier(x))
        x = constrain(x, "btd")
        h, _ = rwkv6_time_mix(p["mix"], apply_norm(p["n1"], x, cfg), cfg,
                              impl=impl)
        x = x + h
        h, _ = rwkv6_channel_mix(p["mix"], apply_norm(p["n2"], x, cfg), cfg)
        return x + h, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params)
    return x, jnp.zeros((), jnp.float32)


def rwkv_init_cache(cfg: ModelConfig, batch: int, max_len: int):
    st = rwkv6_init_state(cfg, batch)
    L = cfg.n_layers
    return {k: jnp.zeros((L,) + v.shape, v.dtype) for k, v in st.items()}


def rwkv_prefill(params, x, cfg: ModelConfig, cache, *, impl=None):
    """Full-sequence RWKV prefill via the state-returning chunked WKV scan."""
    def body(x, p):
        xin = apply_norm(p["n1"], x, cfg)
        h, (tm_last, wkv) = rwkv6_time_mix(p["mix"], xin, cfg, impl=impl,
                                           return_state=True)
        x = x + h
        xin = apply_norm(p["n2"], x, cfg)
        h, cm_last = rwkv6_channel_mix(p["mix"], xin, cfg)
        return x + h, (wkv, tm_last, cm_last)

    x, (wkv, tm, cm) = jax.lax.scan(body, x, params)
    return x, {"wkv": wkv, "tm_prev": tm, "cm_prev": cm}


def rwkv_decode(params, x, cfg: ModelConfig, cache, lens, *, impl=None,
                seq_parallel=False):
    def body(x, xs):
        p, wkv, tm_prev, cm_prev = xs
        xin = apply_norm(p["n1"], x, cfg)
        h, (tm_last, new_wkv) = rwkv6_time_mix(
            p["mix"], xin, cfg, x_prev=tm_prev, wkv_state=wkv, impl=impl)
        x = x + h
        xin = apply_norm(p["n2"], x, cfg)
        h, cm_last = rwkv6_channel_mix(p["mix"], xin, cfg, x_prev=cm_prev)
        return x + h, (new_wkv, tm_last, cm_last)

    x, (wkv, tm, cm) = jax.lax.scan(
        body, x, (params, cache["wkv"], cache["tm_prev"], cache["cm_prev"]))
    return x, {"wkv": wkv, "tm_prev": tm, "cm_prev": cm}


# ===========================================================================
# encoder-decoder (whisper)
# ===========================================================================

def encdec_init(key, cfg: ModelConfig):
    enc = [block_init(jax.random.fold_in(key, i), cfg)
           for i in range(cfg.encoder_layers)]
    dec = [block_init(jax.random.fold_in(key, 1000 + i), cfg, cross=True)
           for i in range(cfg.n_layers)]
    return {"encoder": _stack_trees(enc), "decoder": _stack_trees(dec),
            "enc_norm": norm_init(cfg)}


def encode(params, x_enc, cfg: ModelConfig, *, impl=None):
    def body(x, p):
        x, _ = block_forward(p, x, cfg, causal=False, impl=impl)
        return x, None
    x, _ = jax.lax.scan(body, x_enc, params["encoder"])
    return apply_norm(params["enc_norm"], x, cfg)


def encdec_forward(params, x_enc, x_dec, cfg: ModelConfig, *, impl=None,
                   remat=False):
    enc_out = encode(params, x_enc, cfg, impl=impl)

    def body(x, p):
        x, _ = block_forward(p, x, cfg, causal=True, enc_out=enc_out,
                             impl=impl)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x_dec, params["decoder"])
    return x, jnp.zeros((), jnp.float32)


def encdec_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                      enc_len: int):
    dt = jnp.dtype(cfg.dtype)
    L = cfg.n_layers
    mk = lambda s: jnp.zeros((L, batch, s, cfg.n_kv_heads, cfg.head_dim), dt)
    return {"self_k": mk(max_len), "self_v": mk(max_len),
            "cross_k": mk(enc_len), "cross_v": mk(enc_len)}


def encdec_prefill(params, x_enc, x_dec, cfg: ModelConfig, cache, *,
                   impl=None):
    """Full-sequence decoder prefill: fills self-attn and cross-attn caches
    in one pass (no per-token scan)."""
    enc_out = encode(params, x_enc, cfg, impl=impl)
    from .layers import dense

    def body(x, xs):
        p, sk, sv, ck, cv = xs
        h_in = apply_norm(p["n1"], x, cfg)
        h, sk, sv = attn_prefill(p["attn"], h_in, cfg, sk, sv, impl=impl)
        x = x + h
        # cross-attention: fill cross cache from encoder output
        B, Se, _ = enc_out.shape
        ca = p["cross_attn"]
        ckv = dense(ca["wk"], enc_out).reshape(B, Se, cfg.n_kv_heads,
                                               cfg.head_dim)
        cvv = dense(ca["wv"], enc_out).reshape(B, Se, cfg.n_kv_heads,
                                               cfg.head_dim)
        ck = jax.lax.dynamic_update_slice(ck, ckv.astype(ck.dtype),
                                          (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, cvv.astype(cv.dtype),
                                          (0, 0, 0, 0))
        h = attn_forward(p["cross_attn"], apply_norm(p["n_cross"], x, cfg),
                         cfg, causal=False, kv_x=enc_out, impl=impl)
        x = x + h
        y = mlp(p["mlp"], apply_norm(p["n2"], x, cfg), cfg)
        return x + y, (sk, sv, ck, cv)

    x, (sk, sv, ck, cv) = jax.lax.scan(
        body, x_dec, (params["decoder"], cache["self_k"], cache["self_v"],
                      cache["cross_k"], cache["cross_v"]))
    return x, {"self_k": sk, "self_v": sv, "cross_k": ck, "cross_v": cv}


def encdec_decode(params, x, cfg: ModelConfig, cache, lens, enc_lens, *,
                  impl=None, seq_parallel=False):
    """One decoder token; cross K/V already in the cache."""
    def body(x, xs):
        p, sk, sv, ck, cv = xs
        h_in = apply_norm(p["n1"], x, cfg)
        h, sk, sv = attn_decode(p["attn"], h_in, cfg, sk, sv, lens,
                                impl=impl, seq_parallel=seq_parallel)
        x = x + h
        h_in = apply_norm(p["n_cross"], x, cfg)
        h, _, _ = attn_decode(p["cross_attn"], h_in, cfg, ck, cv, enc_lens,
                              impl=impl, cross=True,
                              seq_parallel=seq_parallel)
        x = x + h
        y = mlp(p["mlp"], apply_norm(p["n2"], x, cfg), cfg)
        return x + y, (sk, sv)

    x, (sk, sv) = jax.lax.scan(
        body, x, (params["decoder"], cache["self_k"], cache["self_v"],
                  cache["cross_k"], cache["cross_v"]))
    return x, {"self_k": sk, "self_v": sv,
               "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}
