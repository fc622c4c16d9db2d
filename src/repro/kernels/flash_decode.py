"""Pallas TPU flash-decoding: one query token vs a long KV cache.

Grid walks KV blocks sequentially per (batch, kv-head); the running
(m, l, acc) triple lives in VMEM scratch - the same register-resident merge
the paper performs across tiers, here across KV blocks of a 32K-512K cache.
The per-sequence valid length arrives via scalar-memory (SMEM) so masking
is branch-free.

Two cache layouts share the same online-softmax inner step:

  flash_decode        dense (B, S_max, Hkv, D) caches - one contiguous
                      KV strip per sequence, moved head-major (B, Hkv, S, D)
                      before the launch.
  paged_flash_decode  the stacked, lane-dense page pool (L, Hkv, P, page, W)
                      shared by all sequences and layers, K in lanes [0, D)
                      and V in lanes [D, 2D) of each token row
                      (kernels/kv_write.py).  Each grid step gathers one
                      head's page of layer l through a scalar-prefetched
                      block table: the index map (l, h, bt[b, j], 0, 0) IS
                      the page-table walk, one DMA brings K and V together,
                      and the kernel splits them by lanes in VMEM.  The
                      (m, l, acc) merge never leaves VMEM scratch.

The KV BlockSpecs keep a head off the second-minor axis of a block: the TPU
tiles the last two block dimensions by (8, 128) unless they span the whole
array dimension.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

LOG2E = 1.4426950408889634
NEG_INF = -1e30


def _online_merge(s, mask, v, acc_ref, m_ref, l_ref):
    """Fold one masked score block into the running (m, l, acc) triple.

    THE online-softmax merge, shared by every decode/suffix-prefill kernel:
    s: (rows, bk) f32 scores, mask: (rows, bk) bool, v: (bk, D) f32.  Only
    how s/mask were built differs per kernel (scalar valid-length vs 2-D
    offset-causal)."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(mask, jnp.exp2((s - m_safe) * LOG2E), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                      jnp.exp2((m_prev - m_new) * LOG2E))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv


def _online_softmax_step(q_ref, k, v, acc_ref, m_ref, l_ref, *,
                         k_first, valid, window: int, scale: float,
                         softcap: float = 0.0):
    """One KV-block update of the running (m, l, acc) triple in VMEM.

    k, v: (bk, D) float32.  Shared by the dense and the paged decode
    kernels - only how the KV block got into VMEM differs (contiguous
    BlockSpec walk vs block-table gather of a lane-packed page).
    """
    q = q_ref[0, 0].astype(jnp.float32) * scale              # (G, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bk)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    pos = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < valid
    if window > 0:
        mask = mask & (pos >= valid - window)
    _online_merge(s, mask, v, acc_ref, m_ref, l_ref)


def split_kv(kv_ref, head_dim: int):
    """K and V of one lane-packed (.., rows, W) page block as float32
    (rows, D) arrays: K in lanes [0, D), V in [D, 2D)."""
    kv = kv_ref[0, 0, 0].astype(jnp.float32)
    return kv[:, :head_dim], kv[:, head_dim:2 * head_dim]


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, window: int, scale: float, softcap: float,
                   block_kv: int, gq: int):
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[pl.program_id(0)]
    k_first = j * block_kv
    run = k_first < valid
    if window > 0:
        run = run & (k_first + block_kv > valid - window)

    @pl.when(run)
    def _compute():
        _online_softmax_step(q_ref, k_ref[0, 0].astype(jnp.float32),
                             v_ref[0, 0].astype(jnp.float32), acc_ref,
                             m_ref, l_ref, k_first=k_first, valid=valid,
                             window=window, scale=scale, softcap=softcap)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "logit_softcap", "block_kv"))
def flash_decode(q, k_cache, v_cache, cache_len, *, window: int = 0,
                 scale: Optional[float] = None,
                 logit_softcap: float = 0.0,
                 block_kv: int = 512) -> jax.Array:
    """q: (B,1,Hq,D); caches: (B,S,Hkv,D); cache_len: (B,) or scalar."""
    B, _, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len, jnp.int32)

    block_kv = min(block_kv, max(S, 128))
    pk = (-S) % block_kv
    # head-major (B, Hkv, S, D), so a KV block is (block_kv, D) of one head
    kc = jnp.moveaxis(k_cache, 2, 1)
    vc = jnp.moveaxis(v_cache, 2, 1)
    if pk:
        kc = jnp.pad(kc, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nk = (S + pk) // block_kv

    qg = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(_decode_kernel, window=window, scale=scale,
                               softcap=logit_softcap, block_kv=block_kv,
                               gq=G)
    grid = (B, Hkv, nk)
    o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # cache_len, prefetched
            pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret_mode(),
    )(cache_len, qg, kc, vc)
    return o.reshape(B, 1, Hq, D)


# ===========================================================================
# paged decode: KV pages gathered through a scalar-prefetched block table
# ===========================================================================

def _paged_decode_kernel(layer_ref, bt_ref, len_ref, q_ref, kv_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, window: int, scale: float,
                         softcap: float, page_size: int):
    """layer_ref: (1,) layer index, bt_ref: (B, n_max) block table,
    len_ref: (B,) valid lengths - all scalar-prefetched into SMEM; the
    pool's BlockSpec index map already walked the table, so kv_ref holds
    page j of THIS sequence at this layer, K and V side by side."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = len_ref[b]
    k_first = j * page_size
    run = k_first < valid
    if window > 0:
        run = run & (k_first + page_size > valid - window)

    @pl.when(run)
    def _compute():
        k, v = split_kv(kv_ref, q_ref.shape[-1])
        _online_softmax_step(q_ref, k, v, acc_ref, m_ref, l_ref,
                             k_first=k_first, valid=valid, window=window,
                             scale=scale, softcap=softcap)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "logit_softcap"))
def paged_flash_decode(q, kv_pages, layer, block_table, cache_len, *,
                       window: int = 0,
                       scale: Optional[float] = None,
                       logit_softcap: float = 0.0) -> jax.Array:
    """Decode against a paged KV cache.

    q:           (B, 1, Hq, D)
    kv_pages:    (L, Hkv, P, page_size, W) stacked, lane-dense page pool
                 (all sequences, all layers): K in lanes [0, D), V in
                 [D, 2D)
    layer:       scalar int32, the layer whose pages are read
    block_table: (B, n_max) int32 - page ids per sequence, position-major;
                 unused entries must point at a valid page (the engine keeps
                 page 0 as a never-allocated null page)
    cache_len:   (B,) or scalar valid lengths
    Returns (B, 1, Hq, D).
    """
    B, _, Hq, D = q.shape
    _, Hkv, _, ps, W = kv_pages.shape
    G = Hq // Hkv
    n_max = block_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len, jnp.int32)
    block_table = jnp.asarray(block_table, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(_paged_decode_kernel, window=window,
                               scale=scale, softcap=logit_softcap,
                               page_size=ps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer + block table + lengths land in SMEM
        num_scalar_prefetch=3,
        grid=(B, Hkv, n_max),
        in_specs=[
            pl.BlockSpec((1, 1, G, D),
                         lambda b, h, j, l, bt, cl: (b, h, 0, 0)),
            # the index map IS the page-table walk: head h of page j of
            # sequence b, at layer l - K and V in one block
            pl.BlockSpec((1, 1, 1, ps, W),
                         lambda b, h, j, l, bt, cl: (l[0], h, bt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, j, l, bt, cl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret_mode(),
        name="paged_flash_decode",
    )(layer, block_table, cache_len, qg, kv_pages)
    return o.reshape(B, 1, Hq, D)
