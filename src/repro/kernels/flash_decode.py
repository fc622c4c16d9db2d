"""Pallas TPU flash-decoding: one query token vs a long KV cache.

The running (m, l, acc) triple lives in VMEM scratch - the same
register-resident merge the paper performs across tiers, here across KV
blocks of a 32K-512K cache.  The per-sequence valid length arrives via
scalar memory (SMEM), so masking is branch-free.

Two cache layouts, two iteration spaces:

  flash_decode        dense (B, S_max, Hkv, D) caches - one contiguous
                      KV strip per sequence, moved head-major (B, Hkv, S, D)
                      before the launch; the grid (B, Hkv, S / block_kv)
                      walks it block by block through BlockSpecs.
  paged_flash_decode  the stacked, lane-dense page pool (L, Hkv, P, page, W)
                      shared by all sequences and layers, K in lanes [0, D)
                      and V in lanes [D, 2D) of each token row
                      (kernels/kv_write.py), left in HBM.  The grid is one
                      step per row.  A row loops over its LIVE pages only -
                      from the window's first to ceil(valid / page) - read
                      through the scalar-prefetched block table; entries
                      past the live length are never read.  The pages come
                      in blocks of pages_per_block, each page one strided
                      DMA of every KV head (kv_pages.at[l, :, page]) into a
                      double-buffered VMEM block; the next block, or the
                      next row's first, is in flight while one is merged.
                      A page's QK^T and PV products run for every head in
                      one step, one 2-D product per head, and the
                      online softmax merges one page at a time, in page
                      order, so a head's arithmetic does not depend on the
                      block size or on how many heads the call holds (the
                      tensor-parallel wrapper passes a head slice).

pages_per_block follows from the shape alone: as many of one head's
(page, W) pages as BLOCK_HEAD_BYTES holds, at most a table row; the VMEM
limit the call asks for grows by the double-buffered block's bytes.

The dense kernel's KV BlockSpecs keep a head off the second-minor axis of a
block: the TPU tiles the last two block dimensions by (8, 128) unless they
span the whole array dimension.
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

    k, v: (bk, D) float32, one head's KV block of the dense kernel.
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
# paged decode: each row walks its own live pages, all KV heads per DMA
# ===========================================================================

# bytes of ONE KV head's pages that a page block holds; the block's DMA
# buffer holds every head of those pages, twice over (double buffering)
BLOCK_HEAD_BYTES = 256 * 1024
# the scoped-VMEM limit the compiler grants a kernel by default (v5e)
_DEFAULT_VMEM = 16 * 1024 * 1024


def pages_per_block(page_size: int, lanes: int, itemsize: int,
                    n_max: int) -> int:
    """Pages one DMA block fetches: as many of one head's (page_size,
    lanes) pages as BLOCK_HEAD_BYTES holds, at most a whole table row.
    Independent of the head count, so a head-sharded call blocks its pages
    as the unsharded one does."""
    head_page = page_size * lanes * itemsize
    return max(1, min(n_max, BLOCK_HEAD_BYTES // head_page))


def _head_dots(a, b, dims):
    """dot_general of a[h] and b[h] for each leading head h, stacked: one
    2-D product per head, so a head's result does not depend on how many
    heads the call holds."""
    return jnp.stack([
        jax.lax.dot_general(a[h], b[h], (dims, ((), ())),
                            preferred_element_type=jnp.float32)
        for h in range(a.shape[0])])


def _online_merge_heads(s, mask, v, acc_ref, m_ref, l_ref):
    """_online_merge with a leading head axis: s (H, G, bk) f32 scores,
    mask broadcastable to s, v (H, bk, D) f32; the (m, l, acc) triple is
    (H, G, 1), (H, G, 1), (H, G, D).  Per head, the same arithmetic."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(mask, jnp.exp2((s - m_safe) * LOG2E), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                      jnp.exp2((m_prev - m_new) * LOG2E))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + _head_dots(p, v, ((1,), (0,)))


def _paged_decode_kernel(layer_ref, bt_ref, len_ref, q_ref, kv_hbm, o_ref,
                         buf, sem, slot_ref, acc_ref, m_ref, l_ref, *,
                         window: int, scale: float, softcap: float,
                         page_size: int, block: int):
    """One grid step per row.  layer_ref (1,), bt_ref (B, n_max) and
    len_ref (B,) are scalar-prefetched into SMEM; kv_hbm is the whole pool,
    left in HBM.  The row's live pages - from the window's first to
    ceil(valid / page_size) - are copied in blocks of `block` pages into
    buf (2, block, Hkv, page_size, W), each page one strided DMA of every
    head, and merged one page at a time, in page order.  While a block is
    merged the next one, or the next row's first, is in flight; slot_ref
    carries the buffer slot from row to row."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    n_max = bt_ref.shape[1]
    D = q_ref.shape[-1]

    def span(row):
        """(valid length, first page, end page) of a row's walk."""
        valid = len_ref[row]
        first = 0
        if window > 0:
            first = jnp.minimum(jnp.maximum(valid - window, 0) // page_size,
                                n_max - 1)
        end = jnp.minimum((valid + page_size - 1) // page_size, n_max)
        return valid, first, end

    def block_copies(row, blk, slot, start: bool):
        """Start, or wait for, the page DMAs of block blk of a row: only
        its pages below the row's end page, so the table entries past the
        live length are never read.  A row of length 0 still fetches its
        first page, so every row has a block in flight to wait for."""
        _, first, end = span(row)
        j0 = first + blk * block
        stop = jnp.maximum(end, first + 1)
        for i in range(block):
            @pl.when(j0 + i < stop)
            def _():
                page = bt_ref[row, j0 + i]
                copy = pltpu.make_async_copy(
                    kv_hbm.at[layer_ref[0], :, page], buf.at[slot, i],
                    sem.at[slot])
                copy.start() if start else copy.wait()

    @pl.when(b == 0)
    def _first_block():
        slot_ref[0] = 0
        block_copies(0, 0, 0, start=True)

    valid, first, end = span(b)
    n_blocks = jnp.maximum(end - first + block - 1, block) // block
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[0]                                             # (Hkv, G, D)

    def merge_page(j, kv):
        """Fold page j of the row, kv (Hkv, page_size, W), into the
        running triple."""
        k = kv[:, :, :D]
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = _head_dots(q.astype(dt), k.astype(dt), ((1,), (1,))) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                       2)
        mask = pos < valid
        if window > 0:
            mask = mask & (pos >= valid - window)
        v = kv[:, :, D:2 * D].astype(jnp.float32)
        _online_merge_heads(s, mask, v, acc_ref, m_ref, l_ref)

    def block_body(blk, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(blk + 1 < n_blocks)
        def _next_block():
            block_copies(b, blk + 1, nxt, start=True)

        @pl.when((blk + 1 == n_blocks) & (b + 1 < rows))
        def _next_row():
            block_copies(b + 1, 0, nxt, start=True)

        block_copies(b, blk, slot, start=False)
        j0 = first + blk * block

        def page_body(i, c):
            merge_page(j0 + i, buf[slot, i])
            return c

        jax.lax.fori_loop(0, jnp.clip(end - j0, 0, block), page_body, 0)
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_blocks, block_body, 0)
    l = jnp.maximum(l_ref[...], 1e-20)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


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
                 entries past a row's valid length are never read
                 (a row of length 0 reads its first entry)
    cache_len:   (B,) or scalar valid lengths, at most n_max * page_size
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
    itemsize = jnp.dtype(kv_pages.dtype).itemsize
    block = pages_per_block(ps, W, itemsize, n_max)
    buf_bytes = 2 * block * Hkv * ps * W * itemsize

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(_paged_decode_kernel, window=window,
                               scale=scale, softcap=logit_softcap,
                               page_size=ps, block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer + block table + lengths land in SMEM
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, l, bt, cl: (b, 0, 0, 0)),
            # the pool stays in HBM: the kernel DMAs the live pages itself
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D),
                               lambda b, l, bt, cl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block, Hkv, ps, W), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a row's last block prefetches the next row's first: in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_DEFAULT_VMEM + buf_bytes),
        interpret=backend.interpret_mode(),
        name="paged_flash_decode",
    )(layer, block_table, cache_len, qg, kv_pages)
    return o.reshape(B, 1, Hq, D)
