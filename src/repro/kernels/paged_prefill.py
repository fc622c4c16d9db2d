"""Pallas TPU paged chunk prefill: ragged batches of mid-prompt chunk runs
against a partially filled paged KV pool.

The primary entry point is BATCHED: one launch executes K chunks of K
DIFFERENT sequences, each at its own prompt position.  Row k carries three
scalar-prefetched per-row facts in SMEM:

  offset[k]     absolute position of the row's first query token
  true_len[k]   the row's prefill cursor AFTER its last real token
                (offset + real chunk length; pages past it are skipped)
  tables[k, :]  the sequence's block-table row (position-major page ids)

so the serve engine can fold every prefill chunk the scheduler planned
this tick - K sequences at K different prompt positions, ragged lengths
zero-padded to one static chunk shape - into ONE kernel launch instead of
K.  This is the software analogue of the paper's bubble-free vertical
dataflow: the win of fine-grained chunking only materializes once the
per-chunk dispatch overhead is folded away (FlatAttention / Zen-Attention
make the same argument for tile-based NPU attention).

Every paged prefill goes through the kernel, handing it queries at
absolute positions ``offset + i`` whose K/V for positions < offset is
already resident in the page pool:

  batched chunked prefill  (serve/engine.py) - every chunk of this tick's
                   token-budget plan, packed by scheduler.pack_chunks.
  prefix caching   (serve/prefix_cache.py) - a request's chunks start at
                   the longest cached prefix; offset = cached tokens.
  single rows      the K=1 special case, kept under the established
                   ``paged_prefill_attention`` name as the kernel tests'
                   single-row oracle.

Either way the queries must attend causally over EVERYTHING before them -
earlier pages AND the chunk's own K/V, both reached through the row's
block-table row.

Under tensor parallelism (kernels/ops.py _tp_head_sharded,
docs/tensor_parallel.md) this kernel runs unmodified inside shard_map on
each device's contiguous head slice: per-head attention never mixes
heads, the scalar-prefetched tables/offsets/cursors ride in replicated,
and the caller requires n_kv_heads % tp_degree == 0 so every shard holds
whole GQA groups.  Nothing in here is sharding-aware - the kernel sees a
smaller head count and is otherwise bit-identical.

The same pool as paged_flash_decode (kernels/flash_decode.py), walked
here through BlockSpecs, one (row, head, page) per grid step: the pool is
the stacked, lane-dense (L, Hkv, P, page, W) array with K in lanes
[0, D) and V in [D, 2D) of each token row (kernels/kv_write.py), the layer
index and block tables are scalar-prefetched into SMEM, the BlockSpec
index map (l, h, tbl[k, j], 0, 0) IS the page-table walk - one DMA brings
a head's K and V page together, split by lanes in VMEM - and the running
(m, l, acc) online-softmax state stays in VMEM scratch across KV pages.
The ingredient a one-token decode does not need is a 2-D causal mask -
each chunk row r masks columns > offset[k] + r - computed branch-free
from the prefetched offset.

The grid walks the FULL block-table row (n_max pages, a static shape);
pages at or past the row's true_len are skipped with pl.when, so the cost
scales with the attended prefix, not with max_seq.  Dead (padding) rows
carry true_len == 0 and an all-null table: every page is skipped and the
row's output is exactly zero.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_decode import _online_merge, split_kv
from . import backend

NEG_INF = -1e30


def _chunk_kernel(layer_ref, tbl_ref, off_ref, tl_ref, ql_ref, q_ref, kv_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, page_size: int,
                  window: int, scale: float, softcap: float, gq: int,
                  s_suf: int):
    """layer_ref: (1,) layer index, tbl_ref: (K, n_max) block-table rows,
    off_ref/tl_ref/ql_ref: (K,) per-row chunk start / prefill cursor /
    real query count - all scalar-prefetched; kv_ref holds page j of row
    b's sequence at this layer, K and V side by side (the index map
    already walked the table).  Query rows at or past ql are
    PAD lanes (a speculative verify row drafts fewer than S - 1 tokens):
    their output is forced to exactly zero in the finalize, so ragged
    verify batches stay bit-deterministic whatever the pad rows hold."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    off = off_ref[b]
    tl = tl_ref[b]
    k_first = j * page_size
    # the row's last real query attends through position true_len - 1;
    # pages fully past that frontier contribute nothing (and may be the
    # null page).  A dead row (true_len == 0) skips every page.
    run = k_first < tl
    if window > 0:
        run = run & (k_first + page_size > off - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (S*G, D)
        k, v = split_kv(kv_ref, q.shape[-1])                 # (ps, D) each
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        # row r of the flattened (s_suf * G) block is query chunk-index
        # r // gq at absolute position off + r // gq
        row = off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // gq
        col = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col <= row
        if window > 0:
            mask = mask & (col > row - window)
        _online_merge(s, mask, v, acc_ref, m_ref, l_ref)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o = acc_ref[...] / l
        # zero pad query lanes (flattened row r is query index r // gq)
        ql = ql_ref[b]
        qidx = jax.lax.broadcasted_iota(jnp.int32, (s_suf * gq, 1), 0) // gq
        o_ref[0, 0] = jnp.where(qidx < ql, o, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "logit_softcap"))
def batched_paged_prefill_attention(q, kv_pages, layer, page_tables,
                                    q_offsets, true_lens, q_lens=None, *,
                                    window: int = 0,
                                    scale: Optional[float] = None,
                                    logit_softcap: float = 0.0) -> jax.Array:
    """Ragged batched mid-prompt chunk-prefill attention through per-row
    block tables: K chunks of K different sequences in ONE launch.

    q:           (K, S, Hq, D) chunk queries; row k sits at absolute
                 positions q_offsets[k] + arange(S).  Each row's K/V must
                 already be written into its pages
                 (attn_prefill_chunks_paged does both), as must all K/V
                 for positions < q_offsets[k] (cached prefix pages and/or
                 earlier chunks - which may be other rows of the SAME
                 launch: the per-layer page write lands before this
                 kernel reads the pool, so packing two chunks of one
                 sequence is exact as long as their offsets are ordered).
    kv_pages:    (L, Hkv, P, page_size, W) stacked, lane-dense page pool,
                 K in lanes [0, D) and V in [D, 2D)
    layer:       scalar int32, the layer whose pages are read
    page_tables: (K, n_max) int32 - per-row block-table rows,
                 position-major; entries past the reservation point at the
                 null page 0 and are never touched by the causal mask
    q_offsets:   (K,) int32, absolute position of each row's first token
    true_lens:   (K,) int32, each row's prefill cursor after its last
                 REAL token (ragged lengths: rows are zero-padded to S).
                 A dead padding row carries 0 and an all-null table row;
                 its output is exactly zero.
    q_lens:      (K,) int32 per-row REAL query count (the draft-length
                 lane of the speculative verify path: a verify row holds
                 1 + m real queries for an m-token draft chain).  Rows at
                 or past a row's q_len come back as exactly zero, so
                 ragged batches are bit-deterministic whatever their pad
                 lanes contain.  Defaults to true_lens - q_offsets (every
                 non-dead position real), preserving the historical
                 contract of the chunk-prefill callers.
    Returns (K, S, Hq, D).
    """
    K, S, Hq, D = q.shape
    _, Hkv, _, ps, W = kv_pages.shape
    G = Hq // Hkv
    n_max = page_tables.shape[1]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    page_tables = jnp.asarray(page_tables, jnp.int32)
    off = jnp.asarray(q_offsets, jnp.int32).reshape(K)
    tl = jnp.asarray(true_lens, jnp.int32).reshape(K)
    ql = jnp.clip(tl - off, 0, S) if q_lens is None \
        else jnp.asarray(q_lens, jnp.int32).reshape(K)

    # head-major GQA grouping, one grid row per (sequence row, KV head);
    # the S query positions x G group heads of one KV head are flattened
    # into the rows of one (S*G, D) block (row r is query r // G)
    qg = q.reshape(K, S, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        K, Hkv, S * G, D)
    kernel = functools.partial(_chunk_kernel, page_size=ps, window=window,
                               scale=scale, softcap=logit_softcap, gq=G,
                               s_suf=S)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer + tables + offsets + true_lens + q_lens in SMEM
        num_scalar_prefetch=5,
        grid=(K, Hkv, n_max),
        in_specs=[
            pl.BlockSpec((1, 1, S * G, D),
                         lambda b, h, j, l, tbl, off, tl, ql: (b, h, 0, 0)),
            # the index map IS the page-table walk: head h of page j of
            # row b, at layer l - K and V in one block
            pl.BlockSpec((1, 1, 1, ps, W),
                         lambda b, h, j, l, tbl, off, tl, ql:
                         (l[0], h, tbl[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, S * G, D),
                               lambda b, h, j, l, tbl, off, tl, ql:
                               (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((S * G, D), jnp.float32),
            pltpu.VMEM((S * G, 1), jnp.float32),
            pltpu.VMEM((S * G, 1), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, Hkv, S * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret_mode(),
        name="batched_paged_prefill_attention",
    )(layer, page_tables, off, tl, ql, qg, kv_pages)
    return o.reshape(K, Hkv, S, G, D).transpose(0, 2, 1, 3, 4).reshape(
        K, S, Hq, D)


def paged_prefill_attention(q, kv_pages, layer, page_row, q_offset, *,
                            window: int = 0,
                            scale: Optional[float] = None,
                            logit_softcap: float = 0.0) -> jax.Array:
    """Single-sequence mid-prompt chunk prefill: the K=1 special case of
    batched_paged_prefill_attention.

    q: (1, S, Hq, D); page_row: (n_max,) this sequence's block-table row;
    q_offset: scalar int32.  Every position of the chunk is treated as
    real (true_len = q_offset + S), matching the historical single-row
    contract.  Returns (1, S, Hq, D)."""
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    return batched_paged_prefill_attention(
        q, kv_pages, layer, jnp.asarray(page_row, jnp.int32)[None],
        off, off + q.shape[1], window=window, scale=scale,
        logit_softcap=logit_softcap)
