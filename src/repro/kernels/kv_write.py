"""Pallas TPU KV page write: new K/V rows into one layer of the stacked
page pool, in place.

The pool is one array for every layer, K and V side by side in the lanes
of each token row:

  kv_pages  (L, Hkv, P, page_size, W)   K in lanes [0, D), V in [D, 2D),
                                        W = 2D

so that at head_dim 64 and above the TPU's default layout of the stored
shape is the row-major layout the paged attention kernels read, and no
XLA op ever slices, scatters into, restacks or relayouts it.  This
kernel is the pool's only writer on the chip: the pool goes in and comes
out as the same buffer (``input_output_aliases``), and each grid step
rewrites one block of one page of one layer, the layer index and the
step's page scalar-prefetched.

A step is a block read-modify-write: the block comes into VMEM, the rows
[lo, hi) of it that this step owns are replaced by the new tokens, and
the block goes back.  Rows outside [lo, hi) keep what the pool held, so
a chunk may start or end mid-page and pad positions are never written.
A chunk step's block is a whole page; a decode step (one token per row)
moves only the 8-row sublane tile that holds its token.  The steps are
sorted by block, so every step that touches one block runs back to
back: the block is fetched once, stays in the output buffer across
those steps (Pallas writes an output block back only when its index
changes), and each of them adds its rows.  Two chunks of one sequence
packed into one launch may therefore share a boundary page.  A step with
nothing to write (a row with fewer pages than the launch's widest row,
or a dead row) targets block 0 of the null page 0 with an empty range.

Where each step goes depends only on the block table and the positions,
not on the layer: `write_plan` computes it once per model step and every
layer's write reuses it.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend


class WritePlan(NamedTuple):
    """The sorted grid of a write, one entry per (row, page slot) step:
    the page, the block of `block_rows` rows within it, the row, the
    row's page slot (counted from the row's first page) and the range
    [lo, hi) of the block's rows the step writes; plus each row's first
    position, which aligns the staged chunk rows to their pages."""
    page: jax.Array
    blk: jax.Array
    row: jax.Array
    slot: jax.Array
    lo: jax.Array
    hi: jax.Array
    starts: jax.Array


def block_rows(n_tokens: int, page_size: int) -> int:
    """Rows of the pool block one step rewrites: the whole page for a
    chunk, the 8-row sublane tile (or the whole of a smaller page) for a
    decode token."""
    return math.gcd(page_size, 8) if n_tokens == 1 else page_size


def write_plan(tables, starts, ends, n_tokens: int,
               page_size: int) -> WritePlan:
    """Row r writes positions [starts[r], min(ends[r], starts[r] +
    n_tokens)) through its block-table row tables[r].  Steps are sorted
    by (page, block), so the steps of one block are adjacent; a step with
    nothing to write gets page 0, block 0 and an empty range."""
    R, n_max = tables.shape
    rows = block_rows(n_tokens, page_size)
    n_slots = -(-(n_tokens - 1) // page_size) + 1
    starts = jnp.asarray(starts, jnp.int32)
    stop = jnp.minimum(jnp.asarray(ends, jnp.int32), starts + n_tokens)
    lp = starts[:, None] // page_size + jnp.arange(n_slots)[None, :]
    p0 = lp * page_size
    lo = jnp.maximum(starts[:, None], p0) - p0
    hi = jnp.minimum(stop[:, None], p0 + page_size) - p0
    live = hi > lo
    page = jnp.take_along_axis(jnp.asarray(tables, jnp.int32),
                               jnp.minimum(lp, n_max - 1), axis=1)
    page = jnp.where(live, page, 0).reshape(-1)
    blk = jnp.where(live, lo // rows, 0)
    lo = jnp.where(live, lo - blk * rows, 0).reshape(-1)
    hi = jnp.where(live, hi - blk * rows, 0).reshape(-1)
    blk = blk.reshape(-1)
    order = jnp.argsort(page * (page_size // rows) + blk, stable=True)
    row = jnp.repeat(jnp.arange(R, dtype=jnp.int32), n_slots)
    slot = jnp.tile(jnp.arange(n_slots, dtype=jnp.int32), R)
    return WritePlan(*(a[order] for a in (page, blk, row, slot, lo, hi)),
                     starts=starts)


def _write_kernel(layer_ref, page_ref, blk_ref, row_ref, slot_ref, lo_ref,
                  hi_ref, src_ref, pool_ref, out_ref):
    """src_ref: (1, Hkv, T, W) the step's new rows (T = page_size: a page
    image aligned to the page; T = 1: one token row, broadcast);
    pool_ref/out_ref: (1, Hkv, 1, rows, W) the block, aliased."""
    i = pl.program_id(0)
    prev = jnp.maximum(i - 1, 0)
    fresh = ((i == 0) | (page_ref[i] != page_ref[prev])
             | (blk_ref[i] != blk_ref[prev]))

    @pl.when(fresh)
    def _load():
        out_ref[...] = pool_ref[...]

    block = out_ref[0, :, 0]                                # (Hkv, rows, W)
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    keep = (rows >= lo_ref[i]) & (rows < hi_ref[i])
    new = jnp.broadcast_to(src_ref[0], block.shape)
    out_ref[0, :, 0] = jnp.where(keep, new, block)


@jax.jit
def kv_page_write(kv_pages, layer, new, plan: WritePlan) -> jax.Array:
    """Write token rows into layer `layer` of the stacked page pool.

    kv_pages: (L, Hkv, P, page_size, W) the pool, updated in place
    layer:    scalar int32 layer index
    new:      (R, S, Hkv, W) packed K/V rows (ops.pack_kv); row r's token
              i belongs at absolute position starts[r] + i
    plan:     write_plan(tables, starts, ends, S, page_size)
    Returns the pool."""
    _, Hkv, _, ps, W = kv_pages.shape
    R, S = new.shape[:2]
    rows = block_rows(S, ps)
    if S == 1:
        T = 1
        src = jnp.moveaxis(new, 2, 1)                       # (R, Hkv, 1, W)
    else:
        # align each row to its first page: staged token u of row r sits
        # at position (starts[r] // ps) * ps + u
        T = ps
        n_slots = -(-(S - 1) // ps) + 1
        u = jnp.arange(n_slots * ps)[None, :] - (plan.starts % ps)[:, None]
        src = jnp.take_along_axis(new, jnp.clip(u, 0, S - 1)[:, :, None,
                                                              None], axis=1)
        src = jnp.moveaxis(src, 2, 1)              # (R, Hkv, n_slots*ps, W)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer + page, blk, row, slot, lo, hi per step, in SMEM
        num_scalar_prefetch=7,
        grid=(plan.page.shape[0],),
        in_specs=[
            pl.BlockSpec((1, Hkv, T, W),
                         lambda i, l, pg, b, r, s, lo, hi:
                         (r[i], 0, s[i], 0)),
            pl.BlockSpec((1, Hkv, 1, rows, W),
                         lambda i, l, pg, b, r, s, lo, hi:
                         (l[0], 0, pg[i], b[i], 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, 1, rows, W),
                               lambda i, l, pg, b, r, s, lo, hi:
                               (l[0], 0, pg[i], b[i], 0)),
    )
    return pl.pallas_call(
        _write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        # operands: 7 prefetched scalars, src, pool -> the pool is 8
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=backend.interpret_mode(),
        name="kv_page_write",
    )(layer, *plan[:6], src.astype(kv_pages.dtype), kv_pages)
