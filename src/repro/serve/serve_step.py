"""Serving steps: batched single-token decode, the dense whole-prompt
prefill, and the paged ragged chunk batch (every prefill of the paged
engine) and speculative verify.

The decode step is cache-layout agnostic: pass the dense {"k","v"} cache or
the paged {"kv_pages","block_table"} cache and decode_step routes
to the matching kernel (kernels/flash_decode.py).

Lane masking contract (what preemption and chunked prefill lean on): the
fused decode step computes every lane, but a lane whose `lens` is 0 and
whose block-table row is zeroed writes its K/V into the reserved null page
and its `live` mask keeps tokens/lens untouched - so the engine can park,
preempt, or mid-prefill a slot and still run one batched launch over the
full width without corrupting any live page.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models import Model
from . import sampling


def make_serve_step(model: Model, *, seq_parallel: bool = False):
    """serve_step(params, cache, tokens (B,1), lens (B,)) ->
    (logits (B,1,V), new_cache).  One new token against the KV cache."""

    def serve_step(params, cache, tokens, lens):
        return model.decode_step(params, tokens, lens, cache,
                                 seq_parallel=seq_parallel)

    return serve_step


def make_prefill_step(model: Model):
    """prefill_step(params, batch, cache) -> (last_logits, cache, lens)."""

    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache)

    return prefill_step


def copy_page(kv_pages, src, dst):
    """copy_page(kv_pages, src, dst) -> kv_pages: page `src` of the
    stacked (L, Hkv, P, page_size, W) pool copied to page `dst` in every
    layer and head (the data half of copy-on-write).  Jitted with the
    pool donated, the update is in place: one page is read and written."""
    page = jax.lax.dynamic_slice_in_dim(kv_pages, src, 1, axis=2)
    return jax.lax.dynamic_update_slice_in_dim(kv_pages, page, dst, axis=2)


def make_chunk_batch_step(model: Model, *, temperature: float,
                          top_k: int = 0, top_p: float = 1.0,
                          tp_mesh=None):
    """chunk_batch_step(params, batch, cache, page_tables, tokens, lens,
    key) -> (cache, tokens, lens).  ONE jitted launch for a whole tick's
    prefill plan: executes every packed chunk row (Model.prefill_chunks),
    samples the first token of every row that COMPLETED its prompt
    device-side, and folds the results into the engine's (B, 1) tokens
    and (B,) lens with single masked scatters - no per-slot host
    dispatches, no logits ever shipped to the host.

    batch carries the scheduler's pack (serve/scheduler.py ChunkBatch):
    "tokens" (K, S), "offset" (K,), "true_lens" (K,), and "final_slot"
    (K,) - the slot of each final row, `max_batch` (out of range, dropped
    by mode="drop") for non-final and dead padding rows.  `key` feeds
    temperature > 0 sampling and is ignored at 0.  tp_mesh head-shards
    the chunk kernel across the serve mesh (kernels/ops.py)."""

    def chunk_batch_step(params, batch, cache, page_tables, tokens, lens,
                         key):
        logits, cache, cursors = model.prefill_chunks(params, batch, cache,
                                                      page_tables,
                                                      tp_mesh=tp_mesh)
        toks = sample_token(logits, temperature=temperature, top_k=top_k,
                            top_p=top_p, key=key)
        slots = batch["final_slot"]
        tokens = tokens.at[slots, 0].set(toks[:, 0], mode="drop")
        lens = lens.at[slots].set(cursors, mode="drop")
        return cache, tokens, lens

    return chunk_batch_step


def make_fused_decode_step(model: Model, *, temperature: float,
                           top_k: int = 0, top_p: float = 1.0,
                           tp_mesh=None):
    """fused_decode_step(params, cache, tokens, lens, live, key) ->
    (cache, tokens, lens).  One batched decode step with sampling fused
    in: lanes where `live` (B,) is True get their sampled token written
    into tokens and their length bumped by one, dead lanes pass through
    untouched - the whole per-tick decode becomes one launch and zero
    per-slot host round-trips.  `key` feeds temperature > 0 sampling and
    is ignored at 0."""

    def fused_decode_step(params, cache, tokens, lens, live, key):
        logits, cache = model.decode_step(params, tokens, lens, cache,
                                          tp_mesh=tp_mesh)
        toks = sample_token(logits, temperature=temperature, top_k=top_k,
                            top_p=top_p, key=key)
        tokens = jnp.where(live[:, None], toks, tokens)
        lens = lens + live.astype(lens.dtype)
        return cache, tokens, lens

    return fused_decode_step


def make_spec_verify_step(model: Model, *, temperature: float,
                          top_k: int = 0, top_p: float = 1.0,
                          tp_mesh=None):
    """spec_verify_step(params, batch, cache, page_tables, tokens, lens,
    key) -> (cache, tokens, lens, n_acc).  ONE jitted launch verifies
    every draft chain the scheduler planned this tick (SpecBatch,
    serve/scheduler.py): row r holds [pending token, d_1..d_m] at
    offset = the slot's lens, scored through the batched chunk kernel
    (Model.verify_chunks) exactly as decode would have scored them one
    launch at a time - the chain's K/V scatters into the slot's reserved
    pages as a side effect, so accepted tokens need no re-decode.

    Acceptance is sample-and-compare (serve/sampling.py): the target's
    token is sampled at every chain position and a draft token is
    accepted iff it matches; the first mismatch (or chain end) yields
    the target's own token as the bonus, so every row nets n_acc + 1
    tokens.  The device updates tokens[slot] to the bonus (the new
    pending token) and lens[slot] to offset + n_acc + 1 (the new KV
    frontier: everything past it is rejected garbage the causal mask
    hides and later writes overwrite - rollback is free).  The host
    learns n_acc in the SAME fetch as the tick's tokens and reconstructs
    the accepted prefix from its own copy of the draft.

    batch: SpecBatch arrays - "tokens" (K, spec_k+1), "offset",
    "true_lens", "q_lens", "draft_lens", "row_slot" (K,) with dead pad
    rows carrying the out-of-range sentinel max_batch the mode="drop"
    scatter discards."""

    def spec_verify_step(params, batch, cache, page_tables, tokens, lens,
                         key):
        logits, cache = model.verify_chunks(params, batch, cache,
                                            page_tables, tp_mesh=tp_mesh)
        tgt = sampling.sample_chain(logits, key, temperature=temperature,
                                    top_k=top_k, top_p=top_p)
        n_acc, bonus = sampling.speculative_accept(
            tgt, batch["tokens"], batch["draft_lens"])
        slots = batch["row_slot"]
        tokens = tokens.at[slots, 0].set(bonus, mode="drop")
        lens = lens.at[slots].set(
            batch["offset"] + n_acc + 1, mode="drop")
        return cache, tokens, lens, n_acc

    return spec_verify_step


def sample_token(logits, *, temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, key: Optional[jax.Array] = None):
    """logits: (B, 1, V) -> (B, 1) int32 through the device-side sampling
    stack (serve/sampling.py): greedy at temperature <= 0 (key ignored),
    otherwise temperature -> top-k -> top-p -> categorical."""
    return sampling.sample(logits[:, -1], key, temperature=temperature,
                           top_k=top_k, top_p=top_p)[:, None]
