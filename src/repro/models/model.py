"""Top-level language-model API: build_model(cfg) -> init / forward / loss /
cache / prefill / decode_step, for every architecture family.

Batch dict keys:
  tokens        (B, S)  text / decoder tokens (int32)
  vision_embeds (B, P, d_model)   [vlm stub frontend]
  audio_embeds  (B, S_enc, d_model) [audio stub frontend]
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import LEARNED_POS_OFFSET, ModelConfig
from ..sharding import constrain
from . import transformer as T
from .layers import (apply_norm, embed, embed_init, norm_init, pdtype,
                     sinusoidal_positions, sinusoidal_rows, unembed)


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    forward: Callable
    loss: Callable
    init_cache: Callable
    prefill: Callable
    decode_step: Callable
    # the one paged prefill (attention families only): K chunks of K
    # different sequences, each with its own block-table row / offset /
    # cursor, in ONE call through the offset-causal block-table kernel,
    # attending over everything already written (cached prefix + earlier
    # chunks).  A whole prompt, or a prefix-cached suffix, is one chunk.
    # The serve tick packs its whole chunk plan through this
    # (serve/scheduler.py plans the chunks, serve/paged_cache.py owns the
    # pages, serve/prefix_cache.py the cached prefixes).
    prefill_chunks: Optional[Callable] = None
    # speculative verification: the same ragged chunk pass, but returning
    # EVERY position's logits (K, S, V) instead of each row's last - one
    # launch scores a whole draft chain per row so the serve engine can
    # accept/reject it in place (serve/serve_step.py make_spec_verify_step)
    verify_chunks: Optional[Callable] = None


def build_model(cfg: ModelConfig) -> Model:
    fam = cfg.family

    # ---------------- init -------------------------------------------------
    def init(key):
        k_emb, k_blocks = jax.random.split(key)
        params: Dict[str, Any] = {"tok": embed_init(k_emb, cfg),
                                  "final_norm": norm_init(cfg)}
        if fam in ("dense", "moe", "vlm"):
            params["blocks"] = T.stack_init(k_blocks, cfg)
        elif fam == "hybrid":
            params["blocks"] = T.hybrid_init(k_blocks, cfg)
        elif fam == "ssm":
            params["blocks"] = T.rwkv_init(k_blocks, cfg)
        elif fam == "audio":
            params["blocks"] = T.encdec_init(k_blocks, cfg)
        else:
            raise ValueError(f"unknown family {fam}")
        return params

    # ---------------- embedding helpers ------------------------------------
    def _add_positions(params, x, pos):
        """x (..., d_model) plus the absolute-position rows of `pos` (x's
        leading shape) where the model has absolute positions: rows
        pos + LEARNED_POS_OFFSET of the learned table (clamped to it: only pad
        positions of a padded chunk can lie past it, and their rows are
        dropped), or the sinusoid rows of exactly these positions."""
        if cfg.use_rope or cfg.rwkv:
            return x
        if cfg.learned_positions:
            rows = jnp.take(params["tok"]["pos_embed"], pos + LEARNED_POS_OFFSET,
                            axis=0, mode="clip")
        else:
            rows = sinusoidal_rows(pos, cfg.d_model)
        return x + rows.astype(x.dtype)

    def _embed_tokens(params, tokens):
        x = embed(params["tok"], tokens, cfg)
        return _add_positions(params, x, jnp.arange(tokens.shape[1])[None])

    def _assemble_input(params, batch):
        x = _embed_tokens(params, batch["tokens"])
        prefix = 0
        if fam == "vlm" and "vision_embeds" in batch:
            v = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([v, x], axis=1)
            prefix = v.shape[1]
        return x, prefix

    # ---------------- forward ----------------------------------------------
    def forward(params, batch, *, impl=None, remat=False):
        """Returns (logits (B,S,V), aux_loss)."""
        if fam == "audio":
            enc = batch["audio_embeds"]
            pos = sinusoidal_positions(enc.shape[1], cfg.d_model)
            enc = enc + pos[None].astype(enc.dtype)
            x_dec = _embed_tokens(params, batch["tokens"])
            x, aux = T.encdec_forward(params["blocks"], enc, x_dec, cfg,
                                      impl=impl, remat=remat)
        else:
            x, _prefix = _assemble_input(params, batch)
            x = constrain(x, "btd")
            if fam in ("dense", "moe", "vlm"):
                x, aux = T.stack_forward(params["blocks"], x, cfg, impl=impl,
                                         remat=remat)
            elif fam == "hybrid":
                x, aux = T.hybrid_forward(params["blocks"], x, cfg,
                                          impl=impl, remat=remat)
            else:
                x, aux = T.rwkv_forward(params["blocks"], x, cfg, impl=impl,
                                        remat=remat)
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["tok"], x, cfg)
        logits = constrain(logits.astype(jnp.float32), "btv")
        return logits, aux

    # ---------------- loss --------------------------------------------------
    def loss(params, batch, *, impl=None, remat=False, aux_weight=0.01):
        logits, aux = forward(params, batch, impl=impl, remat=remat)
        tokens = batch["tokens"]
        labels = batch.get("labels", tokens)
        prefix = 0
        if fam == "vlm" and "vision_embeds" in batch:
            prefix = batch["vision_embeds"].shape[1]
            logits = logits[:, prefix:]
        # next-token prediction
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        tgt = labels[:, 1:]
        nll = -jnp.take_along_axis(lp, tgt[..., None], -1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:].astype(jnp.float32)
            ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            ce = jnp.mean(nll)
        total = ce + aux_weight * aux
        return total, {"ce": ce, "aux": aux}

    # ---------------- cache -------------------------------------------------
    def init_cache(batch_size: int, max_len: int, enc_len: int = 0, *,
                   page_size: int = 0, num_pages: int = 0):
        """Dense layout by default; page_size > 0 selects the paged layout:
        one stacked (L, Hkv, num_pages, page_size, 2 * head_dim) page pool
        shared by all sequences and layers - K in lanes [0, D) and V in
        [D, 2D) of each token row - plus a (batch, ceil(max_len/page_size))
        block table.  Page 0 is reserved as the null page (see
        serve/paged_cache.py)."""
        dt = pdtype(cfg)
        if page_size > 0:
            if fam not in ("dense", "moe", "vlm"):
                raise ValueError(
                    f"paged KV cache needs an attention family, got {fam}")
            from ..configs.base import dense_equivalent_pages, pages_for_tokens
            L = cfg.n_layers
            n_max = pages_for_tokens(max_len, page_size)
            if num_pages <= 0:
                num_pages = dense_equivalent_pages(batch_size, max_len,
                                                   page_size)
            shp = (L, cfg.n_kv_heads, num_pages, page_size, 2 * cfg.head_dim)
            return {"kv_pages": jnp.zeros(shp, dt),
                    "block_table": jnp.zeros((batch_size, n_max), jnp.int32)}
        if fam in ("dense", "moe", "vlm"):
            L = cfg.n_layers
            shp = (L, batch_size, max_len, cfg.n_kv_heads, cfg.head_dim)
            return {"k": jnp.zeros(shp, dt), "v": jnp.zeros(shp, dt)}
        if fam == "hybrid":
            return T.hybrid_init_cache(cfg, batch_size, max_len)
        if fam == "ssm":
            return T.rwkv_init_cache(cfg, batch_size, max_len)
        if fam == "audio":
            return T.encdec_init_cache(cfg, batch_size, max_len,
                                       enc_len or max_len)
        raise ValueError(fam)

    # ---------------- prefill ------------------------------------------------
    def prefill(params, batch, cache, *, impl=None):
        """Fill the cache with the prompt; returns (last_logits, cache, lens)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        if fam in ("dense", "moe", "vlm"):
            x, prefix = _assemble_input(params, batch)
            x, cache = T.stack_prefill(params["blocks"], x, cfg, cache,
                                       impl=impl)
            lens = jnp.full((B,), S + prefix, jnp.int32)
        elif fam == "audio":
            enc = batch["audio_embeds"]
            pos = sinusoidal_positions(enc.shape[1], cfg.d_model)
            enc_in = enc + pos[None].astype(enc.dtype)
            x_dec = _embed_tokens(params, tokens)
            x, cache = T.encdec_prefill(params["blocks"], enc_in, x_dec, cfg,
                                        cache, impl=impl)
            lens = jnp.full((B,), S, jnp.int32)
        elif fam == "hybrid":
            x, _ = _assemble_input(params, batch)
            x, cache = T.hybrid_prefill(params["blocks"], x, cfg, cache,
                                        impl=impl)
            lens = jnp.full((B,), S, jnp.int32)
        elif fam == "ssm":
            x, _ = _assemble_input(params, batch)
            x, cache = T.rwkv_prefill(params["blocks"], x, cfg, cache,
                                      impl=impl)
            lens = jnp.full((B,), S, jnp.int32)
        else:
            return _prefill_via_decode(params, batch, cache, impl=impl)
        # prompts padded to a bucketed length carry their real lengths in
        # batch["true_lens"]; trailing pad K/V is masked by `lens` downstream
        tl = batch.get("true_lens")
        if tl is not None:
            lens = jnp.asarray(tl, jnp.int32) + (lens - S)
        x = apply_norm(params["final_norm"], x, cfg)
        x_last = x[:, -1:] if tl is None else \
            jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
        logits = unembed(params["tok"], x_last, cfg)
        return logits.astype(jnp.float32), cache, lens

    # ---------------- paged prefill -----------------------------------------
    def prefill_chunks(params, batch, cache, page_tables, *, impl=None,
                       tp_mesh=None):
        """Prefill a RAGGED BATCH of mid-prompt chunks: K chunks of K
        different sequences, each at its own prompt position, in ONE pass
        (the serve engine's one-launch tick packs every chunk the
        scheduler planned into a single call here).

        batch: {"tokens": (K, S_pad) chunk tokens (each row zero-padded),
                "offset": (K,) absolute position of each row's first token,
                "true_lens": (K,) cursor AFTER each row's last real token
                (= offset + real chunk length)}; page_tables: (K, n_max)
        per-row block-table rows.  Every row's queries attend causally
        over everything already resident - cached prefix pages, earlier
        chunks' K/V (including other rows of the SAME call, when two
        chunks of one sequence are packed together with ordered offsets),
        and the row's own chunk - through the offset-causal batched
        block-table kernel (kernels/paged_prefill.py), so composing
        chunks left to right reproduces one whole-prompt chunk exactly.
        Dead padding rows carry true_lens == 0 and an all-null table row;
        their logits are garbage the caller drops.
        Returns (chunk_last_logits (K, 1, V), cache, cursors (K,)): each
        row's logits are those of its LAST real token (meaningful for
        final chunks, whose cursor equals the prompt length and whose
        logits seed decoding)."""
        if fam not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"chunked prefill needs an attention family, got {fam}")
        tokens = batch["tokens"]
        B, S = tokens.shape
        offs = jnp.asarray(batch["offset"], jnp.int32)
        lens = jnp.asarray(batch["true_lens"], jnp.int32)
        x = embed(params["tok"], tokens, cfg)
        # absolute positions start at each row's offset
        x = _add_positions(params, x, offs[:, None] + jnp.arange(S)[None, :])
        x = constrain(x, "btd")
        x, cache = T.stack_prefill_chunks_paged(params["blocks"], x, cfg,
                                                cache, page_tables, offs,
                                                lens, impl=impl,
                                                tp_mesh=tp_mesh)
        x = apply_norm(params["final_norm"], x, cfg)
        # each row's last REAL token sits at chunk index lens - offset - 1
        # (clamped to 0 for dead padding rows, whose logits are dropped)
        idx = jnp.maximum(lens - offs - 1, 0)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = unembed(params["tok"], x_last, cfg)
        return logits.astype(jnp.float32), cache, lens

    def verify_chunks(params, batch, cache, page_tables, *, impl=None,
                      tp_mesh=None):
        """Score a ragged batch of SPECULATIVE DRAFT CHAINS: row k holds
        [pending token, draft_1 .. draft_m] at absolute positions
        batch["offset"][k] + arange(S) - exactly the prefill_chunks
        contract (each row's K/V scatters into its pages, then the
        offset-causal batched kernel attends over everything resident) -
        but returns EVERY position's logits, because acceptance needs the
        target distribution at each chain position, not just the last.

        batch adds "q_lens" (K,): the per-row REAL query count (1 + m),
        fed to the kernel's draft-length lane so pad positions come back
        as exactly-zero rows (deterministic logits whatever the pad lanes
        hold).  Returns (logits (K, S, V) float32, cache).  Writing the
        whole chain's K/V is speculative too: positions past the accepted
        frontier are simply left behind the row's `lens` - masked by the
        causal/true_len tests everywhere KV is read - and overwritten by
        whatever decodes next, so rejection needs no page bookkeeping."""
        if fam not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"speculative verification needs an attention family, "
                f"got {fam}")
        tokens = batch["tokens"]
        B, S = tokens.shape
        offs = jnp.asarray(batch["offset"], jnp.int32)
        lens = jnp.asarray(batch["true_lens"], jnp.int32)
        qls = jnp.asarray(batch["q_lens"], jnp.int32)
        x = embed(params["tok"], tokens, cfg)
        x = _add_positions(params, x, offs[:, None] + jnp.arange(S)[None, :])
        x = constrain(x, "btd")
        x, cache = T.stack_prefill_chunks_paged(params["blocks"], x, cfg,
                                                cache, page_tables, offs,
                                                lens, q_lens=qls, impl=impl,
                                                tp_mesh=tp_mesh)
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["tok"], x, cfg)
        return logits.astype(jnp.float32), cache

    def _fill_cross_cache(params, cache, enc_out):
        from .layers import dense
        dec = params["blocks"]["decoder"]
        B, Se, _ = enc_out.shape

        def body(_, xs):
            p, ck, cv = xs
            ca = p["cross_attn"]
            k = dense(ca["wk"], enc_out).reshape(B, Se, cfg.n_kv_heads,
                                                 cfg.head_dim)
            v = dense(ca["wv"], enc_out).reshape(B, Se, cfg.n_kv_heads,
                                                 cfg.head_dim)
            return None, (k.astype(ck.dtype), v.astype(cv.dtype))

        _, (ck, cv) = jax.lax.scan(body, None,
                                   (dec, cache["cross_k"], cache["cross_v"]))
        out = dict(cache)
        out["cross_k"], out["cross_v"] = ck, cv
        return out

    def _prefill_via_decode(params, batch, cache, *, impl=None):
        """Sequential prefill through decode_step (recurrent families and the
        whisper decoder); exact, used at example/smoke scale."""
        tokens = batch["tokens"]
        B, S = tokens.shape

        def body(carry, t):
            cache, lens, _ = carry
            logits, cache = decode_step(params, tokens[:, t][:, None], lens,
                                        cache, impl=impl)
            return (cache, lens + 1, logits), None

        B = tokens.shape[0]
        dummy = jnp.zeros((B, 1, cfg.vocab_size), jnp.float32)
        (cache, lens, logits), _ = jax.lax.scan(
            body, (cache, jnp.zeros((B,), jnp.int32), dummy), jnp.arange(S))
        return logits, cache, lens

    # ---------------- decode -------------------------------------------------
    def decode_step(params, tokens, lens, cache, *, impl=None,
                    seq_parallel=False, enc_lens=None, tp_mesh=None):
        """tokens: (B,1); lens: (B,) positions to write.  Returns
        (logits (B,1,V), new_cache).  tp_mesh head-shards the paged decode
        across the serve mesh (attention families with a paged cache
        only)."""
        x = _add_positions(params, embed(params["tok"], tokens, cfg),
                           lens[:, None])
        if fam == "audio":
            el = enc_lens if enc_lens is not None \
                else jnp.full_like(lens, cache["cross_k"].shape[2])
            x, cache = T.encdec_decode(params["blocks"], x, cfg, cache, lens,
                                       el, impl=impl,
                                       seq_parallel=seq_parallel)
        elif fam in ("dense", "moe", "vlm"):
            if "kv_pages" in cache:
                if seq_parallel:
                    raise ValueError(
                        "paged decode does not compose with the "
                        "sequence-parallel cache layout")
                x, cache = T.stack_decode_paged(params["blocks"], x, cfg,
                                                cache, lens, impl=impl,
                                                tp_mesh=tp_mesh)
            else:
                x, cache = T.stack_decode(params["blocks"], x, cfg, cache,
                                          lens, impl=impl,
                                          seq_parallel=seq_parallel)
        elif fam == "hybrid":
            x, cache = T.hybrid_decode(params["blocks"], x, cfg, cache,
                                       lens, impl=impl,
                                       seq_parallel=seq_parallel)
        else:
            x, cache = T.rwkv_decode(params["blocks"], x, cfg, cache,
                                     lens, impl=impl,
                                     seq_parallel=seq_parallel)
        x = apply_norm(params["final_norm"], x, cfg)
        logits = unembed(params["tok"], x, cfg)
        return logits.astype(jnp.float32), cache

    is_attn = fam in ("dense", "moe", "vlm")
    return Model(cfg=cfg, init=init, forward=forward, loss=loss,
                 init_cache=init_cache, prefill=prefill,
                 decode_step=decode_step,
                 prefill_chunks=prefill_chunks if is_attn else None,
                 verify_chunks=verify_chunks if is_attn else None)
