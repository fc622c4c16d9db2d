"""Plain reference forward of OPT's decoder as published (arXiv:2205.01068,
facebook/opt-*/config.json with do_layer_norm_before true).

Straightforward jax.numpy in float32 under
`jax.default_matmul_precision("highest")`, no kernels, no cache, no
batching: one sequence, full causal attention, and imports nothing of the
serving program.  The block:

    x = E[tok] + P[pos + 2]                     (learned positions, offset 2)
    h = x + W_o . MHA(LN1(x)) + b_o             (q/k/v biases; q scaled by
                                                 head_dim ** -0.5)
    x = h + W_2 . relu(W_1 . LN2(h) + b_1) + b_2
    logits = LN_f(x) . E^T                      (tied)

LayerNorm is affine with the file's eps; there is no embedding scale and no
project_in/out (word_embed_proj_dim equals hidden_size).

`quant=True` is the control: the program one precision step down.  The
program runs bfloat16 (the file's `assumed`; OPT states float16), so the
control rounds to float8 e4m3 every tensor the program holds in bfloat16:
the weights (per output column), biases and table rows (per vector), the
residual stream after every add, each projection's output, q/k/v (per
head vector), the attention probabilities and the MLP's hidden layer
(activations per row).  Accumulation, norm statistics and softmax stay
float32.  (dense_decoder's control rounds only the matrix products'
operands.  The program's rounding of its residual stream and block
outputs more than doubles its own error, and against that lighter control
OPT's gaps came within 3x of the program's.)

Weights are the benchmark's own seed-made tree (`make_params` below, in
the serving program's layout, biases and position table included), read
one layer at a time and widened to float32 inside the layer's program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .dense_decoder import (DT, HI, POS_BUCKET, SEQ_BUCKET, _mm, _norm,
                            _norm_init, _q8)

# OPTLearnedPositionalEmbedding: position p reads row p + 2 of a table of
# max_position_embeddings + 2 rows
OFFSET = 2
# OPT's init_std: every weight matrix, both embedding tables, and here the
# biases too (OPT starts them at zero; drawn, so that a program that drops
# them cannot agree).  With fan-in scaled matrices (dense_decoder's) and
# 16 layers, the constant parts of the residual stream swamp the token, and
# greedy decoding repeats one token with a margin no rounding can flip.
STD = 0.02


def _check(cfg: dict):
    """The file describes the block this module computes."""
    want = {"activation_function": "relu", "norm": "layernorm",
            "do_layer_norm_before": True, "position_embedding": "learned",
            "enable_bias": True, "tie_word_embeddings": True}
    bad = {k: cfg.get(k) for k, v in want.items() if cfg.get(k) != v}
    if bad or cfg.get("departures"):
        raise ValueError(f"{cfg['name']}: not OPT's published block: {bad}")


def shapes_of(cfg: dict) -> dict:
    """The handful of sizes the tree is built from, out of a benchmark
    configuration file."""
    _check(cfg)
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg["ffn_dim"], "v": cfg["vocab_size"],
            "ctx": cfg["max_position_embeddings"]}


def hyper(cfg: dict) -> tuple:
    """The static sizes the forward needs, as a hashable tuple."""
    c = shapes_of(cfg)
    c.update(norm="layernorm", eps=cfg["layer_norm_eps"])
    return tuple(sorted(c.items()))


def _r8(x, quant):
    """x rounded to float8 per vector (last axis) in the control."""
    return _q8(x, -1) if quant else x


def _attention(q, k, v, quant):
    """q/k/v: (S, H, D); causal, one head at a time so the score block
    stays (S, S)."""
    S, h, d = q.shape
    q, k, v = (jnp.moveaxis(_r8(t, quant), 1, 0) for t in (q, k, v))
    mask = jnp.tril(jnp.ones((S, S), bool))

    def one(args):
        qh, kh, vh = args
        s = jnp.einsum("sd,td->st", qh, kh, precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("st,td->sd", _r8(p, quant), vh, precision=HI)

    o = jax.lax.map(one, (q, k, v))                      # (H, S, D)
    return jnp.moveaxis(o, 0, 1).reshape(S, h * d)


@partial(jax.jit, static_argnames=("quant",))
def _embed(tok, tokens, *, quant):
    table = tok["embed"].astype(jnp.float32)
    x = jnp.take(_r8(table, quant), tokens, axis=0)
    pos = jnp.arange(tokens.shape[0]) + OFFSET
    rows = jnp.take(tok["pos_embed"], pos, axis=0, mode="clip")
    return _r8(x + _r8(rows.astype(jnp.float32), quant), quant)


@partial(jax.jit, static_argnames=("c", "quant"))
def _layer(blocks, i, x, *, c, quant):
    c = dict(c)
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(jnp.float32), blocks)
    if quant:      # weights per output column (in _mm), the rest per vector
        p = jax.tree.map(lambda a: a if a.ndim == 2 else _q8(a, -1), p)
    S = x.shape[0]

    def proj(h, w, b):
        return _r8(_mm(h, w, quant) + b, quant)

    a = p["attn"]
    h = _norm(p["n1"], x, c)
    q = proj(h, a["wq"], a["bq"]).reshape(S, c["hq"], c["hd"])
    k = proj(h, a["wk"], a["bk"]).reshape(S, c["hkv"], c["hd"])
    v = proj(h, a["wv"], a["bv"]).reshape(S, c["hkv"], c["hd"])
    x = _r8(x + proj(_attention(q, k, v, quant), a["wo"], a["bo"]), quant)
    m = p["mlp"]
    h = _norm(p["n2"], x, c)
    u = jax.nn.relu(proj(h, m["w_in"], m["b_in"]))
    return _r8(x + proj(u, m["w_out"], m["b_out"]), quant)


@partial(jax.jit, static_argnames=("c", "quant"))
def _head(final_norm, embed, x, positions, *, c, quant):
    final_norm = jax.tree.map(
        lambda a: _r8(a.astype(jnp.float32), quant), final_norm)
    h = _norm(final_norm, jnp.take(x, positions, axis=0), dict(c))
    return _mm(h, embed.astype(jnp.float32).T, quant)


def logits_at(cfg: dict, params, tokens: np.ndarray, positions: np.ndarray,
              quant: bool = False) -> jax.Array:
    """Float32 logits (len(positions), vocab) of one sequence `tokens`,
    at the given positions (each row predicts the token after it)."""
    c = hyper(cfg)
    S = len(tokens)
    s_pad = -(-S // SEQ_BUCKET) * SEQ_BUCKET
    n = len(positions)
    n_pad = -(-n // POS_BUCKET) * POS_BUCKET
    tok = np.zeros(s_pad, np.int32)
    tok[:S] = tokens
    pos = np.zeros(n_pad, np.int32)
    pos[:n] = positions
    with jax.default_matmul_precision("highest"):
        x = _embed(params["tok"], jnp.asarray(tok), quant=quant)
        for i in range(dict(c)["L"]):
            x = _layer(params["blocks"], jnp.int32(i), x, c=c, quant=quant)
        out = _head(params["final_norm"], params["tok"]["embed"], x,
                    jnp.asarray(pos), c=c, quant=quant)
    return out[:n]


# ---------------------------------------------------------------------------
# weights: the serving program's parameter layout, made from the seed
# ---------------------------------------------------------------------------
def _init(key, shape):
    return jax.random.normal(key, shape, DT) * jnp.asarray(STD, DT)


@partial(jax.jit, static_argnums=0)
def _make(c: tuple, key):
    c = dict(c)
    L, d, f = c["L"], c["d"], c["f"]
    nq, nkv = c["hq"] * c["hd"], c["hkv"] * c["hd"]
    shapes = {"attn": {"wq": (L, d, nq), "wk": (L, d, nkv),
                       "wv": (L, d, nkv), "wo": (L, nq, d), "bq": (L, nq),
                       "bk": (L, nkv), "bv": (L, nkv), "bo": (L, d)},
              "mlp": {"w_in": (L, d, f), "w_out": (L, f, d), "b_in": (L, f),
                      "b_out": (L, d)},
              "tok": {"embed": (c["v"], d),
                      "pos_embed": (c["ctx"] + OFFSET, d)}}
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    made = tree.unflatten([_init(k, s) for k, s in zip(keys, leaves)])
    norm = {"norm": "layernorm", "d": d}
    return {"tok": made["tok"], "final_norm": _norm_init(norm),
            "blocks": {"n1": _norm_init(norm, (L,)), "attn": made["attn"],
                       "n2": _norm_init(norm, (L,)), "mlp": made["mlp"]}}


def make_params(cfg: dict, key):
    """All weights of `cfg` (a benchmark configuration file), on the
    default device, from `key`."""
    return _make(tuple(sorted(shapes_of(cfg).items())), key)


def counts(cfg: dict) -> dict:
    """Sizes the work formulas need (bench/work.py): layers, heads, and
    the weights a token passes through in the blocks (biases included)
    and in the tied unembedding."""
    c = shapes_of(cfg)
    nq, nkv = c["hq"] * c["hd"], c["hkv"] * c["hd"]
    per_layer = c["d"] * (nq + 2 * nkv) + nq * c["d"] \
        + 2 * c["d"] * c["f"] + (nq + 2 * nkv + c["d"]) + (c["f"] + c["d"])
    return {"L": c["L"], "hq": c["hq"], "hkv": c["hkv"], "hd": c["hd"],
            "blocks": c["L"] * per_layer, "unembed": c["v"] * c["d"]}
