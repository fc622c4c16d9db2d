"""Plain reference forward of a dense decoder-only transformer.

Straightforward jax.numpy in float32 at `precision=HIGHEST`, no kernels,
no cache, no batching: one sequence, full causal attention.  It follows
the layer equations the benchmark configuration states, with the
departures from the published model that the file lists (pre-norm
blocks, RMS or layer norm, rotary (half-split) or sinusoidal positions,
grouped query attention, gated-SiLU or tanh-GELU MLP, tied unembedding),
and imports nothing of the serving program.

`quant="fp8"` is the control: every matrix product takes its operands
rounded to float8 e4m3 (activations scaled per row, weights per output
column, q/k/v per head vector), the next precision below the bfloat16 the
configurations state.  Accumulation and softmax stay float32.

Weights are the benchmark's own seed-made tree (`make_params` below, in
the serving program's layout), read one layer at a time and widened to
float32 inside the layer's program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
SEQ_BUCKET = 512
POS_BUCKET = 128
DT = jnp.bfloat16


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, quant):
    """a (..., k) @ w (k, n) in float32."""
    if quant:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=HI)


def _norm(p, x, c):
    if c["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c["eps"])
        return y * p["scale"].astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + c["eps"])
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _freqs(n_half, theta):
    return jnp.exp(-jnp.arange(n_half, dtype=jnp.float32)
                   * (math.log(theta) / n_half))


def _rope(x, theta):
    """x: (S, H, D), positions 0..S-1, half-split rotation."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * _freqs(half, theta)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _attention(q, k, v, quant):
    """q: (S, Hq, D), k/v: (S, Hkv, D); causal, one KV head's group at a
    time so the score block stays (G, S, S)."""
    S, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    qg = jnp.moveaxis(q.reshape(S, hkv, g, d), 0, 2)     # (Hkv, G, S, D)
    kg = jnp.moveaxis(k, 0, 1)                           # (Hkv, S, D)
    vg = jnp.moveaxis(v, 0, 1)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def one(args):
        qh, kh, vh = args
        s = jnp.einsum("gsd,td->gst", qh, kh, precision=HI) / math.sqrt(d)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gst,td->gsd", p, vh, precision=HI)

    o = jax.lax.map(one, (qg, kg, vg))                   # (Hkv, G, S, D)
    return jnp.moveaxis(o, 2, 0).reshape(S, hq * d)


@partial(jax.jit, static_argnames=("c", "quant"))
def _embed(embed, tokens, *, c, quant):
    c = dict(c)
    table = embed.astype(jnp.float32)
    x = jnp.take(_q8(table, -1) if quant else table, tokens, axis=0)
    if c["pos"] == "sinusoidal":
        half = c["d"] // 2
        ang = jnp.arange(tokens.shape[0], dtype=jnp.float32)[:, None] \
            * _freqs(half, 10_000.0)[None]
        x = x + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    return x


@partial(jax.jit, static_argnames=("c", "quant"))
def _layer(blocks, i, x, *, c, quant):
    c = dict(c)
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(jnp.float32), blocks)
    S = x.shape[0]
    h = _norm(p["n1"], x, c)
    a = p["attn"]
    q = _mm(h, a["wq"], quant).reshape(S, c["hq"], c["hd"])
    k = _mm(h, a["wk"], quant).reshape(S, c["hkv"], c["hd"])
    v = _mm(h, a["wv"], quant).reshape(S, c["hkv"], c["hd"])
    if c["pos"] == "rope":
        q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    x = x + _mm(_attention(q, k, v, quant), a["wo"], quant)
    h = _norm(p["n2"], x, c)
    m = p["mlp"]
    if c["gated"]:
        u = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_in"], quant)
    else:
        u = _gelu_tanh(_mm(h, m["w_in"], quant))
    return x + _mm(u, m["w_out"], quant)


@partial(jax.jit, static_argnames=("c", "quant"))
def _head(final_norm, embed, x, positions, *, c, quant):
    h = _norm(final_norm, jnp.take(x, positions, axis=0), dict(c))
    return _mm(h, embed.astype(jnp.float32).T, quant)


def as_run(cfg: dict, key: str):
    """A configuration's value as the program runs it: its entry under
    `departures` where the file lists one, else the published value."""
    return cfg.get("departures", {}).get(key, cfg.get(key))


def hyper(cfg: dict) -> tuple:
    """The static sizes the forward needs, from a benchmark configuration
    file, as a hashable tuple."""
    eps = "rms_norm_eps" if cfg["norm"] == "rmsnorm" else "layer_norm_eps"
    return tuple(sorted({
        "d": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
        "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "norm": cfg["norm"], "eps": as_run(cfg, eps),
        "pos": as_run(cfg, "position_embedding"),
        "theta": float(cfg.get("rope_theta", 10_000.0)),
        "gated": cfg["mlp"] == "gated",
        "L": cfg["num_hidden_layers"]}.items()))


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
    x = _embed(params["tok"]["embed"], jnp.asarray(tok), c=c, quant=quant)
    for i in range(dict(c)["L"]):
        x = _layer(params["blocks"], jnp.int32(i), x, c=c, quant=quant)
    out = _head(params["final_norm"], params["tok"]["embed"], x,
                jnp.asarray(pos), c=c, quant=quant)
    return out[:n]


# ---------------------------------------------------------------------------
# weights: the serving program's parameter layout, made from the seed
# ---------------------------------------------------------------------------
def _norm_init(c: dict, lead=()):
    out = {"scale": jnp.ones(lead + (c["d"],), DT)}
    if c["norm"] == "layernorm":
        out["bias"] = jnp.zeros(lead + (c["d"],), DT)
    return out


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, DT) * jnp.asarray(fan_in ** -0.5, DT)


def shapes_of(cfg: dict) -> dict:
    """The handful of sizes the tree is built from, out of a benchmark
    configuration file."""
    return {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
            "f": cfg.get("intermediate_size") or cfg["ffn_dim"],
            "v": cfg["vocab_size"], "norm": cfg["norm"],
            "gated": cfg["mlp"] == "gated"}


@partial(jax.jit, static_argnums=0)
def _make(c: tuple, key):
    c = dict(c)
    L, d, f = c["L"], c["d"], c["f"]
    nq, nkv = c["hq"] * c["hd"], c["hkv"] * c["hd"]
    ks = jax.random.split(key, 8)
    attn = {"wq": _normal(ks[0], (L, d, nq), d),
            "wk": _normal(ks[1], (L, d, nkv), d),
            "wv": _normal(ks[2], (L, d, nkv), d),
            "wo": _normal(ks[3], (L, nq, d), nq)}
    mlp = {"w_in": _normal(ks[4], (L, d, f), d),
           "w_out": _normal(ks[5], (L, f, d), f)}
    if c["gated"]:
        mlp["w_gate"] = _normal(ks[6], (L, d, f), d)
    embed = jax.random.normal(ks[7], (c["v"], d), DT) * jnp.asarray(0.02, DT)
    return {"tok": {"embed": embed},
            "final_norm": _norm_init(c),
            "blocks": {"n1": _norm_init(c, (L,)), "attn": attn,
                       "n2": _norm_init(c, (L,)), "mlp": mlp}}


def make_params(cfg: dict, key):
    """All weights of `cfg` (a benchmark configuration file), on the
    default device, from `key`."""
    return _make(tuple(sorted(shapes_of(cfg).items())), key)


def counts(cfg: dict) -> dict:
    """Sizes the work formulas need (bench/work.py): layers, heads, and
    the weights a token passes through in the blocks and in the tied
    unembedding."""
    c = shapes_of(cfg)
    per_layer = c["d"] * (c["hq"] + 2 * c["hkv"]) * c["hd"] \
        + c["hq"] * c["hd"] * c["d"] \
        + (3 if c["gated"] else 2) * c["d"] * c["f"]
    return {"L": c["L"], "hq": c["hq"], "hkv": c["hkv"], "hd": c["hd"],
            "blocks": c["L"] * per_layer, "unembed": c["v"] * c["d"]}
