"""Shared neural-net building blocks (pure-functional, dict params)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import LEARNED_POS_OFFSET, ModelConfig


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


@jax.custom_vjp
def grad_cast(x):
    """Identity whose COTANGENT is cast to the primal dtype.  Mixed-precision
    dot transposes otherwise produce fp32 cotangents for bf16 primals, which
    then flow at full size through scatter/gather/collective backwards."""
    return x


def _grad_cast_fwd(x):
    return x, jnp.zeros((0,), x.dtype)    # dtype token (residuals must be arrays)


def _grad_cast_bwd(tok, g):
    return (g.astype(tok.dtype),)


grad_cast.defvjp(_grad_cast_fwd, _grad_cast_bwd)


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def dense(w, x):
    """x: (..., d_in) @ w: (d_in, d_out), bf16-native dot.

    No preferred_element_type=f32 here: the TPU MXU accumulates fp32
    internally for bf16 dots, and a bf16 result keeps the row-parallel
    partial-sum all-reduce (and FSDP weight all-gathers) at half the bytes.
    Requesting f32 results makes SPMD carry every projection collective in
    fp32 (measured 2x collective-term regression; see EXPERIMENTS.md S.Perf).
    """
    y = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())))
    return grad_cast(y.astype(x.dtype))


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def norm_init(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((d,), pdtype(cfg))}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), pdtype(cfg)),
                "bias": jnp.zeros((d,), pdtype(cfg))}
    return {}   # nonparam_ln (OLMo): no learned affine


def linear(params, name: str, x, cfg: ModelConfig):
    """x @ params["w" + name], plus params["b" + name] where the config
    has biases (wq/bq, w_in/b_in, ...)."""
    y = dense(params["w" + name], x)
    return y + params["b" + name].astype(y.dtype) if cfg.bias else y


def bias_init(key, d: int, dtype):
    return (jax.random.normal(key, (d,), jnp.float32) * 0.02).astype(dtype)


def apply_norm(params, x, cfg: ModelConfig):
    eps = cfg.norm_eps
    # grad_cast: the norm computes in fp32, which would otherwise make the
    # cotangent of its input fp32 - and that cotangent is exactly what the
    # sequence-parallel gather/reduce-scatter transpose pair carries.
    x = grad_cast(x)
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if cfg.norm == "layernorm":
        y = y * params["scale"].astype(jnp.float32) \
            + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x, scale, eps: float = 1e-6):
    """Per-head RMS norm (gemma3/qwen3 QK-norm).  x: (..., head_dim)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary position embedding (with dynamic scaling - paper Section V)
# --------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float,
         scaling: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) or (B, S)."""
    D = x.shape[-1]
    half = D // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    pos = positions.astype(jnp.float32) / scaling
    if pos.ndim == 1:
        ang = pos[:, None] * freqs[None, :]                  # (S, half)
        ang = ang[None, :, None, :]                          # (1,S,1,half)
    else:
        ang = pos[:, :, None] * freqs[None, None, :]         # (B,S,half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], -1).astype(x.dtype)


def sinusoidal_rows(positions: jax.Array, d: int) -> jax.Array:
    """The sinusoid rows (..., d) of the given positions, float32: sines of
    position x frequency in the first half, cosines in the second."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (math.log(10_000.0) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def sinusoidal_positions(seq: int, d: int) -> jax.Array:
    return sinusoidal_rows(jnp.arange(seq), d)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d: Optional[int] = None,
             f: Optional[int] = None):
    d = d or cfg.d_model
    f = f or cfg.d_ff
    dt = pdtype(cfg)
    ks = jax.random.split(key, 3)
    p = {"w_in": dense_init(ks[0], d, f, dt),
         "w_out": dense_init(ks[1], f, d, dt, scale=1.0 / math.sqrt(f))}
    if cfg.act == "silu":
        p["w_gate"] = dense_init(ks[2], d, f, dt)
    if cfg.bias:
        kb = jax.random.split(jax.random.fold_in(key, 1), 2)
        p["b_in"] = bias_init(kb[0], f, dt)
        p["b_out"] = bias_init(kb[1], d, dt)
    return p


def mlp(params, x, cfg: ModelConfig):
    h = linear(params, "_in", x, cfg)
    if cfg.act == "silu":
        h = jax.nn.silu(dense(params["w_gate"], x).astype(jnp.float32)) \
            * h.astype(jnp.float32)
        h = h.astype(x.dtype)
    elif cfg.act == "relu":
        h = jax.nn.relu(h)
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return linear(params, "_out", h, cfg)


# --------------------------------------------------------------------------
# Embedding
# --------------------------------------------------------------------------

def embed_init(key, cfg: ModelConfig):
    dt = pdtype(cfg)
    p = {"embed": (jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                                     jnp.float32) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["lm_head"] = (jax.random.normal(
            k2, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02).astype(dt)
    if cfg.learned_positions:
        k3 = jax.random.fold_in(key, 2)
        p["pos_embed"] = (jax.random.normal(
            k3, (cfg.max_seq + LEARNED_POS_OFFSET, cfg.d_model), jnp.float32)
            * 0.02).astype(dt)
    return p


def embed(params, tokens, cfg: ModelConfig):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.family == "dense" and cfg.qk_norm:     # gemma-style input scaling
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(params, x, cfg: ModelConfig):
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = jax.lax.dot_general(
        x, table, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits
