"""Mixture-of-Experts FFN: top-k routing, capacity-based dispatch.

Dispatch is per batch row (capacity = S*K/E per row): the position cumsum
runs along the *unsharded* in-row axis, so under SPMD the whole routing
pipeline partitions cleanly over (batch -> data, experts -> model) with the
token->expert exchange lowering to an all-to-all between the data and model
axes (expert parallelism).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding import constrain
from .layers import dense_init, grad_cast, pdtype


def _edot(pattern, a, w):
    """Expert einsum with fp32 accumulation (see kernels.ref.mixed_einsum)."""
    from ..kernels.ref import mixed_einsum
    return mixed_einsum(pattern, a, w)


def moe_init(key, cfg: ModelConfig):
    dt = pdtype(cfg)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f)
    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32)
                   * scale_in).astype(jnp.float32),
        "experts_in": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                       * scale_in).astype(dt),
        "experts_out": (jax.random.normal(ks[2], (e, f, d), jnp.float32)
                        * scale_out).astype(dt),
    }
    if cfg.act == "silu":
        p["experts_gate"] = (jax.random.normal(ks[3], (e, d, f), jnp.float32)
                             * scale_in).astype(dt)
    return p


def moe_ffn(params, x, cfg: ModelConfig):
    """x: (B, S, D) -> ((B, S, D), aux_loss)."""
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k

    # opt-in explicit all-to-all expert-parallel dispatch (shard_map): the
    # SPMD partitioner cannot infer the token->expert exchange and gathers
    # the K-expanded rows per layer (EXPERIMENTS.md S.Perf Phase C/F).
    import os
    if os.environ.get("REPRO_MOE_A2A"):
        mesh = jax.sharding.get_abstract_mesh()
        if "model" in mesh.axis_names:
            tp = mesh.shape["model"]
            if tp > 1 and E % tp == 0 and S % tp == 0:
                from .moe_a2a import moe_ffn_a2a_local
                from jax.sharding import PartitionSpec as P
                pspec = {k: (P("model", None, None) if k.startswith("experts")
                             else P(None, None)) for k in params}
                fn = jax.shard_map(
                    lambda p, xx: moe_ffn_a2a_local(p, xx, cfg),
                    mesh=mesh,
                    in_specs=(pspec, P(None, "model", None)),
                    out_specs=(P(None, "model", None), P()),
                    check_vma=False)
                return fn(params, x)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, top_idx = jax.lax.top_k(probs, K)               # (B,S,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # Switch-style load-balancing auxiliary loss
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), 2),
                  axis=(0, 1))
    aux_loss = E * jnp.sum(me * ce) / K

    # ---- per-row capacity dispatch -----------------------------------------
    C = int(math.ceil(S * K / E * cfg.moe_capacity_factor))
    flat_e = top_idx.reshape(B, S * K)                         # (B, S*K)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)              # (B, S*K, E)
    pos_in_e = jnp.cumsum(onehot, axis=1) - 1                  # in-row cumsum
    pos = jnp.take_along_axis(pos_in_e, flat_e[..., None],
                              2)[..., 0]                       # (B, S*K)
    keep = pos < C
    pos = jnp.where(keep, pos, C)                              # slot C = drop

    tok_idx = jnp.arange(S * K) // K                           # (S*K,)
    # K-expanded token rows, sequence-sharded like the residual stream
    xk = constrain(jnp.take(x, tok_idx, axis=1), "btd")        # (B,S*K,D)

    def row_scatter(xkr, fe, fp):
        buf = jnp.zeros((E, C + 1, D), xkr.dtype)
        return buf.at[fe, fp].add(xkr)

    buf = jax.vmap(row_scatter)(xk, flat_e, pos)               # (B,E,C+1,D)
    expert_in = buf[:, :, :C]
    expert_in = grad_cast(constrain(expert_in, "becd"))

    # ---- expert computation (gated MLP) ------------------------------------
    h = _edot("becd,edf->becf", expert_in, params["experts_in"])
    if cfg.act == "silu":
        g = _edot("becd,edf->becf", expert_in, params["experts_gate"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    h = grad_cast(constrain(h.astype(x.dtype), "becf"))
    out = _edot("becf,efd->becd", h, params["experts_out"]).astype(x.dtype)
    out = grad_cast(constrain(out, "becd"))

    # ---- combine -------------------------------------------------------
    # Fold the gate weight into the expert output while still in the small
    # EP-sharded (B, E, C, D) layout; gather each token row\'s K expert
    # outputs and sum in bf16.  (The inverse scatter-add combine (V8) and
    # replicated-activation variants (V9) were measured and refuted - see
    # EXPERIMENTS.md S.Perf.)
    gates = jnp.where(keep, gate_vals.reshape(B, S * K), 0.0)  # (B, S*K)
    gate_buf = jax.vmap(
        lambda ge, fe, fp: jnp.zeros((E, C + 1), jnp.float32).at[fe, fp]
        .add(ge))(gates, flat_e, pos)                          # (B, E, C+1)
    out = out * gate_buf[:, :, :C, None].astype(out.dtype)
    out_pad = jnp.pad(out, ((0, 0), (0, 0), (0, 1), (0, 0)))   # drop slot
    gathered = jax.vmap(lambda o, fe, fp: o[fe, fp])(
        out_pad, flat_e, pos)                                  # (B,S*K,D) bf16
    gathered = constrain(gathered, "btd").reshape(B, S, K, D)
    y = jnp.sum(gathered, axis=2)                              # bf16 K-sum
    return y, aux_loss
