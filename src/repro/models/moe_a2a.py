"""Expert-parallel MoE dispatch with an EXPLICIT all-to-all (shard_map).

Phase-C of the perf log showed that XLA's SPMD partitioner cannot infer the
token->expert exchange: it gathers the K-expanded token rows (B, S*K, D) per
layer (8 GiB fp32 for qwen3-235B).  This module routes tokens manually:

  per model-shard (tp shards, E/tp experts each):
    1. route local tokens; destination shard = expert_id // (E/tp)
    2. compact rows per destination (cumsum slots, pair capacity C_pair)
    3. all_to_all  (tp, C_pair, D) token rows + int metadata
    4. receiver dispatches to its local (E/tp, C_loc, D) expert buffers,
       runs the gated-MLP experts, scatters replies back into the recv slots
    5. all_to_all back; the sender gathers each row's reply from the
       (dst, slot) coordinates it recorded, applies gates, sums over K

Wire cost per layer ~= 2 x (local rows x D) exchanged once - the 16x
reduction over the SPMD-inferred gather estimated in EXPERIMENTS.md §Perf C.

Capacity semantics: drops can occur at the pair level (C_pair) and the
expert level (C_loc); with the default factors both are >= the per-row
capacity of models/moe.py, so at moderate imbalance the two paths agree
exactly (tests/test_moe_a2a.py).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig


def _positions_within(groups: jax.Array, n_groups: int) -> jax.Array:
    """Slot index of each element within its group (first-come order)."""
    onehot = jax.nn.one_hot(groups, n_groups, dtype=jnp.int32)
    return jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                               groups[:, None], 1)[:, 0]


def moe_ffn_a2a_local(params, x_local, cfg: ModelConfig, *,
                      axis: str = "model") -> Tuple[jax.Array, jax.Array]:
    """Per-shard body (call inside shard_map over `axis`).

    x_local: (B, S_local, D) - this shard's token slice.
    params:  router replicated; experts_* sharded on the expert dim
             (leading-axis slice of E/tp experts is this shard's).
    Returns (y_local (B, S_local, D), aux_loss).
    """
    B, S_l, D = x_local.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    tp = jax.lax.axis_size(axis)
    my = jax.lax.axis_index(axis)
    e_loc = E // tp

    # ---- 1. routing ------------------------------------------------------
    xt = x_local.reshape(B * S_l, D)
    logits = xt.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    gate_vals, top_idx = jax.lax.top_k(probs, K)            # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    me = jnp.mean(probs, 0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), 1), 0)
    aux = E * jnp.sum(jax.lax.pmean(me, axis)
                      * jax.lax.pmean(ce, axis)) / K

    T = B * S_l
    rows = jnp.repeat(jnp.arange(T), K)                     # (T*K,)
    flat_e = top_idx.reshape(-1)
    flat_g = gate_vals.reshape(-1)
    dst = flat_e // e_loc                                   # destination shard
    loc_e = flat_e % e_loc                                  # expert on dst

    # ---- 2. compact per destination (pair capacity) -----------------------
    c_pair = int(math.ceil(T * K / tp * cfg.moe_capacity_factor))
    slot = _positions_within(dst, tp)
    keep = slot < c_pair
    slot = jnp.where(keep, slot, c_pair)                    # c_pair = drop

    send_x = jnp.zeros((tp, c_pair + 1, D), x_local.dtype) \
        .at[dst, slot].set(xt[rows])
    send_le = jnp.full((tp, c_pair + 1), e_loc, jnp.int32) \
        .at[dst, slot].set(loc_e)                           # e_loc = inert

    # ---- 3. all-to-all ------------------------------------------------------
    recv_x = jax.lax.all_to_all(send_x[:, :c_pair], axis, 0, 0, tiled=False)
    recv_le = jax.lax.all_to_all(send_le[:, :c_pair], axis, 0, 0, tiled=False)
    recv_x = recv_x.reshape(tp * c_pair, D)
    recv_le = recv_le.reshape(tp * c_pair)

    # ---- 4. local expert dispatch + compute --------------------------------
    c_loc = int(math.ceil(tp * c_pair / e_loc * cfg.moe_capacity_factor))
    eslot = _positions_within(recv_le, e_loc + 1)           # +1: inert group
    ekeep = (eslot < c_loc) & (recv_le < e_loc)
    eslot = jnp.where(ekeep, eslot, c_loc)
    le_safe = jnp.where(recv_le < e_loc, recv_le, 0)

    buf = jnp.zeros((e_loc, c_loc + 1, D), x_local.dtype) \
        .at[jnp.where(ekeep, le_safe, 0), eslot].add(
            jnp.where(ekeep[:, None], recv_x, 0))
    ein = buf[:, :c_loc]

    w_in = params["experts_in"]
    h = jnp.einsum("ecd,edf->ecf", ein.astype(jnp.float32),
                   w_in.astype(jnp.float32))
    if cfg.act == "silu":
        g = jnp.einsum("ecd,edf->ecf", ein.astype(jnp.float32),
                       params["experts_gate"].astype(jnp.float32))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    out = jnp.einsum("ecf,efd->ecd", h,
                     params["experts_out"].astype(jnp.float32))
    out = out.astype(x_local.dtype)

    # scatter replies back into the recv slot layout
    out_pad = jnp.pad(out, ((0, 0), (0, 1), (0, 0)))
    reply = jnp.where(ekeep[:, None], out_pad[le_safe, eslot], 0.0)
    reply = reply.reshape(tp, c_pair, D)

    # ---- 5. all-to-all back + sender-side combine ---------------------------
    back = jax.lax.all_to_all(reply, axis, 0, 0, tiled=False)
    back_pad = jnp.pad(back, ((0, 0), (0, 1), (0, 0)))      # drop slot
    contrib = back_pad[dst, slot] * jnp.where(keep, flat_g, 0.0)[:, None] \
        .astype(x_local.dtype)
    y = jnp.zeros((T, D), jnp.float32).at[rows].add(
        contrib.astype(jnp.float32))
    return y.reshape(B, S_l, D).astype(x_local.dtype), aux


def make_sharded_moe(cfg: ModelConfig, mesh, *, axis: str = "model"):
    """shard_map-wrapped MoE FFN: tokens sharded on seq over `axis`, expert
    weights sharded on the expert dim, router replicated."""
    from jax.sharding import PartitionSpec as P
    pspec = {"router": P(None, None),
             "experts_in": P(axis, None, None),
             "experts_out": P(axis, None, None)}
    if cfg.act == "silu":
        pspec["experts_gate"] = P(axis, None, None)

    def fn(params, x):
        return moe_ffn_a2a_local(params, x, cfg, axis=axis)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(pspec, P(None, axis, None)),
        out_specs=(P(None, axis, None), P()), check_vma=False)
