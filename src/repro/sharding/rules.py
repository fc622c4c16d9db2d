"""Logical-axis sharding rules: DP / FSDP / TP / EP / SP on the production mesh.

Mesh axes:
  pod    - pure data parallelism across pods (params replicated per pod)
  data   - data parallelism + FSDP weight sharding
  model  - tensor parallelism (heads / d_ff / vocab) + expert parallelism

Activations use logical names resolved against whatever mesh is active, so
model code works on the single-pod (data, model) mesh, the multi-pod
(pod, data, model) mesh, and unsharded CPU tests (no-op).
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P


def _axis_names() -> Tuple[str, ...]:
    """Axes of the mesh in scope (`jax.set_mesh`); empty when none is."""
    return tuple(jax.sharding.get_abstract_mesh().axis_names)


def dp_axes(names: Optional[Tuple[str, ...]] = None):
    names = _axis_names() if names is None else names
    ax = tuple(a for a in ("pod", "data") if a in names)
    return ax if ax else None


def tp_axis(names: Optional[Tuple[str, ...]] = None):
    names = _axis_names() if names is None else names
    return "model" if "model" in names else None


# --------------------------------------------------------------------------
# Activation constraints (logical names)
# --------------------------------------------------------------------------

_ACT_SPECS = {
    # (batch, seq, d_model) between blocks: batch over DP axes, SEQ over the
    # model axis (Megatron-style sequence parallelism) - the layer-boundary
    # residual stream and remat checkpoints are 1/|model| the size; XLA
    # all-gathers seq before attention/MLP and reduce-scatters after.
    "btd": lambda dp, tp: P(dp, tp, None),
    # (batch, seq, heads, head_dim): heads over TP
    "bshd": lambda dp, tp: P(dp, None, tp, None),
    # K/V for sequence-parallel attention: replicated over the model axis
    # (gathered ONCE per layer, outside the flash KV-block scan)
    "kv_rep": lambda dp, tp: P(dp, None, None, None),
    # token rows replicated over the model axis (MoE dispatch staging)
    "btd_rep": lambda dp, tp: P(dp, None, None),
    # (batch, seq, d_ff): hidden over TP
    "btf": lambda dp, tp: P(dp, None, tp),
    # (batch, seq, vocab): vocab over TP
    "btv": lambda dp, tp: P(dp, None, tp),
    # (batch, seq, topk, d) MoE combine: seq over TP like the residual stream
    "bskd": lambda dp, tp: P(dp, tp, None, None),
    # (batch, experts, capacity, d): batch over DP, experts over TP (EP).
    # Two alternatives were measured and REFUTED (EXPERIMENTS.md S.Perf):
    # replicating the expert activations (V9) or scatter-add combine (V8)
    # both make the SPMD partitioner move the full expert buffers in fp32.
    "becd": lambda dp, tp: P(dp, tp, None, None),
    "becf": lambda dp, tp: P(dp, tp, None, None),
}


def constrain(x: jax.Array, logical: str) -> jax.Array:
    """Apply a sharding constraint if a mesh is active; no-op otherwise.

    "bshd" is shape-aware: shard heads over the model axis when the head
    count divides it; otherwise fall back to sharding the sequence
    (context parallelism) so GQA group reshapes stay shard-local instead of
    forcing XLA to all-gather the whole tensor."""
    names = _axis_names()
    if not names:
        return x
    dp = dp_axes(names)
    tp = tp_axis(names)
    if logical == "bshd" and tp is not None:
        tp_n = jax.sharding.get_abstract_mesh().shape["model"]
        # PREFER sequence sharding (context parallelism): projections and
        # the attention output then stay sequence-local, eliminating the
        # per-layer residual all-gather + partial-sum all-reduce entirely;
        # only K/V blocks are broadcast inside the flash scan.  Head
        # sharding is the fallback when the sequence does not divide.
        if x.shape[1] % tp_n == 0:
            spec = P(dp, tp, None, None)
        elif x.shape[2] % tp_n == 0:
            spec = P(dp, None, tp, None)
        else:
            spec = P(dp, None, None, None)
    else:
        spec = _ACT_SPECS[logical](dp, tp)
    return jax.lax.with_sharding_constraint(x, spec)


# --------------------------------------------------------------------------
# Parameter sharding rules (path regex -> spec builder)
# --------------------------------------------------------------------------
# Param layouts (all may carry a leading stacked-layer axis, detected by
# ndim mismatch and padded with None):
#   embed        (V, D)        vocab over model, D over data (FSDP)
#   lm_head      (V, D)
#   wq/wk/wv     (D, N)        D over data, N (heads*hd) over model
#   wo           (N, D)
#   mlp in/gate  (D, F)
#   mlp out      (F, D)
#   router       (D, E)        replicated E
#   experts_in   (E, D, F)     experts over model (EP), D over data
#   experts_out  (E, F, D)
#   ssm in/out   (D, X) / (X, D)
#   norms, biases, small vectors: replicated

_PARAM_RULES = [
    (r"^(embed|lm_head|cls_head)$", lambda dp, tp: P(tp, dp)),
    (r"(wq|wk|wv|w_in|w_gate|in_proj|router_dense|r_proj|k_proj|v_proj|g_proj|w_proj)$",
     lambda dp, tp: P(dp, tp)),
    (r"(wo|w_out|out_proj)$", lambda dp, tp: P(tp, dp)),
    (r"(experts_in|experts_gate)$", lambda dp, tp: P(tp, dp, None)),
    (r"(experts_out)$", lambda dp, tp: P(tp, None, dp)),
    (r"(router)$", lambda dp, tp: P(dp, None)),
    (r"(conv_w)$", lambda dp, tp: P(None, tp)),
    (r"(pos_embed)$", lambda dp, tp: P(None, dp)),
]


def param_spec(path: str, ndim: int, names: Tuple[str, ...]) -> P:
    """Resolve the PartitionSpec for a parameter by its tree path."""
    dp = dp_axes(names)
    tp = tp_axis(names)
    leaf = path.split("/")[-1]
    stacked = "blocks" in path or "layers" in path or "encoder" in path \
        or "decoder" in path
    for pat, rule in _PARAM_RULES:
        if re.search(pat, leaf):
            spec = rule(dp, tp)
            base = len(spec)
            if ndim > base:
                # leading stacked-layer axes -> replicated
                spec = P(*([None] * (ndim - base) + list(spec)))
            elif ndim < base:
                return P()        # degenerate (e.g. smoke configs)
            return spec
    return P()                    # norms / scalars / biases: replicated


def tree_paths(tree):
    """(path, leaf) pairs with '/'-joined key paths."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        out.append(("/".join(parts), leaf))
    return out


def param_sharding_tree(params, mesh) -> "jax.tree_util.PyTreeDef":
    """NamedSharding pytree matching `params` for the given mesh."""
    names = tuple(mesh.axis_names)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    shardings = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        path = "/".join(parts)
        spec = param_spec(path, getattr(leaf, "ndim", 0), names)
        shardings.append(jax.sharding.NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, shardings)


def cache_spec(names: Tuple[str, ...], *, seq_sharded: bool,
               seq_axis: str = "data") -> P:
    """KV cache (L, B, S, H_kv, D).

    - default: batch over DP, KV heads over TP
    - seq_sharded + seq_axis="data": batch=1 long-context decode - shard the
      SEQUENCE over the data axis (sequence-parallel KV: the paper's tier
      split applied across chips)
    - seq_sharded + seq_axis="model": KV head count does not divide the
      model axis - shard the sequence there instead of replicating the
      cache across it."""
    dp = dp_axes(names)
    tp = tp_axis(names)
    if seq_sharded and seq_axis == "data":
        return P(None, None, "data" if "data" in names else None, tp, None)
    if seq_sharded:
        return P(None, dp, tp, None, None)
    return P(None, dp, None, tp, None)
