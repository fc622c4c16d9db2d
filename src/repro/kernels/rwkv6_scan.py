"""Pallas TPU RWKV6 (Finch) WKV scan with data-dependent per-channel decay.

Chunked matrix form: within a chunk of T steps the pairwise decay products
are expressed through cumulative log-decay sums, turning the recurrence into
two MXU matmuls plus element-wise VPU work; the inter-chunk state S (K x V)
stays in VMEM scratch across the sequential grid dimension (the paper's
fusion principle: no HBM round-trips between chain stages).

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

Per-channel decay makes the intra-chunk term
    y_t = sum_{s<t} [sum_c r_tc k_sc exp(cw_{t-1,c} - cw_{s,c})] v_s
        + (r_t u . k_t) v_t  +  (r_t exp(cw_{t-1}) ) S_in
where cw is the inclusive cumulative log decay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend


def _wkv_kernel(u_ref, r_ref, k_ref, v_ref, w_ref, y_ref, s_ref, *,
                chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, :, 0].astype(jnp.float32)        # (T, K)
    k = k_ref[0, :, 0].astype(jnp.float32)        # (T, K)
    v = v_ref[0, :, 0].astype(jnp.float32)        # (T, V)
    w = w_ref[0, :, 0].astype(jnp.float32)        # (T, K), in (0,1)
    u = u_ref[0].astype(jnp.float32)              # (K,)

    logw = jnp.log(jnp.maximum(w, 1e-30))         # (T, K) <= 0
    cw = jnp.cumsum(logw, axis=0)                 # inclusive

    # r~_t = r_t * exp(cw_{t-1});  k~_s = k_s * exp(-cw_s)
    cw_prev = cw - logw                           # exclusive cumsum
    r_dec = r * jnp.exp(cw_prev)                  # (T, K)
    k_dec = k * jnp.exp(-cw)                      # (T, K)
    # A_ts = sum_c r~_tc k~_sc   for s < t     (strictly lower triangular)
    A = jax.lax.dot_general(r_dec, k_dec, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (T, T)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(tri, A, 0.0)
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (T, V)

    # diagonal bonus term: (r_t . (u * k_t)) v_t
    diag = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True)     # (T, 1)
    y = y + diag * v

    # carry-in: y_t += (r_t * exp(cw_{t-1})) @ S_in
    S_in = s_ref[...]                             # (K, V)
    y = y + jax.lax.dot_general(r_dec, S_in, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: S_out = diag(exp(cw_T)) S_in + sum_s exp(cw_T - cw_s)
    #                                              k_s^T v_s
    k_out = k_dec * jnp.exp(cw[-1])[None, :]      # (T, K)
    S_new = jax.lax.dot_general(k_out, v, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (K, V)
    s_ref[...] = jnp.exp(cw[-1])[:, None] * S_in + S_new

    y_ref[0, :, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w, u, *, chunk: int = 32):
    """r,k,w: (B,S,H,K); v: (B,S,H,V); u: (H,K) -> (B,S,H,V).

    NOTE: the exp(-cw) rescaling bounds usable chunk size: |chunk * log w|
    must stay < ~80 for fp32.  The model clamps its data-dependent decay to
    w >= exp(-2.1) (models/rwkv6.py), so chunk=32 keeps |cw| <= ~68.
    """
    B, S, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        r = jnp.pad(r, zpad)
        k = jnp.pad(k, zpad)
        v = jnp.pad(v, zpad)
        w = jnp.pad(w, zpad, constant_values=1.0)   # decay 1 = no-op
    Sp = S + pad
    nc = Sp // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),           # u
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, V), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1, K), lambda b, h, c: (b, c, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, V), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, H, V), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret_mode(),
    )(u, r, k, v, w)
    return y[:, :S]
