"""Pallas TPU FlashAttention-2 backward kernels.

Two kernels, mirroring the FA-2 work split:

  dq kernel : grid (B, Hq, nq, nk), KV innermost; dq accumulates in VMEM
              scratch and is written once per q-block.
  dkv kernel: grid (B, Hkv, nk, nq), Q innermost; dk/dv accumulate in VMEM
              scratch (summed over the GQA group in-register) and are
              written once per kv-block.

Like the forward, every intermediate (S, P, dP, dS) lives in VREGs/VMEM -
the paper's "buffers to registers" principle applied to the backward chain.
Softcap and sliding-window masks match ops._flash_bwd_rule (the pure-jnp
oracle used for CPU execution and for validation in interpret mode).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

LOG2E = 1.4426950408889634
NEG_INF = -1e30


def _masks(q_first, k_first, bq, bk, seq_kv, causal, window):
    q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_kv
    if causal or window > 0:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    return mask


def _p_and_ds(q, k, v, do, lse, delta, mask, *, softcap, scale):
    """Shared recompute: returns (p, ds) for one (bq, bk) tile, fp32."""
    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        t = jnp.tanh(s_raw / softcap)
        s = softcap * t
    else:
        t = None
        s = s_raw
    p = jnp.exp2((s - lse[:, None]) * LOG2E)
    p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    if softcap > 0.0:
        ds = ds * (1.0 - t * t)
    return p, ds


# ===========================================================================
# dq kernel
# ===========================================================================

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, causal, window, softcap, scale, block_q,
               block_kv, seq_kv):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = jnp.bool_(True)
    q_first, k_first = i * block_q, j * block_kv
    if causal or window > 0:
        run = run & (k_first <= q_first + block_q - 1)
    if window > 0:
        run = run & (k_first + block_kv - 1 > q_first - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        mask = _masks(q_first, k_first, block_q, block_kv, seq_kv,
                      causal, window)
        _, ds = _p_and_ds(q, k, v, do, lse, delta, mask,
                          softcap=softcap, scale=scale)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


# ===========================================================================
# dkv kernel
# ===========================================================================

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal, window, softcap,
                scale, block_q, block_kv, seq_kv, gqa):
    j = pl.program_id(2)
    i = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = jnp.bool_(True)
    q_first, k_first = i * block_q, j * block_kv
    if causal or window > 0:
        run = run & (k_first <= q_first + block_q - 1)
    if window > 0:
        run = run & (k_first + block_kv - 1 > q_first - window)

    @pl.when(run)
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        mask = _masks(q_first, k_first, block_q, block_kv, seq_kv,
                      causal, window)
        # sum over the GQA group in-register
        for g in range(gqa):
            q = q_ref[0, 0, g].astype(jnp.float32)
            do = do_ref[0, 0, g].astype(jnp.float32)
            lse = lse_ref[0, 0, g]
            delta = delta_ref[0, 0, g]
            p, ds = _p_and_ds(q, k, v, do, lse, delta, mask,
                              softcap=softcap, scale=scale)
            dv_acc[...] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# ===========================================================================
# wrapper
# ===========================================================================

@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "logit_softcap", "scale",
                                             "block_q", "block_kv"))
def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: int = 0, logit_softcap: float = 0.0,
                        scale: Optional[float] = None, block_q: int = 128,
                        block_kv: int = 128) -> Tuple[jax.Array, jax.Array,
                                                      jax.Array]:
    """q,do: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D); o: (B,Sq,Hq,D);
    lse: (B,Sq,Hq) natural-log row log-sum-exp.  Returns (dq, dk, dv)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    sc = scale if scale is not None else 1.0 / math.sqrt(D)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)

    pq = (-Sq) % block_q
    pk = (-Skv) % block_kv
    qt = jnp.moveaxis(q, 2, 1)
    dot = jnp.moveaxis(do, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    lset = jnp.moveaxis(lse, 2, 1)
    deltat = jnp.moveaxis(delta, 2, 1)
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
        dot = jnp.pad(dot, ((0, 0), (0, 0), (0, pq), (0, 0)))
        # padded rows must be inert: lse=+inf makes p = exp2(-inf) = 0
        lset = jnp.pad(lset, ((0, 0), (0, 0), (0, pq)),
                       constant_values=1e30)
        deltat = jnp.pad(deltat, ((0, 0), (0, 0), (0, pq)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sqp, Skp = Sq + pq, Skv + pk
    nq, nk = Sqp // block_q, Skp // block_kv

    # ---- dq ----------------------------------------------------------------
    dq_kernel = functools.partial(
        _dq_kernel, causal=causal, window=window, softcap=logit_softcap,
        scale=sc, block_q=block_q, block_kv=block_kv, seq_kv=Skv)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, h, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=backend.interpret_mode(),
    )(qt, kt, vt, dot, lset, deltat)

    # ---- dk/dv --------------------------------------------------------------
    # reshape q-side tensors to (B, Hkv, G, Sqp, ...) for the group loop
    qg = qt.reshape(B, Hkv, G, Sqp, D)
    dog = dot.reshape(B, Hkv, G, Sqp, D)
    lseg = lset.reshape(B, Hkv, G, Sqp)
    deltag = deltat.reshape(B, Hkv, G, Sqp)
    dkv_kernel = functools.partial(
        _dkv_kernel, causal=causal, window=window, softcap=logit_softcap,
        scale=sc, block_q=block_q, block_kv=block_kv, seq_kv=Skv, gqa=G)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, Hkv, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, G, block_q, D),
                         lambda b, h, j, i: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, G, block_q, D),
                         lambda b, h, j, i: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, G, block_q), lambda b, h, j, i: (b, h, 0, i)),
            pl.BlockSpec((1, 1, G, block_q), lambda b, h, j, i: (b, h, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Skp, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Skp, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_kv, D), jnp.float32),
                        pltpu.VMEM((block_kv, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=backend.interpret_mode(),
    )(qg, kt, vt, dog, lseg, deltag)

    dq = jnp.moveaxis(dq[:, :, :Sq], 1, 2)
    dk = jnp.moveaxis(dk[:, :, :Skv], 1, 2)
    dv = jnp.moveaxis(dv[:, :, :Skv], 1, 2)
    return dq, dk, dv
