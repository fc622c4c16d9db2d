"""Pallas TPU FlashAttention-2 forward - the paper's technique, TPU-native.

The 3D-Flow mapping collapsed onto one kernel: the four "tiers" (QK^T |
rowmax/sub | exp/rowsum | PV/rescale) execute back-to-back on the MXU and
VPU with every intermediate (S, m, N, P, b, l, O-partials) living in
VREGs/VMEM scratch - the TPU analogue of hybrid-bonded register-to-register
TSV links.  Block shapes come from core.tpu_mapping.choose_block_config,
which applies the paper's latency-balanced scheduling criterion to the
MXU-vs-VPU stage split, and the Pallas grid pipeline overlaps the next
block's HBM->VMEM DMA with the current block's compute (the "bubble-free"
property).

Executes on TPU compiled, or anywhere via interpret mode (used for CPU
validation against ref.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.tpu_mapping import choose_block_config
from . import backend

LOG2E = 1.4426950408889634
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
               causal: bool, window: int, softcap: float, scale: float,
               block_q: int, block_kv: int, seq_kv: int):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_first = i * block_q
    q_last = q_first + block_q - 1
    k_first = j * block_kv
    k_last = k_first + block_kv - 1

    run = jnp.bool_(True)
    if causal or window > 0:
        run = run & (k_first <= q_last)            # block above the diagonal
    if window > 0:
        run = run & (k_last > q_first - window)    # block left of the window

    @pl.when(run)
    def _compute():
        # ---- tier 0: QK^T (MXU) ------------------------------------------
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
        mask = k_pos < seq_kv
        if causal or window > 0:
            mask = mask & (k_pos <= q_pos)
        if window > 0:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        # ---- tier 1: rowmax + subtract (VPU) ------------------------------
        m_prev = m_ref[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)

        # ---- tier 2: exp2 + rowsum + rescale (VPU) ------------------------
        p = jnp.exp2((s - m_safe) * LOG2E)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp2((m_prev - m_new) * LOG2E))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = m_new

        # ---- tier 3: PV + O rescale (MXU) ---------------------------------
        v = v_ref[0, 0].astype(jnp.float32)                 # (bk, D)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "logit_softcap", "scale",
                                             "block_q", "block_kv"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        logit_softcap: float = 0.0,
                        scale: Optional[float] = None,
                        block_q: int = 0,
                        block_kv: int = 0) -> Tuple[jax.Array, jax.Array]:
    """q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D).  Returns (o, lse)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    if not block_q or not block_kv:
        bc = choose_block_config(D, max(Sq, Skv))
        block_q, block_kv = bc.block_q, bc.block_kv
    block_q = min(block_q, max(Sq, 8))
    block_kv = min(block_kv, max(Skv, 128))

    # pad seq dims to block multiples
    pq = (-Sq) % block_q
    pk = (-Skv) % block_kv
    qt = jnp.moveaxis(q, 2, 1)                    # (B,H,Sq,D)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sqp, Skp = Sq + pq, Skv + pk
    nq, nk = Sqp // block_q, Skp // block_kv

    kernel = functools.partial(
        _fa_kernel, causal=causal, window=window, softcap=logit_softcap,
        scale=scale, block_q=block_q, block_kv=block_kv, seq_kv=Skv)

    grid = (B, Hq, nq, nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            # (block_q, 1): a trailing unit axis keeps the LSE block
            # tiling-legal (a (1, block_q) block over (H, S) is not)
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sqp, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=backend.interpret_mode(),
    )(qt, kt, vt)

    o = jnp.moveaxis(o[:, :, :Sq], 1, 2)          # back to (B,Sq,Hq,D)
    lse = jnp.moveaxis(lse[:, :, :Sq, 0], 1, 2)   # (B,Sq,Hq)
    return o, lse
