"""Pallas TPU chunked Mamba2 (SSD) scan.

The paper's fusion principle applied to an attention-free chain: within a
time chunk the decay / inject / output stages run matrix-form on the MXU
(CB^T masked by the decay kernel), and the inter-chunk state h lives in VMEM
scratch across the sequential grid dimension - no HBM round-trip per chunk.

All decay factors are exp of non-positive numbers (<= 1), so the chunked
form is numerically stable in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[pl.program_id(1)]                       # scalar decay rate
    x = x_ref[0, :, 0].astype(jnp.float32)            # (T, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # (T,) -- wait, see spec
    Bm = b_ref[0].astype(jnp.float32)                 # (T, N)
    Cm = c_ref[0].astype(jnp.float32)                 # (T, N)

    log_a = -dt * A                                   # (T,) <= 0
    csum = jnp.cumsum(log_a)                          # inclusive

    # intra-chunk: y[t] = sum_{s<=t} exp(csum[t]-csum[s]) * dt[s] (C_t.B_s) x[s]
    diff = csum[:, None] - csum[None, :]              # (T, T)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    M = jnp.where(tri, jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (T, T)
    xw = x * dt[:, None]                              # (T, P)
    y = jax.lax.dot_general(CB * M, xw, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (T, P)

    # carry-in: y[t] += exp(csum[t]) * C_t . h_in
    h_in = h_ref[...]                                 # (P, N)
    y_carry = jax.lax.dot_general(Cm, h_in, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (T,P)
    y = y + jnp.exp(csum)[:, None] * y_carry

    # state update: h_out = exp(csum[-1]) h_in + sum_s exp(csum[-1]-csum[s])
    #                                            dt_s x_s (outer) B_s
    w_out = jnp.exp(csum[-1] - csum)[:, None] * xw    # (T, P)
    h_new = jax.lax.dot_general(w_out, Bm, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P, N)
    h_ref[...] = jnp.exp(csum[-1]) * h_in + h_new

    y_ref[0, :, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba2_scan(x, dt, A, Bm, Cm, *, chunk: int = 128):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); Bm/Cm: (B,S,N) -> y: (B,S,H,P)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                  # A (H,)
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret_mode(),
    )(A.astype(jnp.float32), x, dt, Bm, Cm)
    return y[:, :S]
