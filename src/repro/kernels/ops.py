"""Public jit'd kernel wrappers: impl dispatch (pallas | ref) + custom VJP.

The forward is the paper's technique on TPU: the fused QK^T -> softmax -> PV
chain stays VMEM/VREG-resident inside one Pallas kernel (ref = chunked jnp
with identical math, used on CPU and in the dry-run).  The backward is a
memory-efficient chunked FlashAttention-2 backward (recompute-from-(q,k,v,
o,lse); no N^2 residuals), so training never materializes attention scores
either - "from buffers to registers" applied to both passes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref

NEG_INF = ref.NEG_INF
LOG2E = 1.4426950408889634


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# ===========================================================================
# flash_attention with custom (chunked) VJP
# ===========================================================================

def _fwd_impl(q, k, v, causal, window, softcap, scale, impl):
    if impl == "pallas":
        from . import flash_attention as fa
        return fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                      logit_softcap=softcap, scale=scale)
    o = ref.flash_attention(q, k, v, causal=causal, window=window,
                            logit_softcap=softcap, scale=scale)
    lse = _lse_ref(q, k, causal, window, softcap, scale)
    return o, lse


def _lse_ref(q, k, causal, window, softcap, scale, block_kv: int = 512):
    """Row log-sum-exp (natural log), chunked."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    nblk = -(-Skv // block_kv)
    pad = nblk * block_kv - Skv
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    kb = jnp.moveaxis(kp.reshape(B, nblk, block_kv, Hkv, D), 1, 0)
    qf = q.reshape(B, Sq, Hkv, G, D)
    q_pos = jnp.arange(Sq)

    def body(carry, blk):
        m, l = carry
        kblk, j = blk
        k_pos = j * block_kv + jnp.arange(block_kv)
        s = ref.mixed_einsum("bqhgd,bkhd->bqhgk", qf, kblk) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos[None, :] <= (Skv - 1)
        if causal or window > 0:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, -1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask[None, :, None, None, :],
                      jnp.exp2((s - m_safe[..., None]) * LOG2E), 0.0)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp2((m - m_new) * LOG2E))
        return (m_new, l * alpha + p.sum(-1)), None

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    (m, l), _ = jax.lax.scan(body, (m0, l0), (kb, jnp.arange(nblk)))
    lse = m + jnp.log(jnp.maximum(l, 1e-20))
    return lse.reshape(B, Sq, Hq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, window, softcap, scale, impl):
    o, _ = _fwd_impl(q, k, v, causal, window, softcap, scale, impl)
    return o


def _flash_fwd_rule(q, k, v, causal, window, softcap, scale, impl):
    o, lse = _fwd_impl(q, k, v, causal, window, softcap, scale, impl)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, window, softcap, scale, impl, res, do,
                    block_kv: int = 512):
    """FA-2 backward: Pallas kernels on TPU (kernels/flash_backward.py);
    chunked jnp recompute-from-(q,k,v,o,lse) otherwise."""
    q, k, v, o, lse = res
    if impl == "pallas":
        from . import flash_backward as fb
        return fb.flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                      window=window, logit_softcap=softcap,
                                      scale=scale)
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    sc = scale if scale is not None else 1.0 / math.sqrt(D)

    nblk = -(-Skv // block_kv)
    pad = nblk * block_kv - Skv
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else v
    kb = jnp.moveaxis(kp.reshape(B, nblk, block_kv, Hkv, D), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nblk, block_kv, Hkv, D), 1, 0)

    qb = q.reshape(B, Sq, Hkv, G, D)                       # stay bf16
    dob = do.astype(q.dtype).reshape(B, Sq, Hkv, G, D)
    of = o.reshape(B, Sq, Hkv, G, D)
    lsef = lse.astype(jnp.float32).reshape(B, Sq, Hkv, G)
    delta = jnp.sum(dob.astype(jnp.float32) * of.astype(jnp.float32), -1)
    q_pos = jnp.arange(Sq)

    def body(dq_acc, blk):
        kblk, vblk, j = blk
        k_pos = j * block_kv + jnp.arange(block_kv)
        s_raw = ref.mixed_einsum("bqhgd,bkhd->bqhgk", qb, kblk) * sc
        if softcap > 0.0:
            t = jnp.tanh(s_raw / softcap)
            s = softcap * t
        else:
            s = s_raw
        mask = k_pos[None, :] <= (Skv - 1)
        if causal or window > 0:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        p = jnp.exp2((s - lsef[..., None]) * LOG2E)
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        pb = p.astype(q.dtype)
        dv_j = ref.mixed_einsum("bqhgk,bqhgd->bkhd", pb, dob)
        dp = ref.mixed_einsum("bqhgd,bkhd->bqhgk", dob, vblk)
        ds = p * (dp - delta[..., None])
        if softcap > 0.0:
            ds = ds * (1.0 - t * t)
        dsb = ds.astype(q.dtype)
        dq_acc = dq_acc + ref.mixed_einsum("bqhgk,bkhd->bqhgd", dsb, kblk) * sc
        dk_j = ref.mixed_einsum("bqhgk,bqhgd->bkhd", dsb, qb) * sc
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(nblk)))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(B, nblk * block_kv, Hkv, D)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(B, nblk * block_kv, Hkv, D)
    del dk_blocks, dv_blocks
    dq = dq.reshape(B, Sq, Hq, D).astype(q.dtype)
    return dq, dk[:, :Skv].astype(k.dtype), dv[:, :Skv].astype(v.dtype)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_softcap: float = 0.0,
                    scale: Optional[float] = None,
                    impl: Optional[str] = None) -> jax.Array:
    """Fused attention.  q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D) (GQA allowed)."""
    impl = impl or default_impl()
    return _flash_attention(q, k, v, causal, window, logit_softcap, scale, impl)


# ===========================================================================
# decode
# ===========================================================================

def flash_decode(q, k_cache, v_cache, cache_len, *, window: int = 0,
                 logit_softcap: float = 0.0,
                 scale: Optional[float] = None,
                 impl: Optional[str] = None) -> jax.Array:
    impl = impl or default_impl()
    if impl == "pallas":
        from . import flash_decode as fd
        return fd.flash_decode(q, k_cache, v_cache, cache_len, window=window,
                               logit_softcap=logit_softcap, scale=scale)
    return ref.flash_decode(q, k_cache, v_cache, cache_len, window=window,
                            logit_softcap=logit_softcap, scale=scale)


def _tp_active(tp_mesh) -> bool:
    """True when a serve mesh actually shards the head ("model") axis."""
    return tp_mesh is not None and dict(tp_mesh.shape).get("model", 1) > 1


# the stacked (L, Hkv, P, page_size, W) pool, sharded on its head axis
def _pool_heads():
    from jax.sharding import PartitionSpec as P
    return P(None, "model", None, None, None)


def _tp_head_sharded(fn, tp_mesh, n_scalars: int):
    """shard_map a paged attention kernel on the HEAD axis of a serve mesh.

    The wrapped kernel fn(q, kv_pages, *scalars) sees q and the stacked
    page pool with their head axis (axis 2 of q's (B,S,H,D), axis 1 of the
    (L,Hkv,P,ps,W) pool) split across "model" plus n_scalars replicated
    layer/block-table/length operands, computes its local head slice —
    per-head attention math never mixes heads, so the slice is the exact
    per-head result — and all-gathers outputs back to the full head axis.
    With tiled=True the gather re-concatenates head blocks in device
    order, so the output is bit-identical to the unsharded kernel and
    everything downstream (output projection, FFN, sampling) runs
    replicated with the tp=1 float summation order.  The block table
    rides in replicated, the kernels' scalar-prefetch state.
    """
    from jax.sharding import PartitionSpec as P

    def local(*args):
        o = fn(*args)
        return jax.lax.all_gather(o, "model", axis=2, tiled=True)

    return jax.shard_map(
        local, mesh=tp_mesh,
        in_specs=(P(None, None, "model", None), _pool_heads())
        + (P(),) * n_scalars,
        out_specs=P(), check_vma=False)


def pack_kv(k, v, lanes: int):
    """(.., Hkv, D) K and V -> (.., Hkv, lanes) pool rows: K in lanes
    [0, D), V in [D, 2D), zeros after (a pool may be wider than 2D; the
    model's is not)."""
    kv = jnp.concatenate([k, v], axis=-1)
    pad = lanes - kv.shape[-1]
    if pad:
        kv = jnp.pad(kv, [(0, 0)] * (kv.ndim - 1) + [(0, pad)])
    return kv


def kv_write_plan(tables, starts, ends, n_tokens: int, page_size: int, *,
                  impl: Optional[str] = None):
    """Where a step's new K/V rows go in the page pool, computed once per
    step and reused by every layer's kv_page_write.

    Row r writes its n_tokens tokens at absolute positions starts[r] + i
    through the block-table row tables[r] (R, n_max); positions at or past
    ends[r] (pad) are not written.  The Pallas path sorts the write's grid
    steps by page (kernels/kv_write.py write_plan); the ref scatter takes
    the three arrays as they are."""
    impl = impl or default_impl()
    if impl == "pallas":
        from . import kv_write
        return kv_write.write_plan(tables, starts, ends, n_tokens, page_size)
    return tuple(jnp.asarray(a, jnp.int32) for a in (tables, starts, ends))


def kv_page_write(kv_pages, layer, k, v, plan, *,
                  impl: Optional[str] = None, tp_mesh=None) -> jax.Array:
    """Write new K/V into layer `layer` of the stacked page pool.

    kv_pages: (L, Hkv, P, page_size, W); k, v: (R, S, Hkv, D); plan:
    kv_write_plan(tables, starts, ends, S, page_size) for the same impl.
    A chunk may start and end mid-page, and two rows may share a page.
    The Pallas path rewrites only the touched blocks of the layer's
    pages, in place (kernels/kv_write.py); the ref path is the XLA
    scatter.  tp_mesh writes each device's head slice of the head-sharded
    pool under shard_map.  Returns the pool."""
    impl = impl or default_impl()
    if impl == "pallas":
        from . import kv_write
        write = kv_write.kv_page_write
    else:
        def write(kv_pages, layer, new, plan):
            return ref.kv_page_write(kv_pages, layer, new, *plan)
    if _tp_active(tp_mesh):
        from jax.sharding import PartitionSpec as P
        write = jax.shard_map(
            write, mesh=tp_mesh,
            in_specs=(_pool_heads(), P(), P(None, None, "model", None), P()),
            out_specs=_pool_heads(), check_vma=False)
    return write(kv_pages, layer, pack_kv(k, v, kv_pages.shape[-1]), plan)


def paged_flash_decode(q, kv_pages, layer, block_table, cache_len, *,
                       window: int = 0, logit_softcap: float = 0.0,
                       scale: Optional[float] = None,
                       impl: Optional[str] = None,
                       tp_mesh=None) -> jax.Array:
    """Decode against a paged KV cache (vLLM-style block table).

    q: (B,1,Hq,D); kv_pages: (L, Hkv, P, page_size, W) stacked lane-dense
    page pool (K in lanes [0, D), V in [D, 2D)), read at layer `layer`;
    block_table: (B, n_max) int32 page ids; cache_len: (B,) valid lengths.
    The Pallas path walks each row's live pages through the block table
    in SMEM, DMAing every head of a page at once, and keeps the (m, l, acc)
    merge VMEM-resident (kernels/flash_decode.py); the ref path
    gathers pages and reuses the chunked dense decode.

    tp_mesh (a launch/mesh.py serve mesh with a "model" axis > 1) runs the
    kernel under shard_map with q and the pool head-sharded and the block
    table replicated; the output comes back replicated (bit-identical to
    tp=1 — see _tp_head_sharded)."""
    impl = impl or default_impl()
    if _tp_active(tp_mesh):
        def run(qc, kp, l, bt, cl):
            return paged_flash_decode(qc, kp, l, bt, cl, window=window,
                                      logit_softcap=logit_softcap,
                                      scale=scale, impl=impl)
        return _tp_head_sharded(run, tp_mesh, 3)(
            q, kv_pages, layer, block_table, cache_len)
    if impl == "pallas":
        from . import flash_decode as fd
        return fd.paged_flash_decode(q, kv_pages, layer, block_table,
                                     cache_len, window=window,
                                     logit_softcap=logit_softcap,
                                     scale=scale)
    return ref.paged_flash_decode(q, kv_pages, layer, block_table,
                                  cache_len, window=window,
                                  logit_softcap=logit_softcap, scale=scale)


def batched_paged_prefill_attention(q, kv_pages, layer, page_tables,
                                    q_offsets, true_lens, q_lens=None, *,
                                    window: int = 0,
                                    logit_softcap: float = 0.0,
                                    scale: Optional[float] = None,
                                    impl: Optional[str] = None,
                                    tp_mesh=None) -> jax.Array:
    """Ragged batched mid-prompt chunk-prefill attention over partially
    filled block tables: K chunks of K different sequences in ONE call.

    q: (K,S,Hq,D) chunk queries; row k sits at absolute positions
    q_offsets[k] + arange(S) (its K/V already written into its pages),
    zero-padded past true_lens[k] - q_offsets[k]; kv_pages: the stacked
    (L, Hkv, P, page_size, W) pool, read at layer `layer`; page_tables:
    (K,n_max) per-row block-table rows; true_lens: (K,) per-row prefill
    cursors (dead padding rows carry 0 and an all-null table row,
    returning zero).  Each real row attends causally over every earlier
    position and the chunk itself.  The Pallas path walks every row's
    table from SMEM inside one grid (K, heads, kv-pages) launch with the
    (m, l, acc) merge VMEM-resident (kernels/paged_prefill.py); the ref
    path gathers pages per row and applies the offset causal mask.

    tp_mesh shards q and the pool on heads under shard_map with the
    per-row tables/offsets/cursors replicated (see paged_flash_decode)."""
    impl = impl or default_impl()
    if _tp_active(tp_mesh):
        if q_lens is None:
            def run(qc, kp, l, pt, qo, tl):
                return batched_paged_prefill_attention(
                    qc, kp, l, pt, qo, tl, None, window=window,
                    logit_softcap=logit_softcap, scale=scale, impl=impl)
            return _tp_head_sharded(run, tp_mesh, 4)(
                q, kv_pages, layer, page_tables, q_offsets, true_lens)

        def run(qc, kp, l, pt, qo, tl, ql):
            return batched_paged_prefill_attention(
                qc, kp, l, pt, qo, tl, ql, window=window,
                logit_softcap=logit_softcap, scale=scale, impl=impl)
        return _tp_head_sharded(run, tp_mesh, 5)(
            q, kv_pages, layer, page_tables, q_offsets, true_lens, q_lens)
    if impl == "pallas":
        from . import paged_prefill as pp
        return pp.batched_paged_prefill_attention(
            q, kv_pages, layer, page_tables, q_offsets, true_lens, q_lens,
            window=window, logit_softcap=logit_softcap, scale=scale)
    return ref.batched_paged_prefill_attention(
        q, kv_pages, layer, page_tables, q_offsets, true_lens, q_lens,
        window=window, logit_softcap=logit_softcap, scale=scale)


def paged_prefill_attention(q, kv_pages, layer, page_row, q_offset, *,
                            window: int = 0, logit_softcap: float = 0.0,
                            scale: Optional[float] = None,
                            impl: Optional[str] = None) -> jax.Array:
    """Mid-prompt chunk-prefill attention over a partially filled block
    table: the K=1 special case of batched_paged_prefill_attention.

    q: (1,S,Hq,D) chunk queries at absolute positions q_offset + arange(S)
    (chunk K/V already written into its pages) - the uncached suffix after
    a prefix-cache hit, or any chunk of a token-budget scheduled prefill;
    kv_pages/layer: the stacked pool and the layer read; page_row:
    (n_max,) the sequence's block-table row.  Each row attends causally
    over every earlier position and the chunk itself."""
    impl = impl or default_impl()
    if impl == "pallas":
        from . import paged_prefill as pp
        return pp.paged_prefill_attention(q, kv_pages, layer, page_row,
                                          q_offset, window=window,
                                          logit_softcap=logit_softcap,
                                          scale=scale)
    return ref.paged_prefill_attention(q, kv_pages, layer, page_row,
                                       q_offset, window=window,
                                       logit_softcap=logit_softcap,
                                       scale=scale)


def decode_attention_naive(q, k_cache, v_cache, cache_len, *,
                           logit_softcap: float = 0.0,
                           scale: Optional[float] = None) -> jax.Array:
    """Unchunked decode attention for SPMD sequence-parallel KV caches.

    Deliberately written as plain einsum + reductions over the cache's seq
    axis: when the cache is sharded on seq, XLA's SPMD partitioner turns the
    max / sum reductions into partial reductions + small all-reduces - the
    paper's partial-softmax tier merge, synthesized across chips.  (The
    lax.scan-chunked path cannot be partitioned this way.)
    """
    B, _, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    sc = scale if scale is not None else 1.0 / math.sqrt(D)
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.full((B,), cache_len)
    qf = (q.astype(jnp.float32) * sc).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32))
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    mask = jnp.arange(S)[None, :] < cache_len[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.where(mask[:, None, None, :], jnp.exp2((s - m) * LOG2E), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    o = jnp.einsum("bhgk,bkhd->bhgd", p / jnp.maximum(l, 1e-20),
                   v_cache.astype(jnp.float32))
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


def seq_parallel_decode(q, k_cache_local, v_cache_local, cache_len, *,
                        axis: str = "data",
                        scale: Optional[float] = None) -> jax.Array:
    """Sequence-parallel decode INSIDE shard_map: each device holds a slice
    of the KV cache along seq; compute local partial (m, l, o), all-gather
    the tiny partials, merge with the log-sum-exp combine.

    This is the paper's tier-merge applied across chips: partials flow
    "register-to-register" (ICI) instead of re-materializing the cache.
    q: (B,1,Hq,D) replicated; caches: (B, S_local, Hkv, D) local shard.
    """
    B, _, Hq, D = q.shape
    S_local = k_cache_local.shape[1]
    G = Hq // k_cache_local.shape[2]
    idx = jax.lax.axis_index(axis)
    shard_start = idx * S_local
    local_len = jnp.clip(cache_len - shard_start, 0, S_local)

    m, l, o = _decode_partials(q, k_cache_local, v_cache_local, local_len,
                               scale=scale)
    # gather tiny (m, l, o) partials across the sequence shards
    m_all = jax.lax.all_gather(m, axis)          # (P, B, Hkv, G)
    l_all = jax.lax.all_gather(l, axis)
    o_all = jax.lax.all_gather(o, axis)          # (P, B, Hkv, G, D)
    m_c, l_c, o_c = ref.combine_partial_softmax(m_all, l_all, o_all)
    o_final = o_c / jnp.maximum(l_c, 1e-20)[..., None]
    return o_final.reshape(B, 1, Hq, D).astype(q.dtype)


def _decode_partials(q, kc, vc, valid_len, *, scale=None, block_kv: int = 1024):
    B, _, Hq, D = q.shape
    S, Hkv = kc.shape[1], kc.shape[2]
    G = Hq // Hkv
    sc = scale if scale is not None else 1.0 / math.sqrt(D)
    valid_len = jnp.asarray(valid_len)
    if valid_len.ndim == 0:
        valid_len = jnp.full((B,), valid_len)
    nblk = -(-S // block_kv)
    pad = nblk * block_kv - S
    kp = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else kc
    vp = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else vc
    kb = jnp.moveaxis(kp.reshape(B, nblk, block_kv, Hkv, D), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nblk, block_kv, Hkv, D), 1, 0)
    qf = (q.astype(jnp.float32) * sc).reshape(B, Hkv, G, D)

    def body(carry, blk):
        m, l, o = carry
        kblk, vblk, j = blk
        pos = j * block_kv + jnp.arange(block_kv)
        s = jnp.einsum("bhgd,bkhd->bhgk", qf, kblk.astype(jnp.float32))
        mask = pos[None, :] < valid_len[:, None]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask[:, None, None, :],
                      jnp.exp2((s - m_safe[..., None]) * LOG2E), 0.0)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp2((m - m_new) * LOG2E))
        l = l * alpha + p.sum(-1)
        o = o * alpha[..., None] + jnp.einsum(
            "bhgk,bkhd->bhgd", p, vblk.astype(jnp.float32))
        return (m_new, l, o), None

    # varying-zero seed: under shard_map the scan carry must carry the same
    # "varying manual axes" type as the body outputs (which depend on the
    # sharded cache); outside shard_map this is +0.0
    vzero = jnp.sum(kc[:, :0].astype(jnp.float32))
    m0 = jnp.full((B, Hkv, G), NEG_INF, jnp.float32) + vzero
    l0 = jnp.zeros((B, Hkv, G), jnp.float32) + vzero
    o0 = jnp.zeros((B, Hkv, G, D), jnp.float32) + vzero
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), (kb, vb, jnp.arange(nblk)))
    return m, l, o


# ===========================================================================
# SSM / RWKV
# ===========================================================================

def mamba2_scan(x, dt, A, Bm, Cm, *, impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl == "pallas":
        from . import mamba2_scan as mk
        return mk.mamba2_scan(x, dt, A, Bm, Cm)
    if impl == "naive":
        return ref.mamba2_scan(x, dt, A, Bm, Cm)
    return ref.mamba2_scan_chunked(x, dt, A, Bm, Cm)


mamba2_step = ref.mamba2_step


def rwkv6_scan(r, k, v, w, u, *, impl: Optional[str] = None):
    impl = impl or default_impl()
    if impl == "pallas":
        from . import rwkv6_scan as rk
        return rk.rwkv6_scan(r, k, v, w, u)
    if impl == "naive":
        return ref.rwkv6_scan(r, k, v, w, u)
    return ref.rwkv6_scan_chunked(r, k, v, w, u)


rwkv6_step = ref.rwkv6_step
