"""Kernel micro-benchmarks: jit wall time of the portable (ref) paths and
interpret-mode validation cost of the Pallas kernels, the latency-
balanced block configs the scheduler picks for TPU, and the ragged
batched chunk-prefill kernel (one launch for K chunks vs K single-row
launches - the dispatch fold behind the serve engine's one-launch
tick)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import choose_block_config
from repro.kernels import ops, ref

from .common import emit, timed


def run():
    key = jax.random.PRNGKey(0)

    def rn(*s, dtype=jnp.bfloat16):
        return jax.random.normal(key, s, jnp.float32).astype(dtype)

    B, S, H, D = 1, 1024, 8, 128
    q, k, v = rn(B, S, H, D), rn(B, S, H, D), rn(B, S, H, D)
    fa = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                     impl="ref"))
    _, us = timed(lambda: fa(q, k, v).block_until_ready(), reps=3)
    flops = 4 * B * S * S * H * D / 2
    emit("kernel/flash_fwd_ref_1k", us, f"gflops={flops/us/1e3:.1f}")

    kc, vc = rn(B, 32768, H, D), rn(B, 32768, H, D)
    qd = rn(B, 1, H, D)
    fd = jax.jit(lambda q, kc, vc: ops.flash_decode(q, kc, vc, 32768,
                                                    impl="ref"))
    _, us = timed(lambda: fd(qd, kc, vc).block_until_ready(), reps=3)
    emit("kernel/flash_decode_ref_32k", us,
         f"GBps={(2*32768*H*D*2)/us/1e3:.1f}")

    x = rn(2, 512, 8, 64, dtype=jnp.float32)
    dt = jax.nn.softplus(rn(2, 512, 8, dtype=jnp.float32))
    A = jnp.abs(rn(8, dtype=jnp.float32)) + 0.1
    Bm, Cm = rn(2, 512, 16, dtype=jnp.float32), rn(2, 512, 16, dtype=jnp.float32)
    m2 = jax.jit(lambda *a: ops.mamba2_scan(*a, impl="ref"))
    _, us = timed(lambda: m2(x, dt, A, Bm, Cm).block_until_ready(), reps=3)
    emit("kernel/mamba2_chunked_ref", us, "chunk=128")

    r = rn(2, 512, 8, 64, dtype=jnp.float32)
    kk = rn(2, 512, 8, 64, dtype=jnp.float32)
    vv = rn(2, 512, 8, 64, dtype=jnp.float32)
    w = jnp.exp(-jnp.exp(jnp.clip(rn(2, 512, 8, 64, dtype=jnp.float32),
                                  -8, 0.75)))
    u = rn(8, 64, dtype=jnp.float32) * 0.1
    rw = jax.jit(lambda *a: ops.rwkv6_scan(*a, impl="ref"))
    _, us = timed(lambda: rw(r, kk, vv, w, u).block_until_ready(), reps=3)
    emit("kernel/rwkv6_chunked_ref", us, "chunk=32")

    # ragged batched chunk prefill: K chunks of K different sequences at K
    # different prompt positions - ONE launch (the serve one-launch tick)
    # vs K single-row launches
    Kc, Sc, Hqc, Hkvc, Dc, psc = 4, 128, 8, 4, 64, 32
    n_pages, n_max = 64, 16
    # a one-layer stacked pool, head-major, K and V side by side in lanes
    pool = ops.pack_kv(rn(Hkvc, n_pages, psc, Dc, dtype=jnp.float32),
                       rn(Hkvc, n_pages, psc, Dc, dtype=jnp.float32),
                       2 * Dc)[None]
    qc = rn(Kc, Sc, Hqc, Dc, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    perm = rng.permutation(np.arange(1, n_pages))
    tables = np.zeros((Kc, n_max), np.int32)
    offsets = np.array([0, 96, 224, 352], np.int32)
    pos = 0
    for row in range(Kc):
        need = (int(offsets[row]) + Sc + psc - 1) // psc
        tables[row, :need] = perm[pos:pos + need]
        pos += need
    tbl_j = jnp.asarray(tables)
    off_j = jnp.asarray(offsets)
    tls_j = off_j + Sc
    single = jax.jit(lambda q, row, off: ops.paged_prefill_attention(
        q, pool, 0, row, off, impl="ref"))
    batched = jax.jit(lambda q: ops.batched_paged_prefill_attention(
        q, pool, 0, tbl_j, off_j, tls_j, impl="ref"))
    _, us = timed(lambda: [single(qc[row:row + 1], tbl_j[row],
                                  off_j[row]).block_until_ready()
                           for row in range(Kc)], reps=3)
    emit(f"kernel/chunk_prefill_ref_seq_k{Kc}", us, f"launches={Kc}")
    _, us_b = timed(lambda: batched(qc).block_until_ready(), reps=3)
    emit(f"kernel/chunk_prefill_ref_batched_k{Kc}", us_b,
         f"launches=1;speedup={us / max(us_b, 1e-9):.2f}")

    # latency-balanced Pallas block configs (the paper's scheduling method)
    for hd, seq in ((64, 4096), (128, 4096), (128, 32768), (256, 32768)):
        bc = choose_block_config(hd, seq)
        emit(f"kernel/block_config_d{hd}_s{seq}", 0.0,
             f"bq={bc.block_q};bkv={bc.block_kv};"
             f"balance={bc.balanced:.2f};bubble_free={bc.bubble_free};"
             f"vmem_KiB={bc.vmem_bytes//1024}")


if __name__ == "__main__":
    run()
