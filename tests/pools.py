"""Stacked page pools for the kernel-level tests.

The serving pool is one (L, Hkv, P, page_size, W) array: K in lanes [0, D)
and V in lanes [D, 2D) of each token row.  The tests build theirs from
head-major (Hkv, P, page_size, D) K and V pages, at layer LAYER of LAYERS,
with noise in the other layers, so a kernel that reads the wrong layer
fails.  W defaults to 128 lanes, wider than 2D at the tests' small
head_dim, so the kernels' reading of K and V out of a wider row stays
covered; `lanes=2 * D` gives the pool the model builds.
"""
import jax

from repro.kernels import ops

LAYERS, LAYER = 3, 1


def stack_pool(k_pages, v_pages, lanes: int = 128, seed: int = 0):
    kv = ops.pack_kv(k_pages, v_pages, lanes)
    noise = jax.random.normal(jax.random.key(seed), (LAYERS,) + kv.shape,
                              kv.dtype)
    return noise.at[LAYER].set(kv)
