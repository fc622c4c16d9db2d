"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode (how every other test runs the kernels on the CPU) never
checks the TPU's tiling rules: the last two dimensions of a block must be
multiples of (8, 128) or span the whole array dimension.  These tests
compile each attention kernel of the serving path, at granite-3-2b's
published widths (32 query heads, 8 KV heads, head_dim 64, bf16), for a
v5e that is described but not attached.  The TPU compiler refuses here
exactly what it would refuse on the chip.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.kernels import backend, ops
from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import paged_prefill as pp

CFG = get_config("granite-3-2b")
HQ, HKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
DT = jnp.bfloat16
BATCH, MAX_SEQ, CHUNK = 8, 2048, 512     # chip_smoke.py's engine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_chip(monkeypatch):
    """Compile the kernels (interpret mode off) with the persistent
    compilation cache off: a TPU executable written here could not be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(backend, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()       # no trace from an interpret-mode run is reused
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(sharding, rows, q_len, page_size):
    """q plus a head-major pool sized like chip_smoke.py's engine
    (dense-equivalent pages + the null page), with per-row tables."""
    n_max = MAX_SEQ // page_size
    pages = BATCH * n_max + 1
    pool = _spec((HKV, pages, page_size, D), DT, sharding)
    return (_spec((rows, q_len, HQ, D), DT, sharding), pool, pool,
            _spec((rows, n_max), jnp.int32, sharding))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _kernel_named(compiled, name: str) -> bool:
    """The kernel's custom call carries `name`, which the profiler gives
    its device events and the benchmark's roofline readers match."""
    return re.search(rf'%{name}(\.\d+)? = [^\n]*custom_call_target='
                     rf'"tpu_custom_call"', compiled.as_text()) is not None


@pytest.mark.parametrize("page_size", [16, 128])
def test_paged_flash_decode_compiles(topo, one_chip, for_chip, page_size):
    q, kp, vp, bt = _paged_args(one_chip, BATCH, 1, page_size)
    lens = _spec((BATCH,), jnp.int32, one_chip)
    compiled = fd.paged_flash_decode.lower(q, kp, vp, bt, lens).compile()
    assert _kernel_named(compiled, "paged_flash_decode")


@pytest.mark.parametrize("page_size", [16, 128])
def test_batched_paged_prefill_compiles(topo, one_chip, for_chip, page_size):
    rows = 4
    q, kp, vp, tables = _paged_args(one_chip, rows, CHUNK, page_size)
    vec = _spec((rows,), jnp.int32, one_chip)
    compiled = pp.batched_paged_prefill_attention.lower(
        q, kp, vp, tables, vec, vec, vec).compile()
    assert _kernel_named(compiled, "batched_paged_prefill_attention")
    # nothing beyond the kernel's own output lives in HBM at a full chunk
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == rows * CHUNK * HQ * D * 2
    assert mem.temp_size_in_bytes <= 4 * mem.output_size_in_bytes, mem


def test_dense_flash_decode_compiles(topo, one_chip, for_chip):
    q = _spec((BATCH, 1, HQ, D), DT, one_chip)
    cache = _spec((BATCH, MAX_SEQ, HKV, D), DT, one_chip)
    lens = _spec((BATCH,), jnp.int32, one_chip)
    compiled = fd.flash_decode.lower(q, cache, cache, lens).compile()
    assert _has_kernel(compiled)


def test_flash_attention_forward_compiles(topo, one_chip, for_chip):
    q = _spec((1, MAX_SEQ, HQ, D), DT, one_chip)
    kv = _spec((1, MAX_SEQ, HKV, D), DT, one_chip)
    compiled = fa.flash_attention_fwd.lower(q, kv, kv).compile()
    assert _has_kernel(compiled)


def test_tp_sharded_paged_decode_compiles(topo, for_chip):
    """The tensor-parallel decode (tp_degree=4): pools head-sharded on a
    4-chip mesh, the kernel under shard_map, one all-gather of heads."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    rep = NamedSharding(mesh, P())
    page_size = 128
    q, _, _, bt = _paged_args(rep, BATCH, 1, page_size)
    pool = _spec((HKV, BATCH * MAX_SEQ // page_size + 1, page_size, D), DT,
                 NamedSharding(mesh, P("model", None, None, None)))
    lens = _spec((BATCH,), jnp.int32, rep)

    def step(q, kp, vp, bt, lens):
        return ops.paged_flash_decode(q, kp, vp, bt, lens, impl="pallas",
                                      tp_mesh=mesh)

    compiled = jax.jit(step).lower(q, pool, pool, bt, lens).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" in hlo
    # each chip holds a quarter of the pool
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 2 * pool.size * 2 // 4 + (1 << 20)
