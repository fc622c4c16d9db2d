"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode (how every other test runs the kernels on the CPU) never
checks the TPU's tiling rules: the last two dimensions of a block must be
multiples of (8, 128) or span the whole array dimension.  These tests
compile each kernel of the serving path, at granite-3-2b's published
widths (32 query heads, 8 KV heads, head_dim 64, bf16), for a v5e that is
described but not attached.  The TPU compiler refuses here exactly what
it would refuse on the chip.

The step guard compiles the program's own fused decode and chunk-batch
steps the same way and reads the optimized HLO: no XLA op may copy,
slice or restack the KV page pool, which only the Pallas calls touch.
The weight guard reads the same steps' layer scan: no op may slice out
or relayout a copy of one layer's wq, wk or wv.

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
from repro.kernels import kv_write as kw
from repro.kernels import paged_prefill as pp
from repro.models import build_model
from repro.serve import serve_step

CFG = get_config("granite-3-2b")
HQ, HKV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
# the paged path at each head_dim a paged config stores: granite-3-2b's
# 64 (pool rows of 128 lanes) and opt-6.7b's 128 (256 lanes)
PAGED_CFGS = {64: CFG, 128: get_config("opt-6.7b")}
DT = jnp.bfloat16


def _with_head_dims(name, values):
    """Parametrize (name, head_dim) over every value at each paged
    head_dim; the head_dim-64 cases keep the bare value as their id."""
    return pytest.mark.parametrize(
        (name, "head_dim"),
        [pytest.param(v, hd, id=str(v) if hd == 64 else f"{v}-hd{hd}")
         for hd in sorted(PAGED_CFGS) for v in values])
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
    """Compile the kernels (interpret mode off, the model on the Pallas
    path) with the persistent compilation cache off: a TPU executable
    written here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(backend, "interpret_mode", lambda: False)
    monkeypatch.setattr(ops, "default_impl", lambda: "pallas")
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


def _paged_args(sharding, rows, q_len, page_size, layers=2, cfg=CFG):
    """q plus a stacked lane-dense pool sized like chip_smoke.py's engine
    (dense-equivalent pages + the null page), a layer index and per-row
    tables, at cfg's widths."""
    n_max = MAX_SEQ // page_size
    pages = BATCH * n_max + 1
    pool = _spec((layers, cfg.n_kv_heads, pages, page_size,
                  2 * cfg.head_dim), DT, sharding)
    return (_spec((rows, q_len, cfg.n_heads, cfg.head_dim), DT, sharding),
            pool,
            _spec((), jnp.int32, sharding),
            _spec((rows, n_max), jnp.int32, sharding))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _kernel_named(compiled, name: str) -> bool:
    """The kernel's custom call carries `name`, which the profiler gives
    its device events and the benchmark's roofline readers match."""
    return re.search(rf'%{name}(\.\d+)? = [^\n]*custom_call_target='
                     rf'"tpu_custom_call"', compiled.as_text()) is not None


@_with_head_dims("page_size", [16, 128])
def test_paged_flash_decode_compiles(topo, one_chip, for_chip, page_size,
                                     head_dim):
    q, pool, layer, bt = _paged_args(one_chip, BATCH, 1, page_size,
                                     cfg=PAGED_CFGS[head_dim])
    lens = _spec((BATCH,), jnp.int32, one_chip)
    compiled = fd.paged_flash_decode.lower(q, pool, layer, bt,
                                           lens).compile()
    assert _kernel_named(compiled, "paged_flash_decode")
    # the kernel reads the pool where it lies: no relayout copy first
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# the benchmark cells' decode launches: (rows, KV heads, group, head_dim,
# table width, pool pages, layers) - granite-3-2b chat's 64-row bucket and
# the published OPT-6.7B stage's 15 rows of 2,048 tokens
CELL_DECODE = {
    "granite-3-2b.chat-poisson": (64, 8, 4, 64, 32, 480, 40),
    "opt-6.7b-published.long-decode": (15, 32, 1, 128, 16, 241, 16),
}


@pytest.mark.parametrize("cell", sorted(CELL_DECODE))
def test_paged_flash_decode_compiles_at_cell_shapes(topo, one_chip, for_chip,
                                                    cell):
    """The decode kernel at each cell's exact launch shape: its
    double-buffered page blocks must fit the VMEM it asks for, and it
    still reads the whole pool in place."""
    rows, hkv, group, head_dim, n_max, pages, layers = CELL_DECODE[cell]
    q = _spec((rows, 1, hkv * group, head_dim), DT, one_chip)
    pool = _spec((layers, hkv, pages, 128, 2 * head_dim), DT, one_chip)
    compiled = fd.paged_flash_decode.lower(
        q, pool, _spec((), jnp.int32, one_chip),
        _spec((rows, n_max), jnp.int32, one_chip),
        _spec((rows,), jnp.int32, one_chip)).compile()
    assert _kernel_named(compiled, "paged_flash_decode")
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@_with_head_dims("page_size", [16, 128])
def test_batched_paged_prefill_compiles(topo, one_chip, for_chip, page_size,
                                        head_dim):
    rows = 4
    q, pool, layer, tables = _paged_args(one_chip, rows, CHUNK, page_size,
                                         cfg=PAGED_CFGS[head_dim])
    vec = _spec((rows,), jnp.int32, one_chip)
    compiled = pp.batched_paged_prefill_attention.lower(
        q, pool, layer, tables, vec, vec, vec).compile()
    assert _kernel_named(compiled, "batched_paged_prefill_attention")
    # nothing beyond the kernel's own output lives in HBM at a full chunk
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == q.size * 2
    assert mem.temp_size_in_bytes <= 4 * mem.output_size_in_bytes, mem


@_with_head_dims("n_tokens", [1, CHUNK])
def test_kv_page_write_compiles(topo, one_chip, for_chip, n_tokens,
                                head_dim):
    """The page write, decode rows (one token) and chunk rows, writes the
    donated pool in place."""
    cfg = PAGED_CFGS[head_dim]
    rows = BATCH if n_tokens == 1 else 4
    _, pool, layer, tables = _paged_args(one_chip, rows, n_tokens, 128,
                                         cfg=cfg)
    new = _spec((rows, n_tokens, cfg.n_kv_heads, 2 * head_dim), DT,
                one_chip)
    vec = _spec((rows,), jnp.int32, one_chip)

    def write(pool, layer, new, tables, starts, ends):
        plan = kw.write_plan(tables, starts, ends, n_tokens, 128)
        return kw.kv_page_write(pool, layer, new, plan)

    compiled = jax.jit(write, donate_argnums=(0,)).lower(
        pool, layer, new, tables, vec, vec).compile()
    assert _kernel_named(compiled, "kv_page_write")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool.size * 2
    assert mem.temp_size_in_bytes < (1 << 20), mem


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
    q, _, layer, bt = _paged_args(rep, BATCH, 1, page_size)
    pool = _spec((2, HKV, BATCH * MAX_SEQ // page_size + 1, page_size,
                  2 * D), DT,
                 NamedSharding(mesh, P(None, "model", None, None, None)))
    lens = _spec((BATCH,), jnp.int32, rep)

    def step(q, pool, layer, bt, lens):
        return ops.paged_flash_decode(q, pool, layer, bt, lens,
                                      impl="pallas", tp_mesh=mesh)

    compiled = jax.jit(step).lower(q, pool, layer, bt, lens).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" in hlo
    # each chip holds a quarter of the pool
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < pool.size * 2 // 4 + (1 << 20)


# ===========================================================================
# the step guard: the KV page pool is touched only by the Pallas calls
# ===========================================================================

STEP_LAYERS, STEP_BATCH, STEP_PAGES, STEP_SEQ, STEP_ROWS = 2, 64, 480, 4096, 4
# HLO ops that may produce an array of pool size: the pool passes through
# the layer loop and comes out of the aliased page write, nothing else
POOL_OPS = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
            "custom-call"}


# the steps at each pool width (granite-3-2b at head_dim 64, granite-8b at
# 128) and OPT-6.7B's published block (MHA at head_dim 128, learned
# positions, biases), each case id'd by its kind and this suffix
STEP_CFGS = {"": CFG, "-hd128": get_config("granite-8b"),
             "-opt-6.7b": get_config("opt-6.7b")}


def _compile_step(one_chip, kind, arch, rows=STEP_ROWS):
    cfg = STEP_CFGS[arch].replace(n_layers=STEP_LAYERS)
    model = build_model(cfg)
    seq = min(STEP_SEQ, cfg.max_seq)

    def spec(x):
        return _spec(x.shape, x.dtype, one_chip)

    params = jax.tree.map(spec, jax.eval_shape(model.init,
                                               jax.random.key(0)))
    cache = jax.tree.map(spec, jax.eval_shape(lambda: model.init_cache(
        STEP_BATCH, seq, page_size=128, num_pages=STEP_PAGES)))
    vec = lambda *s: _spec(s, jnp.int32, one_chip)          # noqa: E731
    key = _spec((2,), jnp.uint32, one_chip)
    if kind == "decode":
        fn = serve_step.make_fused_decode_step(model, temperature=0.0)
        args = (params, cache, vec(STEP_BATCH, 1), vec(STEP_BATCH),
                _spec((STEP_BATCH,), jnp.bool_, one_chip), key)
        donate = 1
    else:
        fn = serve_step.make_chunk_batch_step(model, temperature=0.0)
        batch = {"tokens": vec(rows, CHUNK), "offset": vec(rows),
                 "true_lens": vec(rows), "final_slot": vec(rows)}
        args = (params, batch, cache, vec(rows, seq // 128),
                vec(STEP_BATCH, 1), vec(STEP_BATCH), key)
        donate = 2
    pool = max(jax.tree.leaves(cache), key=lambda x: x.size)
    compiled = jax.jit(fn, donate_argnums=(donate,)).lower(*args).compile()
    return compiled, pool


def _instructions(hlo: str):
    """(computation, name, element count, opcode) of every instruction
    whose result is an array."""
    comp = ""
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head and not line.startswith(" "):
            comp = head.group(1)
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                     r"([\w-]+)\(", line)
        if m:
            n = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
            yield comp, m.group(1), n, m.group(3)


def _pool_sized_ops(hlo: str, min_elems: int):
    """(name, opcode) of every instruction outside a fusion's body whose
    array result holds at least `min_elems` elements."""
    return [(name, op) for comp, name, n, op in _instructions(hlo)
            if "fused" not in comp and n >= min_elems]


@pytest.mark.parametrize(("kind", "arch"), [
    pytest.param(kind, arch, id=kind + arch)
    for arch in STEP_CFGS for kind in ("decode", "chunk")])
def test_step_never_copies_the_page_pool(topo, one_chip, for_chip, kind,
                                         arch):
    """granite-3-2b (head_dim 64), granite-8b (128) and OPT-6.7B (128, MHA)
    widths cut to 2 layers, 480 pages of 128, batch 64, 4 x 512 chunk
    rows, the cache donated: no XLA op copies, slices or restacks the
    pool, the pool comes out in the buffer it went in, and the step's
    temporaries stay under one layer's slab of the pool."""
    compiled, pool = _compile_step(one_chip, kind, arch)
    hlo = compiled.as_text()
    slab = pool.size // STEP_LAYERS
    bad = [(n, op) for n, op in _pool_sized_ops(hlo, slab)
           if op not in POOL_OPS
           or (op == "custom-call" and not n.startswith("kv_page_write"))]
    assert not bad, bad
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool.size * 2, mem
    assert mem.temp_size_in_bytes < slab * 2, mem
    attention = {"decode": "paged_flash_decode",
                 "chunk": "batched_paged_prefill_attention"}[kind]
    assert _kernel_named(compiled, "kv_page_write")
    assert _kernel_named(compiled, attention)


# the weight guard's chunk rows: 3 x 512 tokens, so that no activation of
# the step holds as many elements as one layer's wq/wk/wv (at 4 x 512,
# granite-3-2b's q has the element count of its wq)
WEIGHT_GUARD_ROWS = 3


def _while_body_ops(hlo: str, attention: str):
    """(name, element count, opcode) of every instruction of the while
    body that calls the `attention` kernel: the layer scan's body."""
    bodies = set(re.findall(r"\bwhile\(.*?\bbody=%([\w.-]+)", hlo))
    ops_of = {}
    for comp, name, n, op in _instructions(hlo):
        if comp in bodies:
            ops_of.setdefault(comp, []).append((name, n, op))
    scan = [ops for ops in ops_of.values()
            if any(name.startswith(attention) for name, _, _ in ops)]
    assert len(scan) == 1, sorted(ops_of)
    return scan[0]


@pytest.mark.parametrize(("kind", "arch"), [
    pytest.param(kind, arch, id=kind + arch)
    for arch in STEP_CFGS for kind in ("decode", "chunk")])
def test_step_reads_qkv_weights_in_place(topo, one_chip, for_chip, kind,
                                         arch):
    """The steps of the page-pool guard (2 layers; chunk steps of 3 rows):
    in the layer scan's body, neither a `copy` nor a fusion results in an
    array of one layer's wq, wk or wv.  Split into heads inside the dot,
    a projection became a convolution that wanted its weight as (heads,
    head_dim, d_model): each step sliced out a copy of each layer's
    weight and transposed it.  A plain dot reads the weight in place.
    The compiler's asynchronous prefetches (copy-start/copy-done,
    slice-start/slice-done) are not relayouts and are not counted."""
    cfg = STEP_CFGS[arch]
    attention = {"decode": "paged_flash_decode",
                 "chunk": "batched_paged_prefill_attention"}[kind]
    compiled, _ = _compile_step(one_chip, kind, arch,
                                rows=WEIGHT_GUARD_ROWS)
    layer = {cfg.d_model * cfg.n_heads * cfg.head_dim,
             cfg.d_model * cfg.n_kv_heads * cfg.head_dim}
    bad = [(name, op)
           for name, n, op in _while_body_ops(compiled.as_text(), attention)
           if op in ("copy", "fusion") and n in layer]
    assert not bad, bad


@pytest.mark.parametrize("head_dim", sorted(PAGED_CFGS))
def test_copy_on_write_copies_one_page_in_place(topo, one_chip, for_chip,
                                                head_dim):
    """The copy-on-write page copy, jitted with the pool donated as the
    engine runs it, on the step guard's pool (2 layers, 480 pages of
    128): the pool comes out in the buffer it went in and the only
    temporary is at most the one page copied (every layer and head of
    it), where an XLA copy of the pool would add the whole pool."""
    cfg = PAGED_CFGS[head_dim]
    pool = _spec((STEP_LAYERS, cfg.n_kv_heads, STEP_PAGES, 128,
                  2 * head_dim), DT, one_chip)
    page = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(serve_step.copy_page, donate_argnums=(0,)).lower(
        pool, page, page).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool.size * 2, mem
    assert mem.temp_size_in_bytes <= pool.size * 2 // STEP_PAGES, mem
