"""Tensor-parallel serving: differential conformance + kernel parity.

Two layers, matching docs/tensor_parallel.md:

  validation      ServeConfig.validate() / engine construction reject
                  every indivisible or unsupported TP combination with a
                  clear error naming the knob - these run on any device
                  count.
  parity          the head-sharded engine and kernels are BIT-identical
                  to single-device: every registered conformance trace
                  replays tp=1 vs tp=2 (assert_tp_conformance), fleets
                  of TP replicas match single-replica fleets, and a
                  hypothesis sweep over random head counts / tp degrees
                  / chunk packings pins the kernel wrappers themselves
                  against the unsharded oracle.  These need >= 2 devices
                  and run in the CI multi-device job
                  (XLA_FLAGS=--xla_force_host_platform_device_count=4);
                  a subprocess smoke keeps one end-to-end TP replay in
                  the default single-device suite.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conformance import (TRACES, assert_tp_conformance,
                         assert_tp_shard_accounting, make_scfg)
from repro.configs import get_smoke_config
from repro.configs.base import ServeConfig
from repro.launch.mesh import make_serve_mesh
from repro.models import build_model
from repro.serve import FleetConfig, FleetRouter, ServeEngine
from pools import LAYER, stack_pool
from traffic import assert_greedy_equivalent, replay_fleet

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")

TP_SCFG = dict(max_batch=4, max_seq=512, page_size=16, prefill_chunk=32,
               tick_token_budget=64, max_new_tokens=12, paged=True)


@pytest.fixture(scope="module")
def model_f32():
    # float32 keeps greedy argmax ties out of the parity comparisons
    cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


# ===========================================================================
# validation: every bad TP combination fails with a clear error
# ===========================================================================

def test_tp_degree_below_one_rejected():
    with pytest.raises(ValueError, match="tp_degree"):
        ServeConfig(**{**TP_SCFG, "tp_degree": 0}).validate()


# tp needs the paged engine; chunked/batched False name paths that no
# longer exist, whatever tp_degree is
_OFF_ERRORS = {"paged": "tp_degree=2 requires paged=True",
               "chunked": "chunked=False is no longer supported",
               "batched": "batched=False is no longer supported"}


@pytest.mark.parametrize("off", sorted(_OFF_ERRORS))
def test_tp_requires_paged_chunked_batched(off):
    kw = {**TP_SCFG, "tp_degree": 2, off: False}
    with pytest.raises(ValueError, match=_OFF_ERRORS[off]):
        ServeConfig(**kw).validate()


def test_tp_indivisible_heads_rejected(model_f32):
    """granite smoke has n_kv_heads=2: tp_degree=3 cannot shard it, and
    the engine must say so by name instead of crashing in shard_map."""
    m, params = model_f32
    with pytest.raises(ValueError, match="n_kv_heads"):
        ServeEngine(m, params, ServeConfig(**{**TP_SCFG, "tp_degree": 3}))


def test_serve_mesh_validates_device_count():
    with pytest.raises(ValueError, match="devices"):
        make_serve_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError):
        make_serve_mesh(0)


def test_serve_mesh_shape():
    mesh = make_serve_mesh(1)
    assert dict(mesh.shape) == {"data": 1, "model": 1}


# ===========================================================================
# single-device suite keeps one end-to-end TP replay (subprocess, the
# tests/test_distributed.py pattern: the main process keeps 1 device)
# ===========================================================================

def test_tp_engine_smoke_subprocess():
    prog = textwrap.dedent("""
        import jax
        from conformance import TRACES, assert_tp_conformance
        from repro.configs import get_smoke_config
        from repro.models import build_model

        assert jax.device_count() >= 2
        cfg = get_smoke_config("granite-3-2b").replace(dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        assert_tp_conformance(m, params, TRACES["mixed"],
                              max_new_tokens=8)
        print("tp smoke OK")
    """)
    root = __file__.rsplit("/tests/", 1)[0]
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={"PYTHONPATH": f"src:{root}/tests",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
             "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd=root, timeout=420)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"


# ===========================================================================
# differential conformance: tp=1 vs tp=2 on every registered trace
# ===========================================================================

@multi_device
@pytest.mark.parametrize("trace", sorted(TRACES))
def test_tp_conformance(trace, model_f32):
    """The tentpole guarantee: head-sharding changes WHERE bytes live
    and HOW MUCH each device streams, never WHAT is generated - greedy
    bit-parity, equal work clocks, page conservation, per-shard byte
    accounting, on every registered traffic shape."""
    m, params = model_f32
    assert_tp_conformance(m, params, TRACES[trace])


@multi_device
def test_tp_composes_with_speculation(model_f32):
    """TP and speculative decoding stack: the sharded verify kernel is
    bit-identical too, so spec-on tp=2 == spec-on tp=1."""
    m, params = model_f32
    _, eng_tp = assert_tp_conformance(m, params, TRACES["mixed"],
                                      speculative=True)
    assert eng_tp.stats()["spec_drafted"] > 0, "speculation never engaged"


@multi_device
def test_tp_fleet_differential(model_f32):
    """Fleets of TP replicas: the same trace through a 1-replica tp=1
    fleet and a 2-replica tp=2 fleet yields bit-identical per-request
    outputs (fleet uids key in submit order), with per-shard accounting
    holding on every replica."""
    m, params = model_f32
    spec = TRACES["mixed"]
    items = spec.build(m.cfg.vocab_size)

    def run(n_replicas, tp):
        scfg = make_scfg(spec, False, max_new_tokens=8, tp_degree=tp)
        router = FleetRouter(m, params, scfg,
                             FleetConfig(n_replicas=n_replicas))
        out, done = replay_fleet(router, spec.build(m.cfg.vocab_size),
                                 check=True)
        return out, done, router

    out1, _, r1 = run(1, 1)
    out2, done2, r2 = run(2, 2)
    assert out1.keys() == out2.keys()
    if out1 != out2:
        assert_greedy_equivalent(m, params, done2, out1)
    for eng in r2.engines:
        assert_tp_shard_accounting(eng)
        assert eng.tp_stats()["tp_degree"] == 2
    assert sum(len(v) for v in out1.values()) \
        == sum(len(v) for v in out2.values())


@multi_device
def test_tp_stats_surface(model_f32):
    """tp_stats() and the serve_tp_* metrics tell one story: the gauge
    carries the degree, per-shard bytes divide the full-page bytes
    exactly, and stats() exposes the degree for the fleet view."""
    m, params = model_f32
    eng = ServeEngine(m, params, ServeConfig(**{**TP_SCFG,
                                                "tp_degree": 2}))
    eng.submit(list(range(1, 40)))
    eng.run_until_done()
    t = eng.tp_stats()
    assert t["tp_degree"] == 2
    assert eng.stats()["tp_degree"] == 2
    assert t["shard_page_bytes"] * 2 == t["page_bytes"]
    assert t["shard_kv_bytes_read"] > 0
    assert t["table_bytes_replicated"] > 0
    snap = eng.metrics_snapshot()
    assert snap["serve_tp_degree"]["value"] == 2


# ===========================================================================
# kernel-level property sweep: random shapes, sharded == unsharded bitwise
# ===========================================================================

def _random_paged(rng, tp, hkv_mult, gqa, n_rows, d=8, page_size=4,
                  n_pages=24, n_max=6):
    """Random head-sharded-compatible paged attention inputs: Hkv a
    multiple of tp, Hq = Hkv * gqa, a stacked pool (tests/pools.py, the
    data at layer LAYER), block tables drawing distinct pages (page 0
    reserved null, as the engine lays it out)."""
    hkv = tp * hkv_mult
    hq = hkv * gqa
    pool = stack_pool(
        jnp.asarray(rng.standard_normal((hkv, n_pages, page_size, d)),
                    jnp.float32),
        jnp.asarray(rng.standard_normal((hkv, n_pages, page_size, d)),
                    jnp.float32))
    tables = np.zeros((n_rows, n_max), np.int32)
    lens = np.zeros((n_rows,), np.int32)
    for r in range(n_rows):
        n = int(rng.integers(1, n_max * page_size + 1))
        lens[r] = n
        need = -(-n // page_size)
        tables[r, :need] = rng.choice(
            np.arange(1, n_pages), size=need, replace=False)
    return hq, pool, jnp.asarray(tables), jnp.asarray(lens)


def _decode_kernel_bitwise(hkv_mult, gqa, n_rows, seed):
    """paged_flash_decode under the head-sharded wrapper == unsharded,
    BITWISE, across random head counts / GQA ratios / batch sizes /
    page layouts (float32)."""
    from repro.kernels import ops
    tp = 2
    rng = np.random.default_rng(seed)
    hq, pool, tables, lens = _random_paged(rng, tp, hkv_mult, gqa, n_rows)
    q = jnp.asarray(rng.standard_normal((n_rows, 1, hq, 8)), jnp.float32)
    mesh = make_serve_mesh(tp)
    o_ref = ops.paged_flash_decode(q, pool, LAYER, tables, lens)
    o_tp = ops.paged_flash_decode(q, pool, LAYER, tables, lens,
                                  tp_mesh=mesh)
    assert o_tp.dtype == o_ref.dtype and o_tp.shape == o_ref.shape
    assert bool(jnp.all(o_tp == o_ref)), \
        float(jnp.abs(o_tp - o_ref).max())


def _chunk_kernel_bitwise(hkv_mult, gqa, n_rows, ragged, seed):
    """batched_paged_prefill_attention (the chunk AND verify kernel -
    `ragged` exercises the q_lens verify path) under the head-sharded
    wrapper == unsharded, bitwise, across random chunk packings."""
    from repro.kernels import ops
    tp = 2
    s = 8
    rng = np.random.default_rng(seed)
    hq, pool, tables, lens = _random_paged(rng, tp, hkv_mult, gqa, n_rows)
    # chunk rows sit at the tail of each row's span: offset + S <= len
    # is not required (the kernel masks by true_lens), so offsets may
    # overhang short rows exactly like a padded final chunk does
    offs = jnp.asarray(np.maximum(np.asarray(lens) - s, 0), jnp.int32)
    q = jnp.asarray(rng.standard_normal((n_rows, s, hq, 8)), jnp.float32)
    q_lens = jnp.asarray(rng.integers(1, s + 1, size=n_rows), jnp.int32) \
        if ragged else None
    mesh = make_serve_mesh(tp)
    o_ref = ops.batched_paged_prefill_attention(q, pool, LAYER, tables,
                                                offs, lens, q_lens)
    o_tp = ops.batched_paged_prefill_attention(q, pool, LAYER, tables,
                                               offs, lens, q_lens,
                                               tp_mesh=mesh)
    assert bool(jnp.all(o_tp == o_ref)), \
        float(jnp.abs(o_tp - o_ref).max())


# seeded non-hypothesis sweep: the kernel parity always runs multi-device,
# even where requirements-dev.txt (hypothesis) is not installed
@multi_device
@pytest.mark.parametrize("hkv_mult,gqa,n_rows,seed",
                         [(1, 1, 1, 0), (1, 2, 2, 1), (2, 2, 3, 2)])
def test_tp_decode_kernel_bitwise_seeded(hkv_mult, gqa, n_rows, seed):
    _decode_kernel_bitwise(hkv_mult, gqa, n_rows, seed)


@multi_device
@pytest.mark.parametrize("tp,hkv,gqa,d", [(2, 4, 4, 64), (4, 4, 1, 128)])
def test_tp_pallas_decode_kernel_bitwise(tp, hkv, gqa, d):
    """The Pallas decode kernel (interpret mode) under the head-sharded
    wrapper == unsharded, bitwise, down to one KV head per device: a
    head's products and merge do not depend on how many heads the call
    holds."""
    from repro.kernels import ops
    if jax.device_count() < tp:
        pytest.skip(f"needs {tp} devices")
    rng = np.random.default_rng(tp)
    ps, n_max, n_rows = 128, 8, 5
    n_pages = n_rows * n_max + 1
    pool = stack_pool(*(jnp.asarray(rng.standard_normal((hkv, n_pages, ps,
                                                         d)), jnp.float32)
                        for _ in range(2)), 2 * d)
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                         .reshape(n_rows, n_max), jnp.int32)
    lens = jnp.asarray([1, ps + 1, 700, n_max * ps, 513], jnp.int32)
    q = jnp.asarray(rng.standard_normal((n_rows, 1, hkv * gqa, d)),
                    jnp.float32)
    o_ref = ops.paged_flash_decode(q, pool, LAYER, tables, lens,
                                   impl="pallas")
    o_tp = ops.paged_flash_decode(q, pool, LAYER, tables, lens,
                                  impl="pallas", tp_mesh=make_serve_mesh(tp))
    assert bool(jnp.all(o_tp == o_ref)), \
        float(jnp.abs(o_tp - o_ref).max())


@multi_device
@pytest.mark.parametrize("hkv_mult,gqa,n_rows,ragged,seed",
                         [(1, 1, 1, False, 3), (1, 2, 2, True, 4),
                          (2, 1, 3, True, 5)])
def test_tp_chunk_kernel_bitwise_seeded(hkv_mult, gqa, n_rows, ragged,
                                        seed):
    _chunk_kernel_bitwise(hkv_mult, gqa, n_rows, ragged, seed)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # requirements-dev.txt extra; seeded sweep above
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @multi_device
    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 2), st.integers(1, 2), st.integers(1, 3),
           st.integers(0, 2 ** 31 - 1))
    def test_tp_decode_kernel_bitwise_property(hkv_mult, gqa, n_rows,
                                               seed):
        _decode_kernel_bitwise(hkv_mult, gqa, n_rows, seed)

    @multi_device
    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 2), st.integers(1, 2), st.integers(1, 3),
           st.booleans(), st.integers(0, 2 ** 31 - 1))
    def test_tp_chunk_kernel_bitwise_property(hkv_mult, gqa, n_rows,
                                              ragged, seed):
        _chunk_kernel_bitwise(hkv_mult, gqa, n_rows, ragged, seed)
else:
    @pytest.mark.skip(reason="hypothesis not installed "
                             "(pip install -r requirements-dev.txt)")
    def test_tp_kernel_bitwise_property():
        pass
