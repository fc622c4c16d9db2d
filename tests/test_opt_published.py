"""OPT-6.7B's published block on the paged serving path, against the plain
float32 reference (bench/references/opt_decoder.py) on seeded random
weights, at a small size on the CPU: 2 layers, d_model 256, 4 heads of 64.

The program runs a paged, ragged chunked prefill of three prompts (chunks
of 24 over pages of 16, so a chunk boundary falls inside a page), then
fused decode steps through the engine's step table; every logit row it
produces is compared with the reference's full forward over the same
tokens.  Putting back any one of the departures the repo used to run
under OPT's name (tanh-GELU, sinusoidal positions, no biases) breaks
both tolerances.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.references import opt_decoder  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ServeConfig  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.layers import sinusoidal_rows  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402
from repro.serve.engine import _shared_steps  # noqa: E402

PAGE, CHUNK, N_DECODE = 16, 24, 9
PROMPTS = (40, 61, 77)
# largest |program - reference| over every compared logit, in units of the
# reference row's standard deviation.  float32: the program and the
# reference differ only in summation order (chunked, paged attention and
# XLA's matmul tiling): 3.3e-6 on seeds 15 and 16, so 1e-4 leaves a factor
# of ~30.  bfloat16: the program rounds products, activations and K/V to 8
# bits of mantissa at every layer: 0.023 and 0.022; 0.15 leaves a factor
# of ~6.5, and the smallest departure (1.79) lies 12x above it.
TOL = {"float32": 1e-4, "bfloat16": 0.15}


def small_cfg(layers: int = 2) -> dict:
    """The published configuration file cut to the test's size."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "opt-6.7b-published.json").read_text())
    cfg.update(num_hidden_layers=layers, hidden_size=256,
               num_attention_heads=4, num_key_value_heads=4, head_dim=64,
               ffn_dim=512, vocab_size=512, word_embed_proj_dim=256)
    cfg["program"].update(n_layers=layers, d_model=256, n_heads=4,
                          n_kv_heads=4, head_dim=64, d_ff=512,
                          vocab_size=512)
    return cfg


@pytest.fixture(scope="module")
def setup():
    cfg = small_cfg()
    params = opt_decoder.make_params(cfg, jax.random.PRNGKey(15))
    rng = np.random.default_rng(15)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n in PROMPTS]
    return cfg, params, prompts


def serve_logits(mcfg, params, prompts):
    """(logit rows per prompt, served tokens per prompt): the last prompt
    position's logits from the chunked prefill, then one row per fused
    decode step, greedy."""
    model = build_model(mcfg)
    dt = jnp.dtype(mcfg.dtype)
    params = jax.tree.map(lambda a: a.astype(dt), params)
    B = len(prompts)
    n_max = 8
    cache = model.init_cache(B, n_max * PAGE, page_size=PAGE,
                             num_pages=B * n_max + 1)
    table = np.arange(1, B * n_max + 1, dtype=np.int32).reshape(B, n_max)
    cache["block_table"] = jnp.asarray(table)
    prefill = jax.jit(model.prefill_chunks)
    rows = [[] for _ in prompts]
    cur = [0] * B
    while any(c < len(p) for c, p in zip(cur, prompts)):
        live = [b for b in range(B) if cur[b] < len(prompts[b])]
        toks = np.zeros((len(live), CHUNK), np.int32)
        offs, ends = [], []
        for r, b in enumerate(live):
            end = min(cur[b] + CHUNK, len(prompts[b]))
            toks[r, :end - cur[b]] = prompts[b][cur[b]:end]
            offs.append(cur[b])
            ends.append(end)
        logits, cache, _ = prefill(
            params, {"tokens": jnp.asarray(toks),
                     "offset": jnp.asarray(offs, np.int32),
                     "true_lens": jnp.asarray(ends, np.int32)},
            cache, jnp.asarray(table[live]))
        for r, b in enumerate(live):
            cur[b] = ends[r]
            if ends[r] == len(prompts[b]):
                rows[b].append(np.asarray(logits[r, 0]))
    steps = _shared_steps(model, 0.0)
    tokens = jnp.asarray([[int(np.argmax(r[-1]))] for r in rows], jnp.int32)
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    live = jnp.ones((B,), bool)
    served = [[int(t)] for t in np.asarray(tokens)[:, 0]]
    for _ in range(N_DECODE):
        logits, _ = steps["decode"](params, cache, tokens, lens)
        cache, tokens, lens = steps["decode_fused"](
            params, cache, tokens, lens, live, jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(tokens)[:, 0],
                                      np.argmax(logits[:, 0], -1))
        for b in range(B):
            rows[b].append(np.asarray(logits[b, 0]))
            served[b].append(int(tokens[b, 0]))
    return [np.stack(r) for r in rows], served


def worst_gap(cfg, params, prompts, rows, served):
    """Largest |program - reference| logit over every compared row, in
    units of the reference row's standard deviation."""
    worst = 0.0
    for p, r, s in zip(prompts, rows, served):
        seq = np.asarray(p + s[:-1], np.int32)
        pos = np.arange(len(p) - 1, len(seq), dtype=np.int32)
        ref = np.asarray(opt_decoder.logits_at(cfg, params, seq, pos))
        assert ref.shape == r.shape
        worst = max(worst, float(np.max(np.abs(r - ref)
                                        / ref.std(-1, keepdims=True))))
    return worst


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_paged_chunked_serving_matches_the_reference(setup, dtype):
    cfg, params, prompts = setup
    mcfg = harness.model_config(cfg).replace(dtype=dtype)
    rows, served = serve_logits(mcfg, params, prompts)
    assert all(len(r) == 1 + N_DECODE for r in rows)
    assert worst_gap(cfg, params, prompts, rows, served) < TOL[dtype]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("departure", [
    {"act": "gelu"}, {"abs_positions": "sinusoidal"}, {"bias": False}],
    ids=["gelu", "sinusoidal", "no-bias"])
def test_each_old_departure_breaks_the_tolerance(setup, departure, dtype):
    """Each departure alone moves the logits by 1.79-5.26 row deviations
    (seeds 15 and 16, either dtype): far past both tolerances."""
    cfg, params, prompts = setup
    mcfg = harness.model_config(cfg).replace(dtype=dtype, **departure)
    rows, served = serve_logits(mcfg, params, prompts)
    assert worst_gap(cfg, params, prompts, rows, served) > TOL[dtype]


def test_published_config_is_the_published_block():
    c = get_config("opt-6.7b")
    assert (c.act, c.bias, c.norm, c.norm_eps) == ("relu", True,
                                                   "layernorm", 1e-5)
    assert (c.use_rope, c.abs_positions, c.max_seq) == \
        (False, "learned", 2048)
    tree = jax.eval_shape(build_model(c.replace(n_layers=1)).init,
                          jax.random.PRNGKey(0))
    assert tree["tok"]["pos_embed"].shape == (2050, 4096)
    assert {"bq", "bk", "bv", "bo"} <= set(tree["blocks"]["attn"])
    assert {"b_in", "b_out"} <= set(tree["blocks"]["mlp"])


def test_request_past_the_position_table_is_refused(setup):
    """The table has rows for positions 0..2047 only: the engine refuses a
    request that would need more, even where its own max_seq is larger."""
    cfg, params, _ = setup
    mcfg = harness.model_config(cfg).replace(dtype="float32")
    scfg = ServeConfig(max_batch=2, max_seq=4096, page_size=PAGE,
                       prefill_chunk=32, tick_token_budget=64, paged=True,
                       num_pages=300)
    eng = ServeEngine(build_model(mcfg), params, scfg)
    with pytest.raises(ValueError, match="max_seq 2048"):
        eng.submit([1] * 2000, max_new_tokens=49)
    eng.submit([1] * 2000, max_new_tokens=48)


def _old_sinusoid_table(n: int, d: int):
    """The 65,536-row table the decode and chunk steps used to build."""
    pos = jnp.arange(0, n, dtype=jnp.float32)[:, None]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (np.log(10_000.0) / half))
    ang = pos * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def test_sinusoid_rows_equal_the_old_table_bit_for_bit():
    at = np.array([0, 1, 2047, 65535])
    old = np.asarray(_old_sinusoid_table(65536, 4096))[at]
    new = np.asarray(jax.jit(sinusoidal_rows, static_argnums=1)(
        jnp.asarray(at), 4096))
    np.testing.assert_array_equal(new, old)


def test_greedy_outputs_equal_at_tp_2_and_4():
    prog = textwrap.dedent("""
        import jax
        from conformance import TRACES, assert_tp_conformance
        from test_opt_published import small_cfg
        from bench import harness
        from bench.references import opt_decoder
        from repro.models import build_model

        assert jax.device_count() >= 4
        cfg = small_cfg()
        m = build_model(harness.model_config(cfg).replace(dtype="float32"))
        params = jax.tree.map(lambda a: a.astype("float32"),
                              opt_decoder.make_params(cfg,
                                                      jax.random.PRNGKey(15)))
        for tp in (2, 4):
            assert_tp_conformance(m, params, TRACES["mixed"], tp_degree=tp,
                                  max_new_tokens=8)
        print("tp OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={"PYTHONPATH": f"{ROOT}/src:{ROOT}/tests:{ROOT}",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd=ROOT, timeout=420)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
