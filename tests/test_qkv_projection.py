"""The q/k/v projection (`models/attention.py::_qkv`) against a plain
einsum with the same weights.

`_qkv` keeps the split into heads behind an optimization barrier so that
the TPU compiler leaves each projection a plain dot (the compile guard in
test_chip_compile.py); the numbers must be the same bf16 product, bias
and qk-norm as the einsum's, within one bf16 rounding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.attention import _qkv, attn_init

# (config, kv_x length or None): GQA with qk-norm, MHA with biases, and
# cross-attention over a source of another length
CASES = {
    "gqa-qk_norm": (get_smoke_config("granite-3-2b").replace(qk_norm=True),
                    None),
    "mha-bias": (get_smoke_config("opt-6.7b"), None),
    "cross": (get_smoke_config("whisper-base"), 11),
}


def _reference(params, x, cfg, kv_x):
    """Heads first, then the dot: x (B, S, d) . w (d, H, D) in float32,
    rounded to bf16 as the program's dot rounds, bias and qk-norm after."""
    src = x if kv_x is None else kv_x

    def proj(name, inp, heads):
        w = params["w" + name].reshape(cfg.d_model, heads, cfg.head_dim)
        y = jnp.einsum("bsd,dhk->bshk", inp, w,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        if cfg.bias:
            y = y + params["b" + name].reshape(heads, cfg.head_dim)
        return y

    q = proj("q", x, cfg.n_heads)
    k = proj("k", src, cfg.n_kv_heads)
    v = proj("v", src, cfg.n_kv_heads)
    if cfg.qk_norm:
        def norm(t, scale):
            tf = t.astype(jnp.float32)
            tf = tf * jax.lax.rsqrt(jnp.mean(tf * tf, -1, keepdims=True)
                                    + 1e-6)
            return (tf * scale.astype(jnp.float32)).astype(t.dtype)
        q, k = norm(q, params["q_norm"]), norm(k, params["k_norm"])
    return q, k, v


@pytest.mark.parametrize("case", sorted(CASES))
def test_qkv_matches_the_plain_einsum(case):
    cfg, kv_len = CASES[case]
    key = jax.random.key(7)
    params = attn_init(key, cfg)
    if cfg.qk_norm:     # scales other than one, so the norm is checked
        params["q_norm"] = jnp.linspace(0.5, 1.5, cfg.head_dim,
                                        dtype=params["q_norm"].dtype)
        params["k_norm"] = params["q_norm"][::-1]
    kx, kk = jax.random.split(jax.random.fold_in(key, 1))
    B, S = 3, 9
    x = jax.random.normal(kx, (B, S, cfg.d_model)).astype(jnp.bfloat16)
    kv_x = (None if kv_len is None else jax.random.normal(
        kk, (B, kv_len, cfg.d_model)).astype(jnp.bfloat16))
    got = jax.jit(lambda p, a, b: _qkv(p, a, cfg, b))(params, x, kv_x)
    want = _reference(params, x, cfg, kv_x)
    kv_s = S if kv_len is None else kv_len
    shapes = [(B, S, cfg.n_heads, cfg.head_dim),
              (B, kv_s, cfg.n_kv_heads, cfg.head_dim),
              (B, kv_s, cfg.n_kv_heads, cfg.head_dim)]
    for name, g, w, shape in zip("qkv", got, want, shapes):
        assert g.shape == shape and g.dtype == jnp.bfloat16, (name, g.shape)
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        # one bf16 rounding of the product (2^-8 relative), and one more of
        # the bias add, measured against the tensor's scale
        np.testing.assert_allclose(g, w, rtol=2 ** -7,
                                   atol=2 ** -8 * np.abs(w).max(),
                                   err_msg=name)
