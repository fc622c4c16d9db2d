"""Explicit all-to-all MoE dispatch (shard_map) vs the SPMD-auto path."""
import subprocess
import sys
import textwrap

import pytest


def run_sub(code: str):
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True,
        env={"PYTHONPATH": "src",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd=__file__.rsplit("/tests/", 1)[0], timeout=420)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"


def test_a2a_moe_matches_dense_dispatch():
    """On a (1, 4) mesh with generous capacity (no drops), the explicit
    all-to-all dispatch must equal the auto-SPMD capacity dispatch."""
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.models.moe import moe_init, moe_ffn
from repro.models.moe_a2a import make_sharded_moe

cfg = get_smoke_config("olmoe-1b-7b").replace(moe_capacity_factor=8.0)
params = moe_init(jax.random.PRNGKey(0), cfg)
B, S = 2, 32
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)
                      ).astype(jnp.bfloat16)
y_ref, aux_ref = moe_ffn(params, x, cfg)

from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(1, 4)
fn = make_sharded_moe(cfg, mesh)
y_a2a, aux_a2a = jax.jit(fn)(params, x)
err = float(jnp.abs(y_a2a.astype(jnp.float32)
                    - y_ref.astype(jnp.float32)).max())
scale = float(jnp.abs(y_ref.astype(jnp.float32)).max())
assert err / (scale + 1e-6) < 0.05, (err, scale)
assert abs(float(aux_a2a) - float(aux_ref)) < 0.05
print("a2a matches dense:", err, scale)
""")
