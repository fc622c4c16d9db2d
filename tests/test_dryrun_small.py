"""Dry-run machinery at smoke scale on the host's real device(s)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec, TrainConfig
from repro.launch.dryrun import parse_collectives
from repro.launch.mesh import make_debug_mesh
from repro.launch.specs import prefill_cell, serve_cell, train_cell


def test_collective_parser():
    hlo = """
  %ag = bf16[16,128]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[4,4]{1,0} all-reduce(%y), to_apply=%sum
  %rs = f32[8]{0} reduce-scatter(%z), dimensions={0}
  %nothing = f32[2,2]{1,0} add(%a, %b)
"""
    c = parse_collectives(hlo)
    assert c["all-gather"]["count"] == 1
    assert c["all-gather"]["bytes"] == 16 * 128 * 2
    assert c["all-reduce"]["bytes"] == 64
    assert c["reduce-scatter"]["bytes"] == 32
    assert c["total_bytes"] == 16 * 128 * 2 + 64 + 32


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_cells_lower_on_debug_mesh(kind):
    cfg = get_smoke_config("granite-3-2b")
    mesh = make_debug_mesh(1, 1)
    shape = ShapeSpec("t", 32, 2, kind)
    with jax.set_mesh(mesh):
        if kind == "train":
            tcfg = TrainConfig(global_batch=2, seq_len=32, remat="full")
            step, args, shardings = train_cell(cfg, shape, mesh, tcfg)
        elif kind == "prefill":
            step, args, shardings = prefill_cell(cfg, shape, mesh)
        else:
            step, args, shardings = serve_cell(cfg, shape, mesh)
        compiled = jax.jit(step, in_shardings=shardings).lower(*args).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        assert float(cost.get("flops", 0)) > 0
