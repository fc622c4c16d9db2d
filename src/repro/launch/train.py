"""Training launcher.

Smoke scale runs anywhere:
    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke

On a real TPU fleet, drop --smoke: the full config is built, the production
mesh is constructed from the actual devices, and state/batch shardings come
from the same rules the dry-run validates.
"""
import argparse

import jax

from ..configs import get_config, get_smoke_config
from ..configs.base import TrainConfig
from .mesh import make_debug_mesh, make_production_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args(argv)

    from ..train.trainer import Trainer

    if args.smoke:
        cfg = get_smoke_config(args.arch)
        tcfg = TrainConfig(global_batch=args.global_batch or 8,
                           seq_len=args.seq or 64, total_steps=args.steps,
                           warmup_steps=5, checkpoint_dir=args.ckpt_dir,
                           grad_compression="int8" if args.compress_grads
                           else "")
        tr = Trainer(cfg, tcfg)
    else:
        cfg = get_config(args.arch)
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        tcfg = TrainConfig(global_batch=args.global_batch or 256,
                           seq_len=args.seq or 4096, total_steps=args.steps,
                           remat="full", checkpoint_dir=args.ckpt_dir,
                           grad_compression="int8" if args.compress_grads
                           else "")
        from ..launch.specs import train_cell
        from ..configs.base import ShapeSpec
        shape = ShapeSpec("train", tcfg.seq_len, tcfg.global_batch, "train")
        with jax.set_mesh(mesh):
            _, _, shardings = train_cell(cfg, shape, mesh, tcfg)
            tr = Trainer(cfg, tcfg, mesh=mesh, state_shardings=shardings[0])
    out = tr.run()
    print(f"finished at step {out['final_step']}; "
          f"last loss {out['metrics'][-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
