"""Serving launcher (continuous batching, dense or paged KV cache).

Serves --requests random 6-token prompts through ServeEngine with random
weights and prints how many tokens came back.  The model runs at the
architecture's published widths unless --smoke picks its 2-layer smoke
configuration:

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --paged --page-size 128 --max-seq 2048 --prefill-chunk 512

--paged serves through the paged KV cache (serve/paged_cache.py): a global
page pool + block table instead of one dense (max_batch, max_seq) strip per
slot, under the token-budget scheduler - prefill chunks of --prefill-chunk
tokens share each tick with decode.  --num-pages 0 sizes the pool to
dense-equivalent capacity; smaller pools trade admission backpressure for
KV memory.  JAX's persistent compilation cache is on
(repro.launch.compile_cache).
"""
import argparse

import jax
import numpy as np

from ..configs import get_config, get_smoke_config
from ..configs.base import ServeConfig
from ..models import build_model
from ..serve.engine import ServeEngine
from .compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="the 2-layer smoke configuration instead of the "
                         "published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV cache, with chunked "
                         "prefill under the token-budget scheduler")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page pool size (0 = dense-equivalent)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="tokens per prefill chunk with --paged (a "
                         "multiple of --page-size)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    scfg = ServeConfig(max_batch=args.max_batch, max_seq=args.max_seq,
                       max_new_tokens=16, paged=args.paged,
                       page_size=args.page_size, num_pages=args.num_pages,
                       prefill_chunk=args.prefill_chunk)
    eng = ServeEngine(model, params, scfg)
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        eng.submit(rng.integers(1, cfg.vocab_size, size=6).tolist())
    done = eng.run_until_done()
    mode = f"paged (page={scfg.page_size}, pool={scfg.pool_pages()}, " \
        f"{scfg.prefill_chunk}-token chunks)" if args.paged else "dense"
    print(f"served {len(done)} requests, "
          f"{sum(len(r.out_tokens) for r in done)} tokens "
          f"[{mode} KV cache, {eng.kv_cache_bytes() / 1e6:.2f} MB]")


if __name__ == "__main__":
    main()
