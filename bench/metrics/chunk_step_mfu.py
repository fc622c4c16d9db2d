"""Model FLOP/s utilization of the batched chunk-prefill program (%): the
model operations of the prompt positions its calls prefilled in the traced
part of the window, over its device time times the chip's peak bf16
FLOP/s.  It bounds what the chunk kernel's roofline share can add."""
from bench.trace_reduce import time_of

PROGRAM = r"chunk_batch_step"


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    k0, k1 = rec.loop.trace_k
    f = rec.flops
    total = sum(f.prompt(s, e, final)
                for t in rec.loop.ticks[k0:k1] for s, e, final in t.chunks)
    n, ns = time_of(tr.modules[0], PROGRAM, tr.window)
    if not n or not total:
        return None
    return 100.0 * total / (ns / 1e9 * rec.peak["bf16_flops_per_s"])
