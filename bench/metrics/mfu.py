"""Model FLOP/s utilization of the whole serving loop over the traced part
of the window (%): the model operations of every prompt position
prefilled and every token generated in it (bench/work.py ModelFlops) over
the traced window's seconds times the chip's peak bf16 FLOP/s."""


def read(rec):
    tr = rec.trace
    k0, k1 = rec.loop.trace_k
    if tr is None or k1 <= k0:
        return None
    f = rec.flops
    total = 0
    for t in rec.loop.ticks[k0:k1]:
        total += sum(f.decode(ctx) for ctx in t.decode_ctx)
        total += sum(f.prompt(s, e, final) for s, e, final in t.chunks)
    peak = rec.peak["bf16_flops_per_s"] * len(tr.ops)
    return 100.0 * total / (tr.window_s * peak)
