"""Share of its roofline the paged decode kernel reached over the traced
part of the window (%): the sum over its calls of the least time each call
could take (the larger of its operations over peak FLOP/s and its bytes
over HBM bandwidth, from the live context lengths, bench/work.py) over the
sum of the kernel's device time.  One call per layer per decode launch."""
import sys

from bench.trace_reduce import time_of
from bench.work import min_seconds, paged_decode_call

KERNEL = r"paged_flash_decode"


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    k0, k1 = rec.loop.trace_k
    calls = [t.decode_ctx for t in rec.loop.ticks[k0:k1] if t.decode_ctx]
    c = rec.counts
    n, ns = time_of(tr.ops[0], KERNEL, tr.window)
    if not calls or not n:
        return None
    if n != len(calls) * c["L"]:
        print(f"paged_decode_roofline: {n} kernel events for "
              f"{len(calls)} launches x {c['L']} layers", file=sys.stderr)
        return None
    need = c["L"] * sum(min_seconds(*paged_decode_call(
        ctx, c["hq"], c["hkv"], c["hd"]), rec.peak) for ctx in calls)
    return 100.0 * need / (ns / 1e9)
