"""Share of its roofline the batched chunk-prefill kernel reached over
the traced part of the window (%): the least time of each call from its
true (unpadded) query rows and their live context (bench/work.py), summed,
over the sum of the kernel's device time.  One call per layer per chunk
launch."""
import sys

from bench.trace_reduce import time_of
from bench.work import min_seconds, paged_prefill_call

KERNEL = r"batched_paged_prefill_attention"


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    k0, k1 = rec.loop.trace_k
    calls = [[(s, e) for s, e, _ in t.chunks]
             for t in rec.loop.ticks[k0:k1] if t.chunks]
    c = rec.counts
    n, ns = time_of(tr.ops[0], KERNEL, tr.window)
    if not calls or not n:
        return None
    if n != len(calls) * c["L"]:
        print(f"paged_prefill_roofline: {n} kernel events for "
              f"{len(calls)} launches x {c['L']} layers", file=sys.stderr)
        return None
    need = c["L"] * sum(min_seconds(*paged_prefill_call(
        rows, c["hq"], c["hkv"], c["hd"]), rec.peak) for rows in calls)
    return 100.0 * need / (ns / 1e9)
