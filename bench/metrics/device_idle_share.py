"""Share of the traced window in which no operation ran on the device,
averaged over the chips used (%)."""
from bench.trace_reduce import busy_ns


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    busy = sum(busy_ns(o, tr.window) for o in tr.ops) / len(tr.ops)
    return 100.0 * (1.0 - busy / (tr.window[1] - tr.window[0]))
