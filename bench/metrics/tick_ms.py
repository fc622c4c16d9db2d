"""Host time per engine tick over the traced part of the window (ms): its
length on the trace's clock over the ticks in it."""


def read(rec):
    tr = rec.trace
    k0, k1 = rec.loop.trace_k
    if tr is None or k1 <= k0:
        return None
    return tr.window_s / (k1 - k0) * 1e3
