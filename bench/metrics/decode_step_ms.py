"""Device time per call of the fused decode program (ms), from the
trace's program executions."""
from bench.trace_reduce import time_of

PROGRAM = r"fused_decode_step"


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    n, ns = time_of(tr.modules[0], PROGRAM, tr.window)
    return ns / n / 1e6 if n else None
