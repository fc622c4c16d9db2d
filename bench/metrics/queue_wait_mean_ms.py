"""Mean, over every request due in the window, of its wait in the engine's
queue: from `submit()` to its first slot and page reservation, the
engine's own stamps `Request.t_submit` and `Request.t_admit` (ms).  The
mean, not a percentile: admission usually succeeds in the next tick, and
a rare block must still show.  Reads nothing where the program does not
stamp admissions."""
import numpy as np


def read(rec):
    loop = rec.loop
    waits = [q.r.t_admit - q.r.t_submit for q in loop.reqs
             if loop.w0 <= q.due < loop.w1
             and getattr(q.r, "t_admit", None) is not None]
    return float(np.mean(waits)) * 1e3 if waits else None
