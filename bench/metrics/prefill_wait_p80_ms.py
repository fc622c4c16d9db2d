"""80th percentile, over every request due in the window, of the time from
its first slot and page reservation (`Request.t_admit`) to its first token
(`Request.token_wall[0]`), both the engine's own stamps (ms): the prefill
chunks streamed over the ticks after admission.  The percentile of
`ttft_p80_ms`, whose other parts are the generator's lateness and
`queue_wait_mean_ms`.  Reads nothing where the program does not stamp
admissions."""
import numpy as np


def read(rec):
    loop = rec.loop
    waits = [q.r.token_wall[0] - q.r.t_admit for q in loop.reqs
             if loop.w0 <= q.due < loop.w1
             and getattr(q.r, "t_admit", None) is not None
             and q.r.token_wall]
    return float(np.percentile(waits, 80)) * 1e3 if waits else None
