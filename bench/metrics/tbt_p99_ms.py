"""99th percentile of the gaps between consecutive output tokens of one
request, over every token stamped in the window (ms).  The 99th and not
the 95th: in the long-decode cells about 5% of ticks carry prompt chunks,
so a 95th percentile would sit on the edge between decode-only and chunk
ticks and jump between the two."""
import numpy as np

from bench.driver import tbt_gaps


def read(rec):
    g = tbt_gaps(rec.loop)
    return float(np.percentile(g, 99)) * 1e3 if len(g) else None
