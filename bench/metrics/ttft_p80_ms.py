"""80th percentile, over every request due in the window, of the time
from when the request was due to the host seeing its first token (ms).
The 80th: the highest percentile with about ten samples beyond it in a
51 s window of the chat cell (~49 requests due); its 95th percentile
rests on two or three requests and spreads by ~20% from seed to seed."""
import numpy as np

from bench.driver import ttfts


def read(rec):
    t = ttfts(rec.loop)
    return float(np.percentile(t, 80)) * 1e3 if len(t) else None
