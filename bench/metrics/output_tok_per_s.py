"""Output tokens the host saw in the window over the window's seconds."""
from bench.driver import window_tokens


def read(rec):
    loop = rec.loop
    return window_tokens(loop) / (loop.w1 - loop.w0)
