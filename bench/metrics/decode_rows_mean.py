"""Live rows per decode launch over the traced part of the window: tokens
generated over token-emitting launches (the engine's registry counters)."""


def read(rec):
    c0, c1 = rec.loop.trace_c
    if c0 is None or c1 is None:
        return None
    launches = c1.decode_launches - c0.decode_launches
    return (c1.gen_tokens - c0.gen_tokens) / launches if launches else None
