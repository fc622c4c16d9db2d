"""Context attended per generated token over the traced part of the window
(tokens): the growth of the engine's serve_decode_ctx_tokens_total (each
token-emitting launch adds its live rows' context lengths) over the growth
of serve_gen_tokens_total.  The counter's growth is read from the
launches' records (LaunchRecord.ctx_tokens), the traced ones picked by the
decode-launch counter.  Reads nothing where the program does not count
attended context."""

try:
    from repro.serve.telemetry import LAUNCH_CAPACITY
except ImportError:          # a program that keeps no such records
    LAUNCH_CAPACITY = None


def read(rec):
    c0, c1 = rec.loop.trace_c
    # once the engine has dropped its oldest records, the n-th
    # token-emitting launch is no longer the n-th record kept
    if c0 is None or c1 is None or LAUNCH_CAPACITY is None \
            or len(rec.loop.launches) >= LAUNCH_CAPACITY:
        return None
    launches = [r for r in rec.loop.launches
                if r.kind in ("decode", "spec_verify")]
    if not launches or len(launches) < c1.decode_launches \
            or not hasattr(launches[0], "ctx_tokens"):
        return None
    ctx = sum(r.ctx_tokens
              for r in launches[c0.decode_launches:c1.decode_launches])
    gen = c1.gen_tokens - c0.gen_tokens
    return ctx / gen if gen else None
