"""Padded share of the chunk launches' query tokens over the traced part
of the window: (padded - true) / padded from the engine's LaunchRecords,
which count bucket rows and the chunk's zero tail (%)."""


def read(rec):
    k0, k1 = rec.loop.trace_k
    recs = [r for r in rec.loop.launches
            if r.kind == "chunk_batch" and k0 <= r.tick < k1]
    padded = sum(r.padded_tokens for r in recs)
    if not padded:
        return None
    return 100.0 * (padded - sum(r.true_tokens for r in recs)) / padded
