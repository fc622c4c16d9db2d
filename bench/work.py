"""Operations and bytes the algorithm needs, computed from shapes and live
lengths: per kernel call, and per model token.

Only live context counts.  A decode row attending to `ctx` positions reads
their K and V once; a chunk row of queries at positions [start, end) reads
the K and V of positions [0, end) once and does causal work over
sum_{p=start}^{end-1} (p + 1) query-key pairs.  Pages the kernel walks or
fetches past the live length are not work, so the counts do not change
when a kernel stops reading dead pages.  One query-key pair costs
4 * head_dim operations per query head (QK^T and PV, a multiply-add each).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def causal_pairs(start: int, end: int) -> int:
    """Query-key pairs of queries at positions [start, end) under a causal
    mask: each query at p sees p + 1 keys."""
    n = end - start
    return n * start + n * (n + 1) // 2


def paged_decode_call(ctx_lens: Iterable[int], hq: int, hkv: int, hd: int,
                      itemsize: int = BF16) -> Tuple[int, int]:
    """(operations, bytes) of one paged decode kernel call (one layer):
    one query per row over its live context."""
    ctx = list(ctx_lens)
    total = sum(ctx)
    flops = 4 * hq * hd * total
    nbytes = 2 * hkv * hd * total * itemsize + 2 * len(ctx) * hq * hd * itemsize
    return flops, nbytes


def paged_prefill_call(rows: Iterable[Tuple[int, int]], hq: int, hkv: int,
                       hd: int, itemsize: int = BF16) -> Tuple[int, int]:
    """(operations, bytes) of one batched chunk-prefill kernel call (one
    layer): each row is the (start, end) of its real queries."""
    flops = nbytes = 0
    for start, end in rows:
        n = end - start
        flops += 4 * hq * hd * causal_pairs(start, end)
        nbytes += 2 * hkv * hd * end * itemsize + 2 * n * hq * hd * itemsize
    return flops, nbytes


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


class ModelFlops:
    """Model operations per token: 2 per weight of the blocks it passes
    through, 2 per unembedding weight where the token's logits are needed,
    and the attention over its context, 4 * L * Hq * head_dim per pair.
    `counts` comes from the configuration's reference module."""

    def __init__(self, counts: dict):
        self.L, self.hq, self.hd = counts["L"], counts["hq"], counts["hd"]
        self.blocks, self.unembed = counts["blocks"], counts["unembed"]

    def prompt(self, start: int, end: int, logits: bool) -> int:
        """Prompt positions [start, end) of one sequence; `logits` when the
        last of them yields the first output token."""
        return (2 * self.blocks * (end - start)
                + 4 * self.L * self.hq * self.hd * causal_pairs(start, end)
                + (2 * self.unembed if logits else 0))

    def decode(self, ctx: int) -> int:
        """One generated token that attends to `ctx` positions."""
        return (2 * (self.blocks + self.unembed)
                + 4 * self.L * self.hq * self.hd * ctx)
