"""The one traffic generator: a mix's data file plus a seed -> a schedule.

A mix is a JSON file under bench/traffic/.  Its trace, every request's
prompt and output length and, in an open loop, its due time, is drawn
i.i.d. from the mix's distributions with the mix's own `draw_seed`, and
every run replays it: the run's seed draws only the prompts' token ids
(the harness draws the weights from it too).  So every seed offers the
same work at the same times, and two runs differ only in what the tokens
are.  The trace is drawn request by request from one stream per quantity,
so a longer horizon extends it and leaves its start as it was.

    loop "open":   requests arrive at due times, whether or not earlier
                   ones are done: Poisson, exponential gaps at
                   arrivals.rate_per_s.  Another rate scales the same gaps.
    loop "closed": `clients` callers; each sends its next request as soon as
                   its previous one finishes.  "fill_pool" means as many
                   clients as the page pool holds at a full-context
                   reservation.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class Item:
    """One request of a schedule: `due` seconds after the start of the
    load (open loop) or the client that sends it (closed loop)."""
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due: Optional[float] = None
    client: Optional[int] = None


def seed_rng(seed: int) -> np.random.Generator:
    """numpy generator for any whole-number seed, 64-bit ones included."""
    return np.random.default_rng(np.random.SeedSequence(seed % 2 ** 64))


def n_clients(mix: dict, num_pages: int, page_size: int,
              context: int) -> int:
    c = mix["clients"]
    if c == "fill_pool":
        return (num_pages - 1) // math.ceil(context / page_size)
    return int(c)


def _length(spec: dict, g: np.random.Generator, context: int) -> int:
    """One length drawn from a mix's distribution, clipped to its range."""
    kind = spec["kind"]
    if kind == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * g.standard_normal())
        lo, hi = spec["min"], spec["max"]
    elif kind == "uniform_of_context":
        lo, hi = spec["min"] * context, spec["max"] * context
        x = lo + g.random() * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(np.clip(np.rint(x), lo, hi))


def schedule(mix: dict, seed: int, *, vocab: int, context: int,
             stretches: Sequence[float], clients: int = 0) -> List[Item]:
    """The requests of one run, in the order they are sent.

    `stretches` are the increasing ends, in seconds from the start of the
    load, of its warm load, its window and what follows; the last, the
    horizon, is all the generator uses.
    Open loop: requests due from 0 until the first one due at or after the
    horizon.  Closed loop: `clients` streams, each long enough for the
    horizon even if every request took a single second; item i belongs to
    client i % clients.  Prompt + output never exceeds the context."""
    horizon_s = float(stretches[-1])
    open_loop = mix["loop"] == "open"
    if open_loop:
        rate = float(mix["arrivals"]["rate_per_s"])
    elif mix["loop"] == "closed":
        if clients < 1:
            raise ValueError("a closed loop needs at least one client")
        n_closed = clients * max(1, math.ceil(horizon_s))
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    g_gap, g_prompt, g_out = (
        np.random.default_rng(s) for s in
        np.random.SeedSequence(int(mix["draw_seed"])).spawn(3))
    tokens = seed_rng(seed)
    items: List[Item] = []
    due = 0.0
    while True:
        if open_loop:
            if items:
                due += g_gap.standard_exponential() / rate
        elif len(items) == n_closed:
            break
        out = _length(mix["output"], g_out, context)
        p = min(_length(mix["prompt"], g_prompt, context), context - out)
        toks = tokens.integers(1, vocab, size=p, dtype=np.int32)
        items.append(Item(toks, out, due=due if open_loop else None,
                          client=None if open_loop
                          else len(items) % clients))
        if open_loop and due >= horizon_s:
            break
    return items
