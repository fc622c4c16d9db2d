"""The timed loop: offer a schedule to the serving engine through
`ServeEngine.submit` / `ServeEngine.tick`, stamp every token when the host
sees it, and keep what the metric readers need.

Clock: time.perf_counter.  A tick ends with the engine's device_get of the
tick's tokens, so a stamp taken after `tick()` returns follows the device's
completion of that tick.  Every token of a tick gets that tick's stamp.

Window: the first tick boundary at or after `warm_s` seconds of load opens
it, the first boundary at least `seconds` later closes it.  Tokens stamped
in (w0, w1] are the window's.  Requests due in [w0, w1) are its attempted
requests; after the close the load goes on unchanged until each of them has
its first token and at least `min_finished` requests have finished (the
correctness sample is drawn from those), or for at most GRACE_S seconds.

Per tick the loop also notes, from the engine's public request objects,
the context of every decode row and the prompt positions every prefill
chunk covered, so the readers can count operations and bytes.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .loadgen import Item

GRACE_S = 60.0


@dataclass
class Req:
    item: Item
    due: float                     # absolute perf_counter seconds
    submit: float = 0.0
    r: object = None               # the engine's Request
    stamps: List[float] = field(default_factory=list)
    done_at: Optional[float] = None

    @property
    def first(self) -> Optional[float]:
        return self.stamps[0] if self.stamps else None


@dataclass
class Tick:
    t0: float
    t1: float
    decode_ctx: List[int]          # attended length of each decode row
    chunks: List[Tuple[int, int, bool]]   # (start, end, final) per row
    emitted: int


@dataclass
class Counters:
    gen_tokens: int
    decode_launches: int


@dataclass
class Loop:
    reqs: List[Req]
    ticks: List[Tick]
    t_base: float
    w0: float = 0.0
    w1: float = 0.0
    k0: int = 0                    # first tick of the window
    k1: int = 0                    # first tick after it
    launches: list = field(default_factory=list)   # LaunchRecords, whole run
    trace_k: Tuple[int, int] = (0, 0)              # ticks of the traced part
    trace_c: Tuple[Optional[Counters], Optional[Counters]] = (None, None)
    compiles_in_window: int = 0


def counters(eng) -> Counters:
    """The engine's registry counters the readers difference."""
    return Counters(int(eng.gen_tokens), int(eng.decode_launches))


def _split(start: int, end: int, chunk: int, n: int):
    """Prefill rows of one request in one tick: [start, end) cut at the
    chunk grid the scheduler plans on (chunks start at multiples of
    `chunk`)."""
    out = []
    s = start
    while s < end:
        e = min((s // chunk + 1) * chunk, end)
        out.append((s, e, e >= n))
        s = e
    return out


def _spans(on: bool):
    """TraceAnnotation when tracing, else a context that does nothing."""
    if on:
        import jax
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def drive(eng, items: List[Item], mix: dict, *, seconds: float,
          warm_s: float, chunk: int, min_finished: int = 0,
          grace_s: float = GRACE_S, trace: Optional[dict] = None,
          compile_count: Callable[[], int] = lambda: 0) -> Loop:
    """Run the schedule against `eng` and return what was seen.

    `trace`, when given, is {"start": fn(), "stop": fn(), "seconds": s}:
    the profiler runs from the window's first tick for `s` seconds."""
    span = _spans(trace is not None)
    t_base = time.perf_counter()
    open_loop = mix["loop"] == "open"
    if open_loop:
        pending = deque(Req(it, t_base + it.due) for it in items)
        streams: Dict[int, deque] = {}
    else:
        pending = deque()
        streams = {}
        for it in items:
            streams.setdefault(it.client, deque()).append(it)
    # closed loop: when each client is free to send; the first requests
    # are staggered so the clients do not move in step
    stagger = float(mix.get("stagger_s", 0.0))
    ready = {c: t_base + c * stagger for c in streams}
    loop = Loop([], [], t_base)
    live: Dict[int, Req] = {}
    n_ticks = 0
    w0 = w1 = None
    tracing = False
    window_ann = None
    comp0 = 0
    while True:
        now = time.perf_counter()
        if w0 is None and now >= t_base + warm_s:
            w0 = loop.w0 = now
            loop.k0 = n_ticks
            comp0 = compile_count()
            if trace is not None:
                trace["start"]()
                tracing = True
                window_ann = span("traced_window")
                window_ann.__enter__()
                loop.trace_k = (n_ticks, n_ticks)
                loop.trace_c = (counters(eng), None)
                now = time.perf_counter()
        if tracing and now >= w0 + trace["seconds"]:
            window_ann.__exit__(None, None, None)
            loop.trace_k = (loop.trace_k[0], n_ticks)
            loop.trace_c = (loop.trace_c[0], counters(eng))
            trace["stop"]()
            tracing = False
            now = time.perf_counter()
        if w0 is not None and w1 is None and now >= w0 + seconds:
            w1 = loop.w1 = now
            loop.k1 = n_ticks
            loop.compiles_in_window = compile_count() - comp0
        if w1 is not None and not tracing:
            due_in = [q for q in loop.reqs if w0 <= q.due < w1]
            n_done = sum(q.done_at is not None for q in loop.reqs)
            if (all(q.first is not None for q in due_in)
                    and n_done >= min_finished) or now > w1 + grace_s:
                break
        with span("submit"):
            if open_loop:
                while pending and pending[0].due <= now:
                    q = pending.popleft()
                    _submit(eng, q, now, live, loop)
            else:
                for c, t_free in list(ready.items()):
                    if t_free <= now and streams[c]:
                        q = Req(streams[c].popleft(), t_free)
                        q.item.client = c
                        _submit(eng, q, now, live, loop)
                        del ready[c]
        if not live:
            nxt = pending[0].due if pending else min(
                (t for c, t in ready.items() if streams[c]), default=None)
            if nxt is None:
                break                   # nothing left to serve
            with span("wait"):
                time.sleep(max(0.0, nxt - time.perf_counter()))
            continue
        decode_ctx = []
        cursors = {}
        for uid, q in live.items():
            r = q.r
            if r.state.value == "decoding":
                decode_ctx.append(len(r.prompt) + len(r.out_tokens))
            elif r.state.value in ("queued", "prefilling"):
                cursors[uid] = r.prefill_pos if r.slot is not None else 0
        n_out = {uid: len(q.r.out_tokens) for uid, q in live.items()}
        t0 = time.perf_counter()
        with span("tick"):
            done = eng.tick()
        t1 = time.perf_counter()
        with span("stamp"):
            emitted = 0
            chunks = []
            for uid, q in live.items():
                r = q.r
                new = len(r.out_tokens) - n_out[uid]
                if new:
                    q.stamps.extend([t1] * new)
                    emitted += new
                if uid in cursors:
                    n = len(r.prompt)
                    state = r.state.value
                    end = r.prefill_pos if state == "prefilling" \
                        else cursors[uid] if state == "queued" else n
                    chunks.extend(_split(cursors[uid], end, chunk, n))
            for r in done:
                q = live.pop(r.uid)
                q.done_at = t1
                if not open_loop:
                    ready[q.item.client] = t1
            loop.ticks.append(Tick(t0, t1, decode_ctx, chunks, emitted))
            n_ticks += 1
    if tracing:
        window_ann.__exit__(None, None, None)
        loop.trace_k = (loop.trace_k[0], n_ticks)
        loop.trace_c = (loop.trace_c[0], counters(eng))
        trace["stop"]()
    if w1 is None:
        raise RuntimeError("the load ended before the window closed")
    loop.launches = eng.launch_records()
    return loop


def _submit(eng, q: Req, now: float, live: Dict[int, Req], loop: Loop):
    uid = eng.submit(q.item.prompt.tolist(), max_new_tokens=q.item.max_new)
    q.r = eng.queue[-1]
    if q.r.uid != uid:
        raise RuntimeError("submitted request is not at the queue's tail")
    q.submit = now
    live[uid] = q
    loop.reqs.append(q)


def window_tokens(loop: Loop) -> int:
    """Output tokens the host saw in (w0, w1]."""
    return sum(t.emitted for t in loop.ticks[loop.k0:loop.k1])


def tbt_gaps(loop: Loop) -> np.ndarray:
    """Gaps between consecutive tokens of one request, for every token
    stamped in (w0, w1] that has a predecessor."""
    out = []
    for q in loop.reqs:
        s = q.stamps
        for a, b in zip(s, s[1:]):
            if loop.w0 < b <= loop.w1:
                out.append(b - a)
    return np.asarray(out, np.float64)


def ttfts(loop: Loop) -> np.ndarray:
    """Due time to first token of every request due in [w0, w1); a request
    that never got one reads +inf."""
    return np.asarray([(q.first - q.due) if q.first is not None
                       else np.inf
                       for q in loop.reqs if loop.w0 <= q.due < loop.w1],
                      np.float64)
