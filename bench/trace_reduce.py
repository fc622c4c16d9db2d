"""Reduction from a profiler trace to device busy time, idle gaps and
per-name device time.

A trace is read once into plain tuples, `(name, start_ns, dur_ns)`:

  ops      events of the "XLA Ops" line of each device plane used
           ("/device:TPU:<i>"), one list per chip
  modules  events of the "XLA Modules" line (one per program execution)
  host     the harness's own TraceAnnotation spans on the host planes
           (tick, submit, wait, stamp, traced_window)

Everything below works on those tuples, so the same code reduces a chip
trace and the small recorded excerpt the tests keep.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start ns, duration ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "traced_window"
HOST_SPANS = ("tick", "submit", "wait", "stamp", WINDOW_SPAN)


@dataclass
class Trace:
    ops: List[List[Event]]             # per chip
    modules: List[List[Event]]         # per chip
    host: List[Event]
    window: Tuple[int, int] = (0, 0)   # ns, from the traced_window span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def _window(host: Sequence[Event]) -> Tuple[int, int]:
    spans = [e for e in host if e[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(spans)}")
    _, t0, d = spans[0]
    return t0, t0 + d


def from_events(ops, modules, host) -> Trace:
    """A Trace from lists already extracted (the recorded test excerpt)."""
    ops = [[tuple(e) for e in chip] for chip in ops]
    modules = [[tuple(e) for e in chip] for chip in modules]
    host = [tuple(e) for e in host]
    return Trace(ops, modules, host, _window(host))


def op_name(name: str) -> str:
    """The HLO instruction's name of an "XLA Ops" event, which the trace
    names by the instruction's whole text ("%fusion.3 = bf16[...] ...")."""
    return name.split(" = ", 1)[0].lstrip("%")


def read_xspace(path: Path, chips: int) -> Trace:
    """Read a `.xplane.pb` written by jax.profiler; op events keep only
    their instruction's name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if dev >= chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = [(op_name(e.name), int(e.start_ns),
                                 int(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] = [(e.name, int(e.start_ns),
                                     int(e.duration_ns))
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    if len(ops) != chips:
        raise ValueError(f"device op lines for {sorted(ops)} of {chips} "
                         f"chips in {path}")
    return Trace([ops[i] for i in range(chips)],
                 [modules.get(i, []) for i in range(chips)], host,
                 _window(host))


def merged(intervals: Iterable[Tuple[int, int]], lo: int,
           hi: int) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals clipped to [lo, hi), sorted and
    disjoint."""
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], window: Tuple[int, int]) -> int:
    """Time in the window during which some operation ran on the device."""
    return sum(e - s for s, e in merged(((t, t + d) for _, t, d in events),
                                        *window))


def gaps(events: Sequence[Event],
         window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The idle intervals of the window: no device operation running."""
    busy = merged(((t, t + d) for _, t, d in events), *window)
    out, cur = [], window[0]
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


def _overlap(a: Tuple[int, int], b: Tuple[int, int]) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute_gaps(idle: Sequence[Tuple[int, int]],
                   host: Sequence[Event]) -> List[Tuple[str, int]]:
    """Each idle gap named by the harness span it overlaps most (the
    window span itself aside); "other" where none overlaps it."""
    spans = [(n, (t, t + d)) for n, t, d in host if n != WINDOW_SPAN]
    spans.sort(key=lambda s: s[1][0])
    out = []
    j = 0
    for g in idle:
        while j < len(spans) and spans[j][1][1] <= g[0]:
            j += 1
        best, best_ov = "other", 0
        k = j
        while k < len(spans) and spans[k][1][0] < g[1]:
            ov = _overlap(g, spans[k][1])
            if ov > best_ov:
                best, best_ov = spans[k][0], ov
            k += 1
        out.append((best, g[1] - g[0]))
    return out


def idle_by_span(trace: Trace, chip: int = 0) -> Dict[str, int]:
    """Idle nanoseconds of one chip, summed by what the host was doing."""
    totals: Dict[str, int] = {}
    for name, ns in attribute_gaps(gaps(trace.ops[chip], trace.window),
                                   trace.host):
        totals[name] = totals.get(name, 0) + ns
    return totals


def in_window(events: Sequence[Event],
              window: Tuple[int, int]) -> List[Event]:
    """Events that start inside the window."""
    return [e for e in events if window[0] <= e[1] < window[1]]


def time_of(events: Sequence[Event], pattern: str,
            window: Optional[Tuple[int, int]] = None) -> Tuple[int, int]:
    """(count, total ns) of the events whose name matches `pattern`."""
    rx = re.compile(pattern)
    evs = events if window is None else in_window(events, window)
    hits = [d for n, _, d in evs if rx.search(n)]
    return len(hits), sum(hits)


_SUFFIX = re.compile(r"(\.\d+)+$")


def leaves(events: Sequence[Event]) -> List[Event]:
    """The ops that contain no other op: a loop or call op spans the ops
    of its body on the same line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, t, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= t + d:
            out.append((name, t, d))
    return out


def top_ops(events: Sequence[Event], window: Tuple[int, int],
            n: int = 10) -> List[Tuple[str, float]]:
    """The n leaf op names (numeric suffixes folded) that took most device
    time in the window, in seconds."""
    totals: Dict[str, int] = {}
    for name, _, d in leaves(in_window(events, window)):
        key = _SUFFIX.sub("", name)
        totals[key] = totals.get(key, 0) + d
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in top]
