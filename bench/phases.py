#!/usr/bin/env python3
"""The engine's own phases in a profiler trace, and the device's idle time
split by them.

The serving engine names its phases through `Telemetry.phase`
(src/repro/serve/telemetry.py): `engine.tick` around each tick, and nested
in it `engine.admit`, `engine.table_upload`, `engine.plan`,
`engine.chunk_launch`, `engine.decode_launch`, `engine.device_get`,
`engine.emit` (and the rarer launch phases).  Each is a TraceAnnotation,
so a profiled run holds them on the host thread, on the clock of the
device's ops.  A phase event is `(name, start_ns, dur_ns, args)`.

  read_program    the `engine.*` events of a `.xplane.pb`
  innermost       the phases flattened into disjoint pieces, each named by
                  the innermost phase open in it
  idle_by_phase   the device's idle ns in a window by innermost phase
  tick_idle_ns    idle ns inside `engine.tick` spans, per tick
  tick_host_ns    `engine.tick` less its `engine.device_get`, per tick

Run as a script, it runs one cell with the profiler on the window's first
seconds, as the benchmark's traced run does, keeps what it needs and
prints the split as one JSON line, also kept as `<dir>/summary.json`:

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        --out <dir>

`<dir>/excerpt.json` is a 20 ms slice around a tick boundary, in the form
of bench/tests/trace_excerpt_engine.json; the trace itself is removed.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    import sys
    from pathlib import Path
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.trace_reduce import Event, busy_ns, gaps  # noqa: E402

Phase = Tuple[str, int, int, dict]    # name, start ns, duration ns, args
PREFIX = "engine."
TICK = "engine.tick"
FETCH = "engine.device_get"
OUTSIDE = "other"                     # idle with no engine phase open


def read_program(path) -> List[Phase]:
    """The `engine.*` events of the host planes of a `.xplane.pb`, in
    start order."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, int(e.start_ns),
                                int(e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def innermost(program: Sequence[Phase]) -> List[Tuple[str, int, int]]:
    """Disjoint `(name, start, end)` pieces covering every phase, each
    named by the innermost phase open in it (phases nest on one thread;
    a child is clipped to its parent)."""
    pieces: List[Tuple[str, int, int]] = []
    stack: List[Tuple[str, int]] = []          # open phases: name, end
    cur = 0
    for name, t, d, *_ in sorted(program, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= t:
            n, e = stack.pop()
            pieces.append((n, cur, e))
            cur = e
        if stack:
            pieces.append((stack[-1][0], cur, t))
        stack.append((name, min(t + d, stack[-1][1]) if stack else t + d))
        cur = t
    while stack:
        n, e = stack.pop()
        pieces.append((n, cur, e))
        cur = e
    return [p for p in pieces if p[2] > p[1]]


def idle_by_phase(ops: Sequence[Event], program: Sequence[Phase],
                  window: Tuple[int, int]) -> Dict[str, int]:
    """Idle ns of one chip in the window, by the innermost engine phase
    the host was in; OUTSIDE where none was open."""
    pieces = innermost(program)
    out: Dict[str, int] = {}
    j = 0
    for g0, g1 in gaps(ops, window):
        while j < len(pieces) and pieces[j][2] <= g0:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][1] < g1:
            ov = min(g1, pieces[k][2]) - max(g0, pieces[k][1])
            if ov > 0:
                out[pieces[k][0]] = out.get(pieces[k][0], 0) + ov
                covered += ov
            k += 1
        if g1 - g0 > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0) + g1 - g0 - covered
    return out


def ticks_in(program: Sequence[Phase],
             window: Tuple[int, int]) -> List[Phase]:
    """The `engine.tick` events that start inside the window."""
    return [e for e in program
            if e[0] == TICK and window[0] <= e[1] < window[1]]


def tick_idle_ns(ops: Sequence[Event], program: Sequence[Phase],
                 window: Tuple[int, int]) -> Optional[float]:
    """Device idle ns inside `engine.tick` spans in the window, over the
    ticks that start in it: the host time per tick the chip waits for."""
    n = len(ticks_in(program, window))
    if not n:
        return None
    idle = idle_by_phase(ops, program, window)
    return sum(ns for name, ns in idle.items() if name != OUTSIDE) / n


def tick_host_ns(program: Sequence[Phase],
                 window: Tuple[int, int]) -> Optional[float]:
    """Mean over the ticks that start in the window of the tick's length
    less its blocking fetch: the host's own time per tick."""
    ticks = ticks_in(program, window)
    if not ticks:
        return None
    fetches = [e for e in program if e[0] == FETCH]
    own = [d - sum(f[2] for f in fetches if t <= f[1] < t + d)
           for _, t, d, _ in ticks]
    return sum(own) / len(own)


def summary(trace, program: Sequence[Phase]) -> dict:
    """Every number the split gives for one traced window (first chip)."""
    w = trace.window
    ops = trace.ops[0]
    ticks = ticks_in(program, w)
    idle = idle_by_phase(ops, program, w)
    window_idle = (w[1] - w[0]) - busy_ns(ops, w)
    in_ticks = sum(ns for name, ns in idle.items() if name != OUTSIDE)
    own: Dict[str, int] = {}
    for name, s, e in innermost([p for p in program
                                 if w[0] <= p[1] < w[1]]):
        own[name] = own.get(name, 0) + e - s
    n = len(ticks)
    host = tick_host_ns(program, w)
    each = []           # each tick's idle ns, in all and in its fetch
    for _, t, d, _ in ticks:
        span = (t, min(t + d, w[1]))
        own_idle = idle_by_phase(ops, [p for p in program
                                       if t <= p[1] < t + d], span)
        each.append((sum(own_idle.values()) - own_idle.get(OUTSIDE, 0),
                     own_idle.get(FETCH, 0)))
    return {
        "window_s": (w[1] - w[0]) / 1e9,
        "ticks": n,
        "device_idle_share": 100.0 * window_idle / (w[1] - w[0]),
        "window_idle_ms": window_idle / 1e6,
        "idle_in_ticks_share": 100.0 * in_ticks / window_idle
        if window_idle else None,
        "tick_host_ms": host / 1e6 if host is not None else None,
        "tick_device_idle_ms": in_ticks / n / 1e6 if n else None,
        "idle_ms_by_phase": {k: v / 1e6 for k, v in
                             sorted(idle.items(), key=lambda kv: -kv[1])},
        "own_ms_per_tick": {k: v / 1e6 / n for k, v in
                            sorted(own.items(), key=lambda kv: -kv[1])}
        if n else {},
        "idle_ms_each_tick": [a / 1e6 for a, _ in each],
        "fetch_idle_ms_each_tick": [f / 1e6 for _, f in each],
    }


def excerpt(trace, program: Sequence[Phase], source: str,
            half_ns: int = 10_000_000) -> dict:
    """A slice of 2 * half_ns around the end of the first tick whose
    successor launches prefill chunks (else of the first tick): the ops,
    modules, harness spans and whole ticks (with their phases) that
    overlap it, and what the reductions read there."""
    ticks = ticks_in(program, trace.window)
    chunked = [a for a, b in zip(ticks, ticks[1:])
               if any(p[0] == "engine.chunk_launch"
                      and b[1] <= p[1] < b[1] + b[2] for p in program)]
    t = (chunked or ticks)[0]
    end = t[1] + t[2]
    lo, hi = end - half_ns, end + half_ns

    def over(e):
        return e[1] < hi and e[1] + e[2] > lo

    spans = [k for k in ticks if over(k)]
    prog = [p for p in program
            if any(k[1] <= p[1] < k[1] + k[2] for k in spans)]
    host = [list(e) for e in trace.host if over(e)
            and e[0] != "traced_window"]
    window = (lo, hi)
    ops = [list(e) for e in trace.ops[0] if over(e)]
    ex = {"source": source,
          "ops": [ops],
          "modules": [[list(e) for e in trace.modules[0] if over(e)]],
          "host": host + [["traced_window", lo, hi - lo]],
          "program": [list(p) for p in prog]}
    ops_t = [tuple(e) for e in ops]
    ex["expect"] = {
        "busy_ns": busy_ns(ops_t, window),
        "idle_by_phase": idle_by_phase(ops_t, prog, window),
        "tick_idle_ns": tick_idle_ns(ops_t, prog, window),
        "tick_host_ns": tick_host_ns(prog, window),
    }
    return ex


def main(argv) -> int:
    import argparse
    import json
    import shutil
    import tempfile
    import time
    from pathlib import Path

    import jax

    from bench import driver, harness, trace_reduce
    from bench.loadgen import schedule
    from bench.spec import load_cell, reference_module

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="idle time by engine phase")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    args.out.mkdir(parents=True, exist_ok=True)
    cell = load_cell(args.workload)
    harness.enable_compile_cache()
    device = harness.check_device(cell.chips)
    cfg, mix = cell.cfg, cell.mix
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    model = build_model(harness.model_config(cfg))
    params = reference_module(cfg).make_params(cfg, harness.jax_key(args.seed))
    jax.block_until_ready(params)
    scfg, clients = harness.serve_config(cfg, mix)
    eng = ServeEngine(model, params, scfg)
    harness.warm_shapes(eng, scfg, cfg["vocab_size"], args.seed)
    warm_s = float(mix["warm_s"])
    items = schedule(mix, args.seed, vocab=cfg["vocab_size"],
                     context=cfg["max_position_embeddings"],
                     stretches=[warm_s, warm_s + args.seconds,
                                warm_s + args.seconds + driver.GRACE_S
                                + 30.0],
                     clients=clients)
    tdir = Path(tempfile.mkdtemp(prefix="bench-phases-"))
    try:
        loop = driver.drive(eng, items, mix, seconds=args.seconds,
                            warm_s=warm_s, chunk=scfg.prefill_chunk,
                            trace=harness._trace_ctl(tdir))
        path = sorted(tdir.rglob("*.xplane.pb"))[-1]
        tr = trace_reduce.read_xspace(path, cell.chips)
        program = read_program(path)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    res = summary(tr, program)
    k0, k1 = loop.trace_k
    res.update(workload=cell.name, seed=args.seed, device=device,
               harness_ticks=k1 - k0, setup_s=loop.w0 - t_start,
               window_tokens=driver.window_tokens(loop))
    src = (f"{device['kind']}, {cell.name}, seed {args.seed}, a 20 ms "
           f"slice around a tick boundary of a traced run, with the "
           f"engine's phases")
    (args.out / "excerpt.json").write_text(
        json.dumps(excerpt(tr, program, src)))
    (args.out / "summary.json").write_text(json.dumps(res, indent=1))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
