"""The readers of what the engine records itself: its phases in a profiler
trace (bench/phases.py) and its admission stamps (the queue and prefill
waits)."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import driver, harness
from bench import phases as ph
from bench import trace_reduce as tr
from bench.driver import Loop, Req
from bench.loadgen import Item, schedule
from bench.spec import reader, reference_module
from bench.tests.tiny import tiny_cell

RECORDED = Path(__file__).resolve().parent / "trace_excerpt_engine.json"


def _synthetic():
    # window [0, 100); device busy [10, 20), [50, 60), [90, 95); tick A
    # [5, 70) with its phases, tick B [80, 100) admitting
    ops = [("fusion.1", 10, 10), ("paged_flash_decode.2", 50, 10),
           ("copy.3", 90, 5)]
    program = [("engine.tick", 5, 65, {}), ("engine.admit", 5, 3, {}),
               ("engine.plan", 8, 1, {}),
               ("engine.chunk_launch", 9, 3, {"rows": 2}),
               ("engine.decode_launch", 12, 2, {"live_rows": 3}),
               ("engine.device_get", 20, 42, {}),
               ("engine.emit", 62, 6, {}),
               ("engine.table_upload", 65, 2, {}),
               ("engine.tick", 80, 20, {}), ("engine.admit", 80, 5, {})]
    return ops, program, (0, 100)


def test_innermost_names_each_instant_by_the_deepest_phase():
    _, program, _ = _synthetic()
    pieces = ph.innermost(program[:8])
    assert pieces == [
        ("engine.admit", 5, 8), ("engine.plan", 8, 9),
        ("engine.chunk_launch", 9, 12), ("engine.decode_launch", 12, 14),
        ("engine.tick", 14, 20), ("engine.device_get", 20, 62),
        ("engine.emit", 62, 65), ("engine.table_upload", 65, 67),
        ("engine.emit", 67, 68), ("engine.tick", 68, 70)]
    # a child that outlives its parent is clipped to it
    assert ph.innermost([("engine.tick", 0, 10, {}),
                         ("engine.emit", 8, 5, {})]) == [
        ("engine.tick", 0, 8), ("engine.emit", 8, 10)]


def test_idle_split_by_phase():
    ops, program, window = _synthetic()
    idle = ph.idle_by_phase(ops, program, window)
    assert idle == {"other": 15, "engine.admit": 8, "engine.plan": 1,
                    "engine.chunk_launch": 1, "engine.device_get": 32,
                    "engine.emit": 4, "engine.table_upload": 2,
                    "engine.tick": 12}
    assert sum(idle.values()) + tr.busy_ns(ops, window) == 100


def test_tick_idle_and_host_time():
    ops, program, window = _synthetic()
    # 60 ns idle inside the two ticks that start in the window
    assert ph.tick_idle_ns(ops, program, window) == 30
    # tick A 65 less its 42 ns fetch, tick B 20 with none
    assert ph.tick_host_ns(program, window) == (23 + 20) / 2
    assert ph.tick_idle_ns(ops, program, (0, 4)) is None
    assert ph.tick_host_ns(program, (0, 4)) is None


def test_summary_per_tick():
    ops, program, window = _synthetic()
    t = tr.from_events([ops], [[]], [("traced_window", 0, 100)])
    got = ph.summary(t, program)
    assert got["ticks"] == 2
    assert got["idle_in_ticks_share"] == 100.0 * 60 / 75
    # tick A [5, 70): 45 ns idle, 32 of them in its fetch; tick B 15, 0
    assert got["idle_ms_each_tick"] == [45e-6, 15e-6]
    assert got["fetch_idle_ms_each_tick"] == [32e-6, 0.0]
    assert got["own_ms_per_tick"]["engine.device_get"] == 42e-6 / 2


@pytest.fixture(scope="module")
def recorded():
    ex = json.loads(RECORDED.read_text())
    t = tr.from_events(ex["ops"], ex["modules"], ex["host"])
    return ex, t, [tuple(p) for p in ex["program"]]


def test_recorded_split_adds_up(recorded):
    ex, t, program = recorded
    ops = t.ops[0]
    idle = ph.idle_by_phase(ops, program, t.window)
    assert idle == ex["expect"]["idle_by_phase"]
    busy = tr.busy_ns(ops, t.window)
    assert busy == ex["expect"]["busy_ns"]
    assert busy + sum(idle.values()) == t.window[1] - t.window[0]
    n = len(ph.ticks_in(program, t.window))
    in_ticks = sum(v for k, v in idle.items() if k != ph.OUTSIDE)
    assert ph.tick_idle_ns(ops, program, t.window) * n == in_ticks
    assert ph.tick_idle_ns(ops, program, t.window) \
        == ex["expect"]["tick_idle_ns"]
    assert ph.tick_host_ns(program, t.window) \
        == ex["expect"]["tick_host_ns"]
    # the harness's own attribution is unchanged by the program's spans
    assert sum(tr.idle_by_span(t).values()) == sum(idle.values())


def test_recorded_split_matches_a_painted_reference(recorded):
    """Paint each nanosecond of the window with its innermost phase (a
    phase that starts later inside another paints over it) and its
    device state, and count."""
    ex, t, program = recorded
    lo, hi = t.window
    names = sorted({p[0] for p in program})
    owner = np.full(hi - lo, -1, np.int8)
    for name, s, d, _ in sorted(program, key=lambda p: (p[1], -p[2])):
        owner[max(s, lo) - lo:max(min(s + d, hi), lo) - lo] = \
            names.index(name)
    busy = np.zeros(hi - lo, bool)
    for _, s, d in t.ops[0]:
        busy[max(s, lo) - lo:max(min(s + d, hi), lo) - lo] = True
    counts = np.bincount(owner[~busy] + 1, minlength=len(names) + 1)
    want = {(names[i - 1] if i else ph.OUTSIDE): int(c)
            for i, c in enumerate(counts) if c}
    assert ph.idle_by_phase(t.ops[0], program, t.window) == want


def test_recorded_phases_nest_and_carry_args(recorded):
    ex, t, program = recorded
    ticks = [p for p in program if p[0] == ph.TICK]
    assert ticks
    for name, s, d, args in program:
        assert any(k[1] <= s and s + d <= k[1] + k[2] for k in ticks), name
        if name == "engine.chunk_launch":
            assert set(args) == {"rows", "live_rows", "true_tokens"}
            assert 0 < args["live_rows"] <= args["rows"]
        if name == "engine.decode_launch":
            assert set(args) == {"live_rows"}
    assert {"engine.chunk_launch", "engine.decode_launch",
            "engine.device_get", "engine.emit"} <= {p[0] for p in program}


def _req(due, submit, admit, first):
    q = Req(Item(np.zeros(4, np.int32), 1), due)
    q.submit = submit
    q.r = SimpleNamespace(t_submit=submit, t_admit=admit,
                          token_wall=[first] if first else [])
    return q


def test_wait_readers():
    reqs = [_req(10.0, 10.1, 10.2, 10.7),     # waits 0.1, then 0.5
            _req(12.0, 12.0, 12.4, 12.6),     # waits 0.4, then 0.2
            _req(5.0, 5.0, 9.0, 9.5),         # due before the window
            _req(15.0, 15.0, None, None)]     # never admitted
    rec = SimpleNamespace(loop=Loop(reqs, [], 0.0, w0=10.0, w1=20.0))
    assert reader("queue_wait_mean_ms")(rec) == pytest.approx(250.0)
    assert reader("prefill_wait_p80_ms")(rec) == pytest.approx(
        np.percentile([0.5, 0.2], 80) * 1e3)
    # a program that does not stamp admissions: nothing to read
    for q in reqs:
        del q.r.t_admit
    assert reader("queue_wait_mean_ms")(rec) is None
    assert reader("prefill_wait_p80_ms")(rec) is None


def test_engine_stamps_share_the_harness_clock():
    """The tiny chat cell through the harness's loop: every request due
    in the window is submitted, admitted and given its first token in
    that order on the harness's own clock."""
    cell = tiny_cell("granite-3-2b.chat-poisson", rate=4.0, warm_s=0.5)
    cfg, mix = cell.cfg, cell.mix
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    model = build_model(harness.model_config(cfg))
    params = reference_module(cfg).make_params(cfg, harness.jax_key(7))
    scfg, clients = harness.serve_config(cfg, mix)
    eng = ServeEngine(model, params, scfg)
    # compile every shape first, as the harness does: a window that passes
    # inside one compiling tick would hold no submitted request
    harness.warm_shapes(eng, scfg, cfg["vocab_size"], 7)
    items = schedule(mix, 7, vocab=cfg["vocab_size"],
                     context=cfg["max_position_embeddings"],
                     stretches=[0.5, 3.5, 3.5 + driver.GRACE_S + 30.0],
                     clients=clients)
    t0 = time.perf_counter()
    loop = driver.drive(eng, items, mix, seconds=3.0, warm_s=0.5,
                        chunk=scfg.prefill_chunk)
    due = [q for q in loop.reqs if loop.w0 <= q.due < loop.w1]
    assert due
    for q in due:
        r = q.r
        assert t0 <= q.due <= q.submit <= r.t_submit <= r.t_admit \
            <= r.token_wall[0] <= q.first
    rec = SimpleNamespace(loop=loop)
    assert reader("queue_wait_mean_ms")(rec) == pytest.approx(
        1e3 * np.mean([q.r.t_admit - q.r.t_submit for q in due]))
    assert reader("prefill_wait_p80_ms")(rec) > 0
