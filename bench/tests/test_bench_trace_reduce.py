"""The reduction from trace to metrics: busy union, idle gaps attributed to
the harness's spans, and device time by name."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

RECORDED = Path(__file__).resolve().parent / "trace_excerpt.json"


def _synthetic():
    # a loop op over two body ops, a gap, one op; window [0, 40)
    ops = [[("while.1", 0, 15), ("fusion.1", 0, 5),
            ("paged_flash_decode.3", 5, 10), ("fusion.2", 30, 6)]]
    modules = [[("jit_fused_decode_step(7)", 0, 15),
                ("jit_chunk_batch_step(2)", 30, 5)]]
    host = [("traced_window", 0, 40), ("tick", 0, 20), ("submit", 20, 8),
            ("wait", 28, 8), ("tick", 36, 4)]
    return tr.from_events(ops, modules, host)


def test_busy_is_the_union_of_op_intervals():
    t = _synthetic()
    assert t.window == (0, 40)
    assert tr.busy_ns(t.ops[0], t.window) == 15 + 6


def test_gaps_and_their_attribution():
    t = _synthetic()
    assert tr.gaps(t.ops[0], t.window) == [(15, 30), (36, 40)]
    # (15, 30): tick 5, submit 8, wait 2 -> submit; (36, 40): tick 4
    assert tr.attribute_gaps(tr.gaps(t.ops[0], t.window), t.host) == [
        ("submit", 15), ("tick", 4)]
    assert tr.idle_by_span(t) == {"submit": 15, "tick": 4}


def test_gap_with_no_span_is_other():
    t = tr.from_events([[("a", 0, 2)]], [[]],
                       [("traced_window", 0, 10), ("tick", 0, 2)])
    assert tr.idle_by_span(t) == {"other": 8}


def test_time_by_name():
    t = _synthetic()
    assert tr.time_of(t.ops[0], r"paged_flash_decode") == (1, 10)
    assert tr.time_of(t.modules[0], r"fused_decode_step", t.window) == (1, 15)
    # the loop op holds its body's time and is left out
    assert tr.top_ops(t.ops[0], t.window, 2) == [("fusion", 11e-9),
                                                 ("paged_flash_decode", 1e-8)]


def test_op_name_is_the_instruction_name():
    assert tr.op_name("%paged_flash_decode.9 = bf16[64,8,4,64]{3,2,1,0} "
                      "custom-call(s32[64,32] %x)") == "paged_flash_decode.9"
    assert tr.op_name("%copy.70 = bf16[8] copy(%paged_flash_decode.9)") \
        == "copy.70"


def test_window_needs_exactly_one_span():
    with pytest.raises(ValueError, match="traced_window"):
        tr.from_events([[]], [[]], [("tick", 0, 1)])


@pytest.fixture(scope="module")
def recorded():
    ex = json.loads(RECORDED.read_text())
    return ex, tr.from_events(ex["ops"], ex["modules"], ex["host"])


def test_recorded_busy_and_idle_add_up(recorded):
    ex, t = recorded
    busy = tr.busy_ns(t.ops[0], t.window)
    idle = sum(tr.idle_by_span(t).values())
    assert busy + idle == t.window[1] - t.window[0]
    assert busy == ex["expect"]["busy_ns"]
    assert tr.idle_by_span(t) == ex["expect"]["idle_by_span"]


def test_recorded_kernel_time_by_name(recorded):
    ex, t = recorded
    for pattern, (n, ns) in ex["expect"]["time_of"].items():
        assert tr.time_of(t.ops[0] + t.modules[0], pattern, t.window) \
            == (n, ns)
