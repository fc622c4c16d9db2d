"""decode_ctx_tokens_mean: the attended context counted by the program over
the traced launches, per generated token, worked by hand; nothing where
the program does not count it."""
from types import SimpleNamespace

from bench import spec
from bench.driver import Counters, Loop
from repro.serve.telemetry import LaunchRecord

read = spec.reader("decode_ctx_tokens_mean")


def _launch(kind, ctx):
    return LaunchRecord(tick=0, kind=kind, rows=8, live_rows=2,
                        true_tokens=2, padded_tokens=8, kv_pages_read=0,
                        kv_pages_written=0, new_kv_tokens=2, work_clock=0,
                        ctx_tokens=ctx)


def _rec(launches, c0, c1):
    loop = Loop([], [], 0.0, launches=launches, trace_c=(c0, c1))
    return SimpleNamespace(loop=loop)


def test_traced_launches_per_generated_token_by_hand():
    # token-emitting launches 1..4 of the run; the trace holds launches 2
    # and 3 (a decode of 300 + 500 context, a verify of 1000): 1800
    # positions attended for 6 tokens
    launches = [_launch("decode", 100), _launch("chunk_batch", 0),
                _launch("decode", 800), _launch("spec_verify", 1000),
                _launch("chunk_batch", 0), _launch("decode", 7)]
    rec = _rec(launches, Counters(gen_tokens=2, decode_launches=1),
               Counters(gen_tokens=8, decode_launches=3))
    assert read(rec) == 1800 / 6


def test_reads_nothing_without_the_program_counter():
    # a program whose records carry no attended context
    old = [SimpleNamespace(kind="decode"), SimpleNamespace(kind="decode")]
    c0, c1 = Counters(0, 0), Counters(4, 2)
    assert read(_rec(old, c0, c1)) is None
    assert read(_rec([], c0, c1)) is None
    assert read(_rec([_launch("decode", 5)], None, None)) is None


def test_reads_nothing_once_the_engine_dropped_records(monkeypatch):
    # the engine keeps LAUNCH_CAPACITY records: at that many the oldest may
    # have dropped, and the counters no longer index the records kept
    launches = [_launch("decode", 10)] * 4
    c0, c1 = Counters(0, 1), Counters(6, 3)
    assert read(_rec(launches, c0, c1)) == 20 / 6
    monkeypatch.setitem(read.__globals__, "LAUNCH_CAPACITY", 4)
    assert read(_rec(launches, c0, c1)) is None
