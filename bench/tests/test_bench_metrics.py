"""The metric arithmetic: percentiles over all samples, a rate over the
whole window, and TTFT counted from the due time."""
import numpy as np

from bench import spec
from bench.driver import Loop, Req, Tick, tbt_gaps, ttfts, window_tokens
from bench.loadgen import Item


def _req(due, submit, stamps):
    q = Req(Item(np.zeros(4, np.int32), len(stamps)), due)
    q.submit = submit
    q.stamps = list(stamps)
    return q


class _Rec:
    def __init__(self, loop):
        self.loop = loop
        self.trace = None
        self.setup_s = 12.5


def _loop():
    # window (10, 20]; ticks of very different lengths
    ticks = [Tick(9.0, 10.0, [], [], 3),
             Tick(10.0, 10.1, [], [], 4), Tick(10.1, 19.0, [], [], 1),
             Tick(19.0, 20.0, [], [], 5),
             Tick(20.0, 21.0, [], [], 7)]
    reqs = [
        # due at 10.0, sent late at 10.6, first token at 11.0: TTFT 1.0
        _req(10.0, 10.6, [11.0, 12.0, 13.0, 14.0]),
        # due in the window, one long gap
        _req(12.0, 12.0, [13.0, 19.0]),
        # due before the window: no TTFT sample, its gaps in the window do
        _req(5.0, 5.0, [6.0, 9.0, 10.5]),
        # due after the close
        _req(20.0, 20.0, [21.0]),
    ]
    loop = Loop(reqs, ticks, 0.0, w0=10.0, w1=20.0, k0=1, k1=4)
    return loop


def test_ttft_counts_from_the_due_time():
    t = ttfts(_loop())
    assert sorted(t.tolist()) == [1.0, 1.0]


def test_unserved_request_reads_infinite():
    loop = _loop()
    loop.reqs.append(_req(15.0, 15.0, []))
    assert np.isinf(ttfts(loop)).sum() == 1


def test_tbt_pools_every_gap_of_every_request():
    g = tbt_gaps(_loop())
    # 11->12, 12->13, 13->14, 13->19, 9->10.5 (10.5 in the window);
    # 6->9 ends before it
    assert sorted(g.tolist()) == [1.0, 1.0, 1.0, 1.5, 6.0]
    p99 = spec.reader("tbt_p99_ms")(_Rec(_loop()))
    assert p99 == np.percentile([1.0, 1.0, 1.0, 1.5, 6.0], 99) * 1e3
    # not the mean of per-request percentiles
    assert p99 != np.mean([np.percentile([1, 1, 1], 99),
                           np.percentile([6.0], 99),
                           np.percentile([1.5], 99)]) * 1e3


def test_rate_is_over_the_whole_window():
    loop = _loop()
    assert window_tokens(loop) == 4 + 1 + 5
    rate = spec.reader("output_tok_per_s")(_Rec(loop))
    assert rate == 10 / 10.0
    # the mean of per-tick rates would be far higher
    per_tick = np.mean([4 / 0.1, 1 / 8.9, 5 / 1.0])
    assert abs(rate - per_tick) > 10


def test_ttft_p80_reader_uses_all_due_requests():
    loop = _loop()
    for due, first in [(11.0, 11.5), (13.0, 15.0), (18.0, 18.25)]:
        loop.reqs.append(_req(due, due, [first]))
    v = spec.reader("ttft_p80_ms")(_Rec(loop))
    assert v == np.percentile([1.0, 1.0, 0.5, 2.0, 0.25], 80) * 1e3


def test_setup_reader():
    assert spec.reader("setup_s")(_Rec(_loop())) == 12.5
