"""The traffic generator: one trace per mix, drawn i.i.d. from the mix's
draw_seed and replayed by every seed, which draws only the token ids."""
import numpy as np
import pytest

from bench import loadgen
from bench.spec import BENCH, load_json

CHAT = load_json(BENCH / "traffic" / "chat-poisson.json")
LONG = load_json(BENCH / "traffic" / "long-decode.json")
STRETCHES = [40.0, 91.0, 181.0]
SEEDS = [1, 2200000101, 2 ** 40 + 7]


def _open(seed, mix=CHAT, stretches=STRETCHES):
    return loadgen.schedule(mix, seed, vocab=1000, context=4096,
                            stretches=stretches)


def _trace(items):
    return [(i.due, len(i.prompt), i.max_new) for i in items]


def test_same_seed_same_schedule():
    a, b = _open(SEEDS[1]), _open(SEEDS[1])
    assert [i.due for i in a] == [i.due for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_every_seed_replays_the_same_trace(seed):
    ref, got = _open(SEEDS[0]), _open(seed)
    assert _trace(got) == _trace(ref)
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(got, ref))


def test_another_rate_scales_the_same_gaps():
    fast = dict(CHAT, arrivals=dict(CHAT["arrivals"], rate_per_s=2.0))
    slow = dict(CHAT, arrivals=dict(CHAT["arrivals"], rate_per_s=1.0))
    a, b = _open(SEEDS[0], fast), _open(SEEDS[0], slow)
    n = min(len(a), len(b))
    assert np.allclose([2 * i.due for i in a[:n]], [i.due for i in b[:n]])
    assert [i.max_new for i in a[:n]] == [i.max_new for i in b[:n]]


def test_lengths_reach_the_clipped_range():
    """Lengths are drawn, not set at strata: over a long trace the outputs
    pass the 97th percentile (~568 tokens), and every length keeps to the
    mix's clip; the mean gap is near 1 / rate."""
    items = _open(SEEDS[0], stretches=[2000.0])
    out = np.array([i.max_new for i in items])
    prompt = np.array([len(i.prompt) for i in items])
    assert out.max() > 568 and out.min() >= CHAT["output"]["min"]
    assert out.max() <= CHAT["output"]["max"]
    assert prompt.min() >= CHAT["prompt"]["min"]
    assert prompt.max() <= CHAT["prompt"]["max"]
    assert (prompt + out <= 4096).all()
    gap = np.diff([i.due for i in items]).mean()
    assert abs(gap * CHAT["arrivals"]["rate_per_s"] - 1) < 0.1


def test_open_loop_covers_the_horizon():
    items = _open(SEEDS[0])
    assert items[-1].due >= STRETCHES[-1] > items[-2].due
    dues = [i.due for i in items]
    assert dues == sorted(dues) and dues[0] == 0.0


def test_longer_horizon_extends_the_trace():
    short, long = _open(SEEDS[1]), _open(SEEDS[1], stretches=[400.0])
    assert _trace(long[:len(short)]) == _trace(short)
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(short, long))


def test_closed_loop_assigns_clients_round_robin():
    items = loadgen.schedule(LONG, SEEDS[2], vocab=1000, context=4096,
                             stretches=[30.0], clients=7)
    assert len(items) == 7 * 30
    assert [i.client for i in items[:8]] == [0, 1, 2, 3, 4, 5, 6, 0]
    assert all(2048 <= len(i.prompt) <= 3584 and 256 <= i.max_new <= 512
               for i in items)


def test_unknown_distribution_raises():
    bad = dict(CHAT, prompt={"kind": "zipf"})
    with pytest.raises(ValueError):
        _open(SEEDS[0], bad)
