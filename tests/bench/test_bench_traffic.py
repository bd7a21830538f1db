"""The traffic generator: a seed repeats, seeds share their work."""
import collections

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import traffic

MIX = {"kind": "serve", "loop": "closed", "clients": 16, "max_len": 1024,
       "prompt": {"median": 192, "sigma": 0.6, "min": 32, "max": 512},
       "output": {"median": 128, "sigma": 0.7, "min": 16, "max": 496},
       "tokens": "uniform", "pool": 64, "pairing_seed": 0, "order_seed": 1}


def _take(seed, n, mix=MIX):
    t = traffic.ClosedLoopTraffic(mix, seed, vocab=151936)
    return [t.next() for _ in range(n)]


def _sizes(requests):
    return [(len(t), o) for t, o in requests]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 33 + 5])
def test_a_seed_repeats(seed):
    a, b = _take(seed, 80), _take(seed, 80)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))


def test_seeds_offer_the_same_sizes_in_the_same_order():
    a, b = _take(1, 160), _take(2 ** 31 + 9, 160)
    assert _sizes(a) == _sizes(b)
    assert not np.array_equal(a[0][0][:4], b[0][0][:4])


def test_each_pass_sends_the_whole_pool_in_a_new_order():
    a = _sizes(_take(5, 128))
    assert collections.Counter(a[:64]) == collections.Counter(a[64:])
    assert a[:64] != a[64:]


def test_the_order_comes_from_the_mix_file():
    a = _sizes(_take(1, 64))
    b = _sizes(_take(1, 64, dict(MIX, order_seed=2)))
    assert collections.Counter(a) == collections.Counter(b) and a != b


def test_lengths_follow_the_clipped_lognormal():
    t = traffic.ClosedLoopTraffic(MIX, 0, vocab=100)
    p = np.array([x for x, _ in t.pool])
    o = np.array([x for _, x in t.pool])
    assert p.min() >= 32 and p.max() <= 512 and o.min() >= 16
    assert abs(np.median(p) - 192) <= 8 and abs(np.median(o) - 128) <= 8
    assert all(x + y <= 1024 for x, y in t.pool)
    # the shortest pooled prompt (45) pads to 64
    assert p.min() == 45 and t.buckets() == [64, 128, 256, 512]


def test_tokens_lie_in_the_vocabulary():
    for tokens, _ in _take(3, 20):
        assert tokens.dtype == np.int32
        assert tokens.min() >= 0 and tokens.max() < 151936


def test_a_request_longer_than_the_cache_is_refused():
    bad = dict(MIX, max_len=600)
    with pytest.raises(ValueError, match="max_len"):
        traffic.ClosedLoopTraffic(bad, 0, vocab=10)
