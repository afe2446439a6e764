"""Generators and traffic kinds: seeded, repeatable, and shaped as the
sources say."""

import numpy as np

from bench import gen
from bench.spec import generator_kind


def test_scrambled_zipf_hot_set_share_matches_analytic():
    n, hot, theta = 200_000, 4096, 0.99
    z = gen.ScrambledZipf(n, theta)
    w = 1.0 / np.arange(1, n + 1) ** theta
    analytic = w[:hot].sum() / w.sum()
    draws = z.draw(np.random.default_rng(7), 400_000)
    counts = np.bincount(draws, minlength=n)
    measured = np.sort(counts)[::-1][:hot].sum() / draws.shape[0]
    assert abs(measured - analytic) < 0.02
    # the hot items are scattered over the key space, not the first ranks
    top = np.argsort(counts)[::-1][:hot]
    assert top.max() > n // 2


def test_ycsb_keys_unique_in_domain_and_fixed():
    k = gen.ycsb_keys(50_000)
    assert k.shape == (50_000,)
    assert np.unique(k).shape[0] == k.shape[0]
    assert k.min() >= 0 and k.max() < 1 << gen.KEY_BITS
    np.testing.assert_array_equal(k, gen.ycsb_keys(50_000))
    # FNV-1a 64 of record index 0, masked to 62 bits
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ 0) * 0x100000001B3) % (1 << 64)
    assert int(k[0]) == h & ((1 << 62) - 1)


def test_osm_keys_repeat_for_a_seed_and_differ_across_seeds():
    a = gen.osm_keys(30_000, np.random.default_rng([2**31 + 5, 1]))
    b = gen.osm_keys(30_000, np.random.default_rng([2**31 + 5, 1]))
    c = gen.osm_keys(30_000, np.random.default_rng([2**31 + 6, 1]))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a.shape == (30_000,)


def test_values_name_key_version_and_seed():
    v = gen.ValueMaker(1000, 11)
    keys = np.array([5, 6], np.int64)
    a = v(keys, 0)
    assert a.shape == (2, 1000) and a.dtype == np.uint8
    np.testing.assert_array_equal(a, v(keys, 0))
    assert not np.array_equal(a, v(keys, 1))
    assert not np.array_equal(a, gen.ValueMaker(1000, 12)(keys, 0))
    assert not np.array_equal(a[0], a[1])


def _schedule(seed, rate=5000.0, horizon=2.0):
    rng = np.random.default_rng(seed)
    kind = generator_kind("open_loop")
    g = kind.make({"rate_per_s": rate}, rng, lambda due: due)
    g.start(0.0)
    return np.array(g.poll(horizon))


def test_poisson_schedule_repeats_for_a_seed_and_differs_across_seeds():
    a, b, c = _schedule(3), _schedule(3), _schedule(4)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a[:100], c[:100])
    # about rate x horizon arrivals, exponential gaps of mean 1/rate
    assert abs(a.shape[0] - 10_000) < 400
    assert abs(np.diff(a).mean() - 1 / 5000) < 2e-5


def test_closed_loop_keeps_one_request_per_client():
    kind = generator_kind("closed_loop")
    made = []
    g = kind.make({"clients": 3}, None, lambda due: made.append(due) or due)
    g.start(0.0)
    assert len(g.poll(0.0)) == 3 and g.poll(1.0) == []
    g.completed(None, 1.0)
    assert g.poll(1.5) == [1.5]
    assert g.next_due() is None
