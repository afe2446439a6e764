"""The reader of the hot-key cache's host time, on hand-made pairs of
registry snapshots: cache time per batch over the window, and nothing
from a program that has no such counter."""

import types

import pytest

from bench.harness import Context
from bench.spec import metric_reader


def _snap(**counters):
    return {name: {"samples": [{"labels": {}, "value": v}]}
            for name, v in counters.items()}


def _ctx(snap0, snap1, window_s=5.0, seconds=51.0):
    rec = types.SimpleNamespace(t0=100.0, t1=100.0 + window_s)
    return Context(None, seconds, rec, 0.0, snap0, snap1, None, "cpu")


def test_cache_host_us_is_time_per_batch():
    ctx = _ctx(_snap(server_cache_host_us_total=5_000.0,
                     server_batches_total=100),
               _snap(server_cache_host_us_total=65_000.0,
                     server_batches_total=250))
    assert metric_reader("cache_host_us")(ctx) == pytest.approx(400.0)
    # a window that served no batch: no number
    ctx = _ctx(_snap(server_cache_host_us_total=5_000.0,
                     server_batches_total=100),
               _snap(server_cache_host_us_total=5_000.0,
                     server_batches_total=100))
    assert metric_reader("cache_host_us")(ctx) is None


def test_cache_host_us_reads_nothing_without_the_cache_counter():
    # a program that exports the batch count but no cache time
    ctx = _ctx(_snap(server_batches_total=100),
               _snap(server_batches_total=250))
    assert metric_reader("cache_host_us")(ctx) is None


def test_cache_host_us_reads_nothing_in_a_program_without_counters():
    ctx = _ctx(_snap(server_ticks_total=1), _snap(server_ticks_total=9))
    assert metric_reader("cache_host_us")(ctx) is None
    assert metric_reader("cache_host_us")(_ctx({}, {})) is None
