"""The readers of the server's host-effect counters, on hand-made pairs
of registry snapshots: each reads its window delta, over the measured
window's seconds where it is a share, and reads nothing from a program
that has no such counter."""

import types

import pytest

from bench.harness import Context
from bench.spec import metric_reader

NEW = ("gc_pause_pct", "stalled_ticks", "wal_sync_ms", "bubble_pct")


def _snap(**counters):
    return {name: {"samples": [{"labels": {}, "value": v}]}
            for name, v in counters.items()}


def _ctx(snap0, snap1, window_s=5.0, seconds=51.0):
    rec = types.SimpleNamespace(t0=100.0, t1=100.0 + window_s)
    return Context(None, seconds, rec, 0.0, snap0, snap1, None, "cpu")


def test_gc_pause_pct_over_the_measured_window():
    # 150 ms of pauses in a 5 s traced slice, whatever --seconds was
    ctx = _ctx(_snap(server_gc_pause_us_total=20_000.0),
               _snap(server_gc_pause_us_total=170_000.0))
    assert metric_reader("gc_pause_pct")(ctx) == pytest.approx(3.0)


def test_stalled_ticks_counts_the_window_delta():
    ctx = _ctx(_snap(server_stalled_ticks_total=4),
               _snap(server_stalled_ticks_total=7))
    assert metric_reader("stalled_ticks")(ctx) == 3
    ctx = _ctx(_snap(server_stalled_ticks_total=4),
               _snap(server_stalled_ticks_total=4))
    assert metric_reader("stalled_ticks")(ctx) == 0


def test_wal_sync_ms_is_time_per_barrier():
    ctx = _ctx(_snap(server_wal_sync_us_total=1_000.0,
                     server_wal_syncs_total=10),
               _snap(server_wal_sync_us_total=4_000.0,
                     server_wal_syncs_total=16))
    assert metric_reader("wal_sync_ms")(ctx) == pytest.approx(0.5)
    # a read-only window synced nothing: no number
    ctx = _ctx(_snap(server_wal_sync_us_total=0.0,
                     server_wal_syncs_total=0),
               _snap(server_wal_sync_us_total=0.0,
                     server_wal_syncs_total=0))
    assert metric_reader("wal_sync_ms")(ctx) is None


def test_bubble_pct_over_the_measured_window():
    ctx = _ctx(_snap(server_bubble_us_total=0.0),
               _snap(server_bubble_us_total=250_000.0), window_s=10.0)
    assert metric_reader("bubble_pct")(ctx) == pytest.approx(2.5)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_counter(name):
    ctx = _ctx(_snap(server_ticks_total=1), _snap(server_ticks_total=9))
    assert metric_reader(name)(ctx) is None
    assert metric_reader(name)(_ctx({}, {})) is None
