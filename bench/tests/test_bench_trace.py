"""The reduction from a profiler trace to device metrics, on a hand-made
event list, and the device GET's work count by hand."""

import math

import numpy as np
import pytest

from bench import tracing, work

MS = 1_000_000  # ns


def _events():
    # window 0..100 ms; device ops: [10,30) and [20,40) overlap -> busy
    # [10,40); [60,70) -> busy 40 ms; idle [0,10) [40,60) [70,100)
    return {
        "host": [
            ("bench.window", 0, 100 * MS),
            ("bench.tick", 5 * MS, 45 * MS),
            ("bench.dispatch_get", 42 * MS, 44 * MS),
            ("bench.submit", 50 * MS, 65 * MS),
        ],
        "devices": {"/device:TPU:0": {
            "ops": [("%fusion.1 = u32[8] fusion(...)", 10 * MS, 30 * MS),
                    ("%while.3 = (s32[]) while(...)", 20 * MS, 40 * MS),
                    ("%fusion.1 = u32[8] fusion(...)", 60 * MS, 70 * MS),
                    ("%copy = s32[] copy()", 95 * MS, 120 * MS)],
            "modules": [("jit__local_get_all_shards(12)", 10 * MS, 40 * MS),
                        ("jit__shard_filter_probe(3)", 60 * MS, 70 * MS),
                        ("jit_other(4)", 95 * MS, 120 * MS)],
        }},
    }


def test_busy_union_idle_share_and_programs():
    out = tracing.reduce_events(_events())
    assert out["window_s"] == pytest.approx(0.1)
    # [10,40) + [60,70) + [95,100) clipped to the window
    assert out["busy_s"] == pytest.approx(0.045)
    p = out["programs"]
    assert p["_local_get_all_shards"] == {"seconds": pytest.approx(0.03),
                                          "calls": 1}
    assert p["_shard_filter_probe"]["seconds"] == pytest.approx(0.01)
    assert p["other"]["seconds"] == pytest.approx(0.005)
    assert tracing.get_device_s(out) == pytest.approx(0.04)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["_local_get_all_shards:fusion.1"] == pytest.approx(0.02)
    assert ops["_local_get_all_shards:while.3"] == pytest.approx(0.02)
    assert ops["_shard_filter_probe:fusion.1"] == pytest.approx(0.01)


def test_idle_gaps_attributed_to_innermost_host_span():
    out = tracing.reduce_events(_events())
    idle = dict(out["breakdown"]["idle_gaps"])
    # idle [0,10): 5 ms no span, 5 ms tick; [40,60): tick 40-42, 44-45,
    # dispatch_get 42-44, none 45-50, submit 50-60; [70,95): none 70-95
    assert idle["bench.tick"] == pytest.approx(0.008)
    assert idle["bench.dispatch_get"] == pytest.approx(0.002)
    assert idle["bench.submit"] == pytest.approx(0.010)
    assert idle[tracing.NO_SPAN] == pytest.approx(0.005 + 0.005 + 0.025)
    assert sum(idle.values()) == pytest.approx(0.1 - 0.045)


def test_reduction_refuses_a_trace_without_window_or_device():
    ev = _events()
    with pytest.raises(ValueError):
        tracing.reduce_events({"host": ev["host"][1:],
                               "devices": ev["devices"]})
    with pytest.raises(ValueError):
        tracing.reduce_events({"host": ev["host"], "devices": {}})


def test_names():
    assert tracing.program_name("jit__local_get_all_shards(123)") == \
        "_local_get_all_shards"
    assert tracing.op_name("%fusion.31 = u32[19456]{0} fusion(x)") == \
        "fusion.31"


def test_get_work_by_hand():
    # one probe, 3 segments (2 bisect steps), delta 1 (window of 5 keys),
    # 2 hashes: 8 probe + 16 filter + 16 bisect + 16 model + 40 window
    # + 8 vptr + 9 out = 113 bytes; ops 8 + 2 + 2 + 5 = 17
    assert work.per_probe(3, 1, 2, True) == (113, 17)
    assert work.per_probe(3, 1, 2, False) == (97, 9)
    w = work.get_work([10, 6], np.array([3, 3]), 1, 2, True)
    assert w == {"batches": 2, "probes": 16, "bytes": 113 * 16,
                 "ops": 17 * 16}
    # shards with different segment counts average their per-probe costs
    w = work.get_work([4], np.array([1, 7]), 8, 7, True)
    b1 = 8 + 56 + 8 * 1 + 16 + 8 * 19 + 17
    b7 = 8 + 56 + 8 * math.ceil(math.log2(8)) + 16 + 8 * 19 + 17
    assert w["bytes"] == pytest.approx(4 * (b1 + b7) / 2)
