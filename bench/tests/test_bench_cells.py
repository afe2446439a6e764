"""Whole runs of each cell at a CPU size, the reference's control, and the
entry point's refusal to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.control import factory

from ._tiny import CELLS, ROOT, run_tiny


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_cpu_size(cell):
    out = run_tiny(cell)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["check"]["compared_keys"]["value"] >= 100
    assert set(out["metrics"]) >= {"setup_s", "get_p50_ms"}
    # every shard's memtable rolled into a rebuilt device state, and the
    # run's writes and as many unwritten keys were read back from it
    check = out["check"]
    assert check["rolled_shards"]["value"] == check["rolled_shards"]["min"]
    assert check["keys_read_after_roll"]["value"] >= 100
    assert check["wrong_keys_after_roll"]["value"] == 0
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_exact_reference_passes(cell):
    ctrl = run_tiny(cell, server_factory=factory(exact=False))
    assert not ctrl["correct"]
    assert ctrl["check"]["wrong_keys"]["value"] > 0
    ref = run_tiny(cell, server_factory=factory(exact=True))
    assert ref["correct"], ref["check"]


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "uden.get-uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_entry_point_refuses_a_machine_without_tpu():
    proc = _run_entry(ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "platform=cpu" in proc.stderr
    assert _no_result(proc)


def test_entry_point_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_entry(tmp_path, {"PYTHONPATH": "src"})
    assert proc.returncode != 0
    assert _no_result(proc)
