"""Each cell of BENCHMARK.json cut to a size a CPU test run can hold: the
same harness, traffic kinds and checks, fewer keys, a shorter window and
an open-loop rate the CPU keeps up with."""

import json
import time
from pathlib import Path

from bench.harness import run_cell
from bench.spec import load_cell

ROOT = Path(__file__).resolve().parents[2]
CELLS = tuple(w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"])
CPU_RATE = 500.0


def tiny_cell(name: str):
    cell = load_cell(ROOT, name)
    cell.config["keys"] = 20_000
    cell.traffic["warmup_s"] = 0.3
    if "rate_per_s" in cell.traffic:
        cell.traffic["rate_per_s"] = min(cell.traffic["rate_per_s"],
                                         CPU_RATE)
    return cell


def run_tiny(name: str, seed: int = 2**31 + 17, **kw) -> dict:
    return run_cell(tiny_cell(name), seed, 1.0, False, time.perf_counter(),
                    **kw)
