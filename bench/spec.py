"""The benchmark as data: ``BENCHMARK.json`` and the files it names.

A cell names a configuration and a traffic mix; a metric names a reader.
Each is found by its name, so a later change adds a cell, a configuration,
a traffic mix, a generator kind or a metric by adding files and entries:

* configuration -- the ``file`` its ``configs`` entry gives;
* traffic mix   -- ``<bench>/traffic/<traffic>.json``, whose ``kind``
  names the generator ``<bench>/traffic/kinds/<kind>.py``;
* metric        -- ``<bench>/metrics/<name>.py``, a module with
  ``read(ctx) -> float | None``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    """Resolve cell ``name`` of ``<root>/BENCHMARK.json``."""
    spec = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((Path(root) / cfg_entry["file"]).read_text())
    config["name"] = cfg_entry["name"]
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    traffic["name"] = w["traffic"]
    return Cell(name, int(w["chips"]), config, traffic,
                _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name))


def generator_kind(kind: str, bench_dir: Path = BENCH_DIR):
    return load_module(bench_dir / "traffic" / "kinds" / f"{kind}.py",
                       f"bench_kind_{kind}")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    return load_module(bench_dir / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}").read


def read_metrics(entries: list, ctx, bench_dir: Path = BENCH_DIR) -> dict:
    """Run each metric's reader; a reader that finds nothing to read
    returns None and its metric is left out of the result."""
    out = {}
    for m in entries:
        v = metric_reader(m["name"], bench_dir)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
