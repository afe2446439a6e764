"""BENCHMARK.json keeps to its contract, and the harness finds a cell's
configuration, traffic mix, generator kind and metrics by name alone."""

import json
import re
import types
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_paths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert (ROOT / "bench" / "run.py").is_file()


def test_names_units_and_entry_keys(bench):
    metric_keys = {"name", "unit", "better", "source"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == metric_keys | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == metric_keys | {"layer", "moves"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_reports_what_its_metrics_move(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for c in cells:
        assert c in e2e["setup_s"]
        assert sum(c in ws for n, ws in e2e.items() if n != "setup_s") >= 1
        assert any(c in m.get("workloads", cells) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        for c in m.get("workloads", cells):
            assert c in e2e[m["moves"]], (m["name"], c)
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        spec.generator_kind(cell.traffic["kind"])
        assert cell.config["name"] == w["config"]


def _dummy_tree(tmp_path):
    root, bdir = tmp_path / "root", tmp_path / "root" / "benchdir"
    for d in ("configs", "traffic/kinds", "metrics"):
        (bdir / d).mkdir(parents=True)
    (bdir / "configs" / "toy.json").write_text(json.dumps({"keys": 7}))
    (bdir / "traffic" / "toy-mix.json").write_text(
        json.dumps({"kind": "toy_kind", "rate_per_s": 3}))
    (bdir / "traffic" / "kinds" / "toy_kind.py").write_text(
        "def make(mix, rng, make_request):\n"
        "    return ('toy', mix['rate_per_s'])\n")
    (bdir / "metrics" / "toy_metric.py").write_text(
        "def read(ctx):\n    return ctx.value * 2\n")
    (bdir / "metrics" / "silent_metric.py").write_text(
        "def read(ctx):\n    return None\n")
    metrics = [{"name": "toy_metric", "unit": "ms", "better": "lower",
                "source": "host_clock", "bound": 0.1},
               {"name": "silent_metric", "unit": "%", "better": "higher",
                "source": "host_clock", "layer": "toy", "moves": "toy_metric",
                "workloads": ["toy.cell"]},
               {"name": "other_cells_metric", "unit": "%", "better": "higher",
                "source": "host_clock", "layer": "toy", "moves": "toy_metric",
                "workloads": ["another.cell"]}]
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "benchdir/configs/toy.json"}],
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "toy-mix", "chips": 1}],
        "end_to_end": metrics[:1], "per_layer": metrics[1:]}))
    return root, bdir


def test_cell_kind_and_metrics_found_by_name_in_added_files(tmp_path):
    root, bdir = _dummy_tree(tmp_path)
    cell = spec.load_cell(root, "toy.cell", bench_dir=bdir)
    assert cell.config == {"keys": 7, "name": "toy"}
    assert cell.traffic["kind"] == "toy_kind"
    assert [m["name"] for m in cell.per_layer] == ["silent_metric"]
    kind = spec.generator_kind("toy_kind", bench_dir=bdir)
    assert kind.make(cell.traffic, None, None) == ("toy", 3)
    ctx = types.SimpleNamespace(value=4.5)
    got = spec.read_metrics(cell.end_to_end + cell.per_layer, ctx,
                            bench_dir=bdir)
    # the reader that found nothing leaves its metric out of the line
    assert got == {"toy_metric": {"value": 9.0, "unit": "ms"}}
    with pytest.raises(KeyError):
        spec.load_cell(root, "no.such.cell", bench_dir=bdir)
