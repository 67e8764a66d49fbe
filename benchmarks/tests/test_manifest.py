"""BENCHMARK.json names only files that exist and only allowed characters."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_exist(cell):
    assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
    assert NAME.match(cell["name"]) and NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert os.path.exists(os.path.join(ROOT, configs[cell["config"]]["file"]))
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entries(config):
    assert sorted(config) == ["file", "name", "reduced", "source", "why"]
    doc = json.load(open(os.path.join(ROOT, config["file"])))
    assert doc["name"] == config["name"] and doc["reduced"] == config["reduced"]
    assert config["file"].startswith("benchmarks/") and 1 <= len(config["source"]) <= 200
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    for key in config["reduced"]:
        assert NAME.match(key) and not key.endswith(("_dim", "_rank", "_size"))


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_entries(metric):
    cells = {w["name"] for w in BENCH["workloads"]}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert set(metric.get("workloads", [])) <= cells
    if "bound" in metric:  # end to end
        assert sorted(set(metric) - {"workloads"}) == ["better", "bound", "name", "source", "unit"]
        assert 0.01 <= metric["bound"] <= 0.1 and metric["source"] in ("host_clock", "device_trace")
    else:
        assert sorted(set(metric) - {"workloads"}) == ["better", "layer", "moves", "name", "source", "unit"]
        spec = json.load(open(os.path.join(ROOT, "benchmarks", "metrics", metric["name"] + ".json")))
        for key in ("layer", "unit", "source", "moves", "better"):
            assert spec[key] == metric[key], key
        if spec["read"]["kind"] == "python":
            assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics", metric["name"] + ".py"))
        e2e = {m["name"]: m for m in BENCH["end_to_end"]}
        assert metric["moves"] in e2e
        reporting = set(e2e[metric["moves"]].get("workloads", cells))
        assert set(metric.get("workloads", reporting)) <= reporting


def test_every_cell_reports_enough():
    for cell in BENCH["workloads"]:
        e2e = [m["name"] for m in BENCH["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
        layer = [m for m in BENCH["per_layer"] if cell["name"] in m.get("workloads", [cell["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer


def test_files_under_paths_use_name_characters():
    for base, _, files in os.walk(os.path.join(ROOT, "benchmarks")):
        if "__pycache__" in base:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(base, f)
