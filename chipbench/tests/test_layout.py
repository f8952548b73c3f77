"""Every cell, configuration, mix, driver and metric is a file of its own,
found by its name, and every name and unit keeps to the allowed letters."""

from __future__ import annotations

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = ROOT / "chipbench"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1] == "chipbench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    assert NAME.match(cfg["name"])
    path = ROOT / cfg["file"]
    assert path == HERE / "configs" / f"{cfg['name']}.json"
    data = json.loads(path.read_text())
    assert data["name"] == cfg["name"]
    # Every key not borne out by the source is listed, with its reason.
    assert data["reduced"] == cfg["reduced"]
    assert set(data["assumed"]) == set(cfg["reduced"])
    assert all(NAME.match(key) and key in data for key in cfg["reduced"])
    assert any(cfg["name"] == w["config"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files(cell):
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] == 1
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (HERE / "drivers" / f"{traffic['entry']}.py").is_file()
    limits = json.loads((HERE / "workloads" / f"{cell['name']}.json").read_text())["limits"]
    assert limits["budget_violations"] == 0
    reported = [m for m in METRICS if cell["name"] in m.get("workloads", [cell["name"]])]
    e2e = {m["name"] for m in reported if m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(m in BENCH["per_layer"] for m in reported)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (HERE / "metrics" / f"{metric['name']}.py").is_file()
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        names = {m["name"] for m in BENCH["end_to_end"]}
        assert metric["moves"] in names and metric["moves"] != "setup_s"
        assert "\n" not in metric["layer"]


def test_names_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
