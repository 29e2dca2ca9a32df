"""BENCHMARK.json against the files it names: every cell, configuration,
traffic mix and metric is found by file name, every name and unit is
well-formed, and every arrow points at something the cell reports."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as _f:
    MANIFEST = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = MANIFEST["workloads"]
CONFIGS = MANIFEST["configs"]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}


def cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [c["name"] for c in CELLS])


def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert all(not w.startswith("/") and ".." not in w
               for w in MANIFEST["command"])


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_files_exist_and_agree(cell):
    from benchmark.harness import loadgen
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    loaded = loadgen.load_cell(cell["name"])      # raises on any unknown key
    assert (loaded.config_name, loaded.traffic_name, loaded.chips) == \
        (cell["config"], cell["traffic"], cell["chips"])
    assert cell["config"] in {c["name"] for c in CONFIGS}
    reported = [m for m in MANIFEST["end_to_end"]
                if cell["name"] in cells_of(m)]
    assert {"setup_s"} < {m["name"] for m in reported}
    assert any(cell["name"] in cells_of(m) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("config", CONFIGS, ids=lambda c: c["name"])
def test_config_file_states_its_cuts(config):
    assert NAME.match(config["name"])
    assert config["file"].startswith(tuple(MANIFEST["paths"]))
    with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as f:
        data = json.load(f)
    assert set(config["reduced"]) == set(data["reduced"])
    assert all(NAME.match(k) for k in config["reduced"])
    assert data["guarantees"] and data["assumed"] and data["source"]
    assert any(c["config"] == config["name"] for c in CELLS)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_is_well_formed(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert set(cells_of(metric)) <= {c["name"] for c in CELLS}
    if metric["name"] in E2E:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
        return
    # a per-layer metric: a reader of its own, a layer, and an arrow to
    # an end-to-end metric that each of its cells reports
    reader = os.path.join(ROOT, "benchmark", "metrics",
                          metric["name"] + ".py")
    assert os.path.isfile(reader)
    assert metric["layer"] and "\n" not in metric["layer"]
    target = E2E[metric["moves"]]
    assert set(cells_of(metric)) <= set(cells_of(target))
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
