"""BENCHMARK.json against the contract's limits, and every name in it
against the files it has to resolve to."""

import json
import os
import re

import pytest

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.benchmark()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if "layer" in metric:
        allowed |= {"layer", "moves"}
        moved = [m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"]]
        assert len(moved) == 1
        decl = spec.layer_metric(metric["name"])
        assert decl["layer"] == metric["layer"]
        assert decl["unit"] == metric["unit"]
        assert decl["moves"] == metric["moves"]
        assert hasattr(spec.reader(decl["reader"]), "read")
        for cell in metric.get("workloads", []):
            assert spec.Cell(cell).reports(moved[0])
    else:
        allowed |= {"bound"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    assert set(metric) <= allowed


def test_names_are_unique():
    for key in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[key]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_cell_resolves(workload):
    for key in ("name", "config", "traffic"):
        assert NAME.match(workload[key])
    assert workload["chips"] in (1, 4)
    assert 1 <= len(workload["why"]) <= 200
    cell = spec.Cell(workload["name"])
    assert hasattr(cell.driver(), "run")
    assert hasattr(cell.reference(), "sizes_of")
    # weights and their layout are found by the family, like the reference
    assert hasattr(cell.weights(), "make_leaf")
    assert hasattr(cell.layout(), "to_program_params")
    assert "setup_s" in [m["name"] for m in cell.end_to_end()]
    assert len(cell.end_to_end()) >= 2 and cell.per_layer()
    assert cell.depth >= 1


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    assert config["file"].startswith("chipbench/")
    with open(os.path.join(spec.ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"]
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    for key in config["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|_size)$", key)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
