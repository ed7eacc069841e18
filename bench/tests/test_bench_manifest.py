"""The manifest keeps to the benchmark contract's names, units and layout."""
import json
import os
import re

import pytest

from bench import harness

M = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def names():
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in M[sec]:
            yield sec, e["name"]
    for w in M["workloads"]:
        yield "config", w["config"]
        yield "traffic", w["traffic"]
    for c in M["configs"]:
        for k in c["reduced"]:
            yield "reduced", k


@pytest.mark.parametrize("where,name", list(names()))
def test_names_use_allowed_characters(where, name):
    assert NAME.match(name), (where, name)


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_units_and_direction(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    assert any(e["name"] == "setup_s" for e in M["end_to_end"])
    for e in M["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(M)) < 64 * 1024
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("w", M["workloads"], ids=lambda w: w["name"])
def test_cell_files_found_by_name(w):
    cell = harness.Cell(w["name"], 1, 1.0, False)
    assert cell.traffic["kind"] in ("train", "serve")
    assert set(cell.limits)
    assert len(w["why"]) <= 200


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_cells_report_what_they_move(m):
    assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                       m["name"] + ".py"))
    for w in m["workloads"]:
        cell = harness.Cell(w, 1, 1.0, False)
        assert m["moves"] in {e["name"] for e in cell.end_to_end}, w
        assert m["name"] in {p["name"] for p in cell.per_layer}


@pytest.mark.parametrize("c", M["configs"], ids=lambda c: c["name"])
def test_configs_state_source_cuts_and_precision(c):
    cfg = harness.load_json("configs", c["name"] + ".json")
    assert c["file"] == f"bench/configs/{c['name']}.json"
    assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    assert isinstance(cfg["assumed"], list)
    assert cfg["dtype"] == "float32"
    assert cfg["matmul_precision"] == "highest"
    assert any(w["config"] == c["name"] for w in M["workloads"])
