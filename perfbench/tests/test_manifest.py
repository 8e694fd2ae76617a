"""BENCHMARK.json against the contract's rules that can be checked here, and
against the benchmark's own files."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["config"])
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        names.append(w["name"])
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_reports_what_its_layer_metrics_move(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        # without a list of cells: every cell that reports what it moves
        for cell in m.get("workloads", e2e[m["moves"]]):
            assert cell in cells
            assert cell in e2e[m["moves"]], (m["name"], cell)
    for cell in cells:
        assert cell in e2e["setup_s"]
        assert any(cell in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(cell in m.get("workloads", e2e[m["moves"]])
                   for m in manifest["per_layer"])
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_the_files_a_name_stands_for_exist_and_agree(manifest):
    for c in manifest["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            BENCH, "reference", conf["reference"] + ".py"))
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    for m in manifest["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            d = json.load(f)
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert d[k] == m[k], (m["name"], k)
        # which cells report it is BENCHMARK.json's to say, and only its:
        # a later PR adds a cell to a metric without editing the metric's file
        assert "workloads" not in d
        assert "reads" in d and "what" in d
    with open(os.path.join(BENCH, "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)
