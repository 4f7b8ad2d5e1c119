"""BENCHMARK.json and every data file of the benchmark validate against
the contract: names, units, arrows, files found by name."""

import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return _load(ROOT, "BENCHMARK.json")


def test_manifest_has_exactly_the_contract_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_names_units_and_sources(manifest):
    names = []
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(m["name"])
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    assert "workloads" not in next(
        m for m in manifest["end_to_end"] if m["name"] == "setup_s")


def test_cells_and_configurations(manifest):
    cfgs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert len(cells) == len(manifest["workloads"])
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        # the cell's own file holds what the manifest does not say,
        # and says nothing twice
        f = _load(BENCH, "workloads", w["name"] + ".json")
        assert not set(f) & set(w), (w["name"], set(f) & set(w))
        assert {"generator", "params", "workers", "max_requests_per_s",
                "limits"} <= set(f), w["name"]
        assert os.path.isfile(
            os.path.join(BENCH, "traffic", f["generator"] + ".py"))
    # no orphan: a cell's file that the manifest does not list
    for path in glob.glob(os.path.join(BENCH, "workloads", "*.json")):
        assert os.path.basename(path)[:-5] in cells, path
    used = {w["config"] for w in manifest["workloads"]}
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        assert os.path.basename(path)[:-5] in cfgs, path
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, "a configuration no cell uses"
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        f = _load(ROOT, c["file"])
        assert f["reduced"] == c["reduced"]
        assert 1 <= len(c["source"]) <= 200
        for key in ("name", "source", "assumed", "guarantees", "schema",
                    "scale", "datagen"):
            assert key in f, (c["name"], key)
        assert f["name"] == c["name"]
        assert os.path.isfile(
            os.path.join(BENCH, "datagen", f["datagen"] + ".py"))
        # the plain reference lies beside the configuration's file
        assert os.path.isfile(os.path.join(
            BENCH, "configs",
            f.get("reference", c["name"]) + ".reference.py"))
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def test_every_arrow_lands(manifest):
    """Every `moves` names an end-to-end metric that all the metric's
    cells report; every cell reports setup_s, another end-to-end metric
    and a per-layer metric."""
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for w in m.get("workloads", cells):
            assert w in cells, (m["name"], w)
            assert w in e2e[m["moves"]], (m["name"], w, m["moves"])
    for w in cells:
        assert sum(1 for ws in e2e.values() if w in ws) >= 2, w
        assert any(w in m.get("workloads", cells)
                   for m in manifest["per_layer"]), w


def test_every_metric_has_its_reader_file(manifest):
    for m in manifest["per_layer"]:
        spec = _load(BENCH, "metrics", m["name"] + ".json")
        # the reader's own keys only: the manifest says the rest once
        assert not set(spec) & set(m), (m["name"], set(spec) & set(m))
        assert os.path.isfile(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    # no orphan: a metric file that the manifest does not list
    listed = {m["name"] for m in manifest["per_layer"]}
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        assert os.path.basename(path)[:-5] in listed, path


def test_files_under_paths_have_contract_names(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs
                       if x not in ("__pycache__", ".run")]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel


def test_peaks_table_names_its_source():
    peaks = _load(BENCH, "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
