"""The per-layer metrics that read the program's stage spans, its
pause families and its set-up totals: every metric file loads and
names a reader that is there, `total_at_open` over a hand-made scrape,
and a CPU rehearsal prints a number for every one of them.

The throughput cell's stage metrics are data under
`data/stage-metrics-w50/` and no entries of BENCHMARK.json yet:
test_bench_rehearsal.py holds that cell's rehearsal to exactly its
three accepted metrics, and that file is not edited here (PERF.md, Open
questions). They are rehearsed in a temporary copy of the benchmark to
which they are added as files and entries, as `tsbs-load` is."""

import json
import os
import shutil

import pytest

from conftest import HERE, copy_checkout
from test_bench_rehearsal import QUERY_SCALE, _check_line, _rehearse

from benchmark.lib.server import parse_metrics
from benchmark.readers import total_at_open

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
W50_DATA = os.path.join(HERE, "data", "stage-metrics-w50")
LATENCY = "tsbs-single-groupby-1-1-1"
THROUGHPUT = "tsbs-single-groupby-1-1-1-w50"
STAGES = ("http_read", "fingerprint", "parse", "admit", "plan",
          "select_series", "grid_lookup", "device_call", "assemble",
          "encode", "send", "stats_drain")
SETUP = ("setup_flight_decode_s", "setup_tag_columns_s", "setup_intern_s",
         "setup_wal_append_s", "setup_memtable_s", "setup_flush_s",
         "setup_grid_build_s", "setup_compile_s")
LATENCY_METRICS = (
    [f"{s}_ms_per_query" for s in STAGES]
    + ["gc_pause_ms_per_s.query", "background_ms_per_s.query",
       "compiles_per_query"] + list(SETUP))
THROUGHPUT_METRICS = (
    [f"{s}_cpu_ms_per_query.throughput" for s in STAGES]
    + ["request_cpu_ms_per_query.throughput", "admit_wait_ms.throughput",
       "gc_pause_ms_per_s.throughput", "background_ms_per_s.throughput"]
    + [s + ".throughput" for s in SETUP])
# the families of the program that these metrics read
FAMILIES = {"gtpu_span_seconds_sum", "gtpu_span_seconds",
            "gtpu_span_cpu_seconds_total",
            "gtpu_runtime_gc_pause_seconds_sum",
            "gtpu_background_task_seconds_sum",
            "gtpu_device_program_compiles_total",
            "gtpu_device_program_compile_ms"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _families(spec: dict) -> set:
    sides = [spec] + [spec[k] for k in ("num", "den") if k in spec]
    return {s["family"] for s in sides if "family" in s}


@pytest.mark.parametrize("name", LATENCY_METRICS)
def test_latency_cell_metric_is_listed_and_loads(name):
    entry = next(m for m in _load(ROOT, "BENCHMARK.json")["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == [LATENCY]
    assert entry["moves"] == ("setup_s" if name in SETUP
                              else "query_p50_ms")
    spec = _load(BENCH, "metrics", name + ".json")
    assert os.path.isfile(
        os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert _families(spec) and _families(spec) <= FAMILIES, spec


@pytest.mark.parametrize("name", THROUGHPUT_METRICS)
def test_throughput_cell_metric_loads_from_its_data_directory(name):
    entries = {m["name"]: m
               for m in _load(W50_DATA, "manifest.json")["per_layer"]}
    assert set(entries) == set(THROUGHPUT_METRICS)
    assert entries[name]["workloads"] == [THROUGHPUT]
    assert entries[name]["moves"] == (
        "setup_s" if name.startswith("setup_") else "queries_per_s")
    spec = _load(W50_DATA, "metrics", name + ".json")
    assert not set(spec) & set(entries[name])
    assert os.path.isfile(
        os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert _families(spec) and _families(spec) <= FAMILIES, spec


AT_OPEN = """span_seconds_sum{name="write.decode"} 12.5
span_seconds_sum{name="wal.append"} 3.0
span_seconds_sum{name="sql.parse"} 0.25
compile_ms{site="range",program="a"} 1500
compile_ms{site="range_prelude",program="b"} 500
"""
LATER = """span_seconds_sum{name="write.decode"} 99.0
span_seconds_sum{name="grid.build"} 7.0
"""


@pytest.mark.parametrize("spec,want", [
    ({"family": "span_seconds_sum", "labels": {"name": "write.decode"}},
     12.5),
    ({"family": "span_seconds_sum",
      "labels": {"name": ["wal.append", "sql.parse"]}}, 3.25),
    ({"family": "span_seconds_sum"}, 15.75),
    ({"family": "compile_ms", "scale": 0.001}, 2.0),
    ({"family": "compile_ms", "labels": {"site": "range"},
      "scale": 0.001}, 1.5),
    # what appears only after the window opened is not set-up's
    ({"family": "span_seconds_sum", "labels": {"name": "grid.build"}},
     None),
    ({"family": "absent_total"}, None),
], ids=["one-label", "alternatives", "all-labels", "scaled",
        "scaled-one-label", "only-later", "absent"])
def test_total_at_open(spec, want):
    ctx = {"m0": parse_metrics(AT_OPEN), "m1": parse_metrics(LATER)}
    got = total_at_open.read(spec, ctx)
    if want is None:
        assert got is None
    else:
        assert isinstance(got, float) and got == pytest.approx(want)


def test_latency_rehearsal_prints_every_stage_metric(tmp_path):
    p, line = _rehearse(LATENCY, str(tmp_path / "state"), QUERY_SCALE,
                        trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is True
    got = line["metrics"]
    for name in LATENCY_METRICS:
        assert name in got, name
        assert got[name]["value"] >= 0.0
    # the stages a query cannot do without took time, and a warm
    # window compiles nothing
    for s in ("parse", "plan", "select_series", "device_call", "assemble",
              "encode", "send"):
        assert got[f"{s}_ms_per_query"]["value"] > 0.0, s
    assert got["compiles_per_query"]["value"] == 0.0
    for name in ("setup_flight_decode_s", "setup_tag_columns_s",
                 "setup_intern_s", "setup_wal_append_s",
                 "setup_grid_build_s", "setup_compile_s"):
        assert got[name]["value"] > 0.0, name
    # the stages lie inside the server's time in a request
    stages = sum(got[f"{s}_ms_per_query"]["value"] for s in STAGES)
    assert 0.5 * got["http_server_ms.query"]["value"] < stages
    assert stages < got["http_server_ms.query"]["value"]


def test_throughput_rehearsal_prints_every_stage_metric(tmp_path):
    root = str(tmp_path / "checkout")
    manifest = copy_checkout(root)
    shutil.copytree(os.path.join(W50_DATA, "metrics"),
                    os.path.join(root, "benchmark", "metrics"),
                    dirs_exist_ok=True)
    manifest["per_layer"].extend(
        _load(W50_DATA, "manifest.json")["per_layer"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    p, line = _rehearse(THROUGHPUT, str(tmp_path / "state"), QUERY_SCALE,
                        trace=1, cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is True
    _check_line(line, {m["name"] for m in manifest["per_layer"]})
    got = line["metrics"]
    for name in THROUGHPUT_METRICS:
        assert name in got, name
        assert got[name]["value"] >= 0.0
    # a request's CPU holds its stages' CPU (both are read for the same
    # one request in `tracing._CPU_EVERY`, so a short window may read 0)
    stages = sum(got[f"{s}_cpu_ms_per_query.throughput"]["value"]
                 for s in STAGES)
    assert stages <= got["request_cpu_ms_per_query.throughput"]["value"]
