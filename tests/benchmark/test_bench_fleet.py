"""The cell `tsbs-double-groupby-all` over the configuration
`tsbs-cpu-4000-fleet`: its CPU rehearsal prints every metric the cell
lists that a CPU run can read and comes out `correct`, an answer
altered where it is produced comes out not `correct`, the control at a
small size fails `worst_rel_err` in bfloat16 and passes in float32, and
the metric files this cell brought load and name readers that exist."""

import os

import numpy as np
import pytest

from test_bench_rehearsal import _check_line, _names, _rehearse

from benchmark import control
from benchmark.lib.files import load_json as _load, module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "tsbs-double-groupby-all"
CONFIG = "tsbs-cpu-4000-fleet"
HOSTS, HOURS = 64, 3
FLEET_SCALE = ["--scale", f"hosts={HOSTS}", "--scale", f"hours={HOURS}"]
# what the cell brought: metrics of its own, and twins of entries that
# test_bench_stage_metrics.py holds to the latency cell alone
OWN = ("rows_build_ms_per_query", "json_dumps_ms_per_query",
       "response_bytes_per_query", "rows_per_query")
TWINS = {
    "device_call_ms_per_query.fleet": "device_call_ms_per_query",
    "assemble_ms_per_query.fleet": "assemble_ms_per_query",
    "encode_ms_per_query.fleet": "encode_ms_per_query",
    "send_ms_per_query.fleet": "send_ms_per_query",
    "gc_pause_ms_per_s.fleet": "gc_pause_ms_per_s.query",
    "setup_grid_build_s.fleet": "setup_grid_build_s",
    "setup_compile_s.fleet": "setup_compile_s",
}
# accepted metrics whose lists the cell was appended to
SHARED = ("http_server_ms.query", "stmt_ms", "dispatches_per_query",
          "program_wait_ms_per_query", "readback_bytes_per_query",
          "device_busy_ms_per_query", "device_idle_share.query",
          "hbm_bytes_in_use", "range_roofline")
FAMILIES = {"gtpu_span_seconds_sum", "gtpu_runtime_gc_pause_seconds_sum",
            "gtpu_device_program_compile_ms",
            "gtpu_http_response_bytes_total",
            "gtpu_query_rows_returned_total"}


def _entry(name: str) -> dict:
    return next(m for m in _load(ROOT, "BENCHMARK.json")["per_layer"]
                if m["name"] == name)


@pytest.fixture(scope="module")
def state(tmp_path_factory):
    return str(tmp_path_factory.mktemp("fleet_state"))


@pytest.mark.parametrize("name", OWN + tuple(TWINS))
def test_the_cells_own_metric_is_listed_and_loads(name):
    entry = _entry(name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == ("setup_s" if name.startswith("setup_")
                              else "query_p50_ms")
    spec = _load(BENCH, "metrics", name + ".json")
    assert hasattr(module("readers", spec["reader"]), "read")
    sides = [spec] + [spec[k] for k in ("num", "den") if k in spec]
    families = {s["family"] for s in sides if "family" in s}
    assert families and families <= FAMILIES, spec
    if name in TWINS:
        # same reader and keys, same unit, source and layer
        assert spec == _load(BENCH, "metrics", TWINS[name] + ".json")
        twin = _entry(TWINS[name])
        for k in ("unit", "better", "source", "layer", "moves"):
            assert entry[k] == twin[k], (name, k)


@pytest.mark.parametrize("name", SHARED)
def test_an_accepted_metrics_list_ends_with_the_cell(name):
    assert _entry(name)["workloads"][-1] == CELL


def test_the_configuration_states_what_the_contract_asks():
    cfg = _load(BENCH, "configs", CONFIG + ".json")
    src = _load(BENCH, "configs", "tsbs-cpu-4000.json")
    assert cfg["reference"] == "tsbs-cpu-4000"
    assert cfg["scale"] == {"hosts": 4000, "hours": 8}
    assert cfg["source_scale"] == {"hosts": 4000, "hours": 72,
                                   "query_window_hours": 12}
    assert cfg["reduced"] == ["hours", "query_window_hours"]
    # one schema, and the guarantees and the precision of the shared
    # reference's own configuration
    for k in ("schema", "guarantees", "precision", "datagen", "table"):
        assert cfg[k] == src[k], k
    assert cfg["grid_warm_sql"] == src["grid_warm_sql"][:1]
    assert {"query_text", "query_window"} <= set(cfg["assumed"])
    for k in ("reduced_why", "device_bytes"):
        assert len(cfg[k]) > 40, k
    wl = _load(BENCH, "workloads", CELL + ".json")
    assert wl["params"] == {"agg": "avg", "fields": 10, "hosts": 0,
                            "span_hours": 0, "bucket_s": 3600}
    assert wl["workers"] == 1 and wl["max_requests_per_s"] == 40
    assert wl["limits"] == {"rows_missing": 0, "worst_rel_err": 2.4e-7}


def test_the_rehearsal_prints_every_metric_the_cell_lists(state):
    p, line = _rehearse(CELL, state, FLEET_SCALE, seconds=3, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    listed = _names("per_layer", CELL)
    _check_line(line, listed)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    on_cpu = {n for n in listed if not getattr(module(
        "readers", _load(BENCH, "metrics", n + ".json")["reader"]),
        "DEVICE", False)}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == on_cpu
    assert set(OWN) | set(TWINS) <= on_cpu
    rows = HOSTS * HOURS
    assert got["rows_per_query"] == rows
    # ten float32 planes of (hosts, hours), the activity flags of a
    # window the memo does not know, the extent
    assert got["readback_bytes_per_query"] == 10 * rows * 4 + HOSTS + 16
    assert got["dispatches_per_query"] == 1.0
    assert got["response_bytes_per_query"] > 100 * rows
    assert 0 < got["rows_build_ms_per_query"]
    assert 0 < got["json_dumps_ms_per_query"]
    assert (got["rows_build_ms_per_query"] + got["json_dumps_ms_per_query"]
            <= got["encode_ms_per_query.fleet"])
    for k in ("device_call_ms_per_query.fleet", "assemble_ms_per_query.fleet",
              "send_ms_per_query.fleet", "setup_grid_build_s.fleet",
              "setup_compile_s.fleet"):
        assert got[k] > 0, k
    assert got["gc_pause_ms_per_s.fleet"] >= 0
    assert line["notes"]["in_window"] == {
        "compiles": 0, "programs_new": 0, "compaction_merges": 0}
    cmp = line["compared"]
    assert cmp["rows_missing"] == {"value": 0, "limit": 0}
    assert cmp["worst_rel_err"]["value"] <= cmp["worst_rel_err"]["limit"] \
        == 2.4e-7
    assert cmp["queries_off_device"]["value"] == 0
    assert cmp["rows_acked_not_counted"]["value"] == 0
    # every answer compared, at 10 values a row
    assert line["notes"]["compared_answers"] == line["attempted"] > 5
    assert line["notes"]["compared_values"] == line["attempted"] * rows * 10
    assert not os.path.exists(os.path.join(state, "home-" + CELL))


def test_an_untraced_run_prints_the_cells_end_to_end_metrics(state):
    p, line = _rehearse(CELL, state, FLEET_SCALE, seed=2**31 + 3302)
    assert p.returncode == 0, p.stderr[-3000:]
    _check_line(line, _names("end_to_end", CELL))
    assert set(line["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                    "setup_s"}
    assert line["correct"] is True


ALTER_THE_LAST_FIELD = """
def alter(run):
    # an answer altered where it is produced: one host's rows of the
    # tenth field overwritten behind the reference's back
    run.srv.sql("insert into cpu (ts, hostname, region, datacenter, rack, "
                "os, arch, team, service, service_version, "
                "service_environment, usage_guest_nice) select ts, "
                "hostname, region, datacenter, rack, os, arch, team, "
                "service, service_version, service_environment, "
                "usage_guest_nice + 0.015625 from cpu "
                "where hostname = 'host_7'")
HOOKS = {"before_window": alter}
"""


def test_an_altered_answer_comes_out_not_correct(state):
    p, line = _rehearse(CELL, state, FLEET_SCALE,
                        hooks_code=ALTER_THE_LAST_FIELD)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    # a sixty-fourth on every row of one host's one field: far above a
    # float32 rounding, and no row goes missing
    assert line["compared"]["worst_rel_err"]["value"] > 1e-4
    assert line["compared"]["rows_missing"]["value"] == 0


@pytest.mark.parametrize("seed", [3, 2**31 + 3303, 2**31 + 3304])
def test_the_control_fails_in_bfloat16_and_passes_in_float32(seed):
    doc = control.read(np, CELL, seed, 3, {"hosts": HOSTS, "hours": HOURS})
    limit = doc["limits"]["worst_rel_err"]
    assert doc["stated_precision_passes"] and doc["control_fails"]
    assert doc["float32"]["worst_rel_err"] <= limit / 2
    assert doc["float32"]["rows_missing"] == 0
    # by the limit of the values, not by a missing row
    assert doc["bfloat16"]["rows_missing"] == 0
    assert doc["bfloat16"]["worst_rel_err"] > 1000 * limit
