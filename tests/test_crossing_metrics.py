"""The seven per-layer metrics that read the crossing's legs by site
(PR 37): each is a data file for the benchmark's `ratio_of_deltas`
reader over `gtpu_device_program_{dispatch,wait,readback}_ms_total` and
`gtpu_device_program_calls_total`, both with the metric's `site`. Each
entry is listed, its file loads, and its reader resolves against two
scrapes of a server that answered a range query (site `range`), one
over a table written to since (site `grid_upkeep`) and a PromQL
`histogram_quantile` (site `promql_histogram`)."""

import json
import os
import sys
import urllib.parse
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datagen import prom_hist as dg  # noqa: E402
from benchmark.lib import files  # noqa: E402
from benchmark.lib.server import parse_metrics  # noqa: E402
from greptimedb_tpu.instance import Standalone  # noqa: E402
from greptimedb_tpu.promql import fast as F  # noqa: E402
from greptimedb_tpu.telemetry import device_programs as DP  # noqa: E402

LATENCY, FLEET = "tsbs-single-groupby-1-1-1", "tsbs-double-groupby-all"
LIVE, PROMQL = "tsbs-panel-under-ingest", "prom-100k-histogram-quantile"

# name -> (site, leg, the end-to-end metric it moves, its cells)
METRICS = {
    "range_dispatch_ms_per_call": (
        "range", "dispatch", "query_p50_ms", [LATENCY, FLEET, LIVE]),
    "range_wait_ms_per_call": (
        "range", "wait", "query_p50_ms", [LATENCY, FLEET, LIVE]),
    "range_readback_ms_per_call": (
        "range", "readback", "query_p50_ms", [LATENCY, FLEET, LIVE]),
    "upkeep_dispatch_ms_per_call": (
        "grid_upkeep", "dispatch", "query_p95_ms", [LIVE]),
    "hist_dispatch_ms_per_call": (
        "promql_histogram", "dispatch", "query_p50_ms", [PROMQL]),
    "hist_wait_ms_per_call": (
        "promql_histogram", "wait", "query_p50_ms", [PROMQL]),
    "hist_readback_ms_per_call": (
        "promql_histogram", "readback", "query_p50_ms", [PROMQL]),
}

RANGE_Q = ("SELECT ts, host, max(v) RANGE '60s' FROM cpu WHERE host = "
           "'h{}' ALIGN '60s' BY (host)")


@pytest.fixture(scope="module")
def scrapes(tmp_path_factory):
    """(scrape before, scrape after) around three rounds of the three
    kinds of call; a round before the first scrape has compiled each
    program, so the window holds steady-state calls only."""
    from greptimedb_tpu.servers.http import HttpServer

    old_cfg = DP.global_programs.config
    DP.global_programs.config = DP.ProfilingConfig(analysis=False)
    DP.global_programs.reset()
    F.invalidate_cache()
    inst = Standalone(str(tmp_path_factory.mktemp("crossing")),
                      prefer_device=True, warm_start=False)
    srv = HttpServer(inst, port=0).start()

    def post(path, body, **headers):
        return urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{srv.port}{path}", data=body,
            headers=headers), timeout=120).read()

    def scrape():
        return parse_metrics(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=60
        ).read().decode())

    inst.execute_sql("create table cpu (ts timestamp time index, "
                     "host string primary key, v double)")
    tab = inst.catalog.table("public", "cpu")
    hosts, ticks = 8, 120

    def write(tick0, n):
        ts = np.tile((tick0 + np.arange(n, dtype=np.int64)) * 10_000, hosts)
        hs = np.repeat(np.asarray([f"h{i}" for i in range(hosts)], object),
                       n)
        tab.write({"host": hs}, ts, {"v": (ts % 97).astype(np.float64)})

    write(0, ticks)
    ds = dg.make(np, 17, {"instances": 16, "minutes": 10})
    heads = dg.series_heads(ds)
    for k in range(ds.scrapes):
        for body in dg.scrape_bodies(np, ds, k, np.arange(16), heads):
            post("/v1/prometheus/write", body, **dg._RW_HEADERS)

    def round_(i):
        # a body, then a panel over the table: the grid is brought
        # forward by one dispatch of the upkeep, then the range program
        write(ticks + i, 1)
        doc = json.loads(post("/v1/sql", urllib.parse.urlencode(
            {"sql": RANGE_Q.format(i % hosts)}).encode()))
        assert doc["output"][0]["records"]["rows"]
        end = dg.BASE_MS // 1000 + (9 - i % 3) * 60
        doc = json.loads(post(
            "/v1/prometheus/api/v1/query_range", urllib.parse.urlencode({
                "query": f"histogram_quantile(0.{5 + i}, sum by (le) "
                         f"(rate({dg.METRIC}[5m])))",
                "start": end - 240, "end": end, "step": 15}).encode()))
        assert doc["status"] == "success"

    try:
        for i in range(2):      # the grid's build, then every compile
            round_(i)
        m0 = scrape()
        for i in range(2, 5):
            round_(i)
        yield m0, scrape()
    finally:
        srv.stop()
        inst.close()
        F.invalidate_cache()
        DP.global_programs.config = old_cfg
        DP.global_programs.reset()


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_is_listed_loads_and_reads_a_scrape(name, scrapes):
    site, leg, moves, cells = METRICS[name]
    manifest = files.load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "ms/call", "better": "lower",
        "source": "program_counter", "moves": moves, "workloads": cells,
        "layer": entry["layer"],
    }
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"]
                              if m["name"] not in METRICS}
    spec = files.load_json(files.BENCH, "metrics", name + ".json")
    assert spec == {
        "reader": "ratio_of_deltas", "scale": 1.0,
        "num": {"family": f"gtpu_device_program_{leg}_ms_total",
                "labels": {"site": site}},
        "den": {"family": "gtpu_device_program_calls_total",
                "labels": {"site": site}},
    }
    reader = files.module("readers", spec["reader"])
    m0, m1 = scrapes
    ctx = {"m0": m0, "m1": m1, "client": {}}
    got = reader.read(spec, ctx)
    # three calls of the site in the window, all steady state: the
    # reading is a time a call, of this site alone
    calls = [v - m0.get(k, 0.0) for k, v in m1.items()
             if k[0] == "gtpu_device_program_calls_total"
             and dict(k[1])["site"] == site]
    assert sum(calls) == 3, calls
    assert got is not None and 0 <= got < 1000.0
    if leg != "wait":
        assert got > 0      # (a wait can round to nothing on a CPU)
    # a program without the family (the parent): nothing, not an error
    bare = {k: v for k, v in m1.items()
            if k[0] != spec["num"]["family"]}
    assert reader.read(spec, {"m0": m0, "m1": bare, "client": {}}) is None


def test_the_throughput_cell_lists_none_of_them():
    manifest = files.load_json(ROOT, "BENCHMARK.json")
    for m in manifest["per_layer"]:
        if m["name"] in METRICS:
            assert "tsbs-single-groupby-1-1-1-w50" not in m["workloads"]
    assert manifest["per_layer"][-7:] == [
        m for m in manifest["per_layer"] if m["name"] in METRICS]
