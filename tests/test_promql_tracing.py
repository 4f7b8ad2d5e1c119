"""One stage tree per PromQL request over HTTP, as `/v1/sql` has one
(tests/test_tracing.py): `http.read`, `promql.parse`, `promql.resolve`,
`promql.plan`, `device.execute`, `promql.assemble`, `http.encode`,
`http.send`, each once and flat, and their time by name in
`gtpu_span_seconds`; the selector grid's build under `grid.build`; the
remote-write decode under `prom_write.decode`."""

import json
import os
import sys
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datagen import prom_hist as dg  # noqa: E402
from greptimedb_tpu.instance import Standalone  # noqa: E402
from greptimedb_tpu.promql import fast as F  # noqa: E402
from greptimedb_tpu.telemetry import tracing  # noqa: E402
from test_tracing import _family, _finished_trace, _render  # noqa: E402

STAGES = ("http.read", "promql.parse", "promql.resolve", "promql.plan",
          "device.execute", "promql.assemble", "http.encode", "http.send")
ROOT_SPAN = "http /v1/prometheus/api/v1/*"


def _count(name: str) -> float:
    return _family(_render(), "gtpu_span_seconds_count", name=name) or 0.0


@pytest.fixture
def prom(tmp_path):
    """A server that 16 staggered instances have remote-written 10
    minutes of one histogram to; yields (port, ask, bodies sent)."""
    from greptimedb_tpu.servers.http import HttpServer

    tracing.global_traces.clear()
    F.invalidate_cache()
    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    srv = HttpServer(inst, port=0).start()
    ds = dg.make(np, 17, {"instances": 16, "minutes": 10})

    def post(path, body, **headers):
        return urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{srv.port}{path}", data=body,
            headers=headers), timeout=120).read()

    heads = dg.series_heads(ds)
    for k in range(ds.scrapes):
        for body in dg.scrape_bodies(np, ds, k, np.arange(16), heads):
            post("/v1/prometheus/write", body, **dg._RW_HEADERS,
                 traceparent=f"00-{k + 1:032x}-{'cd' * 8}-01")

    def ask(phi, end_min, tid):
        end = dg.BASE_MS // 1000 + end_min * 60
        body = urllib.parse.urlencode({
            "query": f"histogram_quantile({phi}, sum by (le) "
                     f"(rate({dg.METRIC}[5m])))",
            "start": end - 240, "end": end, "step": 15}).encode()
        doc = json.loads(post("/v1/prometheus/api/v1/query_range", body,
                              traceparent=f"00-{tid}-{'cd' * 8}-01"))
        assert doc["status"] == "success"
        assert len(doc["data"]["result"][0]["values"]) == 17
        return _finished_trace(tid)

    try:
        yield srv.port, ask
    finally:
        srv.stop()
        inst.close()
        F.invalidate_cache()
        tracing.global_traces.clear()


def _tree(spans, tid):
    by_id = {s["span_id"]: s for s in spans}
    root = next(s for s in spans if s["name"] == ROOT_SPAN)
    stages = [s for s in spans if s["name"] in STAGES]
    # each stage once
    assert sorted(s["name"] for s in stages) == sorted(STAGES)
    for s in stages:
        assert s["trace_id"] == tid
        up = s
        while up["span_id"] != root["span_id"]:     # under the request
            up = by_id[up["parent_id"]]
        # flat: no stage inside another, so their times add up
        assert by_id[s["parent_id"]]["name"] not in STAGES, s["name"]
    return root, {s["name"]: s for s in stages}


def test_one_query_range_request_yields_the_stage_tree(prom):
    _port, ask = prom
    m0 = {name: _count(name) for name in STAGES + (ROOT_SPAN, "grid.build")}
    # the first query finds no grid: it builds one inside promql.resolve
    tid = "a1" * 16
    spans = ask(0.9, 9, tid)
    _root, stages = _tree(spans, tid)
    assert stages["promql.resolve"]["attributes"]["grid_cache"] == "miss"
    (build,) = [s for s in spans if s["name"] == "grid.build"]
    assert build["attributes"]["site"] == "promql_grid"
    assert build["parent_id"] == stages["promql.resolve"]["span_id"]
    # the next, with another quantile and end, hits it
    tid = "b2" * 16
    spans = ask(0.5, 10, tid)
    root, stages = _tree(spans, tid)
    assert stages["promql.resolve"]["attributes"]["grid_cache"] == "hit"
    assert not [s for s in spans if s["name"] == "grid.build"]
    dev = stages["device.execute"]
    assert dev["attributes"]["site"] == "promql_histogram"
    assert "execute_ms" in dev["attributes"]
    covered = sum(s["duration_ms"] for s in stages.values())
    assert covered <= root["duration_ms"] * 1.01
    time.sleep(0.1)     # the roots close after the last byte
    for name in STAGES + (ROOT_SPAN,):
        assert _count(name) - m0[name] == 2, name
    assert _count("grid.build") - m0["grid.build"] == 1


def test_remote_write_decode_has_its_span(prom):
    spans = _finished_trace(f"{1:032x}")
    names = [s["name"] for s in spans]
    assert names.count("http /v1/prometheus/write") == 1
    assert names.count("prom_write.decode") == 1
    assert names.count("http.read") == 1 and names.count("http.send") == 1
    decode = next(s for s in spans if s["name"] == "prom_write.decode")
    assert decode["attributes"]["body_bytes"] > 0
    # the write path's own stages beneath the same request
    assert {"wal.append", "memtable.append"} <= set(names)
