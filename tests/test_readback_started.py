"""A call that dispatched starts the device->host copy of exactly what
it reads right after the dispatch (`device_call.start`, through
query/readback.start), so its read finds the bytes on their way:
`gtpu_readback_crossings_total{site, started}` counts each crossing
under `dispatch` where its arrays were started there, and under `read`
otherwise (a session hit dispatches nothing and starts nothing). The
start adds no byte to what crosses, and a call that reads nothing (the
grid's upkeep) counts no crossing."""

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
from greptimedb_tpu.query import device_range, readback, sessions  # noqa: E402
from greptimedb_tpu.telemetry import device_trace  # noqa: E402
from greptimedb_tpu.telemetry.metrics import global_registry  # noqa: E402

pytest.importorskip("jax")

HOSTS, CELLS = 96, 120      # more hosts than the rows program takes


def _crossings() -> dict:
    """{(site, started): count} of every crossing so far."""
    return {k: c.value for k, c in device_trace._CROSSINGS._snapshot()}


def _moved(before: dict) -> dict:
    """The crossings counted since `before`, zeros left out."""
    return {k: v - before.get(k, 0.0) for k, v in _crossings().items()
            if v - before.get(k, 0.0)}


def _bytes() -> float:
    return readback.readback_bytes("full") + readback.readback_bytes("delta")


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    """A small `cpu` table on the device path; yields (instance, query
    maker, writer of one more tick)."""
    inst = Standalone(str(tmp_path_factory.mktemp("started")),
                      warm_start=False, prefer_device=True)
    inst.sql("CREATE TABLE cpu (hostname STRING, usage_user DOUBLE, "
             "ts TIMESTAMP TIME INDEX, PRIMARY KEY(hostname))")
    tab = inst.catalog.table("public", "cpu")

    def write(tick0, n):
        host = np.repeat(np.arange(HOSTS), n)
        ts = np.tile((tick0 + np.arange(n, dtype=np.int64)) * 10_000, HOSTS)
        inst._write_columns(tab, {
            "hostname": np.char.add("host_", host.astype(str)).astype(object),
            "usage_user": ((host * 7 + ts // 10_000) % 100).astype(np.float64),
            "ts": ts,
        }, {})

    write(0, CELLS)

    def query(host=None, k=0):
        # a fresh `k` is a fresh literal past the rows held (a multiple
        # of their 10 s cadence): the window memo misses and the program
        # dispatches; no host: the plane path
        where = f"hostname IN ('host_{host}') AND " if host is not None else ""
        return ("SELECT ts, hostname, max(usage_user) RANGE '60s' FROM cpu "
                f"WHERE {where}ts >= 0 AND ts < {1_200_000 + 10_000 * k} "
                "ALIGN '60s' BY (hostname)")

    # compile both programs' shapes before any test counts
    inst.sql(query(0, 100))
    inst.sql(query(None, 100))
    try:
        yield inst, query, write
    finally:
        inst.close()


def _rows(result):
    return sorted(map(tuple, result.rows()))


def test_a_fresh_window_starts_at_the_dispatch_and_its_repeat_reads(db):
    inst, query, _write = db
    c0, b0 = _crossings(), _bytes()
    first = inst.sql(query(3, 1))
    assert _moved(c0) == {("range", "dispatch"): 1}
    fresh_bytes = _bytes() - b0
    assert fresh_bytes > 0
    # the exact repeat is served from the session: nothing dispatched,
    # nothing started, the read opens the copy
    c1, b1 = _crossings(), _bytes()
    again = inst.sql(query(3, 1))
    assert _moved(c1) == {("range", "read"): 1}
    assert _rows(again) == _rows(first) and len(_rows(first)) == 20
    # the session's buffer holds the values alone; the fresh call read
    # them with the activity flags and the extent in one packed array
    assert 0 < _bytes() - b1 < fresh_bytes


@pytest.mark.parametrize("host", [5, None], ids=["rows", "plane"])
def test_the_start_adds_no_byte(db, monkeypatch, host):
    """The same query, once with its copy started at the dispatch and
    once (another fresh literal) with the start taken away: the same
    answer and the same bytes, counted under dispatch and read."""
    inst, query, _write = db
    c0, b0 = _crossings(), _bytes()
    started = inst.sql(query(host, 2))
    started_bytes = _bytes() - b0
    assert _moved(c0) == {("range", "dispatch"): 1}
    monkeypatch.setattr(device_trace.device_call, "start",
                        lambda self, *arrs: None)
    c1, b1 = _crossings(), _bytes()
    unstarted = inst.sql(query(host, 3))
    assert _moved(c1) == {("range", "read"): 1}
    assert _bytes() - b1 == started_bytes
    assert _rows(started) == _rows(unstarted)
    assert len(_rows(started)) == 20 * (1 if host is not None else HOSTS)


def test_a_cursor_poll_starts_only_its_slice(db):
    """A `since` poll on the plane path starts and reads the unseen
    steps alone (mode delta): never the whole result."""
    inst, query, _write = db
    whole = _rows(inst.sql(query(None, 4)))
    d0, f0, c0 = (readback.readback_bytes("delta"),
                  readback.readback_bytes("full"), _crossings())
    tok = sessions.bind_since(900_000)
    try:
        tail = _rows(inst.sql(query(None, 5)))
    finally:
        sessions.reset_since(tok)
    assert _moved(c0) == {("range", "dispatch"): 1}
    assert readback.readback_bytes("full") == f0
    assert 0 < readback.readback_bytes("delta") - d0
    assert tail == [r for r in whole if r[0] > 900_000] and tail


def test_the_upkeep_counts_no_crossing(db):
    """A write brings the grid forward by one dispatch of the upkeep,
    which reads nothing: the only crossing is the query's own."""
    inst, query, write = db
    up = device_range._UPKEEP.labels("append")
    u0, c0 = up.value, _crossings()
    write(CELLS, 1)
    inst.sql(query(7, 6))
    assert up.value == u0 + 1
    assert _moved(c0) == {("range", "dispatch"): 1}


@pytest.fixture
def prom(tmp_path):
    """A server that 16 staggered instances have remote-written 10
    minutes of one histogram to; yields ask(phi, end_min)."""
    from greptimedb_tpu.servers.http import HttpServer

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
            post("/v1/prometheus/write", body, **dg._RW_HEADERS)

    def ask(phi, end_min):
        end = dg.BASE_MS // 1000 + end_min * 60
        doc = json.loads(post(
            "/v1/prometheus/api/v1/query_range", urllib.parse.urlencode({
                "query": f"histogram_quantile({phi}, sum by (le) "
                         f"(rate({dg.METRIC}[5m])))",
                "start": end - 240, "end": end, "step": 15}).encode()))
        assert doc["status"] == "success"
        return doc["data"]["result"]

    try:
        yield ask
    finally:
        srv.stop()
        inst.close()
        F.invalidate_cache()


def test_a_histogram_miss_starts_at_the_dispatch(prom):
    ask = prom
    ask(0.9, 9)                 # the grid's build and the compile
    c0 = _crossings()
    first = ask(0.5, 10)
    assert _moved(c0) == {("promql_histogram", "dispatch"): 1}
    # the same request again: one crossing, whichever way it was served
    c1 = _crossings()
    assert ask(0.5, 10) == first
    assert sum(v for (site, _), v in _moved(c1).items()
               if site == "promql_histogram") == 1


def test_every_label_reads_zero_from_start_up():
    text = global_registry.render()
    for site in ("range", "promql_histogram", "groupby", "compact_merge"):
        for started in ("dispatch", "read"):
            assert (f'gtpu_readback_crossings_total{{site="{site}",'
                    f'started="{started}"}}') in text


# the benchmark's reading of the counter: the share of a site's
# crossings whose copy was started at the dispatch
SHARES = {
    "range_readback_started_share": (
        "range", "query/readback.py",
        ["tsbs-single-groupby-1-1-1", "tsbs-double-groupby-all",
         "tsbs-panel-under-ingest"]),
    "hist_readback_started_share": (
        "promql_histogram", "promql/fast.py",
        ["prom-100k-histogram-quantile",
         "prom-100k-quantile-under-remote-write"]),
}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_the_share_is_listed_and_loads(name):
    site, layer, cells = SHARES[name]
    manifest = files.load_json(ROOT, "BENCHMARK.json")
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": layer,
        "moves": "query_p50_ms", "workloads": cells,
    }
    assert files.load_json(files.BENCH, "metrics", name + ".json") == {
        "reader": "ratio_of_deltas", "scale": 100.0,
        "num": {"family": "gtpu_readback_crossings_total",
                "labels": {"site": site, "started": "dispatch"}},
        "den": {"family": "gtpu_readback_crossings_total",
                "labels": {"site": site}},
    }


def test_the_range_share_reads_a_scrape(db):
    """100 over a window of fresh literals, 50 where half were served
    from the session, nothing from a program without the counter."""
    inst, query, _write = db
    spec = files.load_json(files.BENCH, "metrics",
                           "range_readback_started_share.json")
    reader = files.module("readers", spec["reader"])

    def scrape():
        return parse_metrics(global_registry.render())

    m0 = scrape()
    for k in (40, 41):
        inst.sql(query(2, k))
    m1 = scrape()
    for k in (40, 41):          # the same two again: session hits
        inst.sql(query(2, k))
    m2 = scrape()
    assert reader.read(spec, {"m0": m0, "m1": m1, "client": {}}) == 100.0
    assert reader.read(spec, {"m0": m0, "m1": m2, "client": {}}) == 50.0
    bare = {k: v for k, v in m2.items()
            if k[0] != "gtpu_readback_crossings_total"}
    assert reader.read(spec, {"m0": m0, "m1": bare, "client": {}}) is None
