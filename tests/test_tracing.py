"""Tracing spans through the query path + /v1/traces (VERDICT rows
15/29: tracing subsystem)."""

import json
import urllib.request

import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.telemetry import tracing


@pytest.fixture(autouse=True)
def _fresh_traces():
    tracing.global_traces.clear()
    yield
    tracing.global_traces.clear()


def test_span_nesting_and_attributes():
    with tracing.span("outer", who="me") as root:
        with tracing.span("inner") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        assert tracing.current_trace_id() == root.trace_id
    assert tracing.current_trace_id() is None
    spans = tracing.global_traces.trace(root.trace_id)
    names = {s["name"] for s in spans}
    assert names == {"outer", "inner"}
    outer = next(s for s in spans if s["name"] == "outer")
    assert outer["attributes"] == {"who": "me"}
    assert outer["duration_ms"] is not None


def test_span_error_recorded():
    with pytest.raises(ValueError):
        with tracing.span("boom") as sp:
            raise ValueError("nope")
    spans = tracing.global_traces.trace(sp.trace_id)
    assert "ValueError: nope" in spans[0]["attributes"]["error"]


def test_remote_traceparent_continues_trace():
    tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    with tracing.start_remote(tp, "handler") as sp:
        assert sp.trace_id == "ab" * 16
        assert sp.parent_id == "cd" * 8
    # malformed -> fresh root
    with tracing.start_remote("garbage", "handler") as sp2:
        assert sp2.parent_id is None


def test_sql_pipeline_emits_spans(tmp_path):
    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    try:
        inst.sql("CREATE TABLE t (v DOUBLE, ts TIMESTAMP TIME INDEX)")
        inst.sql("INSERT INTO t (v, ts) VALUES (1.0, 1)")
        inst.sql("SELECT count(*) FROM t")
    finally:
        inst.close()
    all_traces = tracing.global_traces.traces()
    names = {
        s["name"] for tr in all_traces for s in tr["spans"]
    }
    assert "sql.Select" in names and "sql.Insert" in names
    assert "query.scan" in names
    # scan nests under the select statement
    for tr in all_traces:
        by_name = {s["name"]: s for s in tr["spans"]}
        if "query.scan" in by_name and "sql.Select" in by_name:
            assert (by_name["query.scan"]["parent_id"]
                    == by_name["sql.Select"]["span_id"])
            break
    else:
        raise AssertionError("no trace linked scan under select")


def test_http_traces_endpoint(tmp_path):
    from greptimedb_tpu.servers.http import HttpServer

    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    srv = HttpServer(inst, port=0).start()
    try:
        import urllib.parse

        data = urllib.parse.urlencode({"sql": "SELECT 1"}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/sql", data=data,
            headers={"traceparent": "00-" + "11" * 16 + "-"
                     + "22" * 8 + "-01"},
        )
        urllib.request.urlopen(req, timeout=10)
        out = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/traces/" + "11" * 16,
            timeout=10,
        ).read())
        names = {s["name"] for s in out["spans"]}
        assert "http /v1/sql" in names and "sql.Select" in names
    finally:
        srv.stop()
        inst.close()


def test_configure_and_ring_bounds():
    cfg = tracing.configure({"sample_ratio": 0.5, "capacity": 7,
                             "slow_ms": 123.0})
    try:
        assert cfg.sample_ratio == 0.5
        assert tracing.global_traces.cap == 7
        assert not tracing.ring_unbounded()
        for i in range(20):
            with tracing.span(f"t{i}"):
                pass
        assert len(tracing.global_traces.traces(limit=100)) <= 7
        tracing.configure({"capacity": 0})
        assert tracing.ring_unbounded()
    finally:
        tracing.configure({})


def test_tail_sampling_drops_unremarkable_keeps_error_and_slow():
    tracing.configure({"sample_ratio": 0.0, "slow_ms": 50.0})
    try:
        # unremarkable root: dropped at decision time
        with tracing.span("boring") as sp:
            pass
        assert tracing.global_traces.trace(sp.trace_id) == []
        # errored trace: kept (error can be on a CHILD span)
        try:
            with tracing.span("root") as rsp:
                with tracing.span("child"):
                    raise RuntimeError("boom")
        except RuntimeError:
            pass
        assert tracing.global_traces.trace(rsp.trace_id)
        # slow root: kept
        import time as _time

        with tracing.span("slowroot") as ssp:
            _time.sleep(0.06)
        assert tracing.global_traces.trace(ssp.trace_id)
        # mark_keep: kept
        with tracing.span("marked") as msp:
            tracing.mark_keep()
        assert tracing.global_traces.trace(msp.trace_id)
    finally:
        tracing.configure({})


def test_disabled_tracing_is_inert():
    tracing.configure({"enable": False})
    try:
        with tracing.span("x") as sp:
            assert sp.trace_id == ""
            assert tracing.current_trace_id() is None
            assert tracing.traceparent() is None
            with tracing.child_span("y") as c:
                c.attributes["k"] = 1  # writes land nowhere
        assert tracing.global_traces.traces() == []
    finally:
        tracing.configure({})


def test_child_span_without_trace_is_noop():
    with tracing.child_span("orphan") as sp:
        assert sp.trace_id == ""
    assert tracing.global_traces.traces() == []


def test_event_span_and_duration_monotonic():
    with tracing.span("root") as root:
        tracing.event_span("dist.merge", 12.5, stage="merge")
    spans = tracing.global_traces.trace(root.trace_id)
    ev = next(s for s in spans if s["name"] == "dist.merge")
    assert ev["duration_ms"] == 12.5
    assert ev["parent_id"] == root.span_id
    rt = next(s for s in spans if s["name"] == "root")
    # durations come off the monotonic clock: never negative
    assert rt["duration_ms"] is not None and rt["duration_ms"] >= 0


def test_export_and_ingest_spans_round_trip():
    with tracing.export_spans() as exported:
        with tracing.span("datanode.partial") as sp:
            with tracing.span("datanode.scan"):
                pass
    assert {s.name for s in exported} == {
        "datanode.partial", "datanode.scan"
    }
    docs = [s.to_json() for s in exported]
    tracing.global_traces.clear()
    tracing.ingest_spans(docs)
    spans = tracing.global_traces.trace(sp.trace_id)
    assert {s["name"] for s in spans} == {
        "datanode.partial", "datanode.scan"
    }


def test_render_tree_shape():
    with tracing.span("a") as a:
        with tracing.span("b"):
            pass
        with tracing.span("c", x=1):
            pass
    lines = tracing.render_tree(tracing.global_traces.trace(a.trace_id))
    assert lines[0].startswith("a ")
    assert all(ln.startswith("  ") for ln in lines[1:])
    assert any("{x=1}" in ln for ln in lines)


def test_traceparent_helper_and_remote_parenting():
    assert tracing.traceparent() is None
    with tracing.span("root") as sp:
        tp = tracing.traceparent()
        assert tp == f"00-{sp.trace_id}-{sp.span_id}-01"
    with tracing.start_remote(tp, "over-there") as rsp:
        assert rsp.trace_id == sp.trace_id
        assert rsp.parent_id == sp.span_id


def test_information_schema_traces_and_slow_query_trace_id(tmp_path):
    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    try:
        inst.slow_query_log.threshold_s = 0.0  # record everything
        inst.sql("CREATE TABLE t (v DOUBLE, ts TIMESTAMP TIME INDEX)")
        inst.sql("INSERT INTO t (v, ts) VALUES (1.0, 1)")
        inst.sql("SELECT count(*) FROM t")
        res = inst.sql("SELECT span_name, trace_id FROM "
                       "information_schema.traces")
        names = set(res.cols[0].values.tolist())
        assert "sql.Select" in names and "sql.execute" in names
        # slow-query entries carry the trace id of their statement
        entries = inst.slow_query_log.entries()
        assert entries and all(e["trace_id"] for e in entries)
        tids = {s for s in res.cols[1].values.tolist()}
        assert entries[-1]["trace_id"] in tids
        sq = inst.sql("SELECT trace_id FROM "
                      "information_schema.slow_queries")
        assert sq.num_rows == len(entries)
    finally:
        inst.close()


def test_explain_analyze_renders_span_tree(tmp_path):
    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    try:
        inst.sql("CREATE TABLE t (v DOUBLE, ts TIMESTAMP TIME INDEX)")
        inst.sql("INSERT INTO t (v, ts) VALUES (1.0, 1), (2.0, 2)")
        res = inst.sql("EXPLAIN ANALYZE SELECT count(*) FROM t")
        text = "\n".join(res.cols[0].values.tolist())
        assert "Trace:" in text
        assert "query.scan" in text
    finally:
        inst.close()


@pytest.mark.parametrize("where,rows", [
    ("", 0),                        # no matcher: the plane program
    ("WHERE host != 'h1'", 8),      # two series matched: their rows
])
def test_device_spans_on_range_query(tmp_path, where, rows):
    """prefer_device forces the grid path: the trace carries a
    device.execute span with compile/execute/readback attribution, and
    says which range program ran."""
    pytest.importorskip("jax")
    inst = Standalone(str(tmp_path / "data"), warm_start=False,
                      prefer_device=True)
    try:
        inst.sql("CREATE TABLE m (host STRING PRIMARY KEY, v DOUBLE, "
                 "ts TIMESTAMP TIME INDEX)")
        vals = ", ".join(
            f"('h{i % 3}', {i}.0, {1_700_000_000_000 + i * 1000})"
            for i in range(30)
        )
        inst.sql(f"INSERT INTO m (host, v, ts) VALUES {vals}")
        q = (f"SELECT ts, host, avg(v) RANGE '10s' FROM m {where} "
             "ALIGN '10s' BY (host)")
        with tracing.span("req") as root:
            inst.sql(q)
        spans = tracing.global_traces.trace(root.trace_id)
        dev = [s for s in spans if s["name"] == "device.execute"]
        assert dev, {s["name"] for s in spans}
        # a range query is ONE call of one program: one device span,
        # of site=range (the rows' extent is computed inside it)
        assert [s["attributes"]["site"] for s in dev] == ["range"]
        attrs = dev[0]["attributes"]
        # every step of the bound window held a row: nothing trimmed
        assert attrs["trimmed_steps"] == 0
        assert attrs["compile"] == "first_call"
        assert attrs["readback_bytes"] > 0
        assert attrs["rows"] == rows
        # the rows program's one vector rides the call: (delta, lo, hi),
        # eight sids, eight group ids
        assert attrs.get("upload_bytes", 0) >= (4 * 19 if rows else 0)
        assert "execute_ms" in attrs
        # the program-profiler link rides the span
        assert attrs.get("program")
        # steady state: same program shape is a cache hit
        with tracing.span("req2") as root2:
            inst.sql(q)
        dev2 = [
            s for s in tracing.global_traces.trace(root2.trace_id)
            if s["name"] == "device.execute"
            and s["attributes"]["site"] == "range"
        ]
        assert dev2 and dev2[0]["attributes"]["compile"] == "cache_hit"
    finally:
        inst.close()


def test_http_traces_query_param_filter(tmp_path):
    from greptimedb_tpu.servers.http import HttpServer

    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    srv = HttpServer(inst, port=0).start()
    try:
        import urllib.parse

        tid = "ab" * 16
        data = urllib.parse.urlencode({"sql": "SELECT 1"}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/sql", data=data,
            headers={"traceparent": f"00-{tid}-{'cd' * 8}-01"},
        )
        urllib.request.urlopen(req, timeout=10)
        out = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/traces?trace_id={tid}",
            timeout=10,
        ).read())
        assert out["trace_id"] == tid
        assert {s["name"] for s in out["spans"]} >= {"sql.Select"}
        # bounded listing with ?limit=
        out = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/traces?limit=1",
            timeout=10,
        ).read())
        assert len(out["traces"]) <= 1
    finally:
        srv.stop()
        inst.close()


def test_child_exit_never_rolls_sampling_dice():
    """Only the process-local ROOT decides keep/drop: with
    sample_ratio=0, children (including ones under a remote parent)
    finishing early must not drop the in-flight trace before the root
    sees the error that makes it kept."""
    tracing.configure({"sample_ratio": 0.0})
    try:
        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        try:
            with tracing.start_remote(tp, "datanode.partial"):
                with tracing.span("device.execute"):
                    pass  # unremarkable child exits first
                raise RuntimeError("late failure")
        except RuntimeError:
            pass
        spans = tracing.global_traces.trace("ab" * 16)
        assert {s["name"] for s in spans} == {
            "datanode.partial", "device.execute"
        }
    finally:
        tracing.configure({})


def test_malformed_traceparent_never_taints_trace_id():
    """Trace ids are client-controlled and spliced into hand-built
    ticket JSON: anything but strict lowercase hex starts a fresh
    root instead of inheriting the tainted id."""
    bad = [
        "00-" + 'x"' * 16 + "-" + "cd" * 8 + "-01",   # quote in id
        "00-" + "AB" * 16 + "-" + "cd" * 8 + "-01",   # uppercase hex
        "00-" + "00" * 16 + "-" + "cd" * 8 + "-01",   # all-zero id
        "00-" + "ab" * 16 + "-" + "cd" * 8,           # missing flags
    ]
    for tp in bad:
        with tracing.start_remote(tp, "h") as sp:
            assert sp.parent_id is None, tp
            assert sp.trace_id not in tp


def test_sibling_root_drop_cannot_destroy_errored_trace():
    """Two concurrent local roots on one traceparent: the first root
    finishing unremarkably (sampled out) must not drop the trace while
    the second is still in flight and about to record an error."""
    tracing.configure({"sample_ratio": 0.0})
    try:
        tp = "00-" + "ef" * 16 + "-" + "ab" * 8 + "-01"
        b = tracing.start_remote(tp, "request-b")
        b.__enter__()
        # sibling A finishes first, unremarkable => would have dropped
        with tracing.start_remote(tp, "request-a"):
            pass
        assert tracing.global_traces.trace("ef" * 16), \
            "sibling drop destroyed the in-flight trace"
        try:
            raise RuntimeError("late error on B")
        except RuntimeError as e:
            b.__exit__(type(e), e, e.__traceback__)
        spans = tracing.global_traces.trace("ef" * 16)
        assert {s["name"] for s in spans} >= {"request-a", "request-b"}
    finally:
        tracing.configure({})


# ---------------------------------------------------------------------------
# one stage tree per request, time by name, pauses no request owns
# ---------------------------------------------------------------------------

STAGES = (
    "http.read", "sql.fingerprint", "sql.parse", "sched.admit",
    "query.plan", "query.select_series", "query.grid", "device.execute",
    "query.assemble", "http.encode", "http.send", "stmt_stats.drain",
)


def _family(text: str, family: str, **labels) -> float | None:
    """Sum of `family`'s samples in a /metrics text whose labels
    include `labels`; None where no such sample is there."""
    want = [f'{k}="{v}"' for k, v in labels.items()]
    total, seen = 0.0, False
    for ln in text.splitlines():
        if ln.startswith("#") or not ln.startswith(family):
            continue
        head, _, value = ln.rpartition(" ")
        name, _, lbs = head.partition("{")
        if name == family and all(w in lbs for w in want):
            total += float(value)
            seen = True
    return total if seen else None


def _render() -> str:
    from greptimedb_tpu.telemetry.metrics import global_registry

    return global_registry.render()


def _post_sql(port: int, sql: str, **headers) -> bytes:
    import urllib.parse

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/sql",
        data=urllib.parse.urlencode({"sql": sql}).encode(),
        headers=headers)
    return urllib.request.urlopen(req, timeout=60).read()


def _finished_trace(trace_id: str) -> list[dict]:
    """The trace once its root has finished: the client has its last
    byte before the server closes `http.send` and the root."""
    import time

    for _ in range(200):
        spans = tracing.global_traces.trace(trace_id)
        if spans and all(s["duration_ms"] is not None for s in spans):
            return spans
        time.sleep(0.01)
    raise AssertionError(f"trace {trace_id} never finished: {spans}")


@pytest.fixture
def panel(tmp_path, monkeypatch, request):
    """A server holding a small `cpu` table on the device path, warm
    for the panel query's shape; yields (port, query maker). The
    panel's one host takes the rows program; with the parameter
    "plane" every selection counts as past `_ROWS_MAX` and takes the
    plane program."""
    pytest.importorskip("jax")
    from greptimedb_tpu.query import device_range
    from greptimedb_tpu.servers.http import HttpServer
    from greptimedb_tpu.telemetry import stmt_stats

    if getattr(request, "param", "rows") == "plane":
        monkeypatch.setattr(device_range, "_ROWS_MAX", -1)

    inst = Standalone(str(tmp_path / "data"), warm_start=False,
                      prefer_device=True)
    srv = HttpServer(inst, port=0).start()
    inst.sql("CREATE TABLE cpu (hostname STRING, region STRING, "
             "usage_user DOUBLE, ts TIMESTAMP TIME INDEX, "
             "PRIMARY KEY(hostname, region))")
    # a fleet's worth of series, so that the stages outweigh the fixed
    # bookkeeping around them as they do at a deployment's size
    import numpy as np

    hosts, cells = 2048, 360
    host = np.repeat(np.arange(hosts), cells)
    inst._write_columns(inst.catalog.table("public", "cpu"), {
        "hostname": np.char.add("host_", host.astype(str)).astype(object),
        "region": np.char.add("r", (host % 3).astype(str)).astype(object),
        "usage_user": ((host * 7 + np.tile(np.arange(cells), hosts))
                       % 100).astype(np.float64),
        "ts": np.tile(np.arange(cells, dtype=np.int64) * 10000, hosts),
    }, {})

    def query(host: int) -> str:
        return ("SELECT ts, hostname, max(usage_user) RANGE '60s' FROM "
                f"cpu WHERE hostname IN ('host_{host}') AND ts >= 0 AND "
                "ts < 3600000 ALIGN '60s' BY (hostname)")

    for h in range(4):
        _post_sql(srv.port, query(h))
    # the statement statistics fold in line at the bound: bring it
    # down so that one request of the test pays a drain
    monkeypatch.setattr(stmt_stats.StmtStatsRegistry, "_PENDING_MAX", 1)
    try:
        yield srv.port, query
    finally:
        srv.stop()
        inst.close()


def _stage_tree(port: int, sql: str, tid: str, rows: int
                ) -> tuple[float, float]:
    """One request under trace id `tid`, its tree checked (`rows`: the
    bucket of the rows program, 0 for the plane program); returns the
    time its stages cover and its root's."""
    _post_sql(port, sql, traceparent=f"00-{tid}-{'cd' * 8}-01")
    spans = _finished_trace(tid)
    by_id = {s["span_id"]: s for s in spans}
    root = next(s for s in spans if s["name"] == "http /v1/sql")
    stages = [s for s in spans if s["name"] in STAGES]
    assert {s["name"] for s in stages} == set(STAGES)
    for s in stages:
        assert s["trace_id"] == tid
        # a descendant of the request
        up = s
        while up["span_id"] != root["span_id"]:
            up = by_id[up["parent_id"]]
    # flat: no stage inside another, so their times add up
    for s in stages:
        assert by_id[s["parent_id"]]["name"] not in STAGES, s["name"]
    grid = next(s for s in stages if s["name"] == "query.grid")
    assert grid["attributes"]["grid_cache"] == "hit"
    dev = [s for s in stages if s["name"] == "device.execute"]
    assert len(dev) == 1 and all("execute_ms" in s["attributes"]
                                 for s in dev)
    assert dev[0]["attributes"]["rows"] == rows
    for s in dev:
        for gone in ("flops", "roofline_bound", "pct_of_peak",
                     "achieved_gflops"):
            assert gone not in s["attributes"]
    return sum(s["duration_ms"] for s in stages), root["duration_ms"]


@pytest.mark.parametrize("panel,rows,covered", [
    ("plane", 0, 0.9), ("rows", 8, 0.8)], indirect=["panel"])
def test_one_request_yields_the_twelve_stages_in_one_tree(
        panel, rows, covered):
    port, query = panel
    trees = [_stage_tree(port, query(100 + i), f"{i:02x}" * 16, rows)
             for i in range(1, 6)]
    # what the stages leave is the root's (and the statement's) self
    # time: under a tenth of the request on the plane program. The rows
    # program leaves the same self time beside stages half as long (the
    # device call and the selection no longer grow with the fleet):
    # under a fifth. A request that a collection or another test's
    # thread falls into reads lower, so the best of five is held to it
    assert max(c / r for c, r in trees) >= covered, trees


def test_device_execute_span_carries_the_crossings_legs(panel):
    """The operator's view of one query: `/v1/traces` and EXPLAIN
    ANALYZE's tree show where the call's time went, on one clock."""
    port, query = panel
    tid = "37" * 16
    _post_sql(port, query(300), traceparent=f"00-{tid}-{'cd' * 8}-01")
    (dev,) = [s for s in _finished_trace(tid)
              if s["name"] == "device.execute"]
    a = dev["attributes"]
    assert a["site"] == "range"
    assert a["dispatch_ms"] > 0 and a["readback_ms"] > 0
    assert a["wait_ms"] >= 0
    assert a["execute_ms"] == pytest.approx(
        a["dispatch_ms"] + a["wait_ms"], abs=2e-3)
    # the legs lie inside the span; what is left is the host code the
    # span also wraps (the gate, the sessions' put, the window's fold)
    assert (a["dispatch_ms"] + a["wait_ms"] + a["readback_ms"]
            <= dev["duration_ms"] + 0.01)
    doc = json.loads(_post_sql(port, "EXPLAIN ANALYZE " + query(301)))
    text = json.dumps(doc)
    for leg in ("dispatch_ms=", "wait_ms=", "readback_ms=", "execute_ms="):
        assert leg in text, leg
    import time

    time.sleep(0.1)     # the roots close after the last byte


def test_span_time_is_exported_by_name(panel, monkeypatch):
    port, query = panel
    # thread CPU is read for one request in `_CPU_EVERY` and scaled up:
    # read every one here, so that three requests give exact numbers
    monkeypatch.setattr(tracing, "_CPU_EVERY", 1)
    m0 = _render()
    for h in (5, 6, 7):
        _post_sql(port, query(h))
    import time

    time.sleep(0.1)     # the roots close after the last byte
    m1 = _render()

    def moved(family, **labels):
        return ((_family(m1, family, **labels) or 0.0)
                - (_family(m0, family, **labels) or 0.0))

    assert moved("gtpu_span_seconds_count", name="sql.parse") == 3
    assert moved("gtpu_span_seconds_count", name="device.execute") == 3
    assert moved("gtpu_span_seconds_count", name="http /v1/sql") == 3
    for name in ("sql.parse", "query.plan", "http /v1/sql"):
        wall = moved("gtpu_span_seconds_sum", name=name)
        cpu = moved("gtpu_span_cpu_seconds_total", name=name)
        # the CPU reading lies inside the wall reading; the two clocks
        # are not one, so allow their drift
        assert 0 < cpu <= wall * 1.05 + 1e-4, (name, cpu, wall)
    # cumulative buckets end at the count
    assert (_family(m1, "gtpu_span_seconds_bucket", name="sql.parse",
                    le="+Inf")
            == _family(m1, "gtpu_span_seconds_count", name="sql.parse"))


def test_thread_cpu_is_read_for_one_tree_in_n(monkeypatch):
    """One local root in `_CPU_EVERY` has its thread CPU read, its
    children with it, and the reading counts `_CPU_EVERY` times."""
    monkeypatch.setattr(tracing, "_CPU_EVERY", 4)
    m0 = _render()
    flags = []
    for _ in range(8):
        with tracing.span("cpu.root") as root:
            with tracing.child_span("cpu.child") as child:
                sum(range(20000))
            assert child.cpu == root.cpu
            flags.append(root.cpu)
    assert sum(flags) == 2
    m1 = _render()
    wall = _family(m1, "gtpu_span_seconds_sum", name="cpu.child") - (
        _family(m0, "gtpu_span_seconds_sum", name="cpu.child") or 0.0)
    cpu = _family(m1, "gtpu_span_cpu_seconds_total", name="cpu.child") - (
        _family(m0, "gtpu_span_cpu_seconds_total", name="cpu.child")
        or 0.0)
    # two of eight equal pieces of work read, each counted four times
    assert 0.5 * wall < cpu < 1.5 * wall, (cpu, wall)


def test_disabled_tracing_moves_no_span_family(tmp_path):
    import gc

    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    tracing.configure({"enable": False})
    try:
        m0 = _render()
        inst.sql("CREATE TABLE t (v DOUBLE, ts TIMESTAMP TIME INDEX)")
        inst.sql("INSERT INTO t (v, ts) VALUES (1.0, 1)")
        inst.sql("SELECT count(*) FROM t")
        with tracing.background_span("idle.tick"):
            pass
        gc.collect()
        m1 = _render()
    finally:
        tracing.configure({})
        inst.close()
    for family in ("gtpu_span_seconds_count", "gtpu_span_seconds_sum",
                   "gtpu_span_cpu_seconds_total",
                   "gtpu_background_task_seconds_count",
                   "gtpu_runtime_gc_pause_seconds_count"):
        assert _family(m1, family) == _family(m0, family), family
    assert _family(m1, "gtpu_span_seconds_count",
                   name="idle.tick") is None


def test_background_span_is_counted_and_stays_out_of_the_ring():
    with tracing.span("a request"):
        pass
    ring = len(tracing.global_traces.traces(0))
    m0 = _render()
    for _ in range(3):
        with tracing.background_span("test.tick"):
            # a loop's inner spans find no request to join
            with tracing.child_span("region.flush"):
                pass
    m1 = _render()
    for family, label in (("gtpu_span_seconds_count", "name"),
                          ("gtpu_background_task_seconds_count", "task")):
        got = (_family(m1, family, **{label: "test.tick"})
               - (_family(m0, family, **{label: "test.tick"}) or 0.0))
        assert got == 3, family
    assert len(tracing.global_traces.traces(0)) == ring
    # a round slower than slow_ms is an operator's business: kept
    tracing.configure({"slow_ms": 0.0})
    try:
        with tracing.background_span("test.slow_tick"):
            pass
    finally:
        tracing.configure({})
    kept = tracing.global_traces.traces(1)[0]["spans"]
    assert [s["name"] for s in kept] == ["test.slow_tick"]
    assert kept[0]["attributes"] == {"background": True}


def test_an_idle_server_adds_no_trace(tmp_path):
    """Engine maintenance and the flow tick run every few tens of
    milliseconds here: counted, and not one trace."""
    import time

    from greptimedb_tpu.storage.engine import EngineConfig

    root = str(tmp_path / "data")
    inst = Standalone(root, warm_start=False, engine_config=EngineConfig(
        data_root=root, background_interval_s=0.02))
    try:
        inst.enable_flows(tick_interval_s=0.02)
        # the first region's opening starts the maintenance loop
        inst.sql("CREATE TABLE t (v DOUBLE, ts TIMESTAMP TIME INDEX)")
        tracing.global_traces.clear()
        m0 = _render()
        time.sleep(0.5)
        m1 = _render()
        idle = tracing.global_traces.traces(0)
    finally:
        inst.close()
    assert idle == []
    for task in ("engine.maintenance", "flow.tick"):
        got = (_family(m1, "gtpu_background_task_seconds_count", task=task)
               or 0.0) - (_family(
                   m0, "gtpu_background_task_seconds_count", task=task)
                   or 0.0)
        assert got >= 3, (task, got)


def test_forced_collection_is_counted():
    import gc

    m0 = _render()
    gc.collect()
    m1 = _render()
    assert (_family(m1, "gtpu_runtime_gc_pause_seconds_count",
                    generation="2")
            - _family(m0, "gtpu_runtime_gc_pause_seconds_count",
                      generation="2")) == 1
    assert (_family(m1, "gtpu_runtime_gc_pause_seconds_sum",
                    generation="2")
            > _family(m0, "gtpu_runtime_gc_pause_seconds_sum",
                      generation="2"))


def test_capture_holds_gtpu_events_on_the_profilers_clock(panel, tmp_path):
    """While a capture runs, spans, ticks and collections lie in the
    `.xplane.pb` as `gtpu:<name>` events beside the device's."""
    import gc
    import glob
    import threading

    from greptimedb_tpu.telemetry import device_programs as DP

    port, query = panel
    box: dict = {}
    cap = threading.Thread(
        target=lambda: box.update(DP.capture_trace(
            2.0, str(tmp_path / "traces"))))
    cap.start()
    try:
        import time

        time.sleep(0.4)     # the profiler is up
        assert tracing._annotating
        for h in (10, 11):
            _post_sql(port, query(h))
        with tracing.background_span("test.tick"):
            gc.collect()
    finally:
        cap.join(timeout=60)
    assert not tracing._annotating
    pb = glob.glob(box["trace_dir"] + "/**/*.xplane.pb", recursive=True)
    assert pb, box
    from jax.profiler import ProfileData

    names = set()
    nested = set()
    for plane in ProfileData.from_file(pb[0]).planes:
        for line in plane.lines:
            calls = []      # the device.execute events of this thread
            for ev in line.events:
                if not ev.name.startswith("gtpu:"):
                    continue
                names.add(ev.name)
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == "gtpu:device.execute":
                    calls.append(span)
                elif ev.name.startswith("gtpu:device."):
                    # a leg of the crossing: inside its call's event,
                    # and it says whose it is
                    assert dict(ev.stats)["site"] == "range", ev.name
                    if any(lo <= span[0] and span[1] <= hi
                           for lo, hi in calls):
                        nested.add(ev.name)
    assert {"gtpu:" + s for s in STAGES} <= names, names
    assert {"gtpu:http /v1/sql", "gtpu:test.tick", "gtpu:gc.gen2"} <= names
    assert nested == {"gtpu:device.dispatch", "gtpu:device.wait",
                      "gtpu:device.readback"}, (nested, names)
    # the document says what the capture itself took
    assert box["hold_s"] >= 2.0 and box["start_s"] > 0 and box["stop_s"] > 0
    # off the capture a span opens no annotation
    with tracing.span("after") as sp:
        assert sp.trace_id


def test_do_put_stream_is_one_trace_with_its_write_stages(tmp_path):
    import numpy as np
    import pyarrow as pa
    import pyarrow.flight as flight

    from greptimedb_tpu.servers.flight import FlightFrontend

    inst = Standalone(str(tmp_path / "data"), warm_start=False)
    fs = FlightFrontend(inst, port=0).start()
    try:
        inst.sql("CREATE TABLE cpu (hostname STRING PRIMARY KEY, "
                 "usage_user DOUBLE, ts TIMESTAMP TIME INDEX)")
        schema = pa.schema([("hostname", pa.string()),
                            ("ts", pa.timestamp("ms")),
                            ("usage_user", pa.float64())])
        m0 = _render()
        client = flight.connect(f"grpc://127.0.0.1:{fs.port}")
        writer, _ = client.do_put(
            flight.FlightDescriptor.for_path("cpu"), schema)
        for chunk in range(2):
            writer.write_batch(pa.record_batch([
                pa.array([f"host_{i}" for i in range(8)]),
                pa.array(np.arange(8, dtype=np.int64) + chunk * 8,
                         pa.timestamp("ms")),
                pa.array(np.arange(8, dtype=np.float64)),
            ], schema=schema))
        writer.close()      # the acknowledgement
        client.close()
        m1 = _render()
        assert inst.sql("SELECT count(*) FROM cpu").rows()[0][0] == 16
    finally:
        fs.close()
        inst.close()
    puts = [tr["spans"] for tr in tracing.global_traces.traces(0)
            if any(s["name"] == "flight.do_put" for s in tr["spans"])]
    assert len(puts) == 1
    names = [s["name"] for s in puts[0]]
    for stage in ("write.decode", "write.tag_columns", "write.intern",
                  "wal.append", "memtable.append"):
        assert names.count(stage) == 2, (stage, names)
        got = (_family(m1, "gtpu_span_seconds_count", name=stage)
               - (_family(m0, "gtpu_span_seconds_count", name=stage)
                  or 0.0))
        assert got == 2, stage
    root = next(s for s in puts[0] if s["name"] == "flight.do_put")
    assert root["parent_id"] is None
    assert root["attributes"]["table"] == "public.cpu"


def test_ring_order_is_constant_time_and_newest_first():
    tracing.configure({"capacity": 3})
    try:
        ids = []
        for i in range(5):
            with tracing.span(f"t{i}") as sp:
                ids.append(sp.trace_id)
        got = [t["trace_id"] for t in tracing.global_traces.traces(0)]
        assert got == ids[:1:-1]            # the three newest, newest first
        assert [t["trace_id"] for t in
                tracing.global_traces.traces(2)] == ids[:2:-1]
        assert tracing.global_traces.evicted_traces >= 2
        assert not hasattr(tracing.global_traces, "_order")
    finally:
        tracing.configure({})


def test_a_trace_kept_for_cause_outlives_the_traces_kept_by_chance():
    """Eviction takes the traces kept by chance first: at 300 requests
    a second the trace of the one that failed is still there."""
    tracing.configure({"capacity": 16, "slow_ms": 50.0})
    try:
        with pytest.raises(RuntimeError):
            with tracing.span("failed") as bad:
                raise RuntimeError("boom")
        with tracing.span("marked") as marked:
            tracing.mark_keep()
        ids = []
        for i in range(300):
            with tracing.span("sampled") as sp:
                ids.append(sp.trace_id)
        store = tracing.global_traces
        assert store.trace(bad.trace_id) and store.trace(marked.trace_id)
        got = [t["trace_id"] for t in store.traces(0)]
        assert len(got) == 16
        # the rest of the ring is the newest of the others, newest first
        assert got[:14] == ids[:-15:-1]
        assert set(got[14:]) == {bad.trace_id, marked.trace_id}
        # kept for cause, they still go oldest first once they fill
        # three quarters of the ring: errors cannot starve it
        for i in range(40):
            with tracing.span("marked"):
                tracing.mark_keep()
        with tracing.span("sampled") as newest:
            pass
        assert store.trace(bad.trace_id) == []
        assert store.trace(newest.trace_id)
        assert len(store.traces(0)) == 16
    finally:
        tracing.configure({})


def test_the_slowest_trace_of_each_root_name_is_held_beside_the_ring(
        tmp_path):
    """One slot a local-root name, outside the ring's count: after any
    run one GET names the stage that held the worst request."""
    import time

    from greptimedb_tpu.servers.http import HttpServer

    tracing.configure({"capacity": 4, "sample_ratio": 0.0})
    try:
        took = (0.002, 0.06, 0.004, 0.001)
        for i, t in enumerate(took):
            with tracing.span("route") as sp:
                with tracing.span("stage", nth=i):
                    time.sleep(t)
            if i == 1:
                worst = sp.trace_id
        with tracing.span("other"):
            pass
        for _ in range(50):     # the ring has long turned past it
            with tracing.span("route"):
                pass
        store = tracing.global_traces
        assert store.trace(worst) == []     # sampled out of the ring
        slots = {d["name"]: d for d in store.slowest()}
        assert set(slots) == {"route", "other"}
        slot = slots["route"]
        assert slot["trace_id"] == worst and slot["duration_ms"] >= 60.0
        assert [s["name"] for s in slot["spans"]] == ["route", "stage"]
        assert slot["spans"][1]["attributes"] == {"nth": 1}
        assert [d["name"] for d in store.slowest()] == ["route", "other"]
        inst = Standalone(str(tmp_path / "data"), warm_start=False)
        srv = HttpServer(inst, port=0).start()
        try:
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/traces?slowest=1",
                timeout=10).read())
        finally:
            srv.stop()
            inst.close()
        assert doc["slowest"][0]["trace_id"] == worst
        # read and emptied: what is read next is the worst since
        assert store.slowest(reset=True)[0]["trace_id"] == worst
        assert store.slowest() == []
        with tracing.span("route") as since:
            pass
        assert [d["trace_id"] for d in store.slowest()] == [since.trace_id]
    finally:
        tracing.configure({})


def test_a_request_the_ring_turned_past_in_flight_is_still_judged():
    """A stalled request under load: the ring evicts its trace while it
    is in flight; at its finish it is kept for cause and fills its
    name's slot all the same."""
    import contextvars
    import time

    tracing.configure({"capacity": 4, "slow_ms": 30.0})
    try:
        with tracing.span("stalled") as sp:
            def others():       # ten requests of other connections
                for _ in range(10):
                    with tracing.span("quick"):
                        pass

            contextvars.Context().run(others)
            assert tracing.global_traces.trace(sp.trace_id) == []
            with tracing.span("held"):
                time.sleep(0.04)
        spans = tracing.global_traces.trace(sp.trace_id)
        assert [s["name"] for s in spans] == ["stalled", "held"]
        (slot,) = [d for d in tracing.global_traces.slowest()
                   if d["name"] == "stalled"]
        assert slot["trace_id"] == sp.trace_id
    finally:
        tracing.configure({})
