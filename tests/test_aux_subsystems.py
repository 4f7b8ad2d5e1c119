"""Tests for pipeline ETL, script engine, metric engine, COPY, auth, and
fulltext matching (the aux-subsystem tiers of SURVEY.md §2.3/2.5)."""

import json
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.pipeline import Pipeline, PipelineManager
from greptimedb_tpu.query.fulltext import eval_matches
from greptimedb_tpu.script import PyEngine


@pytest.fixture()
def inst(tmp_path):
    s = Standalone(str(tmp_path / "data"))
    yield s
    s.close()


# ----------------------------------------------------------------------
# pipeline ETL
# ----------------------------------------------------------------------

ACCESS_LOG_PIPELINE = """
processors:
  - dissect:
      fields: [message]
      patterns:
        - '%{ip} - %{user} [%{ts}] "%{method} %{path}" %{status} %{size}'
  - date:
      fields: [ts]
      formats: ['%d/%b/%Y:%H:%M:%S']
  - letter:
      fields: [method]
      method: lower
transform:
  - fields: [ip, method, status]
    type: string
    index: tag
  - fields: [path, user]
    type: string
  - fields: [size]
    type: int64
  - fields: [ts]
    type: time
    index: timestamp
"""


def test_pipeline_processors():
    p = Pipeline(ACCESS_LOG_PIPELINE)
    rows = p.run([{
        "message": '1.2.3.4 - alice [15/Nov/2023:10:30:00] '
                   '"GET /api/users" 200 1234'
    }])
    assert len(rows) == 1
    r = rows[0]
    assert r["ip"] == "1.2.3.4"
    assert r["method"] == "get"
    assert r["status"] == "200"
    assert r["size"] == 1234
    assert r["ts"] == 1700044200000


def test_pipeline_ingest_creates_table(inst):
    mgr = PipelineManager.get(inst)
    mgr.upsert_pipeline("access", ACCESS_LOG_PIPELINE)
    n = mgr.ingest("public", "access_logs", "access", [
        {"message": '1.2.3.4 - alice [15/Nov/2023:10:30:00] '
                    '"GET /api/users" 200 1234'},
        {"message": '5.6.7.8 - bob [15/Nov/2023:10:31:00] '
                    '"POST /api/orders" 500 88'},
    ])
    assert n == 2
    res = inst.sql(
        "SELECT ip, method, path, size FROM access_logs ORDER BY ts"
    )
    assert res.rows() == [
        ["1.2.3.4", "get", "/api/users", 1234],
        ["5.6.7.8", "post", "/api/orders", 88],
    ]
    sem = {r[0]: r[5] for r in inst.sql("DESCRIBE TABLE access_logs").rows()}
    assert sem["ip"] == "TAG" and sem["path"] == "FIELD"


def test_identity_pipeline(inst):
    mgr = PipelineManager.get(inst)
    n = mgr.ingest("public", "app_logs", "greptime_identity", [
        {"level": "error", "message": "boom", "code": 7},
        {"level": "info", "message": "ok"},
    ])
    assert n == 2
    res = inst.sql("SELECT level, message, code FROM app_logs "
                   "ORDER BY level")
    rows = res.rows()
    assert rows[0][:2] == ["error", "boom"] and rows[0][2] == 7
    assert rows[1][2] is None


def test_pipeline_persists(tmp_path):
    inst = Standalone(str(tmp_path / "d"))
    PipelineManager.get(inst).upsert_pipeline("p1", ACCESS_LOG_PIPELINE)
    inst.close()

    inst2 = Standalone(str(tmp_path / "d"))
    assert PipelineManager.get(inst2).pipeline_names() == ["p1"]
    inst2.close()



# ----------------------------------------------------------------------
# script engine
# ----------------------------------------------------------------------

def test_script_over_query(inst):
    inst.sql("CREATE TABLE m (host STRING, cpu DOUBLE, mem DOUBLE, "
             "ts TIMESTAMP TIME INDEX, PRIMARY KEY(host))")
    inst.sql("INSERT INTO m VALUES ('a', 10.0, 50.0, 1000), "
             "('b', 30.0, 70.0, 2000)")
    eng = PyEngine(inst)
    eng.insert_script("load", '''
@copr(args=["cpu", "mem"], returns=["load"],
      sql="SELECT cpu, mem FROM m ORDER BY host")
def load(cpu, mem):
    return cpu * 0.6 + mem * 0.4
''')
    res = eng.run_script("load")
    assert res.names == ["load"]
    np.testing.assert_allclose(
        np.asarray(res.cols[0].values, dtype=np.float64), [26.0, 46.0]
    )


def test_script_jax_math(inst):
    eng = PyEngine(inst)
    eng.insert_script("gen", '''
@copr(args=[], returns=["x", "y"])
def gen():
    x = jnp.arange(4.0)
    return x, jnp.sqrt(x)
''')
    res = eng.run_script("gen")
    assert res.names == ["x", "y"]
    np.testing.assert_allclose(res.cols[1].values, np.sqrt(np.arange(4.0)))


def test_script_persists(tmp_path):
    inst = Standalone(str(tmp_path / "d"))
    PyEngine(inst).insert_script("s1", '''
@copr(args=[], returns=["one"])
def one():
    return np.asarray([1.0])
''')
    inst.close()
    inst2 = Standalone(str(tmp_path / "d"))
    eng = PyEngine(inst2)
    assert eng.script_names() == ["s1"]
    assert eng.run_script("s1").rows() == [[1.0]]
    inst2.close()


# ----------------------------------------------------------------------
# metric engine
# ----------------------------------------------------------------------

def test_metric_engine_logical_tables(inst):
    inst.sql(
        "CREATE TABLE http_requests (host STRING, greptime_value DOUBLE, "
        "ts TIMESTAMP TIME INDEX, PRIMARY KEY (host)) ENGINE=metric"
    )
    inst.sql(
        "CREATE TABLE grpc_requests (service STRING, greptime_value DOUBLE,"
        " ts TIMESTAMP TIME INDEX, PRIMARY KEY (service)) ENGINE=metric"
    )
    inst.sql("INSERT INTO http_requests VALUES ('a', 1.0, 1000), "
             "('b', 2.0, 1000)")
    inst.sql("INSERT INTO grpc_requests VALUES ('s1', 10.0, 1000)")
    # isolation: each logical table sees only its rows
    assert inst.sql("SELECT count(*) FROM http_requests").rows() == [[2]]
    assert inst.sql("SELECT count(*) FROM grpc_requests").rows() == [[1]]
    res = inst.sql(
        "SELECT host, greptime_value FROM http_requests ORDER BY host"
    )
    assert res.rows() == [["a", 1.0], ["b", 2.0]]
    # both share ONE physical table
    phys = inst.catalog.table("public", "greptime_physical_table")
    assert phys.row_count() == 3


def test_metric_engine_survives_restart(tmp_path):
    inst = Standalone(str(tmp_path / "d"))
    inst.sql(
        "CREATE TABLE m1 (host STRING, greptime_value DOUBLE, "
        "ts TIMESTAMP TIME INDEX, PRIMARY KEY (host)) ENGINE=metric"
    )
    inst.sql("INSERT INTO m1 VALUES ('x', 5.0, 1000)")
    inst.close()
    inst2 = Standalone(str(tmp_path / "d"))
    assert inst2.sql("SELECT greptime_value FROM m1").rows() == [[5.0]]
    inst2.close()


# ----------------------------------------------------------------------
# COPY TO / FROM
# ----------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["parquet", "csv"])
def test_copy_roundtrip(inst, tmp_path, fmt):
    inst.sql("CREATE TABLE src (host STRING, v DOUBLE, "
             "ts TIMESTAMP TIME INDEX, PRIMARY KEY(host))")
    inst.sql("INSERT INTO src VALUES ('a', 1.5, 1000), ('b', 2.5, 2000)")
    path = str(tmp_path / f"out.{fmt}")
    out = inst.sql(f"COPY src TO '{path}' WITH (format = '{fmt}')")
    inst.sql("CREATE TABLE dst (host STRING, v DOUBLE, "
             "ts TIMESTAMP TIME INDEX, PRIMARY KEY(host))")
    inst.sql(f"COPY dst FROM '{path}' WITH (format = '{fmt}')")
    res = inst.sql("SELECT host, v, ts FROM dst ORDER BY host")
    assert res.rows() == [["a", 1.5, 1000], ["b", 2.5, 2000]]


# ----------------------------------------------------------------------
# fulltext matches
# ----------------------------------------------------------------------

def test_eval_matches():
    vals = np.asarray([
        "Connection timeout on server-1",
        "disk full on server-2",
        "connection refused quickly",
    ], object)
    assert list(eval_matches(vals, "connection")) == [True, False, True]
    assert list(eval_matches(vals, "connection AND timeout")) == [
        True, False, False,
    ]
    assert list(eval_matches(vals, "timeout OR disk")) == [
        True, True, False,
    ]
    assert list(eval_matches(vals, "connection NOT refused")) == [
        True, False, False,
    ]
    assert list(eval_matches(vals, '"disk full"')) == [False, True, False]


def test_matches_in_sql(inst):
    inst.sql("CREATE TABLE logs (app STRING, message STRING, "
             "ts TIMESTAMP TIME INDEX, PRIMARY KEY (app))")
    inst.sql(
        "INSERT INTO logs VALUES "
        "('web', 'connection timeout to db', 1000), "
        "('web', 'request ok', 2000), "
        "('db', 'disk full error', 3000)"
    )
    res = inst.sql(
        "SELECT message FROM logs WHERE matches(message, "
        "'timeout OR \"disk full\"') ORDER BY ts"
    )
    assert res.rows() == [["connection timeout to db"], ["disk full error"]]


# ----------------------------------------------------------------------
# auth
# ----------------------------------------------------------------------

def test_http_basic_auth(tmp_path):
    from greptimedb_tpu.auth import StaticUserProvider
    from greptimedb_tpu.servers.http import HttpServer

    inst = Standalone(str(tmp_path / "d"))
    provider = StaticUserProvider({"admin": "secret"})
    srv = HttpServer(inst, port=0, user_provider=provider).start()
    try:
        import base64
        import urllib.error

        url = f"http://127.0.0.1:{srv.port}/v1/sql"
        body = b"sql=SELECT 1"
        headers = {"Content-Type": "application/x-www-form-urlencoded"}
        # no credentials -> 401
        try:
            urllib.request.urlopen(
                urllib.request.Request(url, body, headers, method="POST")
            )
            raise AssertionError("expected 401")
        except urllib.error.HTTPError as e:
            assert e.code == 401
        # valid credentials -> 200
        tok = base64.b64encode(b"admin:secret").decode()
        req = urllib.request.Request(
            url, body, {**headers, "Authorization": f"Basic {tok}"},
            method="POST",
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200
        # health stays open
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/health"
        ) as resp:
            assert resp.status == 200
    finally:
        srv.stop()
        inst.close()


# ----------------------------------------------------------------------
# log ingest over HTTP (events endpoint)
# ----------------------------------------------------------------------

def test_http_log_ingest(tmp_path):
    from greptimedb_tpu.servers.http import HttpServer

    inst = Standalone(str(tmp_path / "d"))
    srv = HttpServer(inst, port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        # upload pipeline
        req = urllib.request.Request(
            f"{base}/v1/events/pipelines/access",
            ACCESS_LOG_PIPELINE.encode(), method="POST",
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200
        # ingest logs
        payload = json.dumps([{
            "message": '9.9.9.9 - eve [15/Nov/2023:10:32:00] '
                       '"GET /login" 401 0'
        }]).encode()
        req = urllib.request.Request(
            f"{base}/v1/events/logs?db=public&table=weblogs"
            f"&pipeline_name=access",
            payload, {"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req) as resp:
            assert json.loads(resp.read())["rows"] == 1
        res = inst.sql("SELECT ip, status FROM weblogs")
        assert res.rows() == [["9.9.9.9", "401"]]
    finally:
        srv.stop()
        inst.close()
    


def test_explain_analyze_stage_metrics(tmp_path):
    """EXPLAIN ANALYZE reports per-stage metrics (VERDICT r2 task #9):
    rows scanned, exec path, cache state, reduce/device timings, and
    whether the scan rode the tag index."""
    import numpy as np

    from greptimedb_tpu.instance import Standalone
    from greptimedb_tpu.telemetry.metrics import global_registry

    inst = Standalone(str(tmp_path / "data"))
    inst.sql(
        "CREATE TABLE ea (host STRING, v DOUBLE, ts TIMESTAMP TIME INDEX, "
        "PRIMARY KEY (host))"
    )
    table = inst.catalog.table("public", "ea")
    table.write(
        {"host": np.asarray(["a", "b"] * 10, object)},
        np.arange(20, dtype=np.int64) * 1000,
        {"v": np.arange(20, dtype=np.float64)},
    )
    r = inst.sql("EXPLAIN ANALYZE SELECT host, count(*) FROM ea GROUP BY host")
    text = "\n".join(row[0] for row in r.rows())
    assert "rows_scanned: 20" in text
    assert "agg_groups: 2" in text
    assert "exec_path_aggregate:" in text
    assert "reduce_ms:" in text
    assert "scan_path: full_scan" in text
    # a tag matcher rides the index: stamped on the plan and counted
    scans = global_registry.counter(
        "gtpu_index_scans_total", labels=("path",)
    ).labels("index_pruned")
    before = scans.value
    r = inst.sql("EXPLAIN ANALYZE SELECT host, count(*) FROM ea "
                 "WHERE host = 'b' GROUP BY host")
    text = "\n".join(row[0] for row in r.rows())
    assert "scan_path: index_pruned" in text
    assert "rows_scanned: 10" in text
    assert scans.value == before + 1
    # joins report their stage too
    r = inst.sql(
        "EXPLAIN ANALYZE SELECT a.host FROM ea a JOIN ea b ON a.host = b.host"
    )
    text = "\n".join(row[0] for row in r.rows())
    assert "join_rows:" in text and "join_ms:" in text
    inst.close()
