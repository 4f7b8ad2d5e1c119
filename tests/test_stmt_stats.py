"""Statement statistics: pg_stat_statements for the TPU query path
(telemetry/stmt_stats.py).

Fingerprint normalization stability, device/cache/shed attribution on
the flagship double-groupby shape, cardinality collapse past the knob,
ADMIN reset, and agreement between the three surfaces
(information_schema.statement_statistics, /v1/stats/statements,
gtpu_stmt_* on /metrics).
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.telemetry import stmt_stats as S


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test starts from an empty process-wide registry with the
    default config (the registry is process-global by design)."""
    S.configure(None)
    S.global_stmt_stats.reset()
    yield
    S.configure(None)
    S.global_stmt_stats.reset()


@pytest.fixture()
def inst(tmp_path):
    inst = Standalone(str(tmp_path / "data"), prefer_device=False,
                      warm_start=False)
    yield inst
    inst.close()


def _row_for(fp: str, db: str = "public") -> dict | None:
    for doc in S.global_stmt_stats.snapshot():
        if doc["fingerprint"] == fp and doc["schema_name"] == db:
            return doc
    return None


# ---------------------------------------------------------------------------
# fingerprint normalization
# ---------------------------------------------------------------------------

def test_fingerprint_folds_literals_and_in_lists():
    a = S.fingerprint_sql(
        "SELECT ts, avg(v) RANGE '1m' FROM cpu WHERE host IN "
        "('a','b','c') AND ts > 1700000000000 ALIGN '1m' BY (host)"
    )[0]
    b = S.fingerprint_sql(
        "select ts, AVG(v) range '5m' from cpu where host in ('zzz') "
        "and ts > 42 align '5m' by (host)"
    )[0]
    assert a.fp == b.fp
    assert "?" in a.text and "'a'" not in a.text
    # a different SHAPE is a different fingerprint
    c = S.fingerprint_sql(
        "select ts, max(v) range '1m' from cpu align '1m' by (host)"
    )[0]
    assert c.fp != a.fp


def test_fingerprint_collapses_values_rows_and_negatives():
    one = S.fingerprint_sql(
        "insert into t (ts, v) values (1, 2.5)")[0]
    many = S.fingerprint_sql(
        "insert into t (ts, v) values (3, -4.5), (5, 6.5), (7, 8.0)"
    )[0]
    assert one.fp == many.fp
    neg = S.fingerprint_sql("select * from t where v > -5")[0]
    pos = S.fingerprint_sql("select * from t where v > 5")[0]
    assert neg.fp == pos.fp


def test_fingerprint_multi_statement_and_explain_inner():
    fps = S.fingerprint_sql("select 1; select 2; select 'x'")
    assert len(fps) == 3
    assert fps[0].fp == fps[1].fp == fps[2].fp
    exp = S.fingerprint_sql(
        "EXPLAIN ANALYZE SELECT count(v) FROM t WHERE ts > 10")[0]
    plain = S.fingerprint_sql(
        "SELECT count(v) FROM t WHERE ts > 999")[0]
    assert exp.inner_fp == plain.fp
    assert exp.fp != plain.fp
    # strings that do not lex return no fingerprints (parser raises)
    assert S.fingerprint_sql("select 'unterminated") == []


def test_fingerprint_stable_across_whitespace_and_case():
    a = S.fingerprint_sql("SELECT  Count(V)\nFROM  T")[0]
    b = S.fingerprint_sql("select count(v) from t")[0]
    assert a.fp == b.fp
    # quoted identifiers stay case-sensitive
    q1 = S.fingerprint_sql('select "V" from t')[0]
    q2 = S.fingerprint_sql('select "v" from t')[0]
    assert q1.fp != q2.fp


# ---------------------------------------------------------------------------
# attribution on the flagship shape
# ---------------------------------------------------------------------------

def _seed_cpu(inst, hosts=32, cells=64):
    fields = ["usage_user", "usage_system"]
    cols = ", ".join(f"{f} double" for f in fields)
    inst.execute_sql(
        f"create table cpu (ts timestamp time index, "
        f"hostname string primary key, {cols})"
    )
    table = inst.catalog.table("public", "cpu")
    rng = np.random.default_rng(7)
    hostnames = np.asarray([f"host_{i}" for i in range(hosts)],
                           dtype=object)
    ts = np.tile(np.arange(cells, dtype=np.int64) * 10_000, hosts)
    hs = np.repeat(hostnames, cells)
    data = {f: rng.random(len(ts)) * 100.0 for f in fields}
    table.write({"hostname": hs}, ts, data, skip_wal=True)
    table.flush()
    return table


FLAGSHIP = ("SELECT ts, hostname, avg(usage_user) RANGE '1m', "
            "avg(usage_system) RANGE '1m' FROM cpu "
            "ALIGN '1m' BY (hostname)")


def test_device_attribution_one_row_for_repeated_polls(tmp_path):
    """The acceptance shape: a repeatedly-polled dashboard query lands
    on ONE row with device exec path, compile=1/cache-hit>=N-1, and
    non-zero delta-readback bytes on a since-poll."""
    inst = Standalone(str(tmp_path / "dev"), prefer_device=True,
                      warm_start=False)
    try:
        _seed_cpu(inst)
        n = 6
        for _ in range(n):
            assert inst.sql(FLAGSHIP).num_rows > 0
        # delta poll: only the steps past the cursor are read back
        # (the seeded data spans ~640s => ~11 one-minute align steps;
        # a cursor in the middle leaves a non-empty unseen tail)
        ctx = QueryContext()
        ctx.extensions["since_ms"] = 300_000
        inst.execute_sql(FLAGSHIP, ctx)

        fp = S.fingerprint_sql(FLAGSHIP)[0].fp
        docs = [d for d in S.global_stmt_stats.snapshot()
                if d["fingerprint"] == fp]
        assert len(docs) == 1, "every poll must land on ONE row"
        row = docs[0]
        assert row["calls"] == n + 1
        assert row["exec_path"] == "device"
        assert row["compile_count"] >= 1
        assert row["compile_cache_hits"] >= n - 1
        assert row["readback_full_bytes"] > 0
        assert row["readback_delta_bytes"] > 0
        assert row["session_hit_rate"] > 0.0
        assert row["rows_returned"] > 0
        assert row["p99_ms"] >= row["p50_ms"] >= 0.0
        # the exemplar joins the trace ring
        assert row["last_trace_id"]
        from greptimedb_tpu.telemetry.tracing import global_traces

        assert global_traces.trace(row["last_trace_id"])
    finally:
        inst.close()


def test_result_cache_and_queue_attribution(inst):
    from greptimedb_tpu.query.result_cache import ResultCache

    inst.result_cache = ResultCache(enabled=True)
    inst.catalog.result_cache = inst.result_cache
    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    inst.execute_sql("insert into t values (1, 1.0), (2, 2.0)")
    q = "select ts, v from t order by ts"
    for _ in range(4):
        inst.sql(q)
    row = _row_for(S.fingerprint_sql(q)[0].fp)
    assert row is not None
    assert row["calls"] == 4
    # first execution misses, the rest serve from the frontend cache
    assert row["result_cache_hit_rate"] >= 0.5
    # permissive admission still records (near-zero) queue time
    assert row["queue_total_ms"] >= 0.0


def test_shed_and_error_attribution(inst):
    from greptimedb_tpu.errors import QueryOverloadedError
    from greptimedb_tpu.sched import AdmissionController, SchedulerConfig

    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    # one-token bucket that refills at 1e-6 qps: the second immediate
    # statement sheds typed
    inst.scheduler = AdmissionController(SchedulerConfig(
        tenant_qps=1e-6, tenant_burst=1.0,
    ))
    q = "select count(v) from t"
    inst.sql(q)
    with pytest.raises(QueryOverloadedError):
        inst.sql(q)
    row = _row_for(S.fingerprint_sql(q)[0].fp)
    assert row["calls"] == 2
    assert row["errors"] == 1
    assert row["errors_by_code"].get(6002) == 1 or \
        row["errors_by_code"].get("6002") == 1
    assert row["shed_count"] == 1
    # a plain table-not-found error lands under its own code (4001)
    inst.scheduler = AdmissionController()
    from greptimedb_tpu.errors import TableNotFoundError

    with pytest.raises(TableNotFoundError):
        inst.sql("select v from no_such_table")
    row = _row_for(S.fingerprint_sql(
        "select v from no_such_table")[0].fp)
    assert row["errors"] == 1
    assert row["shed_count"] == 0


def test_explain_analyze_stamps_inner_fingerprint(inst):
    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    inst.execute_sql("insert into t values (1, 1.0)")
    plain = "select count(v) from t"
    res = inst.sql(f"explain analyze {plain}")
    lines = [r[0] for r in res.rows()]
    fp = S.fingerprint_sql(plain)[0].fp
    assert any(f"stmt_fingerprint: {fp}" in ln for ln in lines), lines


def test_slow_query_log_carries_fingerprint(inst):
    from greptimedb_tpu.telemetry.slow_query import SlowQueryLog

    inst.slow_query_log = SlowQueryLog(threshold_s=0.0)
    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    q = "select count(v) from t"
    inst.sql(q)
    fp = S.fingerprint_sql(q)[0].fp
    entries = [e for e in inst.slow_query_log.entries()
               if e["query"] == q]
    assert entries and entries[-1]["fingerprint"] == fp
    # the information_schema face joins on the same column
    r = inst.sql("select fingerprint, query from "
                 "information_schema.slow_queries")
    assert [fp, q] in r.rows()


def test_percentiles_count_overflow_observations():
    """Observations past the last histogram bound (60s) must still
    count toward p50/p99 (reported as >= the last bound), not vanish
    — the slowest statements are exactly the rows operators sort by."""
    buckets = [0] * S._N_BUCKETS
    for _ in range(100):
        S._observe_buckets(buckets, 120_000.0)  # 2min, past 60s
    assert sum(buckets) == 100
    assert S._quantile(buckets, 0.50) == S._BUCKETS_MS[-1]
    assert S._quantile(buckets, 0.99) == S._BUCKETS_MS[-1]
    # mixed: half fast, half overflow — p99 lands at the bound, p50
    # inside the fast bucket
    mixed = [0] * S._N_BUCKETS
    for _ in range(50):
        S._observe_buckets(mixed, 1.0)
        S._observe_buckets(mixed, 120_000.0)
    assert S._quantile(mixed, 0.99) == S._BUCKETS_MS[-1]
    assert S._quantile(mixed, 0.50) <= 1.0


# ---------------------------------------------------------------------------
# cardinality collapse + reset
# ---------------------------------------------------------------------------

def test_cardinality_collapse_past_the_knob(inst):
    S.configure({"max_fingerprints": 4, "metric_fingerprints": 2})
    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    shapes = [
        "select count(v) from t",
        "select min(v) from t",
        "select max(v) from t",
        "select sum(v) from t",
        "select avg(v) from t",
        "select count(v), min(v) from t",
    ]
    for q in shapes:
        inst.sql(q)
    docs = S.global_stmt_stats.snapshot()
    assert len(docs) <= 4
    other = _row_for(S.OTHER)
    assert other is not None, "evicted rows must collapse into _other"
    total_calls = sum(d["calls"] for d in docs)
    # 1 create + 6 selects: totals survive the collapse
    assert total_calls == 1 + len(shapes)
    assert S.global_stmt_stats.evicted_rows > 0


def test_admin_reset_statement_statistics(inst):
    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    inst.sql("select count(v) from t")
    assert len(S.global_stmt_stats.snapshot()) >= 2
    res = inst.sql("admin reset_statement_statistics()")
    assert res.rows()[0][0] >= 2
    # only the reset statement itself (recorded after the wipe) remains
    docs = S.global_stmt_stats.snapshot()
    assert all(d["query"].startswith("admin") for d in docs)


def test_disabled_registry_records_nothing(inst):
    S.configure({"enable": False})
    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    inst.sql("select count(v) from t")
    assert S.global_stmt_stats.snapshot() == []


# ---------------------------------------------------------------------------
# surface agreement: information_schema == HTTP == /metrics
# ---------------------------------------------------------------------------

def _http_get(port: int, path: str) -> bytes:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.read()


def test_surfaces_agree(inst):
    from greptimedb_tpu.servers.http import HttpServer

    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    inst.execute_sql("insert into t values (1, 1.0), (2, 2.0)")
    q = "select ts, v from t where ts > 0"
    n = 3
    for _ in range(n):
        inst.sql(q)
    fp = S.fingerprint_sql(q)[0].fp

    srv = HttpServer(inst, port=0).start()
    try:
        # 1. information_schema
        r = inst.sql(
            "select calls, rows_returned from "
            "information_schema.statement_statistics "
            f"where fingerprint = '{fp}'"
        )
        assert r.rows() == [[n, 2 * n]]

        # 2. HTTP endpoint, ordered + bounded
        doc = json.loads(_http_get(
            srv.port, "/v1/stats/statements?order_by=calls&limit=1"
        ))
        assert len(doc["statements"]) == 1
        top = doc["statements"][0]
        assert top["fingerprint"] == fp
        assert top["calls"] == n
        # order_by=calls really ordered
        full = json.loads(_http_get(
            srv.port, "/v1/stats/statements?order_by=calls"
        ))["statements"]
        calls = [d["calls"] for d in full]
        assert calls == sorted(calls, reverse=True)
        # bad limit is a client error
        import urllib.error

        with pytest.raises(urllib.error.HTTPError):
            _http_get(srv.port, "/v1/stats/statements?limit=zzz")

        # 3. /metrics: the same calls under the fingerprint label
        metrics = _http_get(srv.port, "/metrics").decode()
        line = next(
            ln for ln in metrics.splitlines()
            if ln.startswith("gtpu_stmt_calls_total")
            and f'fingerprint="{fp}"' in ln
        )
        assert float(line.rsplit(" ", 1)[1]) == float(n)
        # runtime_metrics (information_schema face of /metrics) agrees
        r = inst.sql(
            "select value from information_schema.runtime_metrics "
            f"where metric_name = 'gtpu_stmt_calls_total' "
            f"and labels like '%{fp}%'"
        )
        assert r.rows() == [[float(n)]]
    finally:
        srv.stop()


def test_metric_label_cardinality_collapses_to_other(inst):
    # configure() re-derives the label grant set under the new cap;
    # earlier tests' prometheus series persist, so measure the DELTA
    # of the _other series instead of its absolute value
    S.configure({"max_fingerprints": 64, "metric_fingerprints": 1})
    from greptimedb_tpu.telemetry.metrics import global_registry

    def other_calls() -> float:
        # the gtpu_stmt_* families are PULL-model: values refresh on
        # render (a scrape), not per statement
        global_registry.render()
        return global_registry.get(
            "gtpu_stmt_calls_total").labels("public", S.OTHER).value

    other0 = other_calls()
    inst.execute_sql(
        "create table t (ts timestamp time index, v double)")
    inst.sql("select count(v) from t")
    inst.sql("select min(v) from t")
    # at most one of the three statements got a real label; the rest
    # collapsed to _other
    assert other_calls() - other0 >= 2
