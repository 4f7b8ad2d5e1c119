"""Device-resident result path: frontend result cache + persistent
query sessions + `since` delta polls (ISSUE 9).

Invalidation proof through the full stack: a cached result payload (or
a session-resident device buffer) must NEVER be served after a
data-mutating op — insert, flush, compact, truncate, ALTER, region
migration — and a stale-version poll falls back to recompute with
correct results (mirrors tests/test_dist_scan_cache.py for the new
layers)."""

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.query.readback import readback_bytes
from greptimedb_tpu.query.result_cache import ResultCache
from greptimedb_tpu.query import sessions as sessions_mod
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.storage.engine import EngineConfig
from greptimedb_tpu.telemetry.metrics import global_registry


def _counter(name: str, *labels) -> float:
    return global_registry.get(name).labels(*labels).value


def _enable_rc(inst, **kw) -> ResultCache:
    rc = ResultCache(enabled=True, **kw)
    inst.result_cache = rc
    inst.catalog.result_cache = rc
    return rc


@pytest.fixture()
def inst(tmp_path):
    inst = Standalone(str(tmp_path / "data"), warm_start=False,
                      prefer_device=False)
    yield inst
    inst.close()


@pytest.fixture()
def dev_inst(tmp_path):
    pytest.importorskip("jax")
    inst = Standalone(str(tmp_path / "data"), warm_start=False,
                      prefer_device=True)
    yield inst
    inst.close()


def _seed(inst, table="t", rows=24):
    inst.execute_sql(
        f"create table {table} (ts timestamp time index, host string "
        "primary key, v double)"
    )
    values = ", ".join(
        f"('h{i % 3}', {1_000_000 + i * 1000}, {float(i)})"
        for i in range(rows)
    )
    inst.execute_sql(f"insert into {table} (host, ts, v) values {values}")


Q = "select host, sum(v), count(*) from t group by host order by host"


# ----------------------------------------------------------------------
# frontend result cache: hits, metrics, invalidation (standalone)
# ----------------------------------------------------------------------

def test_result_cache_hit_serves_same_rows(inst):
    rc = _enable_rc(inst)
    _seed(inst)
    cold = inst.sql(Q).rows()
    h0 = _counter("gtpu_result_cache_hits_total")
    warm = inst.sql(Q).rows()
    assert warm == cold
    assert _counter("gtpu_result_cache_hits_total") > h0
    assert rc.entry_count >= 1 and rc.byte_count > 0


def test_insert_invalidates(inst):
    _enable_rc(inst)
    _seed(inst)
    before = inst.sql(Q).rows()
    inst.sql(Q)  # cached
    inst.execute_sql(
        "insert into t (host, ts, v) values ('h0', 99000000, 1000.0)"
    )
    after = inst.sql(Q).rows()
    assert after != before
    h0 = next(r for r in after if r[0] == "h0")
    b0 = next(r for r in before if r[0] == "h0")
    assert h0[1] == b0[1] + 1000.0 and h0[2] == b0[2] + 1


def test_flush_and_compact_invalidate(inst):
    _enable_rc(inst)
    _seed(inst)
    cold = inst.sql(Q).rows()
    inst.sql(Q)
    m0 = _counter("gtpu_result_cache_misses_total")
    table = inst.catalog.table("public", "t")
    table.flush()  # physical version bumps even though rows don't
    assert inst.sql(Q).rows() == cold
    assert _counter("gtpu_result_cache_misses_total") > m0
    # several flushed generations in one window trip the TWCS picker
    for round_ in range(4):
        inst.execute_sql(
            "insert into t (host, ts, v) values "
            + ", ".join(
                f"('h{i % 3}', {2_000_000 + round_ * 40_000 + i * 1000},"
                f" {float(i)})"
                for i in range(12)
            )
        )
        table.flush()
    want = inst.sql(Q).rows()
    inst.sql(Q)
    m1 = _counter("gtpu_result_cache_misses_total")
    compacted = sum(1 for r in table.regions if r.compact())
    assert compacted > 0
    assert inst.sql(Q).rows() == want
    assert _counter("gtpu_result_cache_misses_total") > m1


def test_truncate_and_alter_invalidate(inst):
    _enable_rc(inst)
    _seed(inst)
    inst.sql(Q)
    inst.sql(Q)
    inst.execute_sql("alter table t add column extra double")
    # schema change busts the key (version embeds column names)
    assert inst.sql("select count(*) from t").rows() == [[24]]
    inst.catalog.table("public", "t").truncate()
    assert inst.sql("select count(*) from t").rows() == [[0]]


def test_drop_purges_entries(inst):
    rc = _enable_rc(inst)
    _seed(inst)
    inst.sql(Q)
    assert rc.entry_count >= 1
    inst.execute_sql("drop table t")
    assert rc.entry_count == 0


def test_volatile_ttl_and_explain_bypass(inst):
    rc = _enable_rc(inst)
    _seed(inst)
    n0 = rc.entry_count
    # now() in the projection is evaluation-time dependent: never cached
    inst.sql("select count(*), now() from t")
    assert rc.entry_count == n0
    # a now()-folded WHERE bound re-fingerprints per call: caching it
    # would insert one dead never-hit entry per poll (volatile_bounds)
    inst.sql("select host, v from t where ts > now() - interval '100y'")
    inst.sql("select host, v from t where ts > now() - interval '100y'")
    assert rc.entry_count == n0
    inst.execute_sql(
        "create table tt (ts timestamp time index, host string "
        "primary key, v double) with (ttl = '1h')"
    )
    import time as _time

    now = int(_time.time() * 1000)
    inst.execute_sql(
        f"insert into tt (host, ts, v) values ('a', {now - 60_000}, 1.0)"
    )
    inst.sql("select host, sum(v) from tt group by host")
    assert rc.entry_count == n0  # TTL window is wall-clock-derived
    # EXPLAIN ANALYZE runs a real execution (never a cached payload)
    inst.sql(Q)
    res = inst.sql("explain analyze " + Q)
    text = "\n".join(res.cols[0].values.tolist())
    assert "Metrics:" in text


# ----------------------------------------------------------------------
# `since` delta cursor
# ----------------------------------------------------------------------

def test_since_filters_plain_select(inst):
    _seed(inst)
    ctx = QueryContext()
    ctx.extensions["since_ms"] = 1_000_000 + 11 * 1000
    res = inst.sql("select ts, host, v from t order by ts", ctx)
    ts = np.asarray(res.column("ts").values, np.int64)
    assert len(ts) == 12 and ts.min() > 1_011_000


def test_since_with_result_cache_serves_delta_from_full(inst):
    rc = _enable_rc(inst)
    _seed(inst)
    full = inst.sql("select ts, host, v from t").rows()
    assert rc.entry_count == 1
    h0 = _counter("gtpu_result_cache_hits_total")
    ctx = QueryContext()
    ctx.extensions["since_ms"] = 1_000_000 + 11 * 1000
    delta = inst.sql("select ts, host, v from t", ctx).rows()
    # served from the cached FULL result by a host-side row filter
    assert _counter("gtpu_result_cache_hits_total") > h0
    assert delta == [r for r in full if r[0] > 1_011_000]
    # a cursor past everything returns zero rows
    ctx2 = QueryContext()
    ctx2.extensions["since_ms"] = 99_000_000_000
    assert inst.sql("select ts, host, v from t", ctx2).rows() == []


def test_since_with_limit_executes_delta_not_cached_slice(inst):
    """The cursor applies BEFORE ORDER BY/LIMIT: a LIMIT plan's cached
    payload cannot be row-filtered (it holds only the first page), so a
    since-poll must execute the delta instead of returning []."""
    _enable_rc(inst)
    _seed(inst)
    q = "select ts, host, v from t order by ts limit 10"
    first = inst.sql(q).rows()
    assert len(first) == 10
    ctx = QueryContext()
    ctx.extensions["since_ms"] = first[-1][0]
    delta = inst.sql(q, ctx).rows()
    assert len(delta) == 10
    assert min(r[0] for r in delta) > first[-1][0]


def test_since_without_ts_projection_executes_delta(inst):
    """A plain select that does not project the time index cannot be
    delta-served from the cache (no column to filter on) — the
    execution path's scan tightening must answer instead."""
    _enable_rc(inst)
    _seed(inst)
    inst.sql("select host, v from t")  # cached full payload
    ctx = QueryContext()
    ctx.extensions["since_ms"] = 1_011_000
    delta = inst.sql("select host, v from t", ctx).rows()
    assert len(delta) == 12  # rows past the cursor, ts unprojected


def test_since_range_device_delta_readback(dev_inst):
    """Device RANGE path: a since-poll slices the session-resident
    buffer device-side — delta readback bytes land on
    gtpu_readback_bytes_total{mode=delta} and rows match the full
    result filtered by ts."""
    inst = dev_inst
    _seed(inst, rows=60)
    q = ("select ts, host, avg(v) range '10s' from t "
         "align '10s' by (host) order by ts, host")
    f0 = readback_bytes("full")
    full = inst.sql(q).rows()
    assert inst.query_engine.last_exec_path == "device"
    full_bytes = readback_bytes("full") - f0
    cut = sorted({r[0] for r in full})[len({r[0] for r in full}) // 2]
    d0 = readback_bytes("delta")
    s0 = _counter("gtpu_session_hits_total")
    ctx = QueryContext()
    ctx.extensions["since_ms"] = cut
    delta = inst.sql(q, ctx).rows()
    assert delta == [r for r in full if r[0] > cut]
    # only the steps past the watermark crossed back to the host
    assert 0 < readback_bytes("delta") - d0 < full_bytes
    # the repeated shape reused the session-resident result buffer
    assert _counter("gtpu_session_hits_total") > s0


def test_since_range_fill_prev_matches_full(dev_inst):
    """FILL PREV + since: the fill math runs over the FULL grid, then
    only post-cursor cells emit — delta rows equal the full result
    filtered by ts (carry-over from pre-cursor steps preserved)."""
    inst = dev_inst
    inst.execute_sql(
        "create table f (ts timestamp time index, host string "
        "primary key, v double)"
    )
    # gaps so PREV actually fills
    rows = [(0, 1.0), (10_000, 2.0), (40_000, 5.0)]
    values = ", ".join(f"('h0', {ts}, {v})" for ts, v in rows)
    inst.execute_sql(f"insert into f (host, ts, v) values {values}")
    q = ("select ts, host, avg(v) range '10s' fill prev from f "
         "align '10s' by (host) order by ts")
    full = inst.sql(q).rows()
    ctx = QueryContext()
    ctx.extensions["since_ms"] = 10_000
    delta = inst.sql(q, ctx).rows()
    assert delta == [r for r in full if r[0] > 10_000]
    # the 20s/30s steps carry the PREV value from the 10s step
    filled = [r for r in delta if r[0] in (20_000, 30_000)]
    assert filled and all(r[2] == 2.0 for r in filled)


def test_session_registry_invalidation(dev_inst):
    inst = dev_inst
    _seed(inst, rows=60)
    q = ("select ts, host, max(v) range '10s' from t "
         "align '10s' by (host)")
    before = inst.sql(q).rows()
    s0 = _counter("gtpu_session_hits_total")
    assert inst.sql(q).rows() == before
    assert _counter("gtpu_session_hits_total") > s0
    inst.execute_sql(
        "insert into t (host, ts, v) values ('h0', 1000000, 500.0)"
    )
    after = inst.sql(q).rows()  # write invalidated the session buffer
    assert after != before
    assert any(r[2] == 500.0 for r in after)


def test_sessions_disabled_still_correct(dev_inst):
    inst = dev_inst
    _seed(inst, rows=60)
    q = ("select ts, host, min(v) range '10s' from t "
         "align '10s' by (host)")
    want = inst.sql(q).rows()
    sessions_mod.configure({"enable": False})
    try:
        assert inst.sql(q).rows() == want
    finally:
        sessions_mod.configure({"enable": True})


# ----------------------------------------------------------------------
# HTTP surface: ?since= param
# ----------------------------------------------------------------------

def test_http_since_param(inst, tmp_path):
    import json
    import urllib.request

    from greptimedb_tpu.servers.http import HttpServer

    _enable_rc(inst)
    _seed(inst)
    srv = HttpServer(inst, port=0).start()
    try:
        def sql(q, since=None):
            url = (f"http://127.0.0.1:{srv.port}/v1/sql?sql="
                   + urllib.parse.quote(q))
            if since is not None:
                url += f"&since={since}"
            with urllib.request.urlopen(url, timeout=10) as resp:
                return json.loads(resp.read())

        q = "select ts, host, v from t order by ts"
        full = sql(q)["output"][0]["records"]["rows"]
        delta = sql(q, since=1_011_000)["output"][0]["records"]["rows"]
        assert delta == [r for r in full if r[0] > 1_011_000]
        # bad cursor -> 400
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as ei:
            sql(q, since="nan")
        assert ei.value.code == 400
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# distributed: full frontend -> datanode path (mirrors
# tests/test_dist_scan_cache.py)
# ----------------------------------------------------------------------

pytest.importorskip("pyarrow.flight")

from greptimedb_tpu.dist.client import MetaClient  # noqa: E402
from greptimedb_tpu.dist.frontend import DistInstance  # noqa: E402
from greptimedb_tpu.dist.region_server import RegionServer  # noqa: E402
from greptimedb_tpu.servers.flight import FlightFrontend  # noqa: E402
from greptimedb_tpu.servers.meta_http import MetasrvServer  # noqa: E402


class _Harness:
    def __init__(self, tmp_path, n_datanodes=2, *, store=None):
        self.meta = MetasrvServer(
            addr="127.0.0.1", port=0, data_home=str(tmp_path / "meta")
        ).start()
        self.meta_addr = f"127.0.0.1:{self.meta.port}"
        self.datanodes = {}
        for i in range(n_datanodes):
            home = str(tmp_path / f"dn{i}")
            inst = Standalone(
                engine_config=EngineConfig(data_root=home,
                                           enable_background=False),
                prefer_device=False, warm_start=False, store=store,
            )
            inst.region_server = RegionServer(inst.engine, home)
            fs = FlightFrontend(inst, port=0).start()
            MetaClient(self.meta_addr).register(
                i, f"127.0.0.1:{fs.server.port}"
            )
            self.datanodes[i] = (inst, fs)
        self.frontend = DistInstance(
            str(tmp_path / "fe"), self.meta_addr, prefer_device=False
        )
        self.rc = _enable_rc(self.frontend)

    def close(self):
        self.frontend.close()
        for inst, fs in self.datanodes.values():
            fs.close()
            inst.close()
        self.meta.close()


@pytest.fixture()
def harness(tmp_path):
    h = _Harness(tmp_path)
    yield h
    h.close()


DQ = "select host, sum(v), count(*) from d1 group by host order by host"


def _seed_dist(fe, rows=40):
    fe.execute_sql(
        "create table d1 (ts timestamp time index, host string "
        "primary key, v double) with (num_regions = 2)"
    )
    values = ", ".join(
        f"('h{i % 4}', {1_000_000 + i * 1000}, {float(i)})"
        for i in range(rows)
    )
    fe.execute_sql(f"insert into d1 (host, ts, v) values {values}")


def test_dist_hit_skips_datanode_execution(harness):
    fe = harness.frontend
    _seed_dist(fe)
    cold = fe.sql(DQ).rows()  # miss: executes the pushdown + caches
    q0 = _counter("gtpu_dist_query_total")
    h0 = _counter("gtpu_result_cache_hits_total")
    warm = fe.sql(DQ).rows()
    assert warm == cold
    assert _counter("gtpu_result_cache_hits_total") > h0
    # the hit ran NO distributed partial execution (version validation
    # is one metadata action, never a plan fan-out)
    assert _counter("gtpu_dist_query_total") == q0


def test_dist_insert_flush_truncate_alter_invalidate(harness):
    fe = harness.frontend
    _seed_dist(fe)
    before = fe.sql(DQ).rows()
    fe.sql(DQ)
    fe.execute_sql(
        "insert into d1 (host, ts, v) values ('h0', 99000000, 1000.0)"
    )
    after = fe.sql(DQ).rows()
    h0 = next(r for r in after if r[0] == "h0")
    b0 = next(r for r in before if r[0] == "h0")
    assert h0[1] == b0[1] + 1000.0 and h0[2] == b0[2] + 1
    # flush: rows unchanged, physical version bumped -> recompute
    m0 = _counter("gtpu_result_cache_misses_total")
    fe.sql(DQ)
    fe.catalog.table("public", "d1").flush()
    assert fe.sql(DQ).rows() == after
    assert _counter("gtpu_result_cache_misses_total") > m0
    # ALTER busts the key (schema rides the version tuple)
    fe.sql(DQ)
    fe.execute_sql("alter table d1 add column extra double")
    assert fe.sql("select count(*) from d1").rows() == [[41]]
    fe.catalog.table("public", "d1").truncate()
    assert fe.sql("select count(*) from d1").rows() == [[0]]


def test_dist_since_delta_through_ticket(harness):
    fe = harness.frontend
    _seed_dist(fe)
    q = "select ts, host, v from d1 order by ts, host"
    full = fe.sql(q).rows()
    ctx = QueryContext()
    ctx.extensions["since_ms"] = 1_000_000 + 19 * 1000
    delta = fe.sql(q, ctx).rows()
    assert delta == [r for r in full if r[0] > 1_019_000]
    assert len(delta) == 20


def test_dist_migration_recomputes_correctly(tmp_path):
    from greptimedb_tpu.storage.object_store import FsObjectStore

    shared = FsObjectStore(str(tmp_path / "shared_store"))
    h = _Harness(tmp_path, n_datanodes=2, store=shared)
    try:
        fe = h.frontend
        fe.execute_sql(
            "create table gm (ts timestamp time index, host string "
            "primary key, v double)"
        )
        fe.execute_sql(
            "insert into gm (host, ts, v) values ('a', 1000, 1.0), "
            "('b', 2000, 2.0)"
        )
        q = "select host, sum(v) from gm group by host order by host"
        want = fe.sql(q).rows()
        fe.sql(q)  # cached on the frontend
        ms = h.meta.metasrv
        rid = fe.catalog.table("public", "gm").info.region_ids()[0]
        src = ms.route_of(rid)
        ms.migrate_region(rid, 1 - src)
        fe.catalog.refresh()
        # version validation decides: a matching physical version may
        # legitimately serve the cached payload (migration preserves
        # data); a re-anchored one recomputes — both must be `want`
        assert fe.sql(q).rows() == want
        # a write on the NEW hosting is visible on the next poll
        fe.execute_sql(
            "insert into gm (host, ts, v) values ('a', 3000, 10.0)"
        )
        assert fe.sql(q).rows() == [["a", 11.0], ["b", 2.0]]
    finally:
        h.close()
