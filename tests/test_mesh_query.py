"""SQL queries executing multi-device: the database itself on the mesh.

The device RANGE path shards its cell-state grids over the series axis of
an 8-device mesh (conftest forces 8 virtual CPU devices); XLA inserts the
cross-shard collectives for the group folds. Capability counterpart of the
reference's distributed merge-scan
(/root/reference/src/query/src/dist_plan/merge_scan.rs:124,
src/partition/src/multi_dim.rs:37) with the Flight gather replaced by ICI
collectives.
"""

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.parallel import mesh as M
from greptimedb_tpu.query.executor import QueryEngine
from greptimedb_tpu.query.planner import plan_select
from greptimedb_tpu.sql.parser import parse_sql


FLAGSHIP = (
    "SELECT ts, host, avg(u) RANGE '1m', max(v) RANGE '1m', "
    "last_value(u) RANGE '1m' FROM cpu ALIGN '1m' BY (host) "
    "ORDER BY ts, host"
)

# test grids are tiny; force the replicate-vs-shard planner to shard so
# the mesh programs actually run (prod defaults gate on 4096 series)
FORCE_SHARD = M.MeshOptions(shard_min_series=1, shard_min_rows=1)


@pytest.fixture
def inst(tmp_path, rng, devices):
    i = Standalone(str(tmp_path))
    i.execute_sql(
        "create table cpu (ts timestamp time index, host string primary key,"
        " u double, v double)"
    )
    tab = i.catalog.table("public", "cpu")
    n_hosts, t = 24, 240
    ts = np.tile(np.arange(t) * 10_000, n_hosts).astype(np.int64)
    hosts = np.repeat([f"h{i:02d}" for i in range(n_hosts)], t).astype(object)
    tab.write(
        {"host": hosts}, ts,
        {"u": rng.random(n_hosts * t) * 100, "v": rng.random(n_hosts * t)},
    )
    yield i
    i.close()


def _run(engine, inst, sql):
    stmt = parse_sql(sql)[0]
    plan, table = inst.plan(stmt, __import__(
        "greptimedb_tpu.session", fromlist=["QueryContext"]
    ).QueryContext())
    return engine.execute(plan, table)


def _compare(ra, rb):
    assert ra.num_rows == rb.num_rows
    for i in range(len(ra.names)):
        a, b = ra.cols[i].values, rb.cols[i].values
        if a.dtype == object:
            assert (a == b).all()
        else:
            np.testing.assert_allclose(
                np.asarray(a, float), np.asarray(b, float),
                rtol=2e-4, atol=1e-3, err_msg=ra.names[i],
            )


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_sql_on_mesh_matches_single(inst, devices, n_dev):
    """Series sharding over 2, 4 and 8 devices answers the flagship
    shape bit for bit as one device does."""
    mesh = M.make_mesh(devices[:n_dev])
    e1 = QueryEngine(prefer_device=True)
    em = QueryEngine(prefer_device=True, mesh=mesh, mesh_opts=FORCE_SHARD)
    r1 = _run(e1, inst, FLAGSHIP)
    assert e1.last_exec_path == "device"
    rm = _run(em, inst, FLAGSHIP)
    assert em.last_exec_path == "device"
    # grids actually live sharded over the mesh
    entry = next(iter(em.range_cache._entries.values()))
    sharding = entry.nrow.sharding
    assert getattr(sharding, "mesh", None) is not None
    assert len(entry.nrow.devices()) == n_dev
    assert r1.num_rows == rm.num_rows
    for name, c1, cm in zip(r1.names, r1.cols, rm.cols):
        a, b = np.asarray(c1.values), np.asarray(cm.values)
        if a.dtype == object:
            assert (a == b).all(), name
        else:
            assert np.array_equal(a, b, equal_nan=True), name


def test_sql_on_mesh_global_group(inst, devices):
    mesh = M.make_mesh(devices)
    em = QueryEngine(prefer_device=True, mesh=mesh, mesh_opts=FORCE_SHARD)
    q = ("SELECT ts, avg(u) RANGE '2m', count(*) RANGE '2m' FROM cpu "
         "ALIGN '1m' BY () ORDER BY ts")
    eh = QueryEngine(prefer_device=False)
    _compare(_run(eh, inst, q), _run(em, inst, q))
    assert em.last_exec_path == "device"


def test_cluster_sql_on_mesh(tmp_path, rng, devices):
    """The full distributed shape: multi-region Cluster table, query
    planned from SQL, executed on the 8-device mesh."""
    from greptimedb_tpu.cluster import Cluster
    from greptimedb_tpu.datatypes.schema import (
        ColumnSchema, Schema, SemanticType,
    )
    from greptimedb_tpu.datatypes.types import ConcreteDataType as T

    cluster = Cluster(str(tmp_path), n_datanodes=3)
    schema = Schema([
        ColumnSchema("ts", T.timestamp_millisecond(),
                     SemanticType.TIMESTAMP, nullable=False),
        ColumnSchema("host", T.string(), SemanticType.TAG, nullable=False),
        ColumnSchema("u", T.float64(), SemanticType.FIELD),
    ])
    table = cluster.create_table("public", "cpu", schema, num_regions=3)
    n_hosts, t = 16, 120
    ts = np.tile(np.arange(t) * 10_000, n_hosts).astype(np.int64)
    hosts = np.repeat(
        [f"h{i:02d}" for i in range(n_hosts)], t
    ).astype(object)
    table.write({"host": hosts}, ts, {"u": rng.random(n_hosts * t) * 100})
    # rows really are spread over the datanodes
    dist = cluster.region_distribution()
    assert sum(1 for rids in dist.values() if rids) == 3

    stmt = parse_sql(FLAGSHIP.replace(", max(v) RANGE '1m'", "")
                     .replace(", last_value(u) RANGE '1m'", ""))[0]
    plan = plan_select(stmt, ts_name="ts", tag_names=["host"],
                       all_columns=["ts", "host", "u"])
    eh = QueryEngine(prefer_device=False)
    rh = eh.execute(plan, cluster.table("public", "cpu"))
    em = QueryEngine(prefer_device=True, mesh=M.make_mesh(devices),
                     mesh_opts=FORCE_SHARD)
    rm = em.execute(plan, cluster.table("public", "cpu"))
    assert em.last_exec_path == "device"
    _compare(rh, rm)
    cluster.shutdown()


def test_groupby_on_8device_mesh_matches_host(inst, devices):
    """Plain GROUP BY: the fused reduce program runs row-sharded over
    the mesh (VERDICT r3 task #2); results must equal the host path."""
    mesh = M.make_mesh(devices)
    em = QueryEngine(prefer_device=True, mesh=mesh, mesh_opts=FORCE_SHARD)
    eh = QueryEngine(prefer_device=False)
    q = ("SELECT host, count(u), sum(u), avg(u), min(v), max(v), "
         "stddev_samp(u) FROM cpu GROUP BY host ORDER BY host")
    rh = _run(eh, inst, q)
    rm = _run(em, inst, q)
    assert em.last_exec_path == "device"
    _compare(rh, rm)


@pytest.mark.parametrize("t", [120, 300])
def test_promql_fast_on_8device_mesh_matches_host(tmp_path, rng, devices, t):
    """PromQL sum by (dc)(rate(...)): the selector-grid fast path runs
    series-sharded over the mesh; equality vs the single-device path,
    bit for bit (a sample is carried along the cell axis inside its
    series, the mesh splits series), also on a grid of more than 128
    cells."""
    from greptimedb_tpu.parallel import mesh as M2
    from greptimedb_tpu.promql import fast as F
    from greptimedb_tpu.promql.engine import PromEngine

    def build(home, mesh):
        rng = np.random.default_rng(7)  # identical data in both builds
        i = Standalone(str(home), prefer_device=True, mesh=mesh,
                       mesh_opts=None if mesh is None else FORCE_SHARD,
                       warm_start=False)
        i.execute_sql(
            "create table http_requests (ts timestamp time index, "
            "host string primary key, dc string primary key, "
            "greptime_value double)"
        )
        tab = i.catalog.table("public", "http_requests")
        n_hosts = 24
        ts = np.tile(np.arange(t) * 10_000, n_hosts).astype(np.int64)
        hosts = np.repeat(
            [f"h{k:02d}" for k in range(n_hosts)], t
        ).astype(object)
        dcs = np.repeat(
            [f"dc{k % 3}" for k in range(n_hosts)], t
        ).astype(object)
        vals = np.cumsum(rng.random(n_hosts * t), 0)
        tab.write({"host": hosts, "dc": dcs}, ts,
                  {"greptime_value": vals})
        return i

    F.invalidate_cache()
    mesh = M2.make_mesh(devices)
    i1 = build(tmp_path / "a", None)
    im = build(tmp_path / "b", mesh)
    q = "sum by (dc) (rate(http_requests[2m]))"
    t0, t1 = 0, (t - 1) * 10_000
    try:
        r1, _ = PromEngine(i1).query_range(q, t0, t1, 60_000)
        F.invalidate_cache()
        rm, _ = PromEngine(im).query_range(q, t0, t1, 60_000)
        # the grid really is sharded over 8 devices
        entry = next(iter(F._CACHE._entries.values()))
        assert entry.mesh is mesh
        assert len(entry.vals.devices()) == 8
        assert entry.vals.shape[1] >= t
        assert [frozenset(lb.items()) for lb in r1.labels] == \
               [frozenset(lb.items()) for lb in rm.labels]
        assert (r1.present == rm.present).all()
        assert r1.present.any()
        assert np.array_equal(
            np.where(r1.present, r1.values, 0.0),
            np.where(rm.present, rm.values, 0.0),
        )
    finally:
        F.invalidate_cache()
        i1.close()
        im.close()


# ----------------------------------------------------------------------
# replicate-vs-shard planner + observability (ISSUE 7)
# ----------------------------------------------------------------------


def test_planner_replicate_vs_shard_decisions(devices):
    """decide_mesh_execution: large grids shard, small ones replicate,
    non-decomposable aggregates force replicate, and a missing mesh is
    always replicate."""
    from greptimedb_tpu.query.planner import decide_mesh_execution

    mesh = M.make_mesh(devices)
    opts = M.MeshOptions()  # prod defaults: 4096 series / 256k rows

    d = decide_mesh_execution(mesh, kind="range", series=100_000,
                              ops=("sum", "mean"), opts=opts)
    assert d.shard and d.reason == "large_grid" and d.devices == 8

    d = decide_mesh_execution(mesh, kind="range", series=64,
                              ops=("sum",), opts=opts)
    assert not d.shard and d.reason == "small_grid"

    d = decide_mesh_execution(mesh, kind="aggregate", rows=1_000_000,
                              ops=("count", "max"), opts=opts)
    assert d.shard and d.reason == "large_rowset"

    d = decide_mesh_execution(mesh, kind="aggregate", rows=500,
                              ops=("count",), opts=opts)
    assert not d.shard and d.reason == "small_rowset"

    # median is not decomposable: the whole query runs replicated
    d = decide_mesh_execution(mesh, kind="aggregate", rows=1_000_000,
                              ops=("median",), opts=opts)
    assert not d.shard and d.reason == "non_decomposable:median"

    d = decide_mesh_execution(None, kind="range", series=1_000_000)
    assert not d.shard and d.reason == "no_mesh"


def test_planner_decision_through_query_path(inst, devices):
    """The live query path consults the planner: with prod thresholds a
    24-series grid replicates (single-device placement); with forced
    thresholds the same query shards over 8 devices."""
    from greptimedb_tpu.query import stats as qstats

    mesh = M.make_mesh(devices)
    q = ("SELECT ts, host, avg(u) RANGE '1m' FROM cpu ALIGN '1m' "
         "BY (host) ORDER BY ts, host")

    e_def = QueryEngine(prefer_device=True, mesh=mesh,
                        mesh_opts=M.MeshOptions())
    with qstats.collect() as st:
        _run(e_def, inst, q)
    assert st.notes["mesh_decision_range"] == "replicate(small_grid)"
    entry = next(iter(e_def.range_cache._entries.values()))
    assert entry.mesh is None

    e_force = QueryEngine(prefer_device=True, mesh=mesh,
                          mesh_opts=FORCE_SHARD)
    with qstats.collect() as st:
        _run(e_force, inst, q)
    assert st.notes["mesh_decision_range"] == "shard(large_grid)"
    assert st.counters["mesh_devices"] == 8
    entry = next(iter(e_force.range_cache._entries.values()))
    assert entry.mesh is mesh


def test_mesh_metrics_and_explain_analyze(tmp_path, rng):
    """gtpu_mesh_* must render in /metrics AND runtime_metrics, and
    EXPLAIN ANALYZE must carry the replicate-vs-shard decision. Uses the
    full [mesh]-config lifecycle (configure() from TOML-shaped knobs).
    A sharded query runs its one sharded program: a `pallas_*` key
    left in an old TOML selects nothing, and no kernel variant shows in
    the plan's notes or in the counter's kinds."""
    import urllib.request

    from greptimedb_tpu.servers.http import HttpServer

    M.reset_for_tests()
    try:
        opts = M.mesh_options_from({
            "enabled": True, "shard_min_series": 1, "shard_min_rows": 1,
            "pallas_kernels": "on", "pallas_min_series": 1,
        })
        assert opts == M.MeshOptions(enabled=True, shard_min_series=1,
                                     shard_min_rows=1)
        mesh = M.configure(opts)
        assert mesh is not None and M.shard_count(mesh) == 8
        inst = Standalone(str(tmp_path), mesh=mesh, mesh_opts=opts,
                          prefer_device=True)
        inst.execute_sql(
            "create table cpu (ts timestamp time index, host string "
            "primary key, u double)"
        )
        tab = inst.catalog.table("public", "cpu")
        n_hosts, t = 16, 240
        ts = np.tile(np.arange(t) * 10_000, n_hosts).astype(np.int64)
        hosts = np.repeat(
            [f"h{i:02d}" for i in range(n_hosts)], t
        ).astype(object)
        tab.write({"host": hosts}, ts, {"u": rng.random(n_hosts * t)})
        r = inst.sql(
            "EXPLAIN ANALYZE SELECT ts, host, avg(u) RANGE '1m' FROM cpu "
            "ALIGN '1m' BY (host) ORDER BY ts, host"
        )
        text = "\n".join(row[0] for row in r.rows())
        assert "mesh_decision_range: shard(large_grid)" in text
        assert "mesh_devices: 8" in text
        assert "mesh_kernel_" not in text
        srv = HttpServer(inst, port=0).start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30
            ) as resp:
                body = resp.read().decode()
            assert "gtpu_mesh_devices 8" in body
            assert ('gtpu_mesh_queries_total{kind="range",mode="shard",'
                    'reason="large_grid"}') in body
            assert '_kernel"' not in body
        finally:
            srv.stop()
        res = inst.sql("select metric_name from "
                       "information_schema.runtime_metrics")
        names = list(res.column("metric_name").values)
        assert "gtpu_mesh_devices" in names
        assert "gtpu_mesh_queries_total" in names
        inst.close()
    finally:
        M.reset_for_tests()


def test_rows_preceding_window_on_global_mesh(tmp_path, rng, monkeypatch,
                                              devices):
    """ROWS k PRECEDING frames run the halo shard_map program when the
    process-wide mesh is configured, matching the host baseline within
    the documented ~ulp tolerance; exact counts stay exact."""
    from greptimedb_tpu.query import stats as qstats
    from greptimedb_tpu.query import window_fns as W

    M.reset_for_tests()
    try:
        mesh = M.configure(M.MeshOptions(enabled=True, shard_min_rows=1))
        assert mesh is not None and mesh.shape[M.AXIS_SHARD] == 8
        monkeypatch.setattr(W, "DEVICE_THRESHOLD", 100)
        inst = Standalone(str(tmp_path / "d"), prefer_device=False,
                          warm_start=False)
        try:
            inst.execute_sql(
                "create table w (ts timestamp time index, g string "
                "primary key, v double)"
            )
            tab = inst.catalog.table("public", "w")
            n = 4000
            ts = np.tile(np.arange(n // 4) * 1000, 4).astype(np.int64)
            gs = np.repeat(
                [f"g{i}" for i in range(4)], n // 4
            ).astype(object)
            tab.write({"g": gs}, ts, {"v": rng.random(n) * 100})
            q = ("select g, ts, sum(v) over (partition by g order by ts "
                 "rows between 5 preceding and current row) as s, "
                 "count(v) over (partition by g order by ts "
                 "rows between 5 preceding and current row) as c "
                 "from w order by g, ts")
            with qstats.collect() as st:
                dev = inst.sql(q).rows()
            assert st.notes.get("exec_path_window") == "device_mesh"
            # host baseline: with the global mesh dropped the same
            # query must run the host path
            M.reset_for_tests()
            with qstats.collect() as st2:
                host = inst.sql(q).rows()
            assert st2.notes.get("exec_path_window") != "device_mesh"
            assert len(host) == len(dev) == n
            for h, d in zip(host, dev):
                assert h[0] == d[0] and h[1] == d[1]
                np.testing.assert_allclose(d[2], h[2], rtol=1e-9)
                assert h[3] == d[3]
        finally:
            inst.close()
    finally:
        M.reset_for_tests()
