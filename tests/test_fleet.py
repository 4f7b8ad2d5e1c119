"""Fleet observability plane (ISSUE 15): heartbeat-carried node
telemetry, cluster-wide information_schema tables, federated metrics
and deep health — unit level plus an in-process wire topology (real
metasrv HTTP + datanode Flight servers + DistInstance frontend with
REAL heartbeat loops)."""

from __future__ import annotations

import json
import time
import urllib.request

import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.meta.kv import MemoryKv
from greptimedb_tpu.meta.metasrv import Metasrv
from greptimedb_tpu.storage.engine import EngineConfig


@pytest.fixture()
def inst(tmp_path):
    inst = Standalone(
        engine_config=EngineConfig(data_root=str(tmp_path / "data"),
                                   enable_background=False),
        prefer_device=False, warm_start=False,
    )
    inst.node_addr = "127.0.0.1:14000"
    yield inst
    inst.close()


def _setup_cpu(inst):
    inst.sql("create table cpu (ts timestamp time index, "
             "host string primary key, v double)")
    inst.sql("insert into cpu values (1000, 'h1', 1.0), "
             "(2000, 'h2', 2.0)")


# ---------------------------------------------------------------------
# node-stats payload + deep health (telemetry/node_stats.py)
# ---------------------------------------------------------------------

def test_node_stats_payload(inst):
    from greptimedb_tpu.telemetry import node_stats as ns

    _setup_cpu(inst)
    doc = ns.build_node_stats(inst)
    assert doc["role"] == "standalone"
    assert doc["addr"] == "127.0.0.1:14000"
    assert doc["version"]
    assert doc["uptime_s"] >= 0.0
    assert doc["regions"] >= 1
    assert doc["wal_backlog_rows"] >= 2   # unflushed inserts
    assert doc["memtable_bytes"] > 0
    # memory accountant tiers are present (values may be 0 cold)
    for k in ("mem_host_bytes", "mem_device_bytes",
              "compaction_backlog", "ingest_rows_total",
              "queries_total"):
        assert k in doc
    json.dumps(doc)  # the payload must survive the heartbeat wire


def test_deep_health_ok_and_degraded(inst, monkeypatch):
    from greptimedb_tpu.telemetry import node_stats as ns

    doc = ns.deep_health(inst)
    assert doc["status"] == "ok"
    assert doc["checks"]["engine"]["ok"]
    assert doc["checks"]["wal_appendable"]["ok"]
    dev = doc["checks"]["device"]
    assert dev["ok"]
    # device identity as jax reports it in THIS process (what
    # chip_smoke.py reads): platform, kind, count, bytes in use per
    # device (None on backends without memory stats, e.g. CPU)
    import jax

    assert dev["platform"] == jax.devices()[0].platform == "cpu"
    assert dev["device_kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(jax.devices())
    assert len(dev["bytes_in_use"]) == dev["count"]
    assert all("ms" in c for c in doc["checks"].values())
    # one failing subsystem degrades the verdict without erroring the
    # probe (and without hiding the other checks)
    monkeypatch.setattr(
        inst.engine, "regions",
        lambda: (_ for _ in ()).throw(RuntimeError("boom")),
    )
    doc = ns.deep_health(inst)
    assert doc["status"] == "degraded"
    assert not doc["checks"]["engine"]["ok"]
    assert "boom" in doc["checks"]["engine"]["detail"]
    assert doc["checks"]["device"]["ok"]   # others still ran


def test_device_check_never_creates_a_backend_for_frontend(monkeypatch):
    """A role that owns no device must not grab one through its own
    health probe (one device-owning process per chip)."""
    from greptimedb_tpu.telemetry import device_programs
    from greptimedb_tpu.telemetry import node_stats as ns

    monkeypatch.setattr(device_programs, "backend_live", lambda: False)
    monkeypatch.setattr(
        ns, "_dispatch_probe",
        lambda: (_ for _ in ()).throw(AssertionError("dispatched")),
    )
    ok, detail, info = ns._probe_device("frontend")
    assert ok and "no backend" in detail and info == {}


# ---------------------------------------------------------------------
# metasrv heartbeat enrichment + phi statuses (meta/metasrv.py)
# ---------------------------------------------------------------------

def test_metasrv_heartbeat_enrichment_ring_and_roles():
    ms = Metasrv(MemoryKv(), stats_history=4)
    ms.register_node(1, "127.0.0.1:5001")
    payload = {"role": "datanode", "addr": "127.0.0.1:5001",
               "uptime_s": 1.0, "regions": 3}
    for i in range(10):
        ms.heartbeat(1, {}, now_ms=1000.0 * i,
                     node_stats={**payload, "uptime_s": float(i)})
    node = ms.nodes[1]
    assert node.stats["uptime_s"] == 9.0
    # bounded ring: only the last 4 samples retained
    assert len(node.stats_history) == 4
    assert [s["uptime_s"] for s in node.stats_history] == [6.0, 7.0,
                                                           8.0, 9.0]
    # a frontend heartbeating a leader that never saw it registers
    # with ITS role — and the selector must never place regions on it
    ms.heartbeat(-5, {}, now_ms=9000.0, node_stats={
        "role": "frontend", "addr": "127.0.0.1:6001"})
    assert ms.nodes[-5].role == "frontend"
    assert ms.nodes[-5].addr == "127.0.0.1:6001"
    chosen = ms.selector.select(list(ms.nodes.values()), 4)
    assert set(chosen) == {1}
    # non-datanode heartbeats get no lease grant
    out = ms.heartbeat(-5, {}, now_ms=9500.0,
                       node_stats={"role": "frontend"})
    assert not any(i.get("type") == "grant_lease" for i in out)
    # the role rides EVERY beat, payload or not: with [fleet]
    # enrichment disabled (node_stats None) a frontend heartbeating a
    # restarted leader must still never become a placement target
    ms2 = Metasrv(MemoryKv())
    ms2.register_node(1, "127.0.0.1:5001")
    ms2.heartbeat(1, {}, now_ms=0.0)
    ms2.heartbeat(-7, {}, now_ms=0.0, role="frontend")
    assert ms2.nodes[-7].role == "frontend"
    assert set(ms2.selector.select(list(ms2.nodes.values()), 2)) == {1}
    # an existing registration heals too (mis-roled by a legacy beat)
    ms2.heartbeat(-7, {}, now_ms=500.0, role="flownode")
    assert ms2.nodes[-7].role == "flownode"
    # addr rides every beat as well: a restarted leader whose FIRST
    # contact with a datanode is a heartbeat (the client's beats never
    # failed across the transition, so it never re-registers) must
    # heal both the registry addr and the persisted peer book
    ms3 = Metasrv(MemoryKv())
    ms3.heartbeat(3, {}, now_ms=0.0, role="datanode",
                  addr="127.0.0.1:5003")
    assert ms3.nodes[3].addr == "127.0.0.1:5003"
    assert ms3.peers()[3] == "127.0.0.1:5003"
    ms3.heartbeat(3, {}, now_ms=500.0, role="datanode",
                  addr="127.0.0.1:5004")   # re-bound address
    assert ms3.peers()[3] == "127.0.0.1:5004"


def test_metasrv_phi_status_transitions():
    ms = Metasrv(MemoryKv(), acceptable_pause_ms=3000.0)
    ms.register_node(1, "127.0.0.1:5001")
    assert ms.node_status(1, now_ms=0.0) == "UNKNOWN"
    for i in range(5):
        ms.heartbeat(1, {}, now_ms=1000.0 * i)
    t0 = 4000.0
    seen = [ms.node_status(1, now_ms=t0 + dt)
            for dt in range(0, 40001, 250)]
    assert seen[0] == "ALIVE"
    assert seen[-1] == "DOWN"
    # the verdict passes through UNHEALTHY between ALIVE and DOWN and
    # is monotone (never recovers without a heartbeat)
    order = {"ALIVE": 0, "UNHEALTHY": 1, "DOWN": 2}
    ranks = [order[s] for s in seen]
    assert ranks == sorted(ranks)
    assert "UNHEALTHY" in seen
    # a fresh heartbeat restores ALIVE
    ms.heartbeat(1, {}, now_ms=t0 + 50000.0)
    assert ms.node_status(1, now_ms=t0 + 50000.0) == "ALIVE"
    # cluster_nodes carries the live verdict + phi + latest stats
    docs = ms.cluster_nodes(now_ms=t0 + 50000.0, history=True)
    assert docs[0]["status"] == "ALIVE"
    assert docs[0]["phi"] is not None
    assert isinstance(docs[0]["history"], list)


# ---------------------------------------------------------------------
# standalone cluster surfaces: nothing hardcoded
# ---------------------------------------------------------------------

def test_cluster_info_and_region_peers_standalone(inst):
    _setup_cpu(inst)
    r = inst.sql("select peer_type, peer_addr, status, uptime_s, "
                 "active_time from information_schema.cluster_info")
    assert r.num_rows == 1
    row = r.rows()[0]
    assert row[0] == "STANDALONE"
    assert row[1] == "127.0.0.1:14000"     # real addr, not ""
    assert row[2] == "ALIVE"
    assert row[3] > 0.0                    # real uptime
    assert int(row[4]) > 0                 # real activity timestamp
    r = inst.sql("select peer_addr, is_leader, status from "
                 "information_schema.region_peers")
    assert r.num_rows >= 1
    assert r.rows()[0] == ["127.0.0.1:14000", "Yes", "ALIVE"]
    # a downgraded (fenced) region reports its REAL state
    region = inst.catalog.table("public", "cpu").regions[0]
    region.writable = False
    try:
        r = inst.sql("select status from "
                     "information_schema.region_peers")
        assert r.rows()[0][0] == "DOWNGRADED"
    finally:
        region.writable = True


def test_cluster_tables_and_federated_surfaces_standalone(inst):
    from greptimedb_tpu.dist import fleet

    _setup_cpu(inst)
    r = inst.sql("select peer_id, role, addr, status, regions, "
                 "uptime_s from information_schema.cluster_node_stats")
    assert r.num_rows == 1
    row = r.rows()[0]
    assert row[1] == "standalone" and row[2] == "127.0.0.1:14000"
    assert row[3] == "ALIVE" and row[4] >= 1
    # the four fan-out tables answer locally with peer/peer_status
    for t in ("cluster_runtime_metrics", "cluster_memory_pools",
              "cluster_statement_statistics"):
        r = inst.sql(f"select distinct peer, peer_status from "
                     f"information_schema.{t}")
        assert r.rows() == [["127.0.0.1:14000", "ok"]], t
    # the device-program registry is PROCESS-wide: it may be empty (this
    # file alone) or carry earlier tests' programs (full suite) — either
    # way every row is local and ok, and the query never errors
    r = inst.sql("select distinct peer, peer_status from "
                 "information_schema.cluster_device_programs")
    assert r.rows() in ([], [["127.0.0.1:14000", "ok"]])
    r = inst.sql("select count(*) from "
                 "information_schema.cluster_runtime_metrics "
                 "where metric_name like 'gtpu_%'")
    assert r.rows()[0][0] > 0
    # federated metrics: node/role labels on our families, TTL cache
    text = fleet.federated_metrics(inst)
    assert 'node="127.0.0.1:14000"' in text
    assert 'role="standalone"' in text
    assert "gtpu_" in text
    assert fleet.federated_metrics(inst) is text   # cached within TTL
    assert fleet.federated_metrics(inst, force=True) is not text
    doc = fleet.federated_health(inst)
    assert doc["status"] == "ok"
    assert doc["nodes"][0]["checks"]["engine"]["ok"]


def test_http_cluster_and_deep_health_routes(inst):
    from greptimedb_tpu.servers.http import HttpServer

    _setup_cpu(inst)
    srv = HttpServer(inst, port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/health?deep=1",
                                    timeout=30) as resp:
            doc = json.loads(resp.read())
        assert doc["status"] == "ok" and doc["checks"]
        with urllib.request.urlopen(f"{base}/v1/cluster/metrics",
                                    timeout=30) as resp:
            text = resp.read().decode()
        assert 'node="127.0.0.1:14000"' in text and "gtpu_" in text
        with urllib.request.urlopen(f"{base}/v1/cluster/health",
                                    timeout=30) as resp:
            doc = json.loads(resp.read())
        assert doc["status"] == "ok"
        # a degraded node answers 503 on the deep probe (plain /health
        # stays a liveness 200)
        real = inst.engine.regions
        inst.engine.regions = (
            lambda: (_ for _ in ()).throw(RuntimeError("down"))
        )
        try:
            from greptimedb_tpu.telemetry import node_stats as ns

            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/health?deep=1",
                                       timeout=30)
            assert ei.value.code == 503
            with urllib.request.urlopen(f"{base}/health",
                                        timeout=30) as resp:
                assert resp.status == 200
        finally:
            inst.engine.regions = real
    finally:
        srv.stop()


# ---------------------------------------------------------------------
# export loop identity labels (satellite)
# ---------------------------------------------------------------------

def test_export_stamps_node_role_labels(inst):
    from greptimedb_tpu.telemetry.export import (
        ExportMetricsTask,
        scrape_registry,
    )
    from greptimedb_tpu.telemetry.metrics import global_registry

    global_registry.counter("test_fleet_export_total", "t").inc(3)
    series = scrape_registry(
        now_ms=5, extra_labels={"node": "n1", "role": "datanode"}
    )
    match = [lab for lab, _s in series
             if lab["__name__"] == "test_fleet_export_total"]
    assert match and match[0]["node"] == "n1"
    assert match[0]["role"] == "datanode"
    # a metric already carrying the label keeps its own value
    global_registry.counter(
        "test_fleet_export_labeled_total", "t", ("node",)
    ).labels("other").inc()
    series = scrape_registry(extra_labels={"node": "n1"})
    match = [lab for lab, _s in series
             if lab["__name__"] == "test_fleet_export_labeled_total"]
    assert match[0]["node"] == "other"
    # the task resolves identity from the instance at tick time and the
    # re-ingested series are tagged — two roles can never collide
    task = ExportMetricsTask(inst, db="t_fleet_export",
                             interval_s=3600.0)
    inst.catalog.create_database("t_fleet_export", if_not_exists=True)
    task.tick()
    r = inst.sql("select node, role from "
                 "t_fleet_export.test_fleet_export_total limit 1")
    assert r.rows()[0] == ["127.0.0.1:14000", "standalone"]


# ---------------------------------------------------------------------
# in-process wire topology: real heartbeats, fan-out, degradation
# ---------------------------------------------------------------------

def test_wire_fleet_fanout_and_down_degradation(tmp_path):
    pytest.importorskip("pyarrow.flight")
    from greptimedb_tpu.dist import fleet
    from greptimedb_tpu.dist.frontend import DistInstance
    from greptimedb_tpu.dist.region_server import RegionServer
    from greptimedb_tpu.servers.flight import FlightFrontend
    from greptimedb_tpu.servers.meta_http import MetasrvServer

    meta = MetasrvServer(
        addr="127.0.0.1", port=0, data_home=str(tmp_path / "meta"),
        acceptable_pause_ms=1500.0,
    ).start()
    meta_addr = f"127.0.0.1:{meta.port}"
    dns, stops = [], []
    fe = None
    try:
        for i in range(2):
            dn = Standalone(
                engine_config=EngineConfig(
                    data_root=str(tmp_path / f"dn{i}"),
                    enable_background=False,
                ),
                prefer_device=False, warm_start=False,
            )
            dn.region_server = RegionServer(
                dn.engine, str(tmp_path / f"dn{i}")
            )
            fs = FlightFrontend(dn, port=0).start()
            addr = f"127.0.0.1:{fs.server.port}"
            stops.append(fleet.start_heartbeat(
                meta_addr, i, dn, role="datanode", addr=addr,
                interval_s=0.3,
            ))
            dns.append((dn, fs, addr))
        fe = DistInstance(str(tmp_path / "fe"), meta_addr,
                          prefer_device=False)
        fe.node_addr = "127.0.0.1:18000"
        stops.append(fleet.start_heartbeat(
            meta_addr,
            fleet.derive_node_id("frontend", fe.node_addr), fe,
            role="frontend", addr=fe.node_addr, interval_s=0.3,
        ))
        # wait for every heartbeat to land
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            r = fe.sql("select role, status from "
                       "information_schema.cluster_node_stats")
            rows = r.rows()
            if (sum(1 for ro, st in rows
                    if ro == "datanode" and st == "ALIVE") >= 2
                    and any(ro == "frontend" and st == "ALIVE"
                            for ro, st in rows)):
                break
            time.sleep(0.2)
        else:
            raise AssertionError(f"fleet never converged: {rows}")

        fe.execute_sql(
            "create table cpu (ts timestamp time index, host string "
            "primary key, v double) with (num_regions = 2)"
        )
        fe.sql("insert into cpu values (1000, 'h1', 1.0), "
               "(2000, 'h2', 2.0)")
        # one row per live node with non-empty addr / uptime / memory
        r = fe.sql("select role, addr, uptime_s, mem_host_bytes, "
                   "mem_device_bytes, regions from "
                   "information_schema.cluster_node_stats "
                   "where role != 'metasrv'")
        assert r.num_rows == 3
        for role, addr, up, mh, md, regions in r.rows():
            assert addr, role
            assert up > 0.0, role
            assert mh >= 0 and md >= 0
        # datanode rows carry their region counts via heartbeats
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            r = fe.sql("select sum(regions) from information_schema."
                       "cluster_node_stats where role = 'datanode'")
            if int(r.rows()[0][0]) >= 2:
                break
            time.sleep(0.3)
        assert int(r.rows()[0][0]) >= 2
        # region_peers: real addrs + detector status
        r = fe.sql("select peer_addr, status from "
                   "information_schema.region_peers")
        assert r.num_rows == 2
        assert {a for a, _s in r.rows()} == {dns[0][2], dns[1][2]}
        assert all(s == "ALIVE" for _a, s in r.rows())
        # cluster fan-out tables: rows from every node
        r = fe.sql("select distinct peer, peer_status from "
                   "information_schema.cluster_runtime_metrics")
        assert {(p, s) for p, s in r.rows()} == {
            (fe.node_addr, "ok"), (dns[0][2], "ok"), (dns[1][2], "ok"),
        }
        r = fe.sql("select count(*) from information_schema."
                   "cluster_memory_pools where peer_status = 'ok'")
        assert int(r.rows()[0][0]) > 0
        # federated metrics: every node's families, node-labeled
        text = fleet.federated_metrics(fe, force=True)
        for addr in (fe.node_addr, dns[0][2], dns[1][2]):
            assert f'node="{addr}"' in text, addr
        assert "gtpu_fleet_heartbeats_total" in text
        doc = fleet.federated_health(fe)
        assert doc["status"] == "ok"
        assert len(doc["nodes"]) == 4   # fe + 2 dn + metasrv

        # SIGKILL-equivalent: stop heartbeats + tear the node down
        stops[1]()
        dns[1][1].close(grace_s=1.0)
        dns[1][0].close()
        deadline = time.monotonic() + 25
        status = None
        while time.monotonic() < deadline:
            r = fe.sql("select status from information_schema."
                       "cluster_node_stats where peer_id = 1")
            status = r.rows()[0][0] if r.num_rows else None
            if status == "DOWN":
                break
            time.sleep(0.3)
        assert status == "DOWN", status
        # fan-out degrades to reachable peers + status, fast and
        # inside the request deadline (the dead peer errors at
        # CONNECT, not after a timeout)
        t0 = time.monotonic()
        r = fe.sql("select distinct peer, peer_status from "
                   "information_schema.cluster_runtime_metrics")
        elapsed = time.monotonic() - t0
        rows = {p: s for p, s in r.rows()}
        assert rows[fe.node_addr] == "ok"
        assert rows[dns[0][2]] == "ok"
        assert rows[dns[1][2]] != "ok"          # degraded, marked
        assert elapsed < fleet.config()["fanout_timeout_s"] + 3.0
        # federated health reports the dead node as unreachable
        doc = fleet.federated_health(fe)
        assert doc["status"] == "degraded"
        dead = [n for n in doc["nodes"] if n["peer"] == dns[1][2]]
        assert dead and dead[0]["status"] == "unreachable"
    finally:
        for s in stops[:1] + stops[2:]:
            try:
                s()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass
        if fe is not None:
            fe.close()
        dns[0][1].close(grace_s=1.0)
        dns[0][0].close()
        meta.close()
