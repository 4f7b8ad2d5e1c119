"""Fleet observability plane: heartbeat-carried telemetry, cluster-wide
information_schema fan-out, federated metrics and deep health.

PRs 8/10/13/14 built deep per-process telemetry; this module makes it
CLUSTER-scoped. Three capabilities:

- **heartbeat enrichment** — `start_heartbeat` is the one register +
  heartbeat loop every role (datanode / flownode / frontend) runs
  against the metasrv: it attaches the compact node-stats payload
  (telemetry/node_stats.build_node_stats) on the `[fleet]`
  stats_interval cadence, applies lease grants on datanodes, and
  re-registers across metasrv leader changes.

- **cluster fan-out** — `cluster_table_doc` serves the
  `information_schema.cluster_{runtime_metrics,statement_statistics,
  device_programs,memory_pools}` tables: the frontend fans a bounded
  `node_telemetry` Flight action (servers/flight.py) to every peer over
  the shared dist fan-out pool and merges rows with `peer` +
  `peer_status` columns. A down peer degrades to one status row — the
  table never errors because one node died, and the whole fan-out stays
  inside the active query deadline.

- **federated surfaces** — `federated_metrics` assembles one Prometheus
  exposition of every node's families re-labeled with `node`/`role`
  behind a TTL cache (scrapes cannot stampede the fleet);
  `federated_health` aggregates per-node deep-health JSON
  (`/v1/cluster/{metrics,health}` in servers/http.py).
"""

from __future__ import annotations

import logging
import time

from greptimedb_tpu import concurrency

_log = logging.getLogger("greptimedb_tpu.dist.fleet")

# [fleet] TOML section defaults (config.py): one module-level config
# per process, shared by every role surface
_DEFAULTS = {
    "enable": True,
    "stats_interval_s": 2.0,     # min spacing of heartbeat payloads
    "heartbeat_interval_s": 2.0,  # heartbeat loop cadence
    "history": 32,               # per-node sample ring on the metasrv
    "fanout_timeout_s": 5.0,     # per-peer bound for cluster_* fan-out
    "cache_ttl_s": 5.0,          # /v1/cluster/metrics scrape cache
}
_cfg = dict(_DEFAULTS)


def configure(options: dict | None) -> None:
    """Apply the `[fleet]` TOML section to this process."""
    o = options or {}
    _cfg["enable"] = bool(o.get("enable", _DEFAULTS["enable"]))
    for k in ("stats_interval_s", "heartbeat_interval_s",
              "fanout_timeout_s", "cache_ttl_s"):
        _cfg[k] = float(o.get(k, _DEFAULTS[k]))
    _cfg["history"] = int(o.get("history", _DEFAULTS["history"]))


def config() -> dict:
    return dict(_cfg)


def derive_node_id(role: str, addr: str) -> int:
    """Stable NEGATIVE node id for non-datanode roles: datanode ids are
    operator-assigned non-negative ints, so derived ids can never
    collide with them (or be selected for region placement — the
    selector filters by role anyway)."""
    import zlib

    return -(zlib.crc32(f"{role}:{addr}".encode()) % 0x7FFFFFFF) - 1


_FLEET_HEARTBEATS = None


def _heartbeat_counter():
    # lazy: registering at import would force the metrics module into
    # every fleet import site
    global _FLEET_HEARTBEATS
    if _FLEET_HEARTBEATS is None:
        from greptimedb_tpu.telemetry.metrics import global_registry

        _FLEET_HEARTBEATS = global_registry.counter(
            "gtpu_fleet_heartbeats_total",
            "metasrv heartbeats sent by this node",
            ("result",),
        )
    return _FLEET_HEARTBEATS


# ----------------------------------------------------------------------
# the one heartbeat loop (every role)
# ----------------------------------------------------------------------

def start_heartbeat(meta_addr: str, node_id: int, inst, *,
                    role: str = "datanode", addr: str | None = None,
                    interval_s: float | None = None):
    """Register + heartbeat against the metasrv HTTP service; returns a
    stop callable. The MetaClient follows leader redirects across a
    comma-separated address list, so a metasrv leader kill re-registers
    this node with the new leader on the next beat. Datanodes apply
    lease grants and enforce fencing exactly as before; every role
    attaches the node-stats payload on the [fleet] stats cadence."""
    from greptimedb_tpu.dist.client import MetaClient
    from greptimedb_tpu.telemetry import node_stats as _ns
    from greptimedb_tpu.telemetry import tracing

    interval = float(interval_s if interval_s is not None
                     else _cfg["heartbeat_interval_s"])
    stop = concurrency.Event()
    client = MetaClient(meta_addr)
    inst.node_role = role
    if addr:
        inst.node_addr = addr

    def loop():
        registered = False
        last_leader = client.addr
        last_stats = -1e18
        while True:   # register immediately, THEN pace by the interval
            with tracing.background_span("fleet.heartbeat"):
                try:
                    if client.addr != last_leader:
                        # leader moved: its memory has no liveness record of
                        # us — re-register before the next heartbeat
                        registered = False
                        last_leader = client.addr
                    if not registered:
                        client.register(node_id, addr, role=role)
                        registered = True
                    stats = {}
                    try:
                        for t in inst.catalog.all_tables():
                            for r in t.regions:
                                stats[str(r.meta.region_id)] = {
                                    "rows": int(getattr(r.memtable, "rows",
                                                        0)),
                                }
                    except Exception as e:  # noqa: BLE001
                        # stats are advisory; heartbeat with what we have
                        _log.debug("region stat collection: %s", e)
                    payload = None
                    now = time.monotonic()
                    if (_cfg["enable"]
                            and now - last_stats >= _cfg["stats_interval_s"]):
                        try:
                            payload = _ns.build_node_stats(inst)
                            last_stats = now
                        except Exception as e:  # noqa: BLE001 - telemetry
                            # must never break liveness
                            _log.debug("node-stats build failed: %s", e)
                    instructions = client.heartbeat(node_id, stats,
                                                    node_stats=payload,
                                                    role=role, addr=addr)
                    inst.fleet_heartbeat_at = time.monotonic()
                    _heartbeat_counter().labels("ok").inc()
                    for ins in instructions:
                        if ins.get("type") == "grant_lease":
                            rs = getattr(inst, "region_server", None)
                            if rs is not None:
                                rs.renew_leases(
                                    ins.get("regions") or [],
                                    float(ins.get("lease_secs", 10.0)),
                                )
                        else:
                            # other mailbox instructions are logged; region
                            # movement is driven by the metasrv directly
                            # over Flight (dist/wire_cluster.py)
                            print(f"# metasrv instruction: {ins}",
                                  flush=True)
                except Exception:
                    registered = False
                    _heartbeat_counter().labels("error").inc()
                # lease enforcement runs even (especially) when heartbeats
                # fail: a partitioned node fences its regions instead of
                # split-braining with a failover target. Nothing here may
                # kill the loop — a dead loop means no fencing at all.
                try:
                    rs = getattr(inst, "region_server", None)
                    if rs is not None:
                        for rid in rs.enforce_leases():
                            print(f"# region {rid} lease expired: fenced",
                                  flush=True)
                except Exception as e:  # noqa: BLE001
                    print(f"# lease enforcement failed: {e}", flush=True)
            if stop.wait(interval):
                return

    t = concurrency.Thread(target=loop, daemon=True,
                           name=f"{role}-heartbeat")
    t.start()

    def stopper():
        stop.set()
        # bounded join: the loop wakes from the interval wait promptly;
        # a beat mid-wire is bounded by the MetaClient timeout
        t.join(timeout=10.0)
        client.close()

    return stopper


# ----------------------------------------------------------------------
# fleet state (who is in the cluster)
# ----------------------------------------------------------------------

def local_node_doc(inst) -> dict:
    """The serving node as a cluster_nodes-shaped doc (standalone mode,
    or a dist frontend whose own heartbeat has not landed yet)."""
    from greptimedb_tpu.telemetry import node_stats as _ns

    stats = _ns.build_node_stats(inst)
    role = stats["role"]
    addr = stats["addr"]
    return {
        "node_id": getattr(inst, "node_id", 0) or 0,
        "role": role,
        "addr": addr,
        # the node assembled this answer: genuinely alive, not a stub
        "status": "ALIVE",
        "phi": 0.0,
        "last_heartbeat_ms": time.time() * 1000,
        "region_count": stats.get("regions", 0),
        "stats": stats,
        "local": True,
    }


def cluster_nodes(inst, *, history: bool = False) -> list[dict]:
    """Every known fleet member. Dist roles ask the metasrv leader
    (bounded MetaClient round); the serving node is appended locally if
    its own heartbeat has not registered it yet. Standalone returns its
    single local doc — the cluster surfaces work on one node too."""
    meta = getattr(inst, "meta", None)
    local = local_node_doc(inst)
    if meta is None or not hasattr(meta, "cluster"):
        return [local]
    try:
        doc = meta.cluster(history=history)
    except Exception as e:  # noqa: BLE001 - metasrv unreachable: the
        # local view is still a truthful (degraded) answer
        _log.debug("metasrv /cluster unreachable: %s", e)
        local["status"] = "ALIVE"
        return [local]
    nodes = list(doc.get("nodes") or [])
    ms = doc.get("metasrv") or {}
    nodes.append({
        "node_id": derive_node_id("metasrv", ms.get("addr", "")),
        "role": "metasrv",
        # the doc only ever comes from the LEADER (MetaClient follows
        # not-leader redirects), and it answered: ALIVE
        "addr": ms.get("addr", ""),
        "status": "ALIVE",
        "phi": 0.0,
        "last_heartbeat_ms": time.time() * 1000,
        "region_count": 0,
        "stats": {"role": "metasrv", "addr": ms.get("addr", ""),
                  "uptime_s": ms.get("uptime_s", 0.0)},
    })
    # the serving node itself (its heartbeat may not have landed yet,
    # and standalone-ish unit topologies run no loop at all)
    key = (local["role"], local["addr"])
    if not any((n.get("role"), n.get("addr")) == key for n in nodes):
        nodes.append(local)
    return nodes


# ----------------------------------------------------------------------
# node_telemetry: the per-node Flight action body (server side)
# ----------------------------------------------------------------------

# the information_schema providers the cluster_* tables fan out over;
# resolved lazily to avoid an import cycle with information_schema
FANOUT_TABLES = ("runtime_metrics", "statement_statistics",
                 "device_programs", "memory_pools")


def _provider(name: str):
    from greptimedb_tpu import information_schema as IS

    if name not in FANOUT_TABLES:
        raise ValueError(f"not a fleet fan-out table: {name}")
    return IS._PROVIDERS[name]


def _jsonable(v):
    """Telemetry docs cross the Flight action boundary as JSON: numpy
    scalars (registry-derived values) coerce to their Python types."""
    import numpy as np

    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return v


def node_telemetry_local(inst, body: dict) -> dict:
    """Serve one node_telemetry request against THIS process (the
    Flight action handler calls it; the local merge half of every
    cluster_* table uses it too, so local and remote rows are built by
    the same code)."""
    from greptimedb_tpu.telemetry import node_stats as _ns

    out: dict = {}
    if body.get("stats", True):
        out["node_stats"] = _ns.build_node_stats(inst)
    tables = body.get("tables") or []
    if tables:
        docs = {}
        for name in tables:
            docs[name] = _jsonable(_provider(name)(inst))
        out["telemetry"] = docs
    if body.get("metrics"):
        from greptimedb_tpu.telemetry.metrics import global_registry

        out["metrics_text"] = global_registry.render()
    if body.get("health"):
        out["health"] = _ns.deep_health(inst)
    return out


# ----------------------------------------------------------------------
# fan-out (frontend side)
# ----------------------------------------------------------------------

_clients_lock = concurrency.Lock()
_clients: dict[str, object] = {}


def _peer_client(inst, addr: str):
    """Addr-keyed DatanodeClient: DistInstance already keeps one
    (its flow-mirror client map); other instances (tests) share a
    bounded module cache. Eviction is LRU and DROPS the reference
    without close() — another fan-out thread may be mid-call on the
    evicted client, and its channel is released when the last user
    lets go."""
    fn = getattr(inst, "_flow_client_for", None)
    if fn is not None:
        return fn(addr)
    from greptimedb_tpu.dist.client import DatanodeClient

    with _clients_lock:
        cli = _clients.get(addr)
        if cli is None:
            if len(_clients) >= 64:
                _clients.pop(next(iter(_clients)))
            cli = _clients[addr] = DatanodeClient(addr)
        else:
            # LRU recency: re-insert so hot peers are evicted last
            _clients.pop(addr)
            _clients[addr] = cli
        return cli


def _fanout_timeout() -> float:
    """Per-peer bound: the [fleet] knob, shrunk to the active query
    deadline's remaining budget when one is bound (sched/deadline) —
    the cluster_* answer must land INSIDE the request deadline."""
    from greptimedb_tpu.sched import deadline as _dl

    t = float(_cfg["fanout_timeout_s"])
    remaining = _dl.call_timeout()
    if remaining is not None:
        t = min(t, max(remaining, 0.1))
    return t


def fanout_peers(inst) -> list[dict]:
    """Flight-addressable peers (datanodes + flownodes) excluding the
    serving node itself; each doc comes from the metasrv fleet state so
    the caller also sees the liveness verdict."""
    me = getattr(inst, "node_addr", "") or ""
    out = []
    for node in cluster_nodes(inst):
        if node.get("local"):
            continue
        if node.get("role") not in ("datanode", "flownode"):
            continue
        addr = node.get("addr") or ""
        if not addr or addr == me:
            continue
        out.append(node)
    return out


def _fanout(inst, body: dict) -> list[tuple[dict, str, dict | None]]:
    """Run node_telemetry against every peer over the shared dist
    fan-out pool; returns [(node_doc, status, response|None)] where
    status is "ok" or the (typed) error text. Bounded per peer AND
    overall — a hung peer degrades, never stalls."""
    from greptimedb_tpu.dist import dist_query

    peers = fanout_peers(inst)
    if not peers:
        return []
    timeout = _fanout_timeout()

    def one(node):
        addr = node["addr"]
        try:
            cli = _peer_client(inst, addr)
            return node, "ok", cli.node_telemetry(body, timeout=timeout)
        except Exception as e:  # noqa: BLE001 - degrade, never error:
            # the typed message (DatanodeUnavailableError etc.) becomes
            # the row's peer_status
            return node, f"{type(e).__name__}: {e}", None

    pool = dist_query._fanout_pool()
    futures = [pool.submit(one, node) for node in peers]
    out = []
    deadline = time.monotonic() + timeout + 2.0
    for node, fut in zip(peers, futures):
        budget = max(deadline - time.monotonic(), 0.05)
        try:
            out.append(fut.result(timeout=budget))
        except Exception:  # noqa: BLE001 - pool-level timeout: the
            # peer call itself is bounded, this is the backstop
            out.append((node, "timeout: fan-out budget exhausted",
                        None))
    return out


def _peer_label(node: dict) -> str:
    return node.get("addr") or f"{node.get('role')}-{node.get('node_id')}"


def local_peer_label(inst) -> str:
    return getattr(inst, "node_addr", "") or "local"


def _neutral(values: list):
    """In-place: replace None in numerically-typed merged columns (the
    down-peer status rows) so the system-table type inference
    (information_schema._query_system_doc) keeps its numpy dtypes."""
    first = next((v for v in values if v is not None), None)
    if first is None or isinstance(first, str):
        return [("" if v is None else v) for v in values]
    if isinstance(first, bool):
        return [(False if v is None else v) for v in values]
    if isinstance(first, int):
        return [(0 if v is None else v) for v in values]
    if isinstance(first, float):
        return [(float("nan") if v is None else v) for v in values]
    return values


def cluster_table_doc(inst, table: str) -> dict:
    """One cluster-wide information_schema doc: the local provider's
    rows plus every reachable peer's, tagged with `peer` +
    `peer_status`; an unreachable peer contributes ONE degraded status
    row instead of failing the query."""
    local_doc = _provider(table)(inst)
    cols = ["peer", "peer_status", *local_doc.keys()]
    rows: dict[str, list] = {c: [] for c in cols}

    def merge(peer: str, status: str, doc: dict | None):
        if doc is None or status != "ok":
            rows["peer"].append(peer)
            rows["peer_status"].append(status)
            for c in cols[2:]:
                rows[c].append(None)
            return
        n = len(next(iter(doc.values()))) if doc else 0
        rows["peer"].extend([peer] * n)
        rows["peer_status"].extend([status] * n)
        for c in cols[2:]:
            vals = doc.get(c)
            if vals is None or len(vals) != n:
                rows[c].extend([None] * n)
            else:
                rows[c].extend(vals)

    merge(local_peer_label(inst), "ok", local_doc)
    for node, status, resp in _fanout(
            inst, {"stats": False, "tables": [table]}):
        doc = ((resp or {}).get("telemetry") or {}).get(table)
        merge(_peer_label(node), status, doc)
    return {c: _neutral(v) if c not in ("peer", "peer_status") else v
            for c, v in rows.items()}


def cluster_node_stats_doc(inst) -> dict:
    """information_schema.cluster_node_stats: one row per fleet member
    from the heartbeat-carried payloads + the phi-accrual verdict."""
    cols = [
        "peer_id", "role", "addr", "status", "phi",
        "last_heartbeat_ms", "version", "uptime_s", "regions",
        "wal_backlog_rows", "memtable_bytes", "sst_count", "sst_bytes",
        "compaction_backlog", "mem_host_bytes", "mem_device_bytes",
        "device_live_bytes", "ingest_rows_total", "queries_total",
        "flows", "samples",
    ]
    rows: dict[str, list] = {c: [] for c in cols}
    for node in cluster_nodes(inst, history=True):
        st = node.get("stats") or {}
        rows["peer_id"].append(int(node.get("node_id", 0)))
        rows["role"].append(str(node.get("role", "")))
        rows["addr"].append(str(node.get("addr", "") or ""))
        rows["status"].append(str(node.get("status", "UNKNOWN")))
        phi = node.get("phi")
        rows["phi"].append(float(phi) if phi is not None else 0.0)
        rows["last_heartbeat_ms"].append(
            int(node.get("last_heartbeat_ms") or 0)
        )
        rows["version"].append(str(st.get("version", "")))
        rows["uptime_s"].append(float(st.get("uptime_s", 0.0)))
        rows["regions"].append(int(
            st.get("regions", node.get("region_count", 0)) or 0
        ))
        for k in ("wal_backlog_rows", "memtable_bytes", "sst_count",
                  "sst_bytes", "compaction_backlog", "mem_host_bytes",
                  "mem_device_bytes", "device_live_bytes", "flows"):
            rows[k].append(int(st.get(k, 0) or 0))
        for k in ("ingest_rows_total", "queries_total"):
            rows[k].append(float(st.get(k, 0.0) or 0.0))
        rows["samples"].append(len(node.get("history") or []))
    return rows


# ----------------------------------------------------------------------
# federated metrics (/v1/cluster/metrics)
# ----------------------------------------------------------------------

_EXPORT_PREFIXES = ("gtpu_", "greptime_")

_scrape_lock = concurrency.Lock()


def _relabel_metrics(text: str, node: str, role: str,
                     families: dict, samples: list) -> None:
    """Parse one node's exposition text; accumulate HELP/TYPE per
    family (first writer wins) and every sample line re-labeled with
    node/role. Only the repo's own families (gtpu_*/greptime_*) export
    — the federated endpoint is for fleet dashboards, not a proxy of
    arbitrary process internals."""
    from greptimedb_tpu.telemetry.export import _LINE

    meta: dict[str, list[str]] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] in ("HELP", "TYPE"):
                meta.setdefault(parts[2], []).append(line)
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        name = m.group("name")
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in meta:
                base = name[:-len(suffix)]
                break
        if not base.startswith(_EXPORT_PREFIXES):
            continue
        if base not in families and base in meta:
            families[base] = meta[base]
        labels = m.group("labels") or ""
        injected = f'node="{node}",role="{role}"'
        if labels:
            injected = injected + "," + labels
        samples.append((base, f"{name}{{{injected}}} {m.group('value')}"))


def federated_metrics(inst, *, force: bool = False) -> str:
    """One Prometheus exposition for the whole fleet: every node's
    gtpu_*/greptime_* families with node/role labels. TTL-cached per
    instance so scrapes cannot stampede the fleet; concurrent scrapes
    serialize behind the assembly and reuse its result."""
    now = time.monotonic()
    ttl = float(_cfg["cache_ttl_s"])
    # the assembly lock intentionally covers the bounded fan-out: a
    # second scraper arriving mid-assembly must WAIT and reuse the
    # fresh result instead of launching its own fleet-wide scrape —
    # serialization here IS the stampede protection, and every wire
    # call under it carries the [fleet] fanout timeout (the I/O itself
    # runs on pool workers; this thread waits on their bounded futures)
    with _scrape_lock:
        # cached on the instance (not a module map keyed by id(inst):
        # a GC'd instance's reused id must never serve another's text)
        cached = getattr(inst, "_fleet_scrape_cache", None)
        if not force and cached is not None and now - cached[0] <= ttl:
            return cached[1]
        families: dict[str, list[str]] = {}
        samples: list[tuple[str, str]] = []
        from greptimedb_tpu.telemetry.metrics import global_registry

        role = getattr(inst, "node_role", "standalone")
        _relabel_metrics(global_registry.render(),
                         local_peer_label(inst), role,
                         families, samples)
        for node, status, resp in _fanout(inst, {"stats": False,
                                                 "metrics": True}):
            if status != "ok" or resp is None:
                continue
            _relabel_metrics(resp.get("metrics_text", ""),
                             _peer_label(node),
                             str(node.get("role", "")),
                             families, samples)
        order: list[str] = []
        by_family: dict[str, list[str]] = {}
        for base, line in samples:
            if base not in by_family:
                order.append(base)
                by_family[base] = []
            by_family[base].append(line)
        lines: list[str] = []
        for base in order:
            lines.extend(families.get(base, []))
            lines.extend(by_family[base])
        text = "\n".join(lines) + "\n"
        inst._fleet_scrape_cache = (time.monotonic(), text)
        return text


# ----------------------------------------------------------------------
# federated deep health (/v1/cluster/health)
# ----------------------------------------------------------------------

def federated_health(inst) -> dict:
    """Aggregate per-node deep-health JSON across the fleet: the local
    probe, every reachable peer's, and the metasrv's liveness; an
    unreachable node reports status `unreachable` instead of erroring
    the aggregate."""
    from greptimedb_tpu.telemetry import node_stats as _ns

    nodes = []
    local = _ns.deep_health(inst)
    nodes.append({"peer": local_peer_label(inst), **local})
    for node, status, resp in _fanout(inst, {"stats": False,
                                             "health": True}):
        if status == "ok" and resp is not None:
            doc = resp.get("health") or {"status": "degraded"}
            nodes.append({"peer": _peer_label(node), **doc})
        else:
            nodes.append({
                "peer": _peer_label(node),
                "role": str(node.get("role", "")),
                "status": "unreachable",
                "detail": status,
            })
    meta = getattr(inst, "meta", None)
    if meta is not None and hasattr(meta, "cluster"):
        try:
            doc = meta._get("/health")
            nodes.append({
                "peer": meta.addr, "role": "metasrv",
                "status": "ok" if doc.get("status") == "ok"
                else "degraded",
                "is_leader": bool(doc.get("is_leader")),
            })
        except Exception as e:  # noqa: BLE001 - metasrv down: report it
            nodes.append({"peer": meta.addr, "role": "metasrv",
                          "status": "unreachable",
                          "detail": f"{type(e).__name__}: {e}"})
    ok = all(n.get("status") == "ok" for n in nodes)
    return {"status": "ok" if ok else "degraded", "nodes": nodes}
