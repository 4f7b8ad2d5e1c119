"""Process entry point: `python -m greptimedb_tpu.cli <role> start`.

Counterpart of /root/reference/src/cmd/src/bin/greptime.rs subcommands
(standalone/frontend/datanode/metasrv/flownode start + cli), with the
reference's layered options resolution (src/cmd/src/options.rs):
defaults < --config-file TOML < GREPTIMEDB_TPU__* env < CLI flags
(config.py).

Role topology:
- standalone: everything in one process (engine + all protocol servers
  + flows), like the reference's `greptime standalone start`.
- datanode: storage engine + Arrow Flight data RPC (+ admin HTTP);
  optionally registers and heartbeats against a metasrv.
- frontend: stateless protocol servers (HTTP/MySQL/Postgres) forwarding
  SQL to datanodes over Flight (servers/remote.py).
- metasrv: control plane over HTTP — KV/CAS, registration, heartbeats,
  region routes (servers/meta_http.py).
- flownode: engine + flow manager, ingest-facing HTTP only.
"""

from __future__ import annotations

import argparse
import gc
import os
import signal
import sys
import time

from greptimedb_tpu.config import load_options

from greptimedb_tpu import concurrency

ROLES = ("standalone", "frontend", "datanode", "metasrv", "flownode")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="greptimedb-tpu")
    sub = ap.add_subparsers(dest="role", required=True)

    for role in ROLES:
        rp = sub.add_parser(role)
        r_sub = rp.add_subparsers(dest="cmd", required=True)
        start = r_sub.add_parser("start")
        start.add_argument("-c", "--config-file", default=None)
        start.add_argument("--data-home", default=None)
        start.add_argument("--http-addr", default=None)
        start.add_argument("--mysql-addr", default=None,
                           help="MySQL wire address ('' disables)")
        start.add_argument("--postgres-addr", default=None,
                           help="PostgreSQL wire address ('' disables)")
        start.add_argument("--flight-addr", default=None,
                           help="Arrow Flight (gRPC) address "
                                "('' disables)")
        start.add_argument("--metasrv-addr", default=None,
                           help="metasrv to register with (datanode) "
                                "or to serve on (metasrv)")
        start.add_argument("--datanode-addrs", default=None,
                           help="comma-separated datanode flight "
                                "addresses (frontend)")
        start.add_argument("--flownode-addr", default=None,
                           help="flownode flight address for flow "
                                "mirroring (frontend)")
        start.add_argument("--node-id", type=int, default=None)
        start.add_argument("--no-flows", action="store_true")

    lint = sub.add_parser(
        "lint", help="run gtlint (AST correctness linter) over the "
                     "given paths; exits non-zero on findings",
    )
    lint.add_argument("paths", nargs="*", default=None)
    lint.add_argument("--format", choices=("text", "json"),
                      default="text")
    lint.add_argument("--baseline", default=None)
    lint.add_argument("--no-baseline", action="store_true")
    lint.add_argument("--write-baseline", action="store_true")
    lint.add_argument("--select", default=None)
    lint.add_argument("--changed", default=None, metavar="REF",
                      help="lint only files differing from this git "
                           "ref (fast pre-commit runs)")
    lint.add_argument("--list-rules", action="store_true")
    lint.add_argument("--contracts-dump", action="store_true",
                      help="emit the extracted whole-program contract "
                           "model (tickets/actions/errors/knobs/"
                           "metrics) as sorted JSON and exit 0")
    lint.add_argument("--explain", default=None, metavar="GTxxx",
                      help="print one rule's doc, examples, and "
                           "suppression syntax (exit 2 on unknown id)")

    san = sub.add_parser(
        "san", help="run a command under the gtsan concurrency "
                    "sanitizer (GTPU_SAN=1) and report lock-order "
                    "cycles, blocking-under-lock, and thread/pool "
                    "leaks; exits non-zero on findings",
    )
    san.add_argument("cmd", nargs=argparse.REMAINDER,
                     help="command to run (prefix with --)")
    san.add_argument("--format", choices=("text", "json"),
                     default="text")
    san.add_argument("--baseline", default=None)
    san.add_argument("--no-baseline", action="store_true")
    san.add_argument("--hold-time-ms", type=float, default=None)
    san.add_argument("--report", default=None)

    cli = sub.add_parser("cli")
    # the real default lives on the parent; subcommand flags use SUPPRESS
    # so `cli --data-home X <cmd>` isn't clobbered by subparser defaults
    cli.add_argument("--data-home", default="./greptimedb_tpu_data")
    cli_sub = cli.add_subparsers(dest="cli_cmd")
    repl = cli_sub.add_parser("repl")
    repl.add_argument("--data-home", default=argparse.SUPPRESS)
    exp = cli_sub.add_parser("export")
    exp.add_argument("--data-home", default=argparse.SUPPRESS)
    exp.add_argument("--output-dir", required=True)
    exp.add_argument("--target", default="all",
                     choices=("all", "schema", "data"))
    exp.add_argument("--database", default=None)
    imp = cli_sub.add_parser("import")
    imp.add_argument("--data-home", default=argparse.SUPPRESS)
    imp.add_argument("--input-dir", required=True)
    imp.add_argument("--database", default=None)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.role == "lint":
        from greptimedb_tpu.tools.lint.runner import main as lint_main

        fwd = list(args.paths or [])
        fwd += ["--format", args.format]
        if args.baseline:
            fwd += ["--baseline", args.baseline]
        for flag in ("no_baseline", "write_baseline", "list_rules",
                     "contracts_dump"):
            if getattr(args, flag):
                fwd.append("--" + flag.replace("_", "-"))
        if args.select:
            fwd += ["--select", args.select]
        if args.changed:
            fwd += ["--changed", args.changed]
        if args.explain:
            fwd += ["--explain", args.explain]
        return lint_main(fwd)
    if args.role == "san":
        from greptimedb_tpu.tools.san.runner import main as san_main

        fwd = []
        if args.format != "text":
            fwd += ["--format", args.format]
        if args.baseline:
            fwd += ["--baseline", args.baseline]
        if args.no_baseline:
            fwd.append("--no-baseline")
        if args.hold_time_ms is not None:
            fwd += ["--hold-time-ms", str(args.hold_time_ms)]
        if args.report:
            fwd += ["--report", args.report]
        cmd = list(args.cmd)
        if cmd and cmd[0] == "--":
            cmd = cmd[1:]
        return san_main(fwd + ["--"] + cmd if cmd else fwd)
    if args.role == "cli":
        cmd = getattr(args, "cli_cmd", None)
        if cmd == "export":
            from greptimedb_tpu.tools import export_data

            report = export_data(args.data_home, args.output_dir,
                                 target=args.target,
                                 database=args.database)
            for db, r in report.items():
                print(f"exported {db}: {r['tables']} tables, "
                      f"{r['rows']} rows")
            return 0
        if cmd == "import":
            from greptimedb_tpu.tools import import_data

            report = import_data(args.data_home, args.input_dir,
                                 database=args.database)
            for db, r in report.items():
                print(f"imported {db}: {r['tables']} statements, "
                      f"{r['rows']} rows")
            return 0
        return _repl(args)
    opts = load_options(
        args.role,
        config_file=args.config_file,
        cli_overrides={
            "data_home": args.data_home,
            "http.addr": args.http_addr,
            "mysql.addr": args.mysql_addr,
            "postgres.addr": args.postgres_addr,
            "grpc.addr": args.flight_addr,
            "metasrv.addr": args.metasrv_addr,
            "datanode.metasrv_addr": args.metasrv_addr,
            "datanode.node_id": args.node_id,
            "frontend.datanode_addrs": (
                args.datanode_addrs.split(",")
                if args.datanode_addrs else None
            ),
            "frontend.flownode_addr": args.flownode_addr,
            "flow.enable": False if args.no_flows else None,
        },
    )
    from greptimedb_tpu.session import set_default_timezone

    # top-level `default_timezone` knob: the timezone new sessions start
    # in until a `SET time_zone` overrides it
    set_default_timezone(opts.get("default_timezone", "UTC"))
    san_sec = opts.section("sanitizer")
    if san_sec.get("enable"):
        # [sanitizer] TOML: enable BEFORE any server builds its locks
        # so every primitive in this process is instrumented, and
        # render the findings to stderr at exit — an instrumented run
        # must never be a silent no-op
        from greptimedb_tpu.tools import san as _san
        from greptimedb_tpu.tools.san.report import attach_exit_report

        attach_exit_report(
            _san.enable(_san.SanConfig.from_options(san_sec)))
    return {
        "standalone": _start_standalone,
        "frontend": _start_frontend,
        "datanode": _start_datanode,
        "metasrv": _start_metasrv,
        "flownode": _start_flownode,
    }[args.role](opts)


def _split(addr: str) -> tuple[str, int]:
    host, _, port = addr.rpartition(":")
    return host or "127.0.0.1", int(port)


def _settle_heap():
    """Start-up is over: the modules, classes, servers and caches it
    made live as long as the process does. Put them out of the
    collector's reach, so that a full collection walks what requests
    make and not, each time, the hundred thousand objects of start-up
    (46 ms a pass on the chip's host, in every third answer of a
    query that returns 32,000 rows: PERF.md section 6, PR 33)."""
    gc.collect()
    gc.freeze()


def _serve_until_signal(closers):
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    _settle_heap()
    try:
        while not stop:
            time.sleep(0.2)
    finally:
        for c in reversed(closers):
            try:
                c()
            except Exception as e:  # noqa: BLE001
                # keep tearing the rest down, but say what broke
                print(f"# shutdown: {c} failed: {e}", flush=True)
    _leave_abandoned_merges()
    return 0


def _leave_abandoned_merges():
    """Everything is closed and flushed, but a compaction merge that
    sat inside one stage past its scheduler's grace (the device
    merge's first compile takes minutes) still holds a worker thread,
    and the interpreter would join it on the way out. It can commit
    nothing any more and its inputs are intact: leave now."""
    # a role that never loaded the storage engine has no merges
    mod = sys.modules.get("greptimedb_tpu.storage.compaction")
    n = mod.abandoned_merges() if mod is not None else 0
    if n:
        print(f"# shutdown: leaving {n} abandoned compaction merge(s) "
              "behind; their inputs stay live", flush=True)
        sys.stderr.flush()
        os._exit(0)


def _wire_protocols(inst, opts, closers) -> None:
    """MySQL/Postgres/Flight servers shared by standalone + frontend."""
    if opts.get("mysql.enable", True) and opts.get("mysql.addr"):
        from greptimedb_tpu.servers.mysql import MySqlServer

        mh, mp = _split(opts.get("mysql.addr"))
        srv = MySqlServer(inst, addr=mh, port=mp).start()
        closers.append(srv.close)
        print(f"greptimedb-tpu mysql protocol on {mh}:{srv.port}",
              flush=True)
    if opts.get("postgres.enable", True) and opts.get("postgres.addr"):
        from greptimedb_tpu.servers.postgres import PostgresServer

        ph, pp = _split(opts.get("postgres.addr"))
        srv = PostgresServer(inst, addr=ph, port=pp).start()
        closers.append(srv.close)
        print(f"greptimedb-tpu postgres protocol on {ph}:{srv.port}",
              flush=True)


def _http_server(inst, opts, closers):
    if not (opts.get("http.enable", True) and opts.get("http.addr")):
        return None
    from greptimedb_tpu.servers.http import HttpServer

    hh, hp = _split(opts.get("http.addr"))
    server = HttpServer(
        inst, addr=hh, port=hp,
        tls_cert=opts.get("http.tls.cert_path") or None,
        tls_key=opts.get("http.tls.key_path") or None,
        influxdb_enable=bool(opts.get("influxdb.enable", True)),
        opentsdb_enable=bool(opts.get("opentsdb.enable", True)),
    ).start()
    closers.append(server.stop)
    return server


def _telemetry(opts, closers, *, mode: str):
    if not opts.get("telemetry.enable", False):
        return
    endpoint = opts.get("telemetry.endpoint", "")
    if not endpoint:
        return
    from greptimedb_tpu.telemetry.report import TelemetryTask

    task = TelemetryTask(
        opts.get("data_home"), endpoint=endpoint,
        interval_s=float(opts.get("telemetry.interval_s", 1800.0)),
        mode=mode,
    ).start()
    closers.append(task.stop)


def _export_metrics(inst, opts, closers, *, role: str = ""):
    """Self-import node metrics (independent of the HTTP server; a node
    with http disabled still exports). Series are stamped with
    node/role labels so two roles exporting into the same
    greptime_metrics database never collide into one series."""
    if not opts.get("export_metrics.enable", False):
        return
    if not hasattr(getattr(inst, "catalog", None), "create_database"):
        return  # stateless roles (frontend) have no local storage
    from greptimedb_tpu.telemetry.export import ExportMetricsTask

    task = ExportMetricsTask(
        inst,
        db=opts.get("export_metrics.db", "greptime_metrics"),
        interval_s=float(opts.get("export_metrics.write_interval_s", 30.0)),
        role=role or None,
    ).start()
    closers.append(task.stop)


def _flight_server(inst, opts, closers):
    if not (opts.get("grpc.enable", True) and opts.get("grpc.addr")):
        return None
    try:
        from greptimedb_tpu.servers.flight import FlightFrontend
    except ImportError:
        print("# pyarrow.flight unavailable; flight disabled", flush=True)
        return None
    fh, fp = _split(opts.get("grpc.addr"))
    srv = FlightFrontend(inst, addr=fh, port=fp).start()
    closers.append(srv.close)
    print(f"greptimedb-tpu arrow flight on {fh}:{srv.server.port}",
          flush=True)
    return srv


def _advertise_addr(opts, srv) -> str | None:
    """The address peers should dial: grpc.advertise_addr if set, else
    the bind address with the RESOLVED port (port 0 binds ephemerally)
    and a routable host when bound to a wildcard."""
    adv = opts.get("grpc.advertise_addr")
    if adv:
        return adv
    if srv is None:
        return opts.get("grpc.addr") or None
    host = srv.addr
    if host in ("", "0.0.0.0", "::"):
        import socket as _socket

        host = _socket.gethostbyname(_socket.gethostname())
    return f"{host}:{srv.server.port}"


def _result_path_options(inst, opts):
    """[sessions] + [result_cache] knobs: the device-resident result
    path (persistent query sessions, frontend result-set cache)."""
    from greptimedb_tpu.query import sessions as _sessions
    from greptimedb_tpu.query.result_cache import ResultCache

    _sessions.configure(opts.section("sessions"))
    inst.result_cache = ResultCache.from_options(
        opts.section("result_cache")
    )
    inst.catalog.result_cache = inst.result_cache


def _make_instance(opts):
    from greptimedb_tpu.instance import Standalone
    from greptimedb_tpu.storage.engine import EngineConfig
    from greptimedb_tpu.storage.object_store import (
        object_store_from_options,
    )
    from greptimedb_tpu.storage.compaction import compaction_options_from
    from greptimedb_tpu.storage.recovery import recovery_options_from

    store = None
    storage = opts.section("storage")
    if (str(storage.get("type", "fs")).lower() != "fs"
            or storage.get("root")):
        store = object_store_from_options(storage, opts.get("data_home"))
    # dedicated cold-tier store ([storage.cold]); absent, regions fall
    # back to the primary store beneath any local read cache
    cold_store = None
    cold_cfg = storage.get("cold")
    if isinstance(cold_cfg, dict) and cold_cfg:
        import os as _os

        cold_store = object_store_from_options(
            cold_cfg, _os.path.join(opts.get("data_home"), "cold")
        )
    # process-wide query mesh ([mesh] knobs): built once from the
    # visible devices and threaded into every QueryEngine this process
    # creates (the replicate-vs-shard planner gates per-query use)
    from greptimedb_tpu.parallel import mesh as mesh_mod

    mesh_opts = mesh_mod.mesh_options_from(opts.section("mesh"))
    mesh = mesh_mod.configure(mesh_opts)
    # [tracing] knobs: sampling + ring capacity for this process
    from greptimedb_tpu.telemetry import tracing as _tracing

    _tracing.configure(opts.section("tracing"))
    # [memory] knobs: global device watermark + census cadence
    from greptimedb_tpu.telemetry import memory as _memory

    _memory.configure(opts.section("memory"))
    # [stmt_stats] knobs: fingerprint registry size + metric label cap
    from greptimedb_tpu.telemetry import stmt_stats as _stmt_stats

    _stmt_stats.configure(opts.section("stmt_stats"))
    # [fleet] knobs: heartbeat telemetry cadence + cluster fan-out
    # bounds + federated-scrape cache TTL (dist/fleet.py)
    from greptimedb_tpu.dist import fleet as _fleet

    _fleet.configure(opts.section("fleet"))
    # [profiling] knobs: device-program registry + roofline peaks
    from greptimedb_tpu.telemetry import device_programs as _dev_prog

    _dev_prog.configure(opts.section("profiling"))
    # [index] knobs: secondary tag-index dataplane (postings caches +
    # the HBM-resident label plane)
    from greptimedb_tpu import index as _index

    _index.configure(opts.section("index"))
    prefer_device = opts.get("query.prefer_device")
    inst = Standalone(
        mesh=mesh, mesh_opts=mesh_opts,
        prefer_device=(None if prefer_device is None
                       else bool(prefer_device)),
        engine_config=EngineConfig(
            data_root=opts.get("data_home"),
            enable_background=opts.get("engine.enable_background", True),
            background_interval_s=opts.get(
                "engine.background_interval_s", 5.0
            ),
            wal_backend=opts.get("wal.backend", "fs"),
            wal_topics=int(opts.get("wal.topics", 4)),
            recovery=recovery_options_from(opts.section("recovery")),
            compaction=compaction_options_from(
                opts.section("compaction")
            ),
        ),
        store=store,
        cold_store=cold_store,
    )
    if opts.get("flow.enable", True):
        try:
            inst.enable_flows(
                tick_interval_s=opts.get("flow.tick_interval_s", 1.0)
            )
        except Exception as e:  # noqa: BLE001
            # the node still serves reads/writes without flows
            print(f"# flows disabled: {e}", flush=True)
    from greptimedb_tpu.sched import AdmissionController, SchedulerConfig

    inst.scheduler = AdmissionController(
        SchedulerConfig.from_options(opts.section("scheduler"))
    )
    _result_path_options(inst, opts)
    from greptimedb_tpu.telemetry.slow_query import SlowQueryLog

    inst.slow_query_log = SlowQueryLog(
        enable=bool(opts.get("logging.slow_query.enable", True)),
        threshold_s=float(opts.get("logging.slow_query.threshold_s", 5.0)),
        sample_ratio=float(
            opts.get("logging.slow_query.sample_ratio", 1.0)
        ),
    )
    # [autotune] knobs: apply AFTER the scheduler/result-cache swaps
    # above so the controllers tune the operator-configured objects;
    # the knob registry reads through `inst` attributes, so the swapped
    # instances are what set_config and the controllers see
    inst.autotune.apply_options(opts.section("autotune"))
    inst.autotune.start()
    return inst


def _start_standalone(opts):
    inst = _make_instance(opts)
    closers = [inst.close]
    server = _http_server(inst, opts, closers)
    if server is not None:
        inst.node_addr = f"{server.addr}:{server.port}"
    _export_metrics(inst, opts, closers, role="standalone")
    _telemetry(opts, closers, mode="standalone")
    _wire_protocols(inst, opts, closers)
    _flight_server(inst, opts, closers)
    print(
        f"greptimedb-tpu standalone listening on http://{server.addr}:"
        f"{server.port}", flush=True,
    )
    return _serve_until_signal(closers)


def _start_datanode(opts):
    inst = _make_instance(opts)
    inst.node_role = "datanode"
    closers = [inst.close]
    # region-server surface: per-region open/write/scan/partial-SQL for
    # the distributed topology (dist/region_server.py)
    from greptimedb_tpu.dist.region_server import RegionServer

    inst.region_server = RegionServer(
        inst.engine, opts.get("data_home"),
        scan_cache_bytes=opts.get("dist_query.scan_cache_bytes"),
        region_scan_parallelism=opts.get(
            "dist_query.region_scan_parallelism"
        ),
    )
    flight_srv = _flight_server(inst, opts, closers)
    _http_server(inst, opts, closers)
    _export_metrics(inst, opts, closers, role="datanode")
    _telemetry(opts, closers, mode="datanode")
    meta_addr = opts.get("datanode.metasrv_addr") or ""
    if meta_addr:
        from greptimedb_tpu.dist import fleet

        fleet.configure(opts.section("fleet"))
        node_id = int(opts.get("datanode.node_id", 0))
        inst.node_id = node_id
        closers.append(fleet.start_heartbeat(
            meta_addr, node_id, inst, role="datanode",
            addr=_advertise_addr(opts, flight_srv),
        ))
    print(
        f"greptimedb-tpu datanode (node {opts.get('datanode.node_id')}) "
        f"flight on {opts.get('grpc.addr')}", flush=True,
    )
    return _serve_until_signal(closers)


def _start_frontend(opts):
    from greptimedb_tpu.telemetry import device_programs as _dev_prog
    from greptimedb_tpu.telemetry import memory as _memory
    from greptimedb_tpu.telemetry import stmt_stats as _stmt_stats
    from greptimedb_tpu.telemetry import tracing as _tracing

    _tracing.configure(opts.section("tracing"))
    _memory.configure(opts.section("memory"))
    # the frontend owns statement execution in a dist topology, so the
    # statement-statistics registry lives here ([stmt_stats] knobs)
    _stmt_stats.configure(opts.section("stmt_stats"))
    # frontends rarely dispatch programs themselves, but the registry
    # still profiles any local device path ([profiling] knobs)
    _dev_prog.configure(opts.section("profiling"))
    # [index] knobs: the frontend's merged-registry matcher lookups
    # ride the same secondary-index path as the datanodes
    from greptimedb_tpu import index as _index

    _index.configure(opts.section("index"))
    meta_addr = opts.get("metasrv.addr") or ""
    if meta_addr:
        # distributed frontend: catalog in the metasrv kv, regions on
        # datanode processes, full SQL engine here (dist/frontend.py)
        from greptimedb_tpu.dist.frontend import DistInstance

        inst = DistInstance(
            opts.get("data_home"), meta_addr,
            flownode_addr=opts.get("frontend.flownode_addr") or None,
            ingest_options=opts.section("ingest"),
            dist_query_options=opts.section("dist_query"),
            scheduler_options=opts.section("scheduler"),
        )
        _result_path_options(inst, opts)
        target = f"metasrv {meta_addr}"
    else:
        # legacy single-datanode proxy: forward statements over Flight
        from greptimedb_tpu.servers.remote import RemoteInstance

        addrs = opts.get("frontend.datanode_addrs") or []
        if isinstance(addrs, str):
            addrs = [a for a in addrs.split(",") if a]
        inst = RemoteInstance(addrs)
        target = f"datanodes {addrs}"
    inst.node_role = "frontend"
    closers = [inst.close]
    _wire_protocols(inst, opts, closers)
    server = _http_server(inst, opts, closers)
    if server is not None:
        inst.node_addr = f"{server.addr}:{server.port}"
    if meta_addr:
        # the frontend heartbeats too: the fleet plane needs ITS
        # uptime/memory/query counters on cluster_node_stats, and the
        # metasrv's phi verdict covers every role, not just datanodes
        from greptimedb_tpu.dist import fleet

        fleet.configure(opts.section("fleet"))
        inst.node_id = fleet.derive_node_id(
            "frontend", inst.node_addr or f"pid:{os.getpid()}"
        )
        closers.append(fleet.start_heartbeat(
            meta_addr, inst.node_id, inst, role="frontend",
            addr=inst.node_addr or None,
        ))
    _telemetry(opts, closers, mode="frontend")
    print(
        f"greptimedb-tpu frontend -> {target} on "
        f"http://{server.addr}:{server.port}", flush=True,
    )
    return _serve_until_signal(closers)


def _start_metasrv(opts):
    from greptimedb_tpu.servers.meta_http import MetasrvServer

    mh, mp = _split(opts.get("metasrv.addr"))
    srv = MetasrvServer(
        addr=mh, port=mp, data_home=opts.get("data_home"),
        selector=opts.get("metasrv.selector", "round_robin"),
        phi_threshold=float(opts.get("metasrv.phi_threshold", 8.0)),
        acceptable_pause_ms=float(
            opts.get("metasrv.acceptable_pause_ms", 10000.0)
        ),
        stats_history=int(opts.get("fleet.history", 32)),
    ).start()
    closers = [srv.close]
    _telemetry(opts, closers, mode="metasrv")
    print(f"greptimedb-tpu metasrv on {mh}:{srv.port}", flush=True)
    return _serve_until_signal(closers)


def _start_flownode(opts):
    meta_addr = opts.get("metasrv.addr") or ""
    if meta_addr:
        # flow evals dispatch device programs (flow/device_state.py),
        # so the dist flownode configures the profiler too (the
        # standalone path rides _make_instance below)
        from greptimedb_tpu.telemetry import (
            device_programs as _dev_prog,
        )

        _dev_prog.configure(opts.section("profiling"))
        # distributed flownode: shared-kv catalog (source/sink tables
        # are RemoteTables over the datanodes), flows local, mirrored
        # deltas arrive over Flight (dist/frontend.py flow mirroring)
        from greptimedb_tpu.dist.frontend import DistInstance

        inst = DistInstance(opts.get("data_home"), meta_addr,
                            ingest_options=opts.section("ingest"))
        inst.node_role = "flownode"
        inst.enable_flows(
            tick_interval_s=opts.get("flow.tick_interval_s", 1.0)
        )
        closers = [inst.close]
        flight_srv = _flight_server(inst, opts, closers)
        # register in the metasrv flownode book so frontends place
        # flows and route mirrors here (dist/frontend.py). Keyed by the
        # ADVERTISED ADDRESS: two flownodes without explicit node ids
        # must not overwrite each other's registration
        try:
            from greptimedb_tpu.dist.client import MetaClient
            from greptimedb_tpu.dist.frontend import DistInstance as _DI

            adv = _advertise_addr(opts, flight_srv) or ""
            if adv:
                MetaClient(meta_addr).kv_put(
                    f"{_DI.FLOWNODE_PREFIX}{adv}", adv
                )
        except Exception as e:  # noqa: BLE001 - registration best-effort
            print(f"# flownode registration failed: {e}", flush=True)
        # heartbeat as a fleet member too: liveness + node-stats ride
        # the same channel as every other role
        from greptimedb_tpu.dist import fleet

        fleet.configure(opts.section("fleet"))
        fl_addr = _advertise_addr(opts, flight_srv) or ""
        inst.node_id = fleet.derive_node_id(
            "flownode", fl_addr or f"pid:{os.getpid()}"
        )
        closers.append(fleet.start_heartbeat(
            meta_addr, inst.node_id, inst, role="flownode",
            addr=fl_addr or None,
        ))
        server = _http_server(inst, opts, closers)
        print(
            f"greptimedb-tpu flownode (dist, metasrv {meta_addr}) "
            f"flight on {opts.get('grpc.addr')}", flush=True,
        )
        _telemetry(opts, closers, mode="flownode")
        return _serve_until_signal(closers)
    inst = _make_instance(opts)   # flows on by default
    closers = [inst.close]
    server = _http_server(inst, opts, closers)
    _telemetry(opts, closers, mode="flownode")
    print(
        f"greptimedb-tpu flownode on http://{server.addr}:{server.port}",
        flush=True,
    )
    return _serve_until_signal(closers)


def _repl(args):
    from greptimedb_tpu.instance import Standalone

    inst = Standalone(args.data_home)
    print("greptimedb-tpu REPL; end statements with ';', \\q to quit")
    buf = []
    while True:
        try:
            line = input("greptime> " if not buf else "      -> ")
        except EOFError:
            break
        if line.strip() in ("\\q", "exit", "quit"):
            break
        buf.append(line)
        if not line.rstrip().endswith(";"):
            continue
        sql = "\n".join(buf)
        buf = []
        try:
            res = inst.sql(sql.rstrip(";"))
            _print_result(res)
        except Exception as e:
            print(f"error: {e}")
    inst.close()
    return 0


def _print_result(res):
    if not res.names:
        print("OK")
        return
    widths = [
        max(len(str(n)), *(len(str(r[i])) for r in res.rows()), 1)
        if res.num_rows else len(str(n))
        for i, n in enumerate(res.names)
    ]

    def fmt(row):
        return " | ".join(str(v).ljust(w) for v, w in zip(row, widths))

    print(fmt(res.names))
    print("-+-".join("-" * w for w in widths))
    for row in res.rows():
        print(fmt(row))
    print(f"({res.num_rows} rows)")


if __name__ == "__main__":
    sys.exit(main())
