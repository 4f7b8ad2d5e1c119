"""Standalone instance: the statement executor over catalog + query engine.

Capability counterpart of the reference's frontend Instance + operator
StatementExecutor (/root/reference/src/frontend/src/instance.rs:111,
src/operator/src/statement.rs:130-312): one entry point that parses SQL,
dispatches every statement kind, routes DML to storage, and runs queries
through the planner/executor. Protocol servers (HTTP/gRPC) call into this.
"""

from __future__ import annotations

import logging

import numpy as np

from greptimedb_tpu.catalog import CatalogManager
from greptimedb_tpu.catalog.manager import region_options_from_table
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema, SemanticType
from greptimedb_tpu.datatypes.types import ConcreteDataType
from greptimedb_tpu.errors import (
    DatabaseNotFoundError,
    ExecutionError,
    InvalidArgumentError,
    PlanError,
    TableNotFoundError,
    UnsupportedError,
)
from greptimedb_tpu.query.executor import Col, QueryEngine, QueryResult
from greptimedb_tpu.query.expr import eval_const, parse_ts_literal
from greptimedb_tpu.query.planner import plan_select
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.sql import ast as A
from greptimedb_tpu.sql.parser import parse_sql
from greptimedb_tpu.storage.engine import EngineConfig, TsdbEngine

from greptimedb_tpu import concurrency

class Output:
    """Statement execution result: either affected rows or a result set."""

    def __init__(self, *, affected_rows: int | None = None,
                 result: QueryResult | None = None):
        self.affected_rows = affected_rows
        self.result = result

    @staticmethod
    def rows(n: int) -> "Output":
        return Output(affected_rows=n)

    @staticmethod
    def records(r: QueryResult) -> "Output":
        return Output(result=r)


def _result_from_lists(names: list[str], columns: list[list]) -> QueryResult:
    cols = []
    for vals in columns:
        validity = np.asarray([v is not None for v in vals], bool)
        if all(isinstance(v, (int, np.integer)) or v is None for v in vals):
            arr = np.asarray([0 if v is None else v for v in vals], np.int64)
        elif all(isinstance(v, (int, float, np.floating)) or v is None
                 for v in vals):
            arr = np.asarray(
                [0.0 if v is None else float(v) for v in vals], np.float64
            )
        else:
            arr = np.asarray(["" if v is None else v for v in vals], object)
        cols.append(Col(arr, None if validity.all() else validity))
    return QueryResult(names, cols)


class _ProcessList:
    """In-process running-statement registry backing SHOW PROCESSLIST and
    ADMIN kill (reference: src/catalog/src/process_manager.rs). A killed
    id raises in the owning thread at its next cancellation checkpoint."""

    def __init__(self):
        import threading

        self._lock = concurrency.Lock()
        self._next_id = 1
        self._running: dict[int, dict] = {}

    def register(self, query: str, ctx) -> int:
        import time

        with self._lock:
            pid = self._next_id
            self._next_id += 1
            self._running[pid] = {
                "id": pid, "query": query, "db": ctx.database,
                "user": ctx.username or "greptime", "start": time.time(),
                # elapsed_s math uses the monotonic clock (GT011): an
                # NTP slew must not show negative or absurd elapsed
                "_start_mono": time.monotonic(),
                "killed": False,
                # Queued until the admission controller grants a slot
                # (sched/admission.py) — SHOW PROCESSLIST separates
                # waiting work from running work under overload
                "state": "Queued",
            }
            return pid

    def set_state(self, pid: int, state: str):
        with self._lock:
            entry = self._running.get(pid)
            if entry is not None:
                entry["state"] = state

    def unregister(self, pid: int):
        with self._lock:
            self._running.pop(pid, None)

    def kill(self, pid_text: str) -> bool:
        try:
            pid = int(pid_text)
        except ValueError:
            return False
        with self._lock:
            entry = self._running.get(pid)
            if entry is None:
                return False
            entry["killed"] = True
            return True

    def check_killed(self, pid: int):
        with self._lock:
            entry = self._running.get(pid)
            killed = entry is not None and entry["killed"]
        if killed:
            from greptimedb_tpu.errors import ExecutionError

            raise ExecutionError(f"query {pid} was killed")

    def snapshot(self) -> list[dict]:
        import time

        with self._lock:
            now = time.monotonic()
            return [
                {**{k: v for k, v in e.items()
                    if not k.startswith("_")},
                 "elapsed_s": now - e["_start_mono"]}
                for e in self._running.values()
            ]


# statement kinds that consume engine/storage resources and therefore
# pass through the admission controller; everything else (SHOW, SET,
# ADMIN kill, DESCRIBE, ...) is control-plane and bypasses it
_ADMITTED_STATEMENTS = (
    A.Select, A.SetOp, A.Tql, A.Insert, A.Delete, A.Copy, A.Explain,
)

_compile_cache_dir: str | None = None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory, so a restarted process skips recompiles (the
    reference has no compile step; this removes the cold-start cliff
    unique to the XLA design).

    One rule: `JAX_COMPILATION_CACHE_DIR`, when set, places the cache
    and no directory is set in code; otherwise the cache lives at the
    fixed `<checkout>/.jax_cache` next to this package. The path is
    part of the cache key's environment, so it never derives from a
    data home, a temp dir, a pid or the clock — a cache that moves
    never hits. Both size/time thresholds are lowered either way so
    the many small query programs are cached too. Failure raises: a
    server that silently recompiles everything is a different
    deployment."""
    global _compile_cache_dir
    import os

    if _compile_cache_dir is not None:
        return _compile_cache_dir
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _compile_cache_dir = path
    return path


class Standalone:
    """Single-process database instance (frontend + datanode + flownode in
    one, like `greptime standalone start`,
    /root/reference/src/cmd/src/standalone.rs:432)."""

    def __init__(self, data_root: str = "./greptimedb_tpu_data", *,
                 engine_config: EngineConfig | None = None,
                 prefer_device: bool | None = None, mesh=None,
                 mesh_opts=None, warm_start: bool = True, store=None,
                 cold_store=None):
        cfg = engine_config or EngineConfig(data_root=data_root,
                                            enable_background=False)
        enable_compile_cache()
        self.engine = TsdbEngine(cfg, store=store, cold_store=cold_store)
        self.catalog = CatalogManager(self.engine)
        self.query_engine = QueryEngine(prefer_device=prefer_device,
                                        mesh=mesh, mesh_opts=mesh_opts)
        self.flows = None  # wired by flow.FlowManager when enabled
        self._procedures = []
        self._process_list = _ProcessList()
        # fleet identity (telemetry/node_stats.py): the role this
        # process plays and the address peers dial it on; cli.py stamps
        # the real values once servers are bound (DistInstance flips
        # the role to frontend/flownode)
        self.node_role = "standalone"
        self.node_addr = ""
        self.node_id = 0
        # admission control + deadline scheduling (sched/): default
        # config is permissive (no quotas/limits => never queues or
        # sheds); cli.py swaps in the [scheduler]-configured one
        from greptimedb_tpu.sched import AdmissionController

        self.scheduler = AdmissionController()
        # frontend result-set cache (query/result_cache.py): disabled
        # by default — cli.py swaps in the [result_cache]-configured
        # one. The catalog gets a handle so drop_table can purge.
        from greptimedb_tpu.query.result_cache import ResultCache

        self.result_cache = ResultCache(enabled=False)
        self.catalog.result_cache = self.result_cache
        from greptimedb_tpu.telemetry.slow_query import SlowQueryLog

        self.slow_query_log = SlowQueryLog()
        # adaptive control plane (autotune/): the knob registry backs
        # ADMIN set_config + information_schema.autotune_* even when
        # the controller loop is off; cli.py applies the [autotune]
        # section and starts the tick thread when enabled
        from greptimedb_tpu.autotune import build_runtime

        self.knobs, self.autotune = build_runtime(self)
        if warm_start:
            # restore device grid snapshots in the background so the
            # first query after a restart skips the SST rescan
            import threading

            def _warm():
                try:
                    from greptimedb_tpu.query.device_range import (
                        warm_from_snapshots,
                    )

                    warm_from_snapshots(self.query_engine, self.catalog)
                except Exception as e:  # noqa: BLE001
                    # cold caches are only slower, never wrong — but a
                    # restart that rebuilds every grid must say why
                    logging.getLogger("greptimedb_tpu.instance").warning(
                        "device cache warm-start skipped: %s", e)

            concurrency.Thread(
                target=_warm, daemon=True, name="device-cache-warm"
            ).start()

    def close(self):
        # stop the control loop FIRST: a tick racing teardown would
        # read sensors over closing pools
        self.autotune.close()
        if self.flows is not None:
            self.flows.stop()
        # fence the region server FIRST: a parked ingest stream must
        # get typed errors, not apply writes into a closing engine
        rs = getattr(self, "region_server", None)
        if rs is not None and hasattr(rs, "close"):
            rs.close()
        self.engine.close()

    # ------------------------------------------------------------------
    # entry points
    # ------------------------------------------------------------------
    def execute_sql(self, sql: str, ctx: QueryContext | None = None
                    ) -> list[Output]:
        import time as _time

        from greptimedb_tpu.telemetry import stmt_stats, tracing

        ctx = ctx or QueryContext()
        outputs = []
        t0 = _time.perf_counter()
        trace_id = None
        fps = []
        try:
            # one span per statement batch: the root on wires that
            # carry no traceparent (mysql/postgres/flight), a child of
            # the server's request span on HTTP — and the trace_id the
            # slow-query log links back to
            with tracing.span("sql.execute", db=ctx.database,
                              channel=ctx.channel) as root:
                trace_id = root.trace_id or None
                # per-statement fingerprints resolved from the raw TEXT
                # (the AST has no literal spans left to fold); aligned
                # with parse_sql's statement order by the shared ';'
                # split
                if stmt_stats.enabled():
                    with tracing.child_span("sql.fingerprint"):
                        fps = stmt_stats.fingerprint_sql(sql)
                with tracing.child_span("sql.parse"):
                    stmts = parse_sql(sql)
                for i, stmt in enumerate(stmts):
                    token = stmt_stats.bind_fingerprint(
                        fps[i] if i < len(fps) else None
                    )
                    try:
                        outputs.append(self.execute_statement(stmt, ctx))
                    finally:
                        stmt_stats.reset_fingerprint(token)
        finally:
            # duration from the monotonic perf counter (GT011), never
            # wall-clock arithmetic
            self.slow_query_log.maybe_record(
                sql, _time.perf_counter() - t0,
                db=ctx.database, channel=ctx.channel,
                trace_id=trace_id,
                fingerprint=fps[0].fp if fps else "",
            )
        return outputs

    def sql(self, sql: str, ctx: QueryContext | None = None) -> QueryResult:
        """Convenience: single-statement query returning a result set."""
        outs = self.execute_sql(sql, ctx)
        out = outs[-1]
        if out.result is None:
            return _result_from_lists(
                ["affected_rows"], [[out.affected_rows or 0]]
            )
        return out.result

    # ------------------------------------------------------------------
    def execute_statement(self, stmt: A.Statement, ctx: QueryContext
                          ) -> Output:
        from greptimedb_tpu.telemetry import stmt_stats, tracing

        from greptimedb_tpu import cancellation

        kind = type(stmt).__name__
        pid = self._process_list.register(kind, ctx)
        token = cancellation.set_check(
            lambda: self._process_list.check_killed(pid)
        )
        try:
            # one statement-statistics observation per statement:
            # everything the execution layers attribute (queue time,
            # exec path, compile/cache hits, transfer bytes, dist rpc
            # time) folds into the fingerprint's registry row on exit
            with stmt_stats.global_stmt_stats.observe(ctx, kind) as obs, \
                    tracing.span(f"sql.{kind}"):
                if isinstance(stmt, _ADMITTED_STATEMENTS):
                    # data-plane statements go through admission
                    # control (quota/slot/deadline); control-plane
                    # statements (SHOW/SET/USE/ADMIN kill...) bypass so
                    # an operator can still inspect and kill work on an
                    # overloaded instance
                    with self.scheduler.admit(ctx):
                        self._process_list.set_state(pid, "Running")
                        out = self._execute_statement(stmt, ctx)
                else:
                    self._process_list.set_state(pid, "Running")
                    out = self._execute_statement(stmt, ctx)
                if obs is not None:
                    obs.add("rows", out.result.num_rows
                            if out.result is not None
                            else (out.affected_rows or 0))
                return out
        finally:
            cancellation.reset(token)
            self._process_list.unregister(pid)

    def _execute_statement(self, stmt: A.Statement, ctx: QueryContext
                           ) -> Output:
        if isinstance(stmt, A.Select):
            return Output.records(self._select(stmt, ctx))
        if isinstance(stmt, A.SetOp):
            from greptimedb_tpu.query import relational

            return Output.records(relational.execute(self, stmt, ctx))
        if isinstance(stmt, A.CreateView):
            db, name = self._resolve(stmt.name, ctx)
            if stmt.text is None:
                raise UnsupportedError("CREATE VIEW requires query text")
            self.catalog.create_view(
                db, name, stmt.text, or_replace=stmt.or_replace
            )
            return Output.rows(0)
        if isinstance(stmt, A.DropView):
            db, name = self._resolve(stmt.name, ctx)
            self.catalog.drop_view(db, name, if_exists=stmt.if_exists)
            return Output.rows(0)
        if isinstance(stmt, A.Insert):
            return Output.rows(self._insert(stmt, ctx))
        if isinstance(stmt, A.Delete):
            return Output.rows(self._delete(stmt, ctx))
        if isinstance(stmt, A.CreateTable):
            self._create_table(stmt, ctx)
            return Output.rows(0)
        if isinstance(stmt, A.CreateDatabase):
            self.catalog.create_database(
                stmt.name, if_not_exists=stmt.if_not_exists
            )
            return Output.rows(1)
        if isinstance(stmt, A.DropDatabase):
            self.catalog.drop_database(stmt.name, if_exists=stmt.if_exists)
            return Output.rows(0)
        if isinstance(stmt, A.DropTable):
            for name in stmt.names:
                db, tname = self._resolve(name, ctx)
                self.catalog.drop_table(db, tname, if_exists=stmt.if_exists)
            return Output.rows(0)
        if isinstance(stmt, A.TruncateTable):
            db, tname = self._resolve(stmt.name, ctx)
            self.catalog.table(db, tname).truncate()
            return Output.rows(0)
        if isinstance(stmt, A.AlterTable):
            return Output.rows(self._alter(stmt, ctx))
        if isinstance(stmt, A.Use):
            if not self.catalog.has_database(stmt.database):
                raise DatabaseNotFoundError(
                    f"database not found: {stmt.database}"
                )
            ctx.database = stmt.database
            return Output.rows(0)
        if isinstance(stmt, A.ShowDatabases):
            return Output.records(self._show_databases(stmt))
        if isinstance(stmt, A.ShowTables):
            return Output.records(self._show_tables(stmt, ctx))
        if isinstance(stmt, A.ShowCreateTable):
            return Output.records(self._show_create_table(stmt, ctx))
        if isinstance(stmt, A.DescribeTable):
            return Output.records(self._describe(stmt, ctx))
        if isinstance(stmt, A.Explain):
            return Output.records(self._explain(stmt, ctx))
        if isinstance(stmt, A.Tql):
            return Output.records(self._tql(stmt, ctx))
        if isinstance(stmt, A.CreateFlow):
            return self._create_flow(stmt, ctx)
        if isinstance(stmt, A.DropFlow):
            return self._drop_flow(stmt, ctx)
        if isinstance(stmt, A.ShowFlows):
            return Output.records(self._show_flows())
        if isinstance(stmt, A.ShowViews):
            return Output.records(_result_from_lists(
                ["Views"], [self.catalog.view_names(ctx.database)]
            ))
        if isinstance(stmt, A.ShowCreateFlow):
            if self.flows is None:
                raise UnsupportedError("flows are not enabled")
            flow = self.flows.maybe_flow(stmt.name)
            if flow is None:
                raise TableNotFoundError(f"flow not found: {stmt.name}")
            return Output.records(_result_from_lists(
                ["Flow", "Create Flow"], [[stmt.name], [flow.raw_sql]]
            ))
        if isinstance(stmt, A.ShowCreateView):
            db, name = self._resolve(stmt.name, ctx)
            sql_text = self.catalog.maybe_view(db, name)
            if sql_text is None:
                raise TableNotFoundError(f"view not found: {name}")
            return Output.records(_result_from_lists(
                ["View", "Create View"],
                [[name], [f"CREATE VIEW {name} AS {sql_text}"]],
            ))
        if isinstance(stmt, A.Copy):
            return Output.rows(self._copy(stmt, ctx))
        if isinstance(stmt, A.Admin):
            return self._admin(stmt, ctx)
        if isinstance(stmt, A.SetVariable):
            return self._set_variable(stmt, ctx)
        if isinstance(stmt, A.ShowVariables):
            return Output.records(self._show_variables(stmt, ctx))
        if isinstance(stmt, A.ShowColumns):
            return Output.records(self._show_columns(stmt, ctx))
        if isinstance(stmt, A.ShowIndex):
            return Output.records(self._show_index(stmt, ctx))
        if isinstance(stmt, A.ShowStatus):
            return Output.records(_result_from_lists(
                ["Variable_name", "Value"], [["Uptime"], ["0"]]
            ))
        if isinstance(stmt, A.ShowCharset):
            return Output.records(_result_from_lists(
                ["Charset", "Description", "Default collation", "Maxlen"],
                [["utf8mb4"], ["UTF-8 Unicode"], ["utf8mb4_bin"], [4]],
            ))
        if isinstance(stmt, A.ShowCollation):
            return Output.records(_result_from_lists(
                ["Collation", "Charset", "Id", "Default", "Compiled",
                 "Sortlen"],
                [["utf8mb4_bin"], ["utf8mb4"], [46], ["Yes"], ["Yes"], [1]],
            ))
        if isinstance(stmt, A.ShowProcesslist):
            return Output.records(self._show_processlist(stmt))
        if isinstance(stmt, A.Prepare):
            ctx.extensions.setdefault("prepared", {})[
                stmt.name.lower()
            ] = stmt.sql_text
            return Output.rows(0)
        if isinstance(stmt, A.Execute):
            prepared = ctx.extensions.get("prepared", {})
            text = prepared.get(stmt.name.lower())
            if text is None:
                raise InvalidArgumentError(
                    f"prepared statement {stmt.name!r} does not exist"
                )
            args = [eval_const(a) for a in stmt.args]
            sub = substitute_placeholders(text, args)
            stmts = parse_sql(sub)
            if len(stmts) != 1:
                raise InvalidArgumentError(
                    "prepared statement must be a single statement"
                )
            return self._execute_statement(stmts[0], ctx)
        if isinstance(stmt, A.Deallocate):
            prepared = ctx.extensions.get("prepared", {})
            if stmt.name == "all":
                prepared.clear()
            elif prepared.pop(stmt.name.lower(), None) is None:
                raise InvalidArgumentError(
                    f"prepared statement {stmt.name!r} does not exist"
                )
            return Output.rows(0)
        raise UnsupportedError(
            f"statement not supported yet: {type(stmt).__name__}"
        )

    # ------------------------------------------------------------------
    # ADMIN maintenance functions (reference:
    # src/sql/src/statements/admin.rs dispatching to the admin function
    # set — flush/compact region + table, migrate_region)
    # ------------------------------------------------------------------
    def _admin(self, stmt: A.Admin, ctx: QueryContext) -> Output:
        def arg(i: int) -> A.Expr:
            if i >= len(stmt.args):
                raise InvalidArgumentError(
                    f"admin {stmt.func}: missing argument {i + 1}"
                )
            return stmt.args[i]

        def const_str(i: int) -> str:
            v = eval_const(arg(i))
            if not isinstance(v, str):
                raise InvalidArgumentError(
                    f"admin {stmt.func}: arg {i} must be a string"
                )
            return v

        def const_int(i: int) -> int:
            v = eval_const(arg(i))
            if isinstance(v, bool) or not isinstance(v, (int, np.integer)):
                raise InvalidArgumentError(
                    f"admin {stmt.func}: arg {i} must be an integer"
                )
            return int(v)

        name = stmt.func
        if name in ("flush_table", "compact_table"):
            ident = const_str(0)
            db, tname = self._resolve(ident, ctx)
            table = self.catalog.table(db, tname)
            # ride the engine's bounded compaction pool: regions fan
            # out under the same concurrency cap as background merges
            # ([compaction] workers — at the default of 1 they
            # serialize, and an in-flight background merge is awaited
            # first). Errors stay typed across every wire
            # ([gtdb:<code>]). ADMIN compaction is FORCED: every
            # multi-file window merges to the top level.
            sched = self.engine.compaction
            if name == "flush_table":
                results = sched.map_sync(
                    lambda r: r.flush() is not None, table.regions
                )
            else:
                results = sched.map_sync(
                    lambda r: bool(r.compact(force=True)), table.regions
                )
            n = sum(1 for ok in results if ok)
            return Output.records(_result_from_lists(
                [f"ADMIN {name}('{ident}')"], [[n]]
            ))
        if name in ("flush_region", "compact_region"):
            rid = const_int(0)
            region = self._region_by_id(rid)
            if name == "flush_region":
                n = 1 if region.flush() is not None else 0
            else:
                n = 1 if region.compact(force=True) else 0
            return Output.records(_result_from_lists(
                [f"ADMIN {name}({rid})"], [[n]]
            ))
        if name == "flush_flow":
            fname = const_str(0)
            n = 1 if self._flush_flow_admin(fname) else 0
            return Output.records(_result_from_lists(
                [f"ADMIN flush_flow('{fname}')"], [[n]]
            ))
        if name == "migrate_region":
            metasrv = getattr(self, "metasrv", None)
            if metasrv is None:
                raise UnsupportedError(
                    "migrate_region requires a metasrv-managed cluster"
                )
            rid, to_node = const_int(0), const_int(1)
            pid = metasrv.migrate_region(rid, to_node)
            return Output.records(_result_from_lists(
                [f"ADMIN migrate_region({rid}, {to_node})"], [[str(pid)]]
            ))
        if name == "kill":
            target = eval_const(arg(0))
            ok = self._process_list.kill(str(target))
            return Output.records(_result_from_lists(
                [f"ADMIN kill('{target}')"], [[1 if ok else 0]]
            ))
        if name == "set_config":
            # the validated runtime-knob update API (autotune/knobs.py):
            # typed bounds, change log, gtpu_autotune_knob_value —
            # the same single write path the controllers use
            path = const_str(0)
            value = eval_const(arg(1))
            old, new = self.knobs.set(path, value, source="admin")
            return Output.records(_result_from_lists(
                [f"ADMIN set_config('{path}')"], [[f"{old} -> {new}"]]
            ))
        if name == "autotune_freeze":
            # hard freeze: controllers stop moving knobs until
            # autotune_unfreeze(); set_config stays available
            self.autotune.freeze(True)
            return Output.records(_result_from_lists(
                ["ADMIN autotune_freeze()"], [[1]]
            ))
        if name == "autotune_unfreeze":
            self.autotune.freeze(False)
            return Output.records(_result_from_lists(
                ["ADMIN autotune_unfreeze()"], [[1]]
            ))
        if name == "reset_device_profiler":
            # drops every device-program registry row; the exported
            # gtpu_device_program_* series zero at the next scrape so
            # all three surfaces stay equal (documented counter reset)
            from greptimedb_tpu.telemetry.device_programs import (
                global_programs,
            )

            n = global_programs.reset()
            return Output.records(_result_from_lists(
                ["ADMIN reset_device_profiler()"], [[n]]
            ))
        if name == "reset_statement_statistics":
            # pg_stat_statements_reset() analog: drops every registry
            # row; the monotone gtpu_stmt_* counters keep counting
            from greptimedb_tpu.telemetry.stmt_stats import (
                global_stmt_stats,
            )

            n = global_stmt_stats.reset()
            return Output.records(_result_from_lists(
                ["ADMIN reset_statement_statistics()"], [[n]]
            ))
        raise UnsupportedError(f"unknown admin function {name!r}")

    def _set_variable(self, stmt: A.SetVariable, ctx: QueryContext
                      ) -> Output:
        for name, value_expr in stmt.assignments:
            value = eval_const(value_expr)
            if name in ("time_zone", "timezone", "session_time_zone"):
                ctx.timezone = str(value)
                ctx.variables["time_zone"] = str(value)
            else:
                ctx.variables[name] = (
                    value if isinstance(value, str) else str(value)
                )
        return Output.rows(0)

    def _show_variables(self, stmt: A.ShowVariables, ctx: QueryContext):
        from greptimedb_tpu.query.expr import like_to_regex
        from greptimedb_tpu.session import DEFAULT_VARIABLES

        merged = dict(DEFAULT_VARIABLES)
        merged.update(ctx.variables)
        items = sorted(merged.items())
        if stmt.like:
            pat = like_to_regex(stmt.like.lower())
            items = [
                (k, v) for k, v in items if pat.fullmatch(k.lower())
            ]
        return _result_from_lists(
            ["Variable_name", "Value"],
            [[k for k, _ in items], [v for _, v in items]],
        )

    def _show_columns(self, stmt: A.ShowColumns, ctx: QueryContext):
        from greptimedb_tpu.query.expr import like_to_regex

        db = stmt.database or ctx.database
        table = self.catalog.table(db, stmt.table)
        pat = like_to_regex(stmt.like.lower()) if stmt.like else None
        names, types, nulls, keys, defaults, semantics = [], [], [], [], [], []
        for cs in table.schema.columns:
            if pat is not None and not pat.fullmatch(cs.name.lower()):
                continue
            names.append(cs.name)
            types.append(cs.data_type.name)
            nulls.append("Yes" if cs.nullable else "No")
            if cs.semantic_type == SemanticType.TIMESTAMP:
                keys.append("TIME INDEX")
            elif cs.semantic_type == SemanticType.TAG:
                keys.append("PRI")
            else:
                keys.append("")
            defaults.append(default_display(cs.default))
            semantics.append(cs.semantic_type.name)
        cols = [names, types, nulls, keys, defaults]
        headers = ["Column", "Type", "Null", "Key", "Default"]
        if stmt.full:
            headers.append("Semantic Type")
            cols.append(semantics)
        return _result_from_lists(headers, cols)

    def _show_index(self, stmt: A.ShowIndex, ctx: QueryContext):
        db = stmt.database or ctx.database
        table = self.catalog.table(db, stmt.table)
        names, key_names, seqs = [], [], []
        for i, tag in enumerate(table.tag_names):
            names.append(stmt.table)
            key_names.append("PRIMARY")
            seqs.append(i + 1)
        names.append(stmt.table)
        key_names.append("TIME INDEX")
        seqs.append(1)
        cols = [names, key_names, seqs,
                table.tag_names + [table.ts_name]]
        return _result_from_lists(
            ["Table", "Key_name", "Seq_in_index", "Column_name"], cols
        )

    def _show_processlist(self, stmt: A.ShowProcesslist):
        entries = self._process_list.snapshot()
        return _result_from_lists(
            ["Id", "User", "db", "Command", "State", "Time", "Info"],
            [[e["id"] for e in entries],
             [e["user"] for e in entries],
             [e["db"] for e in entries],
             ["Query"] * len(entries),
             [e.get("state", "Running") for e in entries],
             [round(e["elapsed_s"], 3) for e in entries],
             [e["query"] for e in entries]],
        )

    # ------------------------------------------------------------------
    # COPY TO/FROM (reference: src/operator/src/statement/copy_table_*.rs
    # + src/common/datasource format readers/writers)
    # ------------------------------------------------------------------
    def _copy(self, stmt: A.Copy, ctx: QueryContext) -> int:
        import pyarrow as pa

        db, name = self._resolve(stmt.table, ctx)
        table = self.catalog.table(db, name)
        fmt = stmt.format
        if stmt.direction == "to":
            res = self._select(A.Select(
                items=[A.SelectItem(A.Star())], from_table=stmt.table,
            ), ctx)
            arrays = {}
            for i, n in enumerate(res.names):
                col = res.cols[i]
                cs = table.schema.maybe_column(n)
                mask = None if col.validity is None else ~col.validity
                if cs is not None and cs.data_type.is_timestamp():
                    arrays[n] = pa.array(
                        col.values.astype("datetime64[ms]"), mask=mask
                    )
                elif cs is not None and cs.data_type.is_decimal():
                    arrays[n] = pa.array(
                        np.asarray(col.values, np.float64), mask=mask
                    ).cast(cs.data_type.to_arrow(), safe=False)
                else:
                    arrays[n] = pa.array(col.values, mask=mask)
            pa_table = pa.table(arrays)
            return _write_format(pa_table, stmt.path, fmt)
        # COPY FROM
        pa_table = _read_format(stmt.path, fmt)
        data = {}
        valid = {}
        from greptimedb_tpu.datatypes.batch import HostColumn

        for n in pa_table.column_names:
            if n not in table.schema:
                continue
            hc = HostColumn.from_arrow(n, pa_table.column(n))
            vals = hc.values
            if hc.data_type.is_timestamp():
                # normalize to ms regardless of the file's inferred unit;
                # divide first (ns ticks * 1000 would overflow int64)
                tps = hc.data_type.ticks_per_second
                if tps >= 1000:
                    vals = vals // (tps // 1000)
                else:
                    vals = vals * (1000 // tps)
            data[n] = vals
            valid[n] = hc.valid_mask
        written = self._write_columns(table, data, valid)
        self._notify_flows(db, name, table, data, valid)
        return written

    # ------------------------------------------------------------------
    # DDL
    # ------------------------------------------------------------------
    def _create_table(self, stmt: A.CreateTable, ctx: QueryContext):
        db, name = self._resolve(stmt.name, ctx)
        if stmt.like_table is not None:
            # CREATE TABLE t LIKE s: clone the source's schema + options
            # (reference: src/operator/src/statement.rs CreateTableLike)
            sdb, sname = self._resolve(stmt.like_table, ctx)
            src = self.catalog.table(sdb, sname)
            self.catalog.create_table(
                db, name, Schema(list(src.schema.columns)),
                engine=src.info.engine,
                options=dict(src.info.options),
                num_regions=len(src.regions),
                if_not_exists=stmt.if_not_exists,
                partition=src.info.partition,
            )
            return
        cols = []
        pk = set(stmt.primary_keys)
        for cd in stmt.columns:
            if cd.time_index or (stmt.time_index == cd.name):
                sem = SemanticType.TIMESTAMP
            elif cd.primary_key or cd.name in pk:
                sem = SemanticType.TAG
            else:
                sem = SemanticType.FIELD
            if sem == SemanticType.TAG and not cd.data_type.is_string():
                # numeric tags are legal in the reference; stored as strings
                # in the series registry (dense-sid design), decoded on read.
                pass
            cols.append(ColumnSchema(
                name=cd.name, data_type=cd.data_type, semantic_type=sem,
                nullable=cd.nullable and sem == SemanticType.FIELD,
                default=_const_default(cd.default), fulltext=cd.fulltext,
            ))
        schema = Schema(cols)
        num_regions = 1
        partition = None
        if stmt.partitions:
            from greptimedb_tpu.catalog.partition import PartitionRule

            num_regions = max(1, len(stmt.partitions))
            rule = PartitionRule.from_ast(
                stmt.partition_columns, stmt.partitions
            )
            for c in rule.columns:
                col = schema.maybe_column(c)
                if col is None or not col.is_tag:
                    raise InvalidArgumentError(
                        f"PARTITION ON column {c!r} must be a tag "
                        "(PRIMARY KEY) column"
                    )
            partition = rule.to_json()
        elif "num_regions" in stmt.options:
            num_regions = int(stmt.options.pop("num_regions"))
        self.catalog.create_table(
            db, name, schema, engine=stmt.engine, options=stmt.options,
            num_regions=num_regions, if_not_exists=stmt.if_not_exists,
            partition=partition,
        )

    def _alter(self, stmt: A.AlterTable, ctx: QueryContext) -> int:
        db, name = self._resolve(stmt.name, ctx)
        if stmt.action == "add_column":
            cd = stmt.column
            sem = SemanticType.TAG if cd.primary_key else SemanticType.FIELD
            self.catalog.alter_add_column(db, name, ColumnSchema(
                name=cd.name, data_type=cd.data_type, semantic_type=sem,
                nullable=True, default=_const_default(cd.default),
            ))
        elif stmt.action == "drop_column":
            self.catalog.alter_drop_column(db, name, stmt.old_name)
        elif stmt.action == "rename":
            self.catalog.rename_table(db, name, stmt.new_name)
        else:
            raise UnsupportedError(f"ALTER action: {stmt.action}")
        return 0

    # ------------------------------------------------------------------
    # DML
    # ------------------------------------------------------------------
    def _insert(self, stmt: A.Insert, ctx: QueryContext) -> int:
        db, name = self._resolve(stmt.table, ctx)
        table = self.catalog.table(db, name)
        schema = table.schema
        if stmt.select is not None:
            res = self._select(stmt.select, ctx)
            cols = stmt.columns or res.names
            data = {
                c: np.asarray(col.values)
                for c, col in zip(cols, res.cols)
            }
            valid = {
                c: col.valid_mask
                for c, col in zip(cols, res.cols)
            }
            _apply_defaults(schema, data, valid, res.num_rows)
            written = self._write_columns(table, data, valid)
            self._notify_flows(db, name, table, data, valid)
            return written

        cols = stmt.columns or schema.column_names
        n = len(stmt.values)
        raw = {c: [] for c in cols}
        for row in stmt.values:
            if len(row) != len(cols):
                raise InvalidArgumentError(
                    f"INSERT row has {len(row)} values, expected {len(cols)}"
                )
            for c, e in zip(cols, row):
                raw[c].append(eval_const(e))
        data = {}
        valid = {}
        for c, vals in raw.items():
            col_schema = schema.column(c)
            arr, v = _coerce_insert(vals, col_schema.data_type)
            data[c] = arr
            valid[c] = v
        _apply_defaults(schema, data, valid, n)
        written = self._write_columns(table, data, valid)
        self._notify_flows(db, name, table, data, valid)
        return written

    def _write_columns(self, table, data: dict, valid: dict) -> int:
        schema = table.schema
        ts_name = schema.time_index.name
        if ts_name not in data:
            raise InvalidArgumentError(
                f"INSERT missing TIME INDEX column {ts_name}"
            )
        from greptimedb_tpu.telemetry import tracing

        tags = {}
        fields = {}
        fvalid = {}
        with tracing.child_span("write.tag_columns"):
            for cname, arr in data.items():
                cs = schema.column(cname)
                if cs.is_time_index:
                    continue
                if cs.is_tag:
                    tags[cname] = np.asarray(
                        ["" if v is None else str(v) for v in arr],
                        object
                    )
                else:
                    fields[cname] = arr
                    if cname in valid and not valid[cname].all():
                        fvalid[cname] = valid[cname]
        ts = np.asarray(data[ts_name], np.int64)
        return table.write(tags, ts, fields, field_valid=fvalid or None)

    def _delete(self, stmt: A.Delete, ctx: QueryContext) -> int:
        db, name = self._resolve(stmt.table, ctx)
        table = self.catalog.table(db, name)
        # select the matching (tags, ts) then write tombstones
        sel = A.Select(
            items=[A.SelectItem(A.Column(t)) for t in table.tag_names]
            + [A.SelectItem(A.Column(table.ts_name))],
            from_table=stmt.table, where=stmt.where,
        )
        res = self._select(sel, ctx)
        if res.num_rows == 0:
            return 0
        tags = {
            t: np.asarray(res.cols[i].values, object)
            for i, t in enumerate(table.tag_names)
        }
        ts = np.asarray(res.cols[-1].values, np.int64)
        table.delete(tags, ts)
        return len(ts)

    def _notify_flows(self, db, name, table, data, valid):
        if self.flows is not None:
            self.flows.on_insert(db, name, table, data, valid)

    # ------------------------------------------------------------------
    # queries
    # ------------------------------------------------------------------
    def _select(self, stmt: A.Select, ctx: QueryContext) -> QueryResult:
        from greptimedb_tpu.query import relational

        if relational.needs_relational(self, stmt, ctx):
            return relational.execute(self, stmt, ctx)
        return self._select_single(stmt, ctx)

    def _select_single(self, stmt: A.Select, ctx: QueryContext) -> QueryResult:
        """Single-table fast path: plan straight onto the storage scan +
        device grid caches."""
        table = None
        ts_name = None
        tag_names: list[str] = []
        all_columns = None
        if stmt.from_table:
            if self._is_information_schema(stmt.from_table, ctx):
                return self._query_information_schema(stmt, ctx)
            if self._is_pg_catalog(stmt.from_table, ctx):
                from greptimedb_tpu.information_schema import (
                    query_pg_catalog,
                )

                return query_pg_catalog(self, stmt, ctx)
        from greptimedb_tpu.telemetry import tracing

        # binding the table and its columns is planning too
        with tracing.child_span("query.plan",
                                table=stmt.from_table or ""):
            if stmt.from_table:
                db, name = self._resolve(stmt.from_table, ctx)
                table = self.catalog.table(db, name)
                ts_name = table.ts_name
                tag_names = table.tag_names
                all_columns = table.schema.column_names
            plan = plan_select(
                stmt, ts_name=ts_name, tag_names=tag_names,
                all_columns=all_columns,
            )
        return self._execute_select_plan(plan, table, ctx)

    def _execute_select_plan(self, plan, table, ctx: QueryContext):
        """Run a planned single-table SELECT through the device-resident
        result path: frontend result cache first (query/result_cache.py
        — a repeated poll on unchanged physical versions never touches
        the datanode or the device), then the `since` delta cursor bound
        for the execution layers (sliced device readback / scan ts
        tightening)."""
        from greptimedb_tpu.query import sessions
        from greptimedb_tpu.query import stats as qstats
        from greptimedb_tpu.telemetry import stmt_stats, tracing

        since = ctx.extensions.get("since_ms")
        rc = getattr(self, "result_cache", None)
        fp = versions = None
        # EXPLAIN ANALYZE collects real execution stats: bypass so its
        # metrics reflect an actual run, never a cached payload
        use_cache = (rc is not None and rc.eligible(plan, table)
                     and qstats.active() is None)
        if use_cache and since is not None:
            from greptimedb_tpu.query import result_cache as RC

            # a since-poll can only be served from the cached FULL
            # payload when the host row filter is equivalent to the
            # execution-path cursor (applied BEFORE ORDER BY/LIMIT):
            # LIMIT/OFFSET plans and row-returning plans that do not
            # project the time index must execute the delta instead.
            # Aggregates ignore the cursor entirely, so their cached
            # payload stays equivalent.
            if plan.kind != "aggregate" and (
                plan.limit is not None or bool(plan.offset)
                or RC.ts_output_name(plan, table) is None
            ):
                use_cache = False
        if use_cache:
            from greptimedb_tpu.query import result_cache as RC

            db = table.info.database
            fp = RC.plan_fingerprint(plan)
            try:
                versions = rc.current_versions(table)
            except Exception:  # noqa: BLE001 - datanode down/unreachable
                # version validation must never own failure semantics:
                # the execution path below maps unreachable datanodes to
                # the typed unavailable error or a degraded partial
                # result ([scheduler] allow_partial_results)
                use_cache = False
                versions = None
        if use_cache:
            entry = rc.get(db, table, fp, versions)
            if entry is not None:
                tracing.set_attr(result_cache="hit")
                qstats.note("result_cache", "hit")
                stmt_stats.add("result_cache_hits")
                # truthful path attribution: the cached payload came
                # from this execution path (EXPLAIN assertions)
                self.query_engine.last_exec_path = entry.exec_path
                res = entry.result
                if since is not None:
                    res = RC.filter_since(res, entry.ts_name, since)
                return res
            tracing.set_attr(result_cache="miss")
            qstats.note("result_cache", "miss")
            stmt_stats.add("result_cache_misses")
        elif rc is not None and rc.enabled:
            tracing.set_attr(result_cache="bypass")
            qstats.note("result_cache", "bypass")
            stmt_stats.add("result_cache_bypass")
        token = sessions.bind_since(since) if since is not None else None
        try:
            res = self._run_select_plan(plan, table)
        finally:
            if token is not None:
                sessions.reset_since(token)
        if use_cache and since is None and not getattr(res, "partial",
                                                       False):
            # only FULL, complete results are cached: a delta answer
            # under a cursor (or a degraded partial) must never be
            # served as the statement's payload
            from greptimedb_tpu.query import result_cache as RC

            rc.put(table.info.database, table, fp, versions, res,
                   RC.ts_output_name(plan, table),
                   self.query_engine.last_exec_path)
        return res

    def _run_select_plan(self, plan, table):
        if table is not None and getattr(table, "remote", False):
            # distributed tables: try the MergeScan split first (partial
            # plans execute datanode-side, only partial states cross the
            # wire); None falls through to remote region scans
            from greptimedb_tpu.dist.dist_query import try_dist_query

            res = try_dist_query(self, plan, table)
            if res is not None:
                return res
        return self.query_engine.execute(plan, table)

    def plan(self, stmt: A.Select, ctx: QueryContext):
        table = None
        ts_name, tag_names, all_columns = None, [], None
        if stmt.from_table:
            db, name = self._resolve(stmt.from_table, ctx)
            table = self.catalog.table(db, name)
            ts_name, tag_names = table.ts_name, table.tag_names
            all_columns = table.schema.column_names
        return plan_select(stmt, ts_name=ts_name, tag_names=tag_names,
                           all_columns=all_columns), table

    def _explain(self, stmt: A.Explain, ctx: QueryContext) -> QueryResult:
        if not isinstance(stmt.statement, (A.Select, A.SetOp)):
            raise UnsupportedError("EXPLAIN supports SELECT only")
        if isinstance(stmt.statement, A.Select) and not (
            stmt.statement.ctes or isinstance(
                stmt.statement.source, (A.JoinSource, A.SubquerySource)
            )
        ):
            plan, _ = self.plan(stmt.statement, ctx)
            lines = plan.explain_lines()
        else:
            lines = ["SelectPlan[relational]"]
        if stmt.analyze:
            import time as _time

            from greptimedb_tpu.query import stats as qstats
            from greptimedb_tpu.telemetry import tracing

            t0 = _time.perf_counter()
            with qstats.collect() as st, tracing.export_spans() as tspans:
                # stamp the ANALYZED statement's fingerprint so the
                # rendered metrics join its statement_statistics row
                # (the inner fingerprint: "EXPLAIN ANALYZE <q>" and a
                # plain "<q>" share it)
                from greptimedb_tpu.telemetry import stmt_stats

                sfp = stmt_stats.explain_fingerprint()
                if sfp:
                    st.note("stmt_fingerprint", sfp)
                if isinstance(stmt.statement, A.SetOp):
                    from greptimedb_tpu.query import relational

                    res = relational.execute(self, stmt.statement, ctx)
                else:
                    res = self._select(stmt.statement, ctx)
            dt = (_time.perf_counter() - t0) * 1000
            lines.append(
                f"  Metrics: rows={res.num_rows} elapsed={dt:.3f}ms"
            )
            lines.extend(st.lines())
            if tspans:
                # the span tree of THIS execution, inline (sched queue,
                # scan cache hit/miss, fan-out, device compile/execute/
                # transfer) — same spans /v1/traces serves
                tid = tracing.current_trace_id()
                remote = tracing.global_traces.trace(tid) if tid else []
                local_ids = {s.span_id for s in tspans}
                docs = [s.to_json() for s in tspans] + [
                    d for d in remote
                    if d["span_id"] not in local_ids
                    and d.get("duration_ms") is not None
                ]
                lines.append(f"  Trace: {tid or '(sampling disabled)'}")
                lines.extend(
                    "    " + ln for ln in tracing.render_tree(docs)
                )
        return _result_from_lists(["plan"], [lines])

    def _tql(self, stmt: A.Tql, ctx: QueryContext) -> QueryResult:
        try:
            from greptimedb_tpu.promql.engine import PromEngine
        except ImportError as e:
            raise UnsupportedError(f"TQL requires the promql module: {e}")

        start = _tql_time(stmt.start)
        end = _tql_time(stmt.end)
        step_ms = _tql_interval(stmt.step)
        lookback_ms = (
            _tql_interval(stmt.lookback) if stmt.lookback is not None
            else 300_000
        )
        engine = PromEngine(self, ctx)
        if stmt.kind == "explain":
            from greptimedb_tpu.promql.parser import parse_promql

            return _result_from_lists(
                ["plan"], [[repr(parse_promql(stmt.query))]]
            )
        return engine.query_range_result(
            stmt.query, start, end, step_ms, lookback_ms=lookback_ms
        )

    # ------------------------------------------------------------------
    # SHOW / DESCRIBE
    # ------------------------------------------------------------------
    def _show_databases(self, stmt: A.ShowDatabases) -> QueryResult:
        names = self.catalog.database_names()
        if stmt.like:
            from greptimedb_tpu.query.expr import like_to_regex

            rx = like_to_regex(stmt.like)
            names = [n for n in names if rx.fullmatch(n)]
        return _result_from_lists(["Database"], [names])

    def _show_tables(self, stmt: A.ShowTables, ctx: QueryContext
                     ) -> QueryResult:
        db = stmt.database or ctx.database
        names = self.catalog.table_names(db)
        if stmt.like:
            from greptimedb_tpu.query.expr import like_to_regex

            rx = like_to_regex(stmt.like)
            names = [n for n in names if rx.fullmatch(n)]
        return _result_from_lists(["Tables"], [names])

    def _describe(self, stmt: A.DescribeTable, ctx: QueryContext
                  ) -> QueryResult:
        db, name = self._resolve(stmt.name, ctx)
        table = self.catalog.table(db, name)
        names, types, keys, nulls, defaults, semantics = [], [], [], [], [], []
        for c in table.schema.columns:
            names.append(c.name)
            types.append(_sql_type_name(c.data_type))
            keys.append("PRI" if c.is_tag or c.is_time_index else "")
            nulls.append("YES" if c.nullable else "NO")
            defaults.append(default_display(c.default))
            semantics.append(
                "TIMESTAMP" if c.is_time_index
                else ("TAG" if c.is_tag else "FIELD")
            )
        return _result_from_lists(
            ["Column", "Type", "Key", "Null", "Default", "Semantic Type"],
            [names, types, keys, nulls, defaults, semantics],
        )

    def _show_create_table(self, stmt: A.ShowCreateTable, ctx: QueryContext
                           ) -> QueryResult:
        db, name = self._resolve(stmt.name, ctx)
        table = self.catalog.table(db, name)
        lines = [f"CREATE TABLE IF NOT EXISTS `{name}` ("]
        defs = []
        for c in table.schema.columns:
            d = f"  `{c.name}` {_sql_type_name(c.data_type)}"
            if not c.nullable:
                d += " NOT NULL"
            dflt = default_sql(c.default)
            if dflt is not None:
                d += f" DEFAULT {dflt}"
            defs.append(d)
        ts = table.schema.time_index.name
        defs.append(f"  TIME INDEX (`{ts}`)")
        if table.tag_names:
            defs.append(
                "  PRIMARY KEY (" +
                ", ".join(f"`{t}`" for t in table.tag_names) + ")"
            )
        lines.append(",\n".join(defs))
        lines.append(")")
        part = getattr(table.info, "partition", None)
        if part:
            cols_txt = ", ".join(f"`{c}`" for c in part["columns"])
            lines.append(
                f"PARTITION ON COLUMNS ({cols_txt}) ("
                + ", ".join(part["exprs"]) + ")"
            )
        lines.append(f"ENGINE={table.info.engine}")
        if table.info.options:
            opts = ", ".join(
                f"{k!r}={v!r}" for k, v in table.info.options.items()
            )
            lines.append(f"WITH({opts})")
        return _result_from_lists(
            ["Table", "Create Table"], [[name], ["\n".join(lines)]]
        )

    # ------------------------------------------------------------------
    # information_schema
    # ------------------------------------------------------------------
    def _is_information_schema(self, name: str, ctx: QueryContext) -> bool:
        if "." in name:
            return name.split(".", 1)[0].lower() == "information_schema"
        return ctx.database.lower() == "information_schema"

    def _is_pg_catalog(self, name: str, ctx: QueryContext) -> bool:
        """pg_catalog shims for psql/ORM introspection (reference:
        src/catalog/src/system_schema/pg_catalog/). Bare names resolve
        here only when no user table shadows them."""
        from greptimedb_tpu.information_schema import PG_CATALOG_TABLES

        if "." in name:
            return name.split(".", 1)[0].lower() == "pg_catalog"
        low = name.lower()
        if low not in PG_CATALOG_TABLES:
            return False
        try:
            db, tname = self._resolve(name, ctx)
            return self.catalog.maybe_table(db, tname) is None
        except Exception:  # noqa: BLE001 - unresolvable db: serve shim
            return True

    def _query_information_schema(self, stmt: A.Select, ctx: QueryContext
                                  ) -> QueryResult:
        from greptimedb_tpu.information_schema import query_information_schema

        return query_information_schema(self, stmt, ctx)

    # ------------------------------------------------------------------
    # flows (wired by flow.FlowManager; stubs raise otherwise)
    # ------------------------------------------------------------------
    def enable_flows(self, *, tick_interval_s: float | None = None):
        if self.flows is None:
            try:
                from greptimedb_tpu.flow import FlowManager
            except ImportError as e:
                raise UnsupportedError(
                    f"flows require the flow module: {e}"
                )
            self.flows = FlowManager(self, tick_interval_s=tick_interval_s)
        elif tick_interval_s is not None:
            # retarget the running ticker; takes effect at its next wait
            self.flows.tick_interval_s = tick_interval_s
        return self.flows

    def _flush_flow_admin(self, fname: str) -> bool:
        """ADMIN flush_flow on the local flow manager; DistInstance
        overrides to forward to the routed flownode."""
        if self.flows is None:
            raise UnsupportedError("flows are not enabled")
        return self.flows.flush_flow(fname)

    def _create_flow(self, stmt: A.CreateFlow, ctx: QueryContext) -> Output:
        self.enable_flows()
        self.flows.create_flow(stmt, ctx)
        return Output.rows(0)

    def _drop_flow(self, stmt: A.DropFlow, ctx: QueryContext) -> Output:
        self.enable_flows()
        self.flows.drop_flow(stmt.name, if_exists=stmt.if_exists)
        return Output.rows(0)

    def _show_flows(self) -> QueryResult:
        if self.flows is None:
            return _result_from_lists(["Flows"], [[]])
        return _result_from_lists(["Flows"], [self.flows.flow_names()])

    # ------------------------------------------------------------------
    def _region_by_id(self, rid: int):
        """Region handle for ADMIN by-id calls: the local engine's region
        in standalone; on a distributed frontend (which owns no storage)
        the catalog's remote-region proxy for that id."""
        from greptimedb_tpu.errors import RegionNotFoundError

        try:
            return self.engine.region(rid)
        except RegionNotFoundError:
            for db in self.catalog.database_names():
                for tname in self.catalog.table_names(db):
                    table = self.catalog.maybe_table(db, tname)
                    for region in (table.regions if table else []):
                        if region.meta.region_id == rid:
                            return region
            raise

    def _resolve(self, name: str, ctx: QueryContext) -> tuple[str, str]:
        if "." in name:
            db, t = name.split(".", 1)
            return db, t
        return ctx.database, name


def format_sql_literal(v) -> str:
    """Python value -> SQL literal text (prepared-statement binding).
    Backslashes are escaped because the lexer treats \\x as an escape
    inside strings — an unescaped trailing backslash would swallow the
    closing quote (injection risk on the wire paths)."""
    if v is None:
        return "NULL"
    if isinstance(v, bool):
        return "TRUE" if v else "FALSE"
    if isinstance(v, (int, np.integer)):
        return str(int(v))
    if isinstance(v, (float, np.floating)):
        return repr(float(v))
    s = str(v).replace("\\", "\\\\").replace("'", "''")
    return f"'{s}'"


def _scan_sql_segments(text: str):
    """Yields ('text'|'quoted'|'qmark'|'dollar', segment) pieces; the ONE
    quoting state machine shared by placeholder substitution and the
    MySQL COM_STMT_PREPARE parameter counter."""
    import re as _re

    i, n = 0, len(text)
    while i < n:
        c = text[i]
        if c == "-" and text[i:i + 2] == "--":
            j = text.find("\n", i)
            j = n if j < 0 else j
            yield "text", text[i:j]
            i = j
            continue
        if c == "/" and text[i:i + 2] == "/*":
            j = text.find("*/", i + 2)
            j = n if j < 0 else j + 2
            yield "text", text[i:j]
            i = j
            continue
        if c in ("'", '"', "`"):
            close = c
            j = i + 1
            while j < n:
                if text[j] == close and j + 1 < n and text[j + 1] == close:
                    j += 2
                elif text[j] == "\\" and close == "'" and j + 1 < n:
                    j += 2
                elif text[j] == close:
                    break
                else:
                    j += 1
            yield "quoted", text[i:j + 1]
            i = j + 1
            continue
        if c == "?":
            yield "qmark", "?"
            i += 1
            continue
        if c == "$":
            m = _re.match(r"\$(\d+)", text[i:])
            if m:
                yield "dollar", m.group(1)
                i += m.end()
                continue
        yield "text", c
        i += 1


def count_placeholders(text: str) -> int:
    """`?` placeholders outside string/quoted-identifier regions."""
    return sum(1 for kind, _ in _scan_sql_segments(text) if kind == "qmark")


def substitute_placeholders(text: str, args: list) -> str:
    """Replace ? (positional) and $n placeholders outside string/quoted
    regions with literal-formatted args (PREPARE/EXECUTE binding — the
    reference binds through sqlparser placeholders; this engine binds at
    the text layer before parsing)."""
    out = []
    pos = 0  # next ? index
    for kind, seg in _scan_sql_segments(text):
        if kind == "qmark":
            if pos >= len(args):
                raise InvalidArgumentError(
                    f"not enough parameters: need > {pos}, have {len(args)}"
                )
            out.append(format_sql_literal(args[pos]))
            pos += 1
        elif kind == "dollar":
            k = int(seg)
            if not (1 <= k <= len(args)):
                raise InvalidArgumentError(
                    f"parameter ${k} out of range (have {len(args)})"
                )
            out.append(format_sql_literal(args[k - 1]))
        else:
            out.append(seg)
    return "".join(out)


def _const_default(default):
    """Normalize a DDL DEFAULT for catalog persistence: pure-literal
    expressions fold to plain values; expressions with function calls
    (now(), current_timestamp()...) persist as {"__expr__": text} and
    re-evaluate on EVERY insert — folding them would freeze the
    table-creation time into all future rows."""
    if not isinstance(default, A.Expr):
        return default

    def has_call(e) -> bool:
        if isinstance(e, A.FuncCall):
            return True
        if isinstance(e, A.BinaryOp):
            return has_call(e.left) or has_call(e.right)
        if isinstance(e, (A.UnaryOp, A.Cast, A.IsNull)):
            return has_call(e.operand)
        if isinstance(e, A.Between):
            return any(has_call(x) for x in (e.operand, e.low, e.high))
        if isinstance(e, A.InList):
            return has_call(e.operand) or any(has_call(x) for x in e.items)
        if isinstance(e, A.Case):
            parts = ([e.operand] if e.operand else []) \
                + [x for w in e.whens for x in w] \
                + ([e.else_] if e.else_ else [])
            return any(has_call(x) for x in parts)
        return False

    if has_call(default):
        return {"__expr__": _default_expr_sql(default)}
    return eval_const(default)


def _default_expr_sql(e: A.Expr) -> str:
    """Serialize a DEFAULT expression for round-trip re-parsing.
    Unlike format_expr (display names), every compound operand is
    parenthesized so precedence survives the round trip exactly."""
    if isinstance(e, A.BinaryOp):
        return (f"({_default_expr_sql(e.left)}) {e.op} "
                f"({_default_expr_sql(e.right)})")
    if isinstance(e, A.UnaryOp):
        return f"{e.op} ({_default_expr_sql(e.operand)})"
    if isinstance(e, A.Cast):
        return f"CAST(({_default_expr_sql(e.operand)}) AS {e.to.name})"
    if isinstance(e, A.FuncCall):
        args = ", ".join(f"({_default_expr_sql(a)})" for a in e.args)
        return f"{e.name}({args})"
    if isinstance(e, A.Case):
        parts = ["CASE"]
        if e.operand is not None:
            parts.append(f"({_default_expr_sql(e.operand)})")
        for c, t in e.whens:
            parts.append(f"WHEN ({_default_expr_sql(c)}) "
                         f"THEN ({_default_expr_sql(t)})")
        if e.else_ is not None:
            parts.append(f"ELSE ({_default_expr_sql(e.else_)})")
        parts.append("END")
        return " ".join(parts)
    if isinstance(e, A.IsNull):
        neg = " NOT" if e.negated else ""
        return f"({_default_expr_sql(e.operand)}) IS{neg} NULL"
    if isinstance(e, A.Between):
        neg = "NOT " if e.negated else ""
        return (f"({_default_expr_sql(e.operand)}) {neg}BETWEEN "
                f"({_default_expr_sql(e.low)}) AND "
                f"({_default_expr_sql(e.high)})")
    if isinstance(e, A.InList):
        neg = "NOT " if e.negated else ""
        items = ", ".join(f"({_default_expr_sql(x)})" for x in e.items)
        return f"({_default_expr_sql(e.operand)}) {neg}IN ({items})"
    from greptimedb_tpu.query.expr import format_expr

    return format_expr(e)


def default_display(default) -> str:
    """Human form of a stored default (SHOW/DESCRIBE)."""
    if default is None:
        return ""
    if isinstance(default, dict) and "__expr__" in default:
        return default["__expr__"]
    return str(default)


def default_sql(default) -> str | None:
    """DDL form of a stored default, exact enough that SHOW CREATE TABLE
    output re-parses to the same constraint (export->import must not
    drop defaults). String literals re-quote; dynamic defaults emit
    their expression text verbatim; None means no DEFAULT clause."""
    if default is None:
        return None
    if isinstance(default, dict) and "__expr__" in default:
        return default["__expr__"]
    return format_sql_literal(default)


import functools


@functools.lru_cache(maxsize=512)
def _parse_default_expr(text: str) -> A.Expr:
    # stored default text is immutable; parsing once keeps the hot
    # single-row insert path off the SQL tokenizer
    from greptimedb_tpu.sql.parser import Parser

    return Parser(text).expr()


def _eval_default(default):
    """Stored default -> concrete value for this insert."""
    if isinstance(default, dict) and "__expr__" in default:
        return eval_const(_parse_default_expr(default["__expr__"]))
    if isinstance(default, A.Expr):
        return eval_const(default)
    return default


def _apply_defaults(schema, data: dict, valid: dict, n: int):
    """Declared DEFAULTs fill columns omitted from an INSERT (explicit
    NULLs stay NULL — standard SQL, ref src/datatypes/src/schema/
    column_schema.rs default constraints). The time index participates
    too (TIMESTAMP TIME INDEX DEFAULT current_timestamp())."""
    for cs in schema.columns:
        if cs.name in data or cs.default is None:
            continue
        arr, v = _coerce_insert([_eval_default(cs.default)] * n,
                                cs.data_type)
        data[cs.name] = arr
        valid[cs.name] = v


def _coerce_insert(vals: list, dt: ConcreteDataType):
    n = len(vals)
    validity = np.asarray([v is not None for v in vals], bool)
    if dt.is_timestamp():
        out = np.zeros(n, np.int64)
        for i, v in enumerate(vals):
            if v is None:
                continue
            out[i] = parse_ts_literal(v) if isinstance(v, str) else int(v)
        return out, validity
    if dt.is_string():
        return (
            np.asarray(["" if v is None else str(v) for v in vals], object),
            validity,
        )
    if dt.is_decimal():
        out = np.zeros(n, np.float64)
        for i, v in enumerate(vals):
            if v is not None:
                out[i] = float(v)
        return out, validity
    np_t = dt.to_numpy()
    out = np.zeros(n, np_t)
    for i, v in enumerate(vals):
        if v is None:
            continue
        out[i] = v
    return out, validity


def _sql_type_name(dt: ConcreteDataType) -> str:
    names = {
        "int8": "TINYINT", "int16": "SMALLINT", "int32": "INT",
        "int64": "BIGINT", "uint8": "TINYINT UNSIGNED",
        "uint16": "SMALLINT UNSIGNED", "uint32": "INT UNSIGNED",
        "uint64": "BIGINT UNSIGNED", "float32": "FLOAT", "float64": "DOUBLE",
        "string": "STRING", "binary": "VARBINARY", "bool": "BOOLEAN",
        "timestamp_s": "TIMESTAMP(0)", "timestamp_ms": "TIMESTAMP(3)",
        "timestamp_us": "TIMESTAMP(6)", "timestamp_ns": "TIMESTAMP(9)",
        "date": "DATE", "json": "JSON",
    }
    return names.get(dt.name, dt.name.upper())


def _write_format(pa_table, path: str, fmt: str) -> int:
    import pyarrow as pa

    if fmt == "parquet":
        import pyarrow.parquet as pq

        pq.write_table(pa_table, path)
    elif fmt == "csv":
        import pyarrow.csv as pacsv

        pacsv.write_csv(pa_table, path)
    elif fmt == "json":
        import json as _json

        rows = pa_table.to_pylist()
        with open(path, "w") as f:
            for r in rows:
                f.write(_json.dumps(r, default=str) + "\n")
    else:
        raise UnsupportedError(f"COPY format {fmt}")
    return pa_table.num_rows


def _read_format(path: str, fmt: str):
    if fmt == "parquet":
        import pyarrow.parquet as pq

        return pq.read_table(path)
    if fmt == "csv":
        import pyarrow.csv as pacsv

        return pacsv.read_csv(path)
    if fmt == "json":
        import pyarrow.json as pajson

        return pajson.read_json(path)
    raise UnsupportedError(f"COPY format {fmt}")


def _tql_time(e: A.Expr) -> int:
    v = eval_const(e)
    if isinstance(v, str):
        try:
            return int(float(v) * 1000)
        except ValueError:
            return parse_ts_literal(v)
    return int(float(v) * 1000)


def _tql_interval(e: A.Expr) -> int:
    if isinstance(e, A.IntervalLit):
        return e.ms
    v = eval_const(e)
    if isinstance(v, str):
        from greptimedb_tpu.sql.parser import parse_interval_ms

        return parse_interval_ms(v)
    return int(float(v) * 1000)
