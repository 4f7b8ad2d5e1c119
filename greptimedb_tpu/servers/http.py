"""HTTP protocol server.

Capability counterpart of /root/reference/src/servers/src/http/ (axum app):
- POST /v1/sql                         SQL in GreptimeDB JSON envelope
- POST /v1/promql, GET/POST /v1/prometheus/api/v1/{query,query_range,
  labels,label/<n>/values,series}      Prometheus HTTP API
- POST /v1/influxdb/write, /v1/influxdb/api/v2/write   line protocol
- POST /v1/prometheus/write|read      remote write/read (snappy protobuf)
- GET  /metrics                        self metrics exposition
- GET  /health, /status                liveness + build info

Stdlib ThreadingHTTPServer: the host plane is IO-bound glue; the device
does the math.
"""

from __future__ import annotations

import gzip
import json
import math
import threading

import time
import traceback
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from greptimedb_tpu.errors import GreptimeError
from greptimedb_tpu.promql.engine import (
    PromEngine,
    ScalarValue,
    VectorValue,
)
from greptimedb_tpu.servers import influx, prom_store
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.telemetry import global_registry, tracing
from greptimedb_tpu.version import __version__

from greptimedb_tpu import concurrency

_REQS = global_registry.counter(
    "greptime_servers_http_requests_total", "HTTP requests", ("path", "code")
)
_LATENCY = global_registry.histogram(
    "greptime_servers_http_latency_seconds", "HTTP latency", ("path",)
)
_INGEST_ROWS = global_registry.counter(
    "greptime_servers_ingest_rows_total", "Rows ingested", ("api",)
)
# what an answer weighs on its way out: a one-host panel is 60 rows and
# a few KB, a fleet report tens of thousands of rows and MBs of JSON text
_RESPONSE_BYTES = global_registry.counter(
    "gtpu_http_response_bytes_total",
    "HTTP response body bytes written, by route", ("path",)
)
_ROWS_RETURNED = global_registry.counter(
    "gtpu_query_rows_returned_total",
    "result rows rendered into HTTP answers, by route", ("path",)
)


def _type_name(tn: str) -> str:
    names = {
        "int8": "Int8", "int16": "Int16", "int32": "Int32", "int64": "Int64",
        "uint8": "UInt8", "uint16": "UInt16", "uint32": "UInt32",
        "uint64": "UInt64", "float32": "Float32", "float64": "Float64",
        "string": "String", "bool": "Boolean", "binary": "Binary",
        "timestamp_s": "TimestampSecond",
        "timestamp_ms": "TimestampMillisecond",
        "timestamp_us": "TimestampMicrosecond",
        "timestamp_ns": "TimestampNanosecond",
        "date": "Date", "json": "Json",
    }
    if tn.startswith("decimal("):
        return "Decimal128" + tn[len("decimal"):]
    return names.get(tn, tn)


def result_to_json(res, route: str) -> dict:
    schema = {
        "column_schemas": [
            {"name": n, "data_type": _type_name(res.type_name(i))}
            for i, n in enumerate(res.names)
        ]
    }
    # a Python list a row, a Python object a value
    with tracing.child_span("result.rows"):
        rows = res.rows()
    _ROWS_RETURNED.labels(route).inc(res.num_rows)
    return {"records": {"schema": schema, "rows": rows,
                        "total_rows": res.num_rows}}


class HttpServer:
    def __init__(self, instance, *, addr: str = "127.0.0.1", port: int = 4000,
                 user_provider=None, enable_scripts: bool = False,
                 tls_cert: str | None = None, tls_key: str | None = None,
                 influxdb_enable: bool = True,
                 opentsdb_enable: bool = True):
        self.instance = instance
        self.addr = addr
        self.port = port
        self.user_provider = user_provider
        # TLS (reference: src/servers/src/tls.rs TlsOption) — serve
        # https when a certificate chain + key are configured
        self.tls_cert = tls_cert
        self.tls_key = tls_key
        # scripts compile arbitrary Python with exec() in the server
        # process (the reference isolates coprocessors in an embedded
        # RustPython VM, src/script/src/python/engine.rs:345). Off by
        # default; enabling requires an authenticating user provider.
        if enable_scripts and user_provider is None:
            raise ValueError("enable_scripts requires a user_provider")
        self.enable_scripts = enable_scripts
        # [influxdb]/[opentsdb] enable knobs: line-protocol ingestion
        # endpoints can be switched off per node
        self.influxdb_enable = influxdb_enable
        self.opentsdb_enable = opentsdb_enable
        self._httpd: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------------------
    def start(self):
        handler = _make_handler(self.instance, self.user_provider,
                                enable_scripts=self.enable_scripts,
                                influxdb_enable=self.influxdb_enable,
                                opentsdb_enable=self.opentsdb_enable)
        if self.tls_cert:
            import ssl

            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(self.tls_cert, self.tls_key)

            class _TlsHTTPServer(ThreadingHTTPServer):
                """Handshake runs per-connection in the handler thread
                (wrapping the listener would serialize all connection
                setup through the accept loop and let one stalled client
                block it indefinitely)."""

                def get_request(self):
                    sock, addr = self.socket.accept()
                    sock.settimeout(10.0)  # bound the TLS handshake
                    tls_sock = ctx.wrap_socket(
                        sock, server_side=True,
                        do_handshake_on_connect=False,
                    )
                    return tls_sock, addr

                def finish_request(self, request, client_address):
                    try:
                        request.do_handshake()
                    except (ssl.SSLError, OSError):
                        # plain-HTTP probes / port scans / stalled
                        # handshakes: close quietly instead of dumping a
                        # traceback per connection
                        try:
                            request.close()
                        except OSError:
                            pass
                        return
                    request.settimeout(None)
                    super().finish_request(request, client_address)

            self._httpd = _TlsHTTPServer((self.addr, self.port), handler)
        else:
            self._httpd = ThreadingHTTPServer((self.addr, self.port), handler)
        self.port = self._httpd.server_address[1]
        self._thread = concurrency.Thread(
            target=self._httpd.serve_forever, daemon=True, name="http-server"
        )
        self._thread.start()
        return self

    def stop(self):
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)


def _make_handler(instance, user_provider=None, *, enable_scripts=False,
                  influxdb_enable=True, opentsdb_enable=True):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        # silence default stderr logging
        def log_message(self, *args):
            pass

        # ------------------------------------------------------------------
        def _send(self, code: int, body: bytes,
                  content_type: str = "application/json"):
            with tracing.child_span("http.send"):
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            route = self._route()
            _REQS.labels(route, str(code)).inc()
            _RESPONSE_BYTES.labels(route).inc(len(body))

        _KNOWN_ROUTES = (
            "/health", "/ready", "/status", "/metrics", "/v1/sql",
            "/v1/promql", "/v1/prometheus/api/v1/", "/v1/prometheus/write",
            "/v1/prometheus/read", "/v1/influxdb/", "/influxdb/",
            "/v1/events", "/v1/opentsdb/api/put", "/api/put",
            "/v1/otlp/v1/metrics", "/v1/traces", "/v1/traces/",
            "/v1/stats/statements",
            "/v1/cluster/metrics", "/v1/cluster/health",
            "/debug/prof/cpu", "/debug/prof/mem", "/debug/prof/hbm",
            "/debug/prof/device", "/debug/prof/device/trace",
        )

        def _raw_path(self) -> str:
            return urllib.parse.urlparse(self.path).path

        def _route(self) -> str:
            """Metric-label-safe route: unknown paths collapse to 'other'
            (unbounded label cardinality would leak memory per 404)."""
            path = self._raw_path()
            for r in self._KNOWN_ROUTES:
                if path == r:
                    return path
                if r.endswith("/") and path.startswith(r):
                    return r + "*"
            return "other"

        def _json(self, code: int, obj):
            self._send(code, json.dumps(obj).encode())

        def _error(self, code: int, msg: str):
            self._json(code, {"error": msg, "code": code})

        def _body(self) -> bytes:
            ln = int(self.headers.get("Content-Length") or 0)
            data = self.rfile.read(ln) if ln else b""
            if self.headers.get("Content-Encoding") == "gzip":
                data = gzip.decompress(data)
            return data

        def _params(self) -> dict:
            q = urllib.parse.urlparse(self.path).query
            return self._merge_qs({}, urllib.parse.parse_qs(q))

        @staticmethod
        def _merge_qs(params: dict, parsed: dict) -> dict:
            # repeatable keys (match[]) keep ALL values as a list
            for k, v in parsed.items():
                if k.endswith("[]"):
                    params.setdefault(k, [])
                    params[k] = list(params[k]) + v
                else:
                    params[k] = v[-1]
            return params

        def _form(self) -> dict:
            body = self._body()
            ctype = self.headers.get("Content-Type", "")
            params = self._params()
            if "application/x-www-form-urlencoded" in ctype:
                self._merge_qs(params, urllib.parse.parse_qs(body.decode()))
            elif body and "json" in ctype:
                try:
                    params.update(json.loads(body))
                except json.JSONDecodeError:
                    pass
            return params

        # ------------------------------------------------------------------
        def do_GET(self):
            self._dispatch("GET")

        def do_POST(self):
            self._dispatch("POST")

        _UNTRACED = ("/health", "/ready", "/-/healthy", "/-/ready",
                     "/metrics", "/v1/traces", "/v1/stats/statements",
                     "/v1/cluster/metrics", "/v1/cluster/health")

        def _dispatch(self, method: str):
            path = self._raw_path()
            t0 = time.perf_counter()
            if path in self._UNTRACED or path.startswith("/v1/traces/"):
                # probe/scrape noise would churn real query traces out
                # of the bounded ring
                return self._dispatch_traced(method, path, t0)
            with tracing.start_remote(
                self.headers.get("traceparent"),
                f"http {self._route()}", method=method,
            ):
                self._dispatch_traced(method, path, t0)

        def _dispatch_traced(self, method: str, path: str, t0: float):
            try:
                if user_provider is not None and path not in (
                    "/health", "/ready", "/-/healthy", "/-/ready",
                ):
                    from greptimedb_tpu.auth import (
                        AccessDeniedError,
                        check_basic_auth,
                    )

                    try:
                        # stashed for the route handlers: /v1/sql tags
                        # the statement's tenant (admission + statement
                        # statistics) without re-validating credentials
                        self._auth_user = check_basic_auth(
                            self.headers.get("Authorization"),
                            user_provider,
                        ) or ""
                    except AccessDeniedError as e:
                        body = json.dumps(
                            {"error": str(e), "code": 401}
                        ).encode()
                        self.send_response(401)
                        self.send_header(
                            "WWW-Authenticate", 'Basic realm="greptime"'
                        )
                        self.send_header(
                            "Content-Type", "application/json"
                        )
                        self.send_header(
                            "Content-Length", str(len(body))
                        )
                        self.end_headers()
                        self.wfile.write(body)
                        _REQS.labels(self._route(), "401").inc()
                        return
                self._route_request(method, path)
            except GreptimeError as e:
                from greptimedb_tpu.errors import StatusCode

                # backpressure sheds with 429 (over-quota tenant /
                # ingest queues full: client backs off + retries); a
                # saturated queue-time SLO, an expired deadline, or an
                # unreachable storage layer is the server's state: 503
                http_code = {
                    StatusCode.RATE_LIMITED: 429,
                    StatusCode.QUERY_OVERLOADED: 429,
                    StatusCode.QUERY_QUEUE_TIMEOUT: 503,
                    StatusCode.DEADLINE_EXCEEDED: 503,
                    StatusCode.STORAGE_UNAVAILABLE: 503,
                }.get(e.status_code, 400)
                self._error(http_code, str(e))
            except BrokenPipeError:
                pass
            except Exception as e:
                traceback.print_exc()
                self._error(500, f"internal error: {e}")
            finally:
                _LATENCY.labels(self._route()).observe(
                    time.perf_counter() - t0
                )

        def _route_request(self, method: str, path: str):
            if path in ("/health", "/ready", "/-/healthy", "/-/ready"):
                params = self._params()
                if params.get("deep") not in (None, "", "0", "false"):
                    # real per-role readiness (telemetry/node_stats.py):
                    # engine open, data dir appendable, object store
                    # reachable, device dispatch OK, metasrv heartbeat
                    # fresh — 503 when degraded so probes can act on it
                    from greptimedb_tpu.telemetry import (
                        node_stats as _ns,
                    )

                    doc = _ns.deep_health(instance)
                    return self._json(
                        200 if doc["status"] == "ok" else 503, doc
                    )
                return self._json(200, {})
            if path == "/v1/cluster/metrics":
                # federated scrape: every node's gtpu_*/greptime_*
                # families re-labeled with node/role, TTL-cached so
                # scrapes cannot stampede the fleet (dist/fleet.py)
                from greptimedb_tpu.dist import fleet

                return self._send(
                    200, fleet.federated_metrics(instance).encode(),
                    "text/plain; version=0.0.4",
                )
            if path == "/v1/cluster/health":
                from greptimedb_tpu.dist import fleet

                doc = fleet.federated_health(instance)
                return self._json(
                    200 if doc["status"] == "ok" else 503, doc
                )
            if path == "/status":
                return self._json(200, {
                    "source_time": "", "commit": "", "branch": "",
                    "rustc_version": "n/a (python/jax)",
                    "hostname": "localhost", "version": __version__,
                })
            if path == "/metrics":
                return self._send(
                    200, global_registry.render().encode(),
                    "text/plain; version=0.0.4",
                )
            if path == "/v1/traces" or path.startswith("/v1/traces/"):
                from greptimedb_tpu.telemetry.tracing import global_traces

                params = self._params()
                tid = params.get("trace_id")
                if path.startswith("/v1/traces/"):
                    tid = path.rsplit("/", 1)[-1].split("?", 1)[0]
                if tid:
                    # ?trace_id= filtering: exactly one stitched trace
                    return self._json(200, {
                        "trace_id": tid,
                        "spans": global_traces.trace(tid),
                    })
                if params.get("slowest") in ("1", "true"):
                    # the slowest finished trace of each route (and of
                    # each slow background task), held beside the ring;
                    # &reset=1 empties the slots once read
                    return self._json(200, {"slowest": global_traces.slowest(
                        reset=params.get("reset") in ("1", "true"))})
                try:
                    limit = int(params.get("limit", "50") or 50)
                except ValueError:
                    return self._error(400, "bad limit")
                return self._json(
                    200, {"traces": global_traces.traces(limit)}
                )
            if path == "/v1/stats/statements":
                # the aggregate statement-statistics registry
                # (telemetry/stmt_stats.py), ordered + bounded:
                # ?order_by=calls|total_ms|p99_ms|...&limit=N
                from greptimedb_tpu.telemetry.stmt_stats import (
                    global_stmt_stats,
                )

                params = self._params()
                try:
                    limit = int(params.get("limit", "0") or 0)
                except ValueError:
                    return self._error(400, "bad limit")
                if limit < 0:
                    return self._error(400, "bad limit")
                return self._json(200, {
                    "statements": global_stmt_stats.snapshot(
                        order_by=params.get("order_by", "total_ms"),
                        limit=limit,
                    ),
                })
            if path == "/debug/prof/cpu":
                # sampling CPU profile of the whole process (pprof
                # analog, src/servers/src/http/pprof.rs)
                from greptimedb_tpu.telemetry import pprof

                params = self._params()
                try:
                    seconds = float(params.get("seconds", "1"))
                except ValueError:
                    return self._error(400, "bad seconds")
                stacks = pprof.sample_cpu(seconds)
                fmt = params.get("format", "text")
                if fmt == "collapsed":
                    body = pprof.render_collapsed(stacks)
                elif fmt == "speedscope":
                    return self._send(
                        200,
                        pprof.render_speedscope(stacks).encode(),
                        "application/json",
                    )
                else:
                    body = pprof.render_report(stacks)
                return self._send(200, body.encode(), "text/plain")
            if path == "/debug/prof/mem":
                from greptimedb_tpu.telemetry import pprof

                params = self._params()
                try:
                    top = int(params.get("top", "30"))
                except ValueError:
                    return self._error(400, "bad top")
                diff = params.get("diff", "0") not in ("0", "", "false")
                return self._send(
                    200, pprof.mem_profile(top, diff=diff).encode(),
                    "text/plain",
                )
            if path == "/debug/prof/hbm":
                # unified memory observability (telemetry/memory.py):
                # per-pool bytes, top-N live device buffers with owner
                # attribution, and the unaccounted leak residue
                from greptimedb_tpu.telemetry import memory as _memory

                params = self._params()
                try:
                    top = int(params.get("top", "10"))
                except ValueError:
                    return self._error(400, "bad top")
                doc = _memory.hbm_report(top=top)
                if params.get("format", "text") == "json":
                    return self._json(200, doc)
                return self._send(
                    200, _memory.render_hbm_text(doc).encode(),
                    "text/plain",
                )
            if path == "/debug/prof/device":
                # the device-program profiler
                # (telemetry/device_programs.py): per-program calls /
                # compile / execute percentiles, XLA cost analysis and
                # the roofline verdict, top-N by cumulative device time
                from greptimedb_tpu.telemetry import (
                    device_programs as _dp,
                )

                params = self._params()
                try:
                    top = int(params.get("top", "20"))
                except ValueError:
                    return self._error(400, "bad top")
                doc = _dp.global_programs.report(top=top)
                if params.get("format", "text") == "json":
                    return self._json(200, doc)
                return self._send(
                    200, _dp.render_text(doc).encode(), "text/plain"
                )
            if path == "/debug/prof/device/trace":
                # on-demand device trace capture via jax.profiler:
                # blocks for ?seconds= and returns the TensorBoard/
                # perfetto-loadable trace directory it wrote
                from greptimedb_tpu.telemetry import (
                    device_programs as _dp,
                )

                params = self._params()
                try:
                    seconds = float(params.get("seconds", "1"))
                except ValueError:
                    return self._error(400, "bad seconds")
                if not (0.0 < seconds <= 60.0):
                    return self._error(
                        400, "seconds must be in (0, 60]"
                    )
                try:
                    doc = _dp.capture_trace(
                        seconds, params.get("dir") or None
                    )
                except _dp.CaptureBusyError as e:
                    return self._error(409, str(e))
                return self._json(200, doc)
            if path == "/v1/sql":
                return self._handle_sql()
            if path == "/v1/promql":
                with tracing.child_span("http.read"):
                    params = self._form()
                return self._handle_promql_range(params)
            _local_only = (
                path.startswith("/v1/prometheus/")
                or path.startswith(("/v1/influxdb/", "/influxdb/"))
                or path in ("/v1/opentsdb/api/put", "/opentsdb/api/put",
                            "/api/put")
                or path.startswith("/v1/otlp/")
            )
            if _local_only and not hasattr(instance, "_write_columns"):
                # frontend-role (remote) instances forward SQL only; the
                # columnar ingest/PromQL surfaces need engine access
                return self._error(
                    501, "not available on a frontend role process; "
                         "send to a datanode or standalone"
                )
            if path.startswith("/v1/prometheus/api/v1/"):
                return self._handle_prom_api(
                    path.removeprefix("/v1/prometheus/api/v1/")
                )
            if path == "/v1/prometheus/write":
                return self._handle_remote_write()
            if path == "/v1/prometheus/read":
                return self._handle_remote_read()
            if path in ("/v1/influxdb/write", "/v1/influxdb/api/v2/write",
                        "/influxdb/write"):
                if not influxdb_enable:
                    return self._send(
                        404, b'{"error":"influxdb protocol disabled"}')
                return self._handle_influx_write()
            if path in ("/v1/opentsdb/api/put", "/opentsdb/api/put",
                        "/api/put"):
                if not opentsdb_enable:
                    return self._send(
                        404, b'{"error":"opentsdb protocol disabled"}')
                return self._handle_opentsdb_put()
            if path == "/v1/otlp/v1/metrics":
                return self._handle_otlp_metrics()
            if path in ("/v1/otlp/v1/traces", "/v1/otlp/v1/logs"):
                return self._handle_otlp_records(path.rsplit("/", 1)[-1])
            if path == "/v1/events/pipelines" or path.startswith(
                "/v1/events"
            ):
                return self._handle_events(method, path)
            if path == "/v1/scripts":
                if not enable_scripts:
                    return self._json(403, {"error": "scripts disabled"})
                return self._handle_scripts()
            if path == "/v1/run-script":
                if not enable_scripts:
                    return self._json(403, {"error": "scripts disabled"})
                return self._handle_run_script()
            self._error(404, f"no route: {path}")

        _engine_lock = concurrency.Lock()

        def _script_engine(self):
            eng = getattr(instance, "_py_engine", None)
            if eng is None:
                with self._engine_lock:
                    eng = getattr(instance, "_py_engine", None)
                    if eng is None:
                        from greptimedb_tpu.script import PyEngine

                        eng = PyEngine(instance)
                        instance._py_engine = eng
            return eng

        def _handle_scripts(self):
            params = self._params()
            name = params.get("name")
            if not name:
                return self._error(400, "missing name parameter")
            source = self._body().decode()
            self._script_engine().insert_script(name, source)
            self._json(200, {"name": name, "status": "compiled"})

        def _handle_run_script(self):
            params = self._params()
            name = params.get("name")
            if not name:
                return self._error(400, "missing name parameter")
            res = self._script_engine().run_script(name)
            self._json(200, {"output": [result_to_json(res, self._route())]})

        # ------------------------------------------------------------------
        def _handle_sql(self):
            with tracing.child_span("http.read"):
                params = self._form()
            sql = params.get("sql")
            if not sql:
                return self._error(400, "missing sql parameter")
            db = params.get("db", "public")
            fmt = params.get("format", "greptimedb_v1").lower()
            if fmt not in ("csv", "table", "greptimedb_v1"):
                return self._error(400, f"unknown format {fmt!r}")
            ctx = QueryContext(database=db)
            # the dispatch gate validated the Authorization header and
            # stashed the user: the tenant on admission + statement-
            # statistics rows, with no second credential check
            ctx.username = getattr(self, "_auth_user", "")
            # per-request deadline: ?timeout=<seconds> or the
            # X-Greptime-Timeout header override the [scheduler]
            # default; the admission controller binds it end to end
            timeout = (params.get("timeout")
                       or self.headers.get("X-Greptime-Timeout"))
            if timeout is not None:
                try:
                    t = float(timeout)
                except ValueError:
                    return self._error(400, f"bad timeout {timeout!r}")
                # nan/inf would make Deadline arithmetic nonsense
                # (never-expiring checks but 0-second RPC budgets);
                # <=0 is an already-spent budget — all client errors
                if not math.isfinite(t) or t <= 0:
                    return self._error(400, f"bad timeout {timeout!r}")
                ctx.extensions["deadline_s"] = t
            # delta-poll cursor: ?since=<epoch ms> (or X-Greptime-Since)
            # restricts row-returning SELECTs to rows whose time index
            # is strictly greater — the incremental-readback protocol
            # (query/sessions.py); the client advances it to the max ts
            # it has seen
            since = (params.get("since")
                     or self.headers.get("X-Greptime-Since"))
            if since is not None:
                try:
                    s = float(since)
                except ValueError:
                    return self._error(400, f"bad since {since!r}")
                if not math.isfinite(s) or s < 0:
                    return self._error(400, f"bad since {since!r}")
                ctx.extensions["since_ms"] = int(s)
            t0 = time.perf_counter()
            outputs = instance.execute_sql(sql, ctx)
            elapsed = (time.perf_counter() - t0) * 1000
            # alternate response formats (ref src/servers/src/http.rs
            # ResponseFormat: csv | table | greptimedb_v1)
            if fmt in ("csv", "table"):
                res = next(
                    (o.result for o in reversed(outputs)
                     if o.result is not None), None
                )
                if res is None:
                    return self._send(200, b"", "text/plain")
                body = (_format_csv(res) if fmt == "csv"
                        else _format_table(res))
                return self._send(
                    200, body.encode(),
                    "text/csv" if fmt == "csv" else "text/plain",
                )
            with tracing.child_span("http.encode"):
                out_json = []
                partial = None
                for o in outputs:
                    if o.result is not None:
                        out_json.append(
                            result_to_json(o.result, "/v1/sql"))
                        if getattr(o.result, "partial", False):
                            partial = {
                                "partial": True,
                                "missing_regions": int(getattr(
                                    o.result, "missing_regions", 0)),
                            }
                    else:
                        out_json.append(
                            {"affectedrows": o.affected_rows or 0})
                doc = {
                    "output": out_json,
                    "execution_time_ms": round(elapsed, 3),
                }
                if partial is not None:
                    # graceful degradation is EXPLICIT: a client must
                    # be able to tell a complete answer from a
                    # shed-datanode one ([scheduler]
                    # allow_partial_results)
                    doc.update(partial)
                with tracing.child_span("json.dumps"):
                    body = json.dumps(doc).encode()
            self._send(200, body)

        # ------------------------------------------------------------------
        def _handle_prom_api(self, endpoint: str):
            with tracing.child_span("http.read"):
                params = self._form()
            db = params.get("db", "public")
            ctx = QueryContext(database=db)
            engine = PromEngine(instance, ctx)
            if endpoint == "status/buildinfo":
                # Grafana probes this before issuing queries
                return self._json(200, {"status": "success", "data": {
                    "version": "2.53.0",
                    "revision": __version__, "branch": "HEAD",
                    "buildUser": "", "buildDate": "", "goVersion": "",
                    "application": "greptimedb-tpu",
                }})
            if endpoint == "metadata":
                data = {}
                limit = int(params.get("limit", "-1") or -1)
                for t in instance.catalog.all_tables():
                    if t.info.database != db or _prom_hidden(t):
                        continue
                    if limit >= 0 and len(data) >= limit:
                        break
                    data[t.name] = [
                        {"type": "gauge", "help": "", "unit": ""}
                    ]
                return self._json(
                    200, {"status": "success", "data": data}
                )
            if endpoint == "rules":
                return self._json(200, {
                    "status": "success", "data": {"groups": []}
                })
            if endpoint == "alertmanagers":
                return self._json(200, {"status": "success", "data": {
                    "activeAlertmanagers": [],
                    "droppedAlertmanagers": [],
                }})
            if endpoint == "query_range":
                return self._handle_promql_range(params)
            if endpoint == "query":
                q = params.get("query", "")
                t = _parse_prom_time(params.get("time"), time.time())
                try:
                    val, ev = engine.query_instant(q, t)
                except GreptimeError as e:
                    return self._prom_error(str(e))
                return self._json(200, _prom_instant_json(val, ev))
            if endpoint == "labels":
                names = {"__name__"}
                for match in _match_params(params):
                    table = _match_table(instance, db, match)
                    if table:
                        names.update(table.tag_names)
                if not _match_params(params):
                    for t in instance.catalog.all_tables():
                        if t.info.database != db or _prom_hidden(t):
                            continue
                        names.update(t.tag_names)
                names = {n for n in names
                         if n == "__name__" or not n.startswith("__")}
                return self._json(
                    200, {"status": "success", "data": sorted(names)}
                )
            if endpoint.startswith("label/") and endpoint.endswith("/values"):
                label = endpoint[len("label/"):-len("/values")]
                values = set()
                if label == "__name__":
                    for t in instance.catalog.all_tables():
                        if t.info.database == db and not _prom_hidden(t):
                            values.add(t.name)
                else:
                    tables = [
                        _match_table(instance, db, m)
                        for m in _match_params(params)
                    ] or [
                        t for t in instance.catalog.all_tables()
                        if t.info.database == db and not _prom_hidden(t)
                    ]
                    for t in tables:
                        if t is None or label not in t.tag_names:
                            continue
                        values.update(_table_label_values(t, label))
                return self._json(
                    200, {"status": "success", "data": sorted(values)}
                )
            if endpoint == "series":
                out = []
                start = _parse_prom_time(params.get("start"), 0)
                end = _parse_prom_time(params.get("end"), time.time())
                for match in _match_params(params):
                    try:
                        # start/end are Prometheus API DATA timestamps
                        # (epoch seconds from request params); their
                        # difference is a query window in the data time
                        # domain, not a process-relative duration
                        val, ev = engine.query_instant(
                            match, end,
                            lookback_ms=max(end - start, 1),  # gtlint: disable=GT011
                        )
                    except GreptimeError:
                        continue
                    if isinstance(val, VectorValue):
                        for i, lab in enumerate(val.labels):
                            if val.present[i].any():
                                out.append(lab)
                return self._json(200, {"status": "success", "data": out})
            if endpoint == "format_query":
                return self._json(200, {
                    "status": "success", "data": params.get("query", ""),
                })
            self._error(404, f"prometheus api: {endpoint}")

        def _handle_promql_range(self, params):
            db = params.get("db", "public")
            engine = PromEngine(instance, QueryContext(database=db))
            q = params.get("query", "")
            now = time.time()
            # default range window in the Prometheus DATA time domain
            # (epoch seconds): rows are stamped with wall clock, so the
            # window bounds must be too
            start = _parse_prom_time(
                params.get("start"), now - 300)  # gtlint: disable=GT011
            end = _parse_prom_time(params.get("end"), now)
            step_s = params.get("step", "60")
            try:
                step_ms = P_parse_step_ms(step_s)
                val, ev = engine.query_range(q, start, end, step_ms)
            except GreptimeError as e:
                return self._prom_error(str(e))
            with tracing.child_span("http.encode"):
                body = json.dumps(_prom_matrix_json(val, ev)).encode()
            self._send(200, body)

        def _prom_error(self, msg: str):
            self._json(400, {
                "status": "error", "errorType": "bad_data", "error": msg,
            })

        # ------------------------------------------------------------------
        def _handle_remote_write(self):
            with tracing.child_span("http.read"):
                params = self._params()
                body = self._body()
            db = params.get("db", "public")
            compressed = "snappy" in (
                self.headers.get("Content-Encoding") or "snappy"
            )
            series, samples = prom_store.remote_write(
                instance, body, db=db, compressed=compressed,
            )
            _INGEST_ROWS.labels("prom_remote_write").inc(samples)
            self._send(204, b"")

        def _handle_remote_read(self):
            params = self._params()
            db = params.get("db", "public")
            resp = prom_store.remote_read(instance, self._body(), db=db)
            self.send_response(200)
            self.send_header("Content-Type", "application/x-protobuf")
            self.send_header("Content-Encoding", "snappy")
            self.send_header("Content-Length", str(len(resp)))
            self.end_headers()
            self.wfile.write(resp)
            _REQS.labels(self._route(), "200").inc()

        def _handle_influx_write(self):
            params = self._params()
            db = params.get("db", params.get("bucket", "public"))
            precision = params.get("precision", "ns")
            body = self._body().decode("utf-8", "replace")
            rows = influx.write_lines(
                instance, body, db=db, precision=precision,
            )
            _INGEST_ROWS.labels("influx_line").inc(rows)
            self._send(204, b"")

        def _handle_opentsdb_put(self):
            from greptimedb_tpu.servers import opentsdb

            params = self._params()
            db = params.get("db", "public")
            try:
                rows = opentsdb.put_json(instance, self._body(), db=db)
            except opentsdb.OpenTsdbError as e:
                return self._json(400, {"error": str(e)})
            _INGEST_ROWS.labels("opentsdb").inc(rows)
            # OpenTSDB returns 204 unless ?details/?summary is asked
            # (value-less flags: parse with blanks kept)
            flags = {
                k for k, _v in urllib.parse.parse_qsl(
                    urllib.parse.urlparse(self.path).query,
                    keep_blank_values=True,
                )
            }
            if "details" in flags or "summary" in flags:
                return self._json(200, {"success": rows, "failed": 0})
            self._send(204, b"")

        def _handle_otlp_metrics(self):
            from greptimedb_tpu.servers import otlp

            db = self.headers.get("X-Greptime-DB-Name", "public")
            ctype = self.headers.get("Content-Type", "")
            try:
                rows = otlp.write_metrics(
                    instance, self._body(), ctype, db=db
                )
            except Exception as e:  # noqa: BLE001 - protocol boundary
                return self._json(400, {"error": str(e)})
            _INGEST_ROWS.labels("otlp").inc(rows)
            # ExportMetricsServiceResponse: empty message
            self._send(200, b"", "application/x-protobuf")

        def _handle_otlp_records(self, kind: str):
            from greptimedb_tpu.servers import otlp

            db = self.headers.get("X-Greptime-DB-Name", "public")
            try:
                if kind == "traces":
                    table = self.headers.get(
                        "X-Greptime-Trace-Table-Name",
                        otlp.TRACE_TABLE_NAME,
                    )
                    rows = otlp.write_traces_protobuf(
                        instance, self._body(), db=db, table=table
                    )
                else:
                    table = self.headers.get(
                        "X-Greptime-Log-Table-Name", otlp.LOG_TABLE_NAME
                    )
                    rows = otlp.write_logs_protobuf(
                        instance, self._body(), db=db, table=table
                    )
            except Exception as e:  # noqa: BLE001 - protocol boundary
                return self._json(400, {"error": str(e)})
            _INGEST_ROWS.labels(f"otlp_{kind}").inc(rows)
            self._send(200, b"", "application/x-protobuf")

        def _handle_events(self, method: str, path: str):
            from greptimedb_tpu.servers import event_handlers

            event_handlers.handle(self, instance, method, path)

    return Handler


# ----------------------------------------------------------------------
# prometheus json shaping
# ----------------------------------------------------------------------

def _parse_prom_time(v, default) -> int:
    """RFC3339 or unix seconds -> ms."""
    if v is None or v == "":
        return int(float(default) * 1000)
    try:
        return int(float(v) * 1000)
    except ValueError:
        from greptimedb_tpu.query.expr import parse_ts_literal

        return parse_ts_literal(v)


def P_parse_step_ms(v) -> int:
    try:
        return max(int(float(v) * 1000), 1)
    except (TypeError, ValueError):
        from greptimedb_tpu.promql.parser import parse_duration_ms

        return max(parse_duration_ms(str(v)), 1)


def _fmt_sample(x: float) -> str:
    if x != x:
        return "NaN"
    if x in (float("inf"), float("-inf")):
        return "+Inf" if x > 0 else "-Inf"
    return repr(float(x))


def _prom_matrix_json(val, ev) -> dict:
    if isinstance(val, ScalarValue):
        values = [
            [t / 1000.0, _fmt_sample(v)]
            for t, v in zip(ev.step_ts.tolist(), val.values.tolist())
        ]
        return {"status": "success",
                "data": {"resultType": "matrix",
                         "result": [{"metric": {}, "values": values}]}}
    result = []
    step_s = ev.step_ts / 1000.0
    for i, lab in enumerate(val.labels):
        idx = np.nonzero(val.present[i])[0]
        if len(idx) == 0:
            continue
        result.append({
            "metric": lab,
            "values": [
                [float(step_s[j]), _fmt_sample(float(val.values[i, j]))]
                for j in idx
            ],
        })
    return {"status": "success",
            "data": {"resultType": "matrix", "result": result}}


def _prom_instant_json(val, ev) -> dict:
    t = float(ev.step_ts[-1]) / 1000.0
    if isinstance(val, ScalarValue):
        return {"status": "success",
                "data": {"resultType": "scalar",
                         "result": [t, _fmt_sample(float(val.values[-1]))]}}
    result = []
    for i, lab in enumerate(val.labels):
        if not val.present[i][-1]:
            continue
        result.append({
            "metric": lab,
            "value": [t, _fmt_sample(float(val.values[i, -1]))],
        })
    return {"status": "success",
            "data": {"resultType": "vector", "result": result}}


def _format_csv(res) -> str:
    import csv
    import io

    buf = io.StringIO()
    w = csv.writer(buf, lineterminator="\r\n")
    w.writerow(res.names)
    for row in res.rows():
        w.writerow(["" if v is None else v for v in row])
    return buf.getvalue()


def _format_table(res) -> str:
    """psql-style ASCII table."""
    rows = [[("NULL" if v is None else str(v)) for v in r]
            for r in res.rows()]
    widths = [
        max(len(n), *(len(r[i]) for r in rows)) if rows else len(n)
        for i, n in enumerate(res.names)
    ]
    def line(ch="-", sep="+"):
        return sep + sep.join(ch * (w + 2) for w in widths) + sep
    def fmt(vals):
        return "|" + "|".join(
            f" {v}{' ' * (widths[i] - len(v))} " for i, v in enumerate(vals)
        ) + "|"
    out = [line(), fmt(res.names), line()]
    out.extend(fmt(r) for r in rows)
    out.append(line())
    return "\n".join(out) + "\n"


def _prom_hidden(t) -> bool:
    """Internal tables (the metric engine's shared physical table) never
    surface through the Prometheus discovery APIs."""
    from greptimedb_tpu.metric_engine import PHYSICAL_TABLE

    return t.name == PHYSICAL_TABLE


def _table_label_values(t, label: str) -> set:
    """Distinct non-empty values of `label` among THIS table's series.
    A logical metric table shares physical regions with every other
    metric, so its values must filter by __table_id rather than read
    the shared dictionary (which would leak other metrics' values)."""
    from greptimedb_tpu import metric_engine as ME

    out: set = set()
    base = t.physical if isinstance(t, ME.LogicalTable) else t
    if getattr(base, "remote", False):
        # distributed tables: series registries live on the datanodes;
        # a field-less scan ships the merged registry back
        matchers = (
            [(ME.TABLE_ID_TAG, "eq", t._tid)]
            if isinstance(t, ME.LogicalTable) else None
        )
        data = base.scan(field_names=[], matchers=matchers)
        if label in data.registry.tag_names:
            return {
                v for v in data.registry.tag_values(label) if v != ""
            }
        return out
    if isinstance(t, ME.LogicalTable):
        for region in t.regions:
            sids = t.scoped_sids(region)
            if len(sids) == 0:
                continue
            vals = region.series.tag_values(label)
            out.update(v for v in vals[sids] if v != "")
        return out
    for region in t.regions:
        idx = region.series.tag_names.index(label)
        out.update(v for v in region.series.dicts[idx].values if v != "")
    return out


def _match_params(params: dict) -> list[str]:
    out = []
    v = params.get("match[]")
    if isinstance(v, list):
        out.extend(v)
    elif v is not None:
        out.append(v)
    if "match" in params:
        out.append(params["match"])
    return out


def _match_table(instance, db: str, match: str):
    from greptimedb_tpu.promql.parser import parse_promql, VectorSelector

    try:
        sel = parse_promql(match)
    except GreptimeError:
        return None
    if isinstance(sel, VectorSelector) and sel.name:
        return instance.catalog.maybe_table(db, sel.name)
    return None
