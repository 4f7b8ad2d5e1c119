"""Arrow Flight data plane.

Capability counterpart of the reference's gRPC + Arrow Flight services
(/root/reference/src/servers/src/grpc/flight.rs:115 FlightCraft,
src/client/src/database.rs do_get): columnar query results stream as
Arrow record batches instead of per-row JSON, and DoPut ingests columnar
batches straight into Table.write.

- DoGet: ticket = SQL text (utf-8) -> one Arrow stream of the result.
- GetFlightInfo: descriptor (cmd = SQL) -> schema + a ticket for DoGet.
- DoPut: descriptor path = table name; uploaded batches append to the
  table (tags = dictionary/string columns, time index from schema).
"""

from __future__ import annotations

import threading

import numpy as np
import pyarrow as pa
import pyarrow.flight as flight

from greptimedb_tpu.datatypes.batch import HostColumn
from greptimedb_tpu.errors import wire_message
from greptimedb_tpu.session import QueryContext

from greptimedb_tpu import concurrency

def wrap_flight_error(e: Exception) -> flight.FlightServerError:
    """Stamp a typed engine error's status code onto the Flight message
    (`[gtdb:<code>]`, the shared errors.wire_message marker) so the far
    side re-raises the dedicated class instead of substring-matching
    text (dist/client.py map_flight_error)."""
    return flight.FlightServerError(wire_message(e))


def result_to_arrow(res) -> pa.Table:
    """QueryResult -> Arrow table (timestamps become timestamp[ms]).

    Declared result types that arrow cannot carry natively here (e.g.
    DECIMAL held as scaled float64 + (p,s) typing, INTERVAL as int64 ms)
    ride as schema metadata so the receiving side restores them — the
    RecordBatch extension-metadata trick the reference uses on Flight
    (/root/reference/src/common/grpc/src/flight.rs:45)."""
    import json as _json

    arrays = []
    fields = []
    for name, col in zip(res.names, res.cols):
        vals = col.values
        mask = None if col.validity is None else ~col.validity
        dt = res.types.get(name)
        if dt is not None and dt.is_timestamp():
            arr = pa.array(np.asarray(vals, np.int64), pa.timestamp("ms"),
                           mask=mask)
        elif vals.dtype == object:
            arr = pa.array(vals, pa.string(), mask=mask)
        else:
            arr = pa.array(vals, mask=mask)
        arrays.append(arr)
        fields.append(pa.field(name, arr.type))
    tbl = pa.Table.from_arrays(arrays, schema=pa.schema(fields))
    declared = {n: dt.name for n, dt in res.types.items() if dt is not None}
    meta = dict(tbl.schema.metadata or {})
    if declared:
        meta[b"gtdb:types"] = _json.dumps(declared).encode()
    if getattr(res, "partial", False):
        # degraded answer (sched/: per-datanode deadline expiry or
        # unavailability under allow_partial_results): the marker must
        # survive the Flight hop so remote frontends re-stamp it
        meta[b"gtdb:partial"] = _json.dumps({
            "missing_regions": int(getattr(res, "missing_regions", 0)),
        }).encode()
    if meta:
        tbl = tbl.replace_schema_metadata(meta)
    return tbl


class _BearerMiddleware(flight.ServerMiddleware):
    def __init__(self, header: str):
        self.header = header

    def sending_headers(self):
        return {"authorization": self.header}


class _BasicAuthMiddlewareFactory(flight.ServerMiddlewareFactory):
    """Basic-credentials handshake -> bearer token, validated on every
    call (what `client.authenticate_basic_token(user, pwd)` speaks)."""

    def __init__(self, provider):
        self.provider = provider
        self._tokens: dict[str, str] = {}
        self._lock = concurrency.Lock()

    def start_call(self, info, headers):
        import base64
        import secrets

        auth = None
        for k, v in headers.items():
            if k.lower() == "authorization" and v:
                auth = v[0]
        if auth is None:
            raise flight.FlightUnauthenticatedError("no credentials")
        if auth.lower().startswith("basic "):
            try:
                user, _, pwd = base64.b64decode(
                    auth[6:]
                ).decode().partition(":")
            except Exception:
                raise flight.FlightUnauthenticatedError("bad credentials")
            if not self.provider.authenticate(user, pwd):
                raise flight.FlightUnauthenticatedError("access denied")
            token = secrets.token_urlsafe(16)
            with self._lock:
                if len(self._tokens) >= 1024:
                    self._tokens.pop(next(iter(self._tokens)))
                self._tokens[token] = user
            return _BearerMiddleware(f"Bearer {token}")
        if auth.startswith("Bearer "):
            with self._lock:
                ok = auth[7:] in self._tokens
            if not ok:
                raise flight.FlightUnauthenticatedError("bad token")
            return _BearerMiddleware(auth)
        raise flight.FlightUnauthenticatedError("unsupported auth scheme")


class _NoOpAuthHandler(flight.ServerAuthHandler):
    """Handshake passthrough: credential checking happens in the header
    middleware (the pyarrow-documented basic-auth pattern)."""

    def authenticate(self, outgoing, incoming):
        pass

    def is_valid(self, token):
        return ""


class FlightServer(flight.FlightServerBase):
    def __init__(self, instance, *, addr: str = "127.0.0.1", port: int = 0,
                 user_provider=None):
        self.instance = instance
        self.user_provider = user_provider
        location = f"grpc://{addr}:{port}"
        kwargs = {}
        if user_provider is not None:
            kwargs["middleware"] = {
                "auth": _BasicAuthMiddlewareFactory(user_provider)
            }
            kwargs["auth_handler"] = _NoOpAuthHandler()
        super().__init__(location, **kwargs)
        self.addr = addr
        # FlightServerBase binds immediately; port resolves the 0 case
        self._location = location
        # get_flight_info -> do_get runs the query once: the info call
        # materializes and parks the table for the matching ticket
        self._pending: dict[bytes, pa.Table] = {}
        self._pending_lock = concurrency.Lock()

    # ---- queries ------------------------------------------------------
    def _run_sql(self, sql: str) -> pa.Table:
        from greptimedb_tpu.telemetry import tracing

        # raw SQL, or a JSON envelope {"sql": ..., "db": ...,
        # "traceparent": ...} so remote frontends can forward session
        # database AND trace context
        db = "public"
        tp = None
        if sql.startswith("{"):
            try:
                import json

                doc = json.loads(sql)
                sql = doc["sql"]
                db = doc.get("db") or "public"
                tp = doc.get("traceparent")
            except (ValueError, KeyError):
                pass
        with tracing.start_remote(tp, "flight sql", db=db):
            # channel tagged so the fingerprint row attributes its
            # traffic to the Flight wire (statement statistics)
            outs = self.instance.execute_sql(
                sql, QueryContext(database=db, channel="flight")
            )
        out = outs[-1]
        if out.result is None:
            # DML/DDL ack: marked in schema metadata so remote frontends
            # never confuse it with a query result that happens to have
            # an "affected_rows" column
            tbl = pa.table({
                "affected_rows": pa.array(
                    [out.affected_rows or 0], pa.int64()
                )
            })
            return tbl.replace_schema_metadata({b"gtdb:affected": b"1"})
        return result_to_arrow(out.result)

    def do_get(self, context, ticket: flight.Ticket):
        with self._pending_lock:
            table = self._pending.pop(ticket.ticket, None)
        if table is None:
            sql = ticket.ticket.decode("utf-8")
            if sql.startswith("{") and '"rpc"' in sql[:40]:
                try:
                    return flight.RecordBatchStream(self._region_rpc(sql))
                except flight.FlightServerError:
                    raise
                except Exception as e:  # noqa: BLE001 - RPC boundary
                    raise wrap_flight_error(e) from e
            try:
                table = self._run_sql(sql)
            except Exception as e:  # noqa: BLE001 - RPC boundary
                raise wrap_flight_error(e) from e
        return flight.RecordBatchStream(table)

    # ---- region server (distributed data plane) -----------------------
    def _region_server(self):
        rs = getattr(self.instance, "region_server", None)
        if rs is None:
            raise flight.FlightServerError(
                "this node does not serve region requests"
            )
        return rs

    def _region_rpc(self, raw: str) -> pa.Table:
        import json

        from greptimedb_tpu.dist import codec as dist_codec

        doc = json.loads(raw)
        rpc = doc.get("rpc")
        if rpc == "region_scan":
            from greptimedb_tpu.dist import plan_codec
            from greptimedb_tpu.sched import deadline as _dl
            from greptimedb_tpu.telemetry import tracing

            rs = self._region_server()
            # re-anchor the shipped deadline budget for cooperative
            # checks along the scan path (a blackholed disk/object
            # store must bound, not block, the scan)
            dl = _dl.Deadline.from_timeout(doc.get("deadline_s"))
            token = _dl.bind(dl) if dl is not None else None
            try:
                if dl is not None:
                    dl.check("region scan")
                # continue the frontend's trace; the produced spans
                # (merged scan, cache hit/miss) ship back in gtdb:spans
                with tracing.export_spans() as exported, \
                        tracing.start_remote(
                            doc.get("traceparent"),
                            "datanode.region_scan",
                            regions=len(doc["region_ids"]),
                        ):
                    rows, tag_values, names, stats = rs.scan(
                        doc["region_ids"],
                        ts_min=doc.get("ts_min"),
                        ts_max=doc.get("ts_max"),
                        field_names=doc.get("fields"),
                        matchers=(
                            [(m[0], m[1], plan_codec.decode(m[2]))
                             for m in doc["matchers"]]
                            if doc.get("matchers") else None
                        ),
                        fulltext=(
                            [tuple(f) for f in doc["fulltext"]]
                            if doc.get("fulltext") else None
                        ),
                    )
            finally:
                if token is not None:
                    _dl.reset(token)
            extra = {"gtdb:stats": stats}
            if doc.get("traceparent") and exported:
                extra["gtdb:spans"] = [s.to_json() for s in exported]
            return dist_codec.scan_to_arrow(
                rows, tag_values, names, extra_meta=extra
            )
        if rpc == "partial_sql":
            from greptimedb_tpu.dist.merge import exec_partial

            # raw ticket rides along as the decode-memo key: hot
            # queries ship byte-identical tickets (dist_query.py caches
            # the encode side)
            return exec_partial(self.instance, doc, raw=raw)
        raise flight.FlightServerError(f"unknown rpc: {rpc}")

    def do_action(self, context, action: flight.Action):
        import json

        body = json.loads(action.body.to_pybytes() or b"{}")
        try:
            out = self._do_action(action.type, body)
        except flight.FlightServerError:
            raise
        except Exception as e:  # noqa: BLE001 - RPC boundary
            raise wrap_flight_error(e) from e
        return [flight.Result(json.dumps(out or {}).encode())]

    def _do_action(self, kind: str, body: dict) -> dict | None:
        if kind == "node_telemetry":
            # fleet observability fan-out (dist/fleet.py): any role
            # with a Flight server answers with its node-stats payload,
            # requested information_schema telemetry docs, metrics
            # text and/or deep-health JSON — all local reads, so a
            # telemetry scrape can never wedge behind the data plane
            from greptimedb_tpu.dist import fleet

            return fleet.node_telemetry_local(self.instance, body)
        if kind in ("create_flow", "drop_flow", "flow_infos",
                    "flow_sources", "flow_epoch", "flush_flow"):
            return self._flow_action(kind, body)
        rs = self._region_server()
        if kind == "open_region":
            rs.open_region(body["meta"])
        elif kind == "close_region":
            rs.close_region(int(body["region_id"]))
        elif kind == "drop_region":
            rs.drop_region(int(body["region_id"]))
        elif kind == "flush_region":
            return {"flushed": rs.flush_region(int(body["region_id"]))}
        elif kind == "compact_region":
            return {"compacted": rs.compact_region(
                int(body["region_id"]),
                force=bool(body.get("force", False)),
            )}
        elif kind == "truncate_region":
            rs.truncate_region(int(body["region_id"]))
        elif kind == "alter_region":
            rs.alter_region(int(body["region_id"]), body["op"],
                            body["name"])
        elif kind == "set_region_writable":
            rs.set_region_writable(int(body["region_id"]),
                                   bool(body["writable"]))
        elif kind == "region_stats":
            return {"stats": rs.region_stats(
                [int(r) for r in body["region_ids"]]
            )}
        elif kind == "data_versions":
            return {"versions": rs.data_versions(
                [int(r) for r in body["region_ids"]]
            )}
        elif kind == "physical_versions":
            return {"versions": rs.physical_versions(
                [int(r) for r in body["region_ids"]]
            )}
        elif kind == "list_regions":
            return {"region_ids": rs.region_ids()}
        else:
            raise flight.FlightServerError(f"unknown action: {kind}")
        return None

    # ---- flownode service (wire-level flow DDL + source registry) -----
    def _flow_action(self, kind: str, body: dict) -> dict:
        inst = self.instance
        flows = getattr(inst, "flows", None)
        if flows is None:
            raise flight.FlightServerError(
                "this node does not run flows"
            )
        if kind == "create_flow":
            refresh = getattr(inst.catalog, "refresh", None)
            if refresh is not None:
                refresh()  # the source table may be newer than our load
            outs = inst.execute_sql(
                body["sql"], QueryContext(database=body.get("db")
                                          or "public")
            )
            return {"affected": outs[-1].affected_rows or 0}
        if kind == "drop_flow":
            flows.drop_flow(body["name"],
                            if_exists=bool(body.get("if_exists")))
            return {}
        if kind == "flow_infos":
            return {"flows": flows.flow_infos()}
        if kind == "flow_sources":
            return {"sources": flows.flow_sources()}
        if kind == "flow_epoch":
            return {"epoch": flows.epoch}
        if kind == "flush_flow":
            return {"flushed": bool(flows.flush_flow(body["name"]))}
        raise flight.FlightServerError(f"unknown flow action: {kind}")

    def _do_put_flow_mirror(self, name: str, reader):
        """Mirrored source-table delta batches from a frontend (the
        reference's frontend->flownode insert mirroring,
        /root/reference/src/operator/src/insert.rs:284-317)."""
        inst = self.instance
        if getattr(inst, "flows", None) is None:
            raise flight.FlightServerError("this node does not run flows")
        import json

        from greptimedb_tpu.telemetry import tracing

        db, _, tname = name.partition(".")
        # DistCatalogManager.table() refreshes from the shared kv on a
        # miss, so a just-created source table resolves here
        table = inst.catalog.table(db, tname)
        for chunk in reader:
            if chunk.data is None:
                continue
            batch = chunk.data
            # the mirroring frontend stamps its trace context on the
            # batch metadata: the flow evaluation joins the insert's
            # trace
            tp = None
            if chunk.app_metadata:
                try:
                    doc = json.loads(chunk.app_metadata.to_pybytes())
                except ValueError:
                    doc = None
                # valid JSON that is not an object (e.g. an array)
                # must be ignored, not abort the stream
                if isinstance(doc, dict):
                    tp = doc.get("traceparent")
            data: dict = {}
            valid: dict = {}
            for i in range(batch.num_columns):
                cname = batch.schema.field(i).name
                arr = batch.column(i)
                if pa.types.is_timestamp(arr.type):
                    arr = arr.cast(pa.timestamp("ms"))
                hc = HostColumn.from_arrow(cname, arr)
                data[cname] = hc.values
                valid[cname] = hc.valid_mask
            try:
                if tp:
                    with tracing.start_remote(
                            tp, "flownode.mirror_apply",
                            table=f"{db}.{tname}",
                            rows=batch.num_rows):
                        inst.flows.on_insert(db, tname, table, data,
                                             valid)
                else:
                    # untraced mirror: no root span — a per-batch root
                    # would churn real query traces out of the ring
                    inst.flows.on_insert(db, tname, table, data, valid)
            except Exception as e:  # noqa: BLE001 - RPC boundary
                raise wrap_flight_error(e) from e

    def list_actions(self, context):
        return [
            ("open_region", "open a region on this datanode"),
            ("close_region", "close a region"),
            ("drop_region", "drop a region"),
            ("flush_region", "flush a region's memtable"),
            ("compact_region", "compact a region's SSTs"),
            ("truncate_region", "truncate a region"),
            ("alter_region", "apply a schema change to a region"),
            ("set_region_writable", "toggle a region's writable flag"),
            ("region_stats", "per-region row/byte statistics"),
            ("data_versions", "per-region logical data versions"),
            ("physical_versions", "per-region physical storage versions"),
            ("list_regions", "region ids served by this datanode"),
            ("create_flow", "create a continuous-aggregation flow"),
            ("drop_flow", "drop a flow"),
            ("flow_infos", "flow definitions hosted by this node"),
            ("flow_sources", "source tables mirrored into flows"),
            ("flow_epoch", "flownode liveness epoch"),
            ("flush_flow", "force-evaluate a flow's pending windows"),
            ("node_telemetry", "node stats / telemetry docs / metrics "
                               "text / deep health for the fleet plane"),
        ]

    def get_flight_info(self, context, descriptor: flight.FlightDescriptor):
        sql = (descriptor.command or b"").decode("utf-8")
        try:
            table = self._run_sql(sql)
        except Exception as e:  # noqa: BLE001
            raise wrap_flight_error(e) from e
        with self._pending_lock:
            if len(self._pending) >= 32:
                self._pending.pop(next(iter(self._pending)))
            self._pending[sql.encode()] = table
        endpoint = flight.FlightEndpoint(sql.encode(), [self._location])
        return flight.FlightInfo(
            table.schema, descriptor, [endpoint], table.num_rows, -1
        )

    # ---- ingest -------------------------------------------------------
    def do_put(self, context, descriptor, reader, writer):
        path = descriptor.path
        if not path:
            raise flight.FlightServerError("DoPut needs a table-name path")
        name = path[0].decode("utf-8")
        if name == "region_write":
            return self._do_put_regions(reader)
        if name == "region_write_stream":
            return self._do_put_region_stream(reader, writer)
        if name.startswith("flow_mirror:"):
            return self._do_put_flow_mirror(name[12:], reader)
        inst = self.instance
        db = "public"
        if "." in name:
            db, name = name.split(".", 1)
        table = inst.catalog.table(db, name)
        from greptimedb_tpu.telemetry import tracing

        # one root per stream, its chunks' stages beneath it (the ring
        # keeps a trace's first spans; the per-name times count all)
        with tracing.start_remote(None, "flight.do_put",
                                  table=f"{db}.{name}"):
            for chunk in reader:
                batch = chunk.data
                data: dict = {}
                valid: dict = {}
                with tracing.child_span("write.decode"):
                    for i in range(batch.num_columns):
                        cname = batch.schema.field(i).name
                        arr = batch.column(i)
                        if pa.types.is_timestamp(arr.type):
                            # normalize to ms before the shared
                            # converter so null timestamps fill to
                            # int 0, not float NaN
                            arr = arr.cast(pa.timestamp("ms"))
                        hc = HostColumn.from_arrow(cname, arr)
                        data[cname] = hc.values
                        valid[cname] = hc.valid_mask
                try:
                    inst._write_columns(table, data, valid)
                except Exception as e:  # noqa: BLE001 - RPC boundary
                    raise wrap_flight_error(e) from e
                inst._notify_flows(db, name, table, data, valid)

    def _do_put_regions(self, reader):
        """Per-region columnar writes: each batch's app_metadata names
        the target region (RegionPutRequest analog). The whole stream is
        decoded and its region ids VALIDATED before anything applies,
        so route staleness (a region migrated away) usually rejects the
        stream before any write. This is best-effort, not transactional
        (a concurrent close can still land mid-apply); the frontend's
        refresh-and-retry therefore relies on last-write-wins dedup for
        idempotence and refuses to retry append-mode tables."""
        import json

        from greptimedb_tpu.dist import codec as dist_codec

        rs = self._region_server()
        batches = []
        for chunk in reader:
            if chunk.data is None:
                continue
            meta = json.loads(
                chunk.app_metadata.to_pybytes()
                if chunk.app_metadata else b"{}"
            )
            batches.append(
                (meta, dist_codec.batch_to_write(chunk.data))
            )
        try:
            self._apply_region_batches(rs, batches)
        except Exception as e:  # noqa: BLE001 - RPC boundary
            raise wrap_flight_error(e) from e

    @staticmethod
    def _apply_region_batches(rs, batches):
        """Validate every region id BEFORE applying anything, so route
        staleness (a region migrated away) rejects the group before any
        write — the property the frontend's dedup-safe retry relies on."""
        for meta, _decoded in batches:
            rs._region(int(meta["region_id"]))  # not-found raises
        rows = 0
        for meta, (tag_columns, ts, fields, valids) in batches:
            rows += rs.write(
                int(meta["region_id"]), tag_columns, ts, fields,
                valids, op=int(meta.get("op", 0) or 0),
                skip_wal=bool(meta.get("skip_wal", False)),
            )
        return rows

    def _do_put_region_stream(self, reader, writer):
        """Long-lived pipelined ingest stream (ingest/sender.py): the
        client writes batch GROUPS (the last batch of a group carries
        `end: true`); each group is validated + applied as a unit and
        acknowledged through the metadata side channel. Apply errors
        ride the ack — typed via their status code — so one stale
        route does not kill the stream for the other regions riding
        it."""
        import json

        from greptimedb_tpu.dist import codec as dist_codec
        from greptimedb_tpu.errors import GreptimeError

        rs = self._region_server()
        pending = []
        for chunk in reader:
            if chunk.data is None:
                continue
            meta = json.loads(
                chunk.app_metadata.to_pybytes()
                if chunk.app_metadata else b"{}"
            )
            pending.append(
                (meta, dist_codec.batch_to_write(chunk.data))
            )
            if not meta.get("end"):
                continue
            gid = meta.get("group", 0)
            batches, pending = pending, []
            # trace context rides the group's end-marker metadata
            # (ingest/sender.py): the apply joins the INSERT's trace on
            # this datanode's ring under the shared trace_id
            tp = next(
                (m.get("traceparent") for m, _ in batches
                 if m.get("traceparent")), None,
            )
            try:
                if tp:
                    from greptimedb_tpu.telemetry import tracing

                    with tracing.start_remote(
                            tp, "datanode.ingest_group",
                            batches=len(batches)):
                        rows = self._apply_region_batches(rs, batches)
                else:
                    rows = self._apply_region_batches(rs, batches)
                ack = {"group": gid, "rows": rows}
            except Exception as e:  # noqa: BLE001 - ack carries it
                code = 0
                if isinstance(e, GreptimeError):
                    code = int(e.status_code)
                ack = {
                    "group": gid, "error": str(e) or type(e).__name__,
                    "code": code,
                }
            writer.write(pa.py_buffer(json.dumps(ack).encode()))


class FlightFrontend:
    """Owns the Flight server thread (FlightServerBase.serve blocks)."""

    def __init__(self, instance, *, addr: str = "127.0.0.1", port: int = 0,
                 user_provider=None):
        self.server = FlightServer(
            instance, addr=addr, port=port, user_provider=user_provider
        )
        self.addr = addr
        self.port = self.server.port
        self._thread: threading.Thread | None = None

    def start(self) -> "FlightFrontend":
        self._thread = concurrency.Thread(
            target=self.server.serve, daemon=True, name="flight-server"
        )
        self._thread.start()
        return self

    def close(self, *, grace_s: float = 5.0):
        """Shut the server down with a BOUNDED wait: pyarrow's
        shutdown() blocks until every in-flight handler returns, and a
        parked long-lived ingest stream (ingest/sender.py) only ends
        when its client side closes — which a hard-stopped test
        topology never does. After the grace period the daemon serve
        thread is abandoned; the engine teardown behind it makes any
        zombie handler fail its acks, which clients surface as the
        retryable unavailable error."""
        done = concurrency.Event()

        def _shutdown():
            try:
                self.server.shutdown()
            finally:
                done.set()

        concurrency.Thread(target=_shutdown, daemon=True,
                         name="flight-shutdown").start()
        done.wait(grace_s)
