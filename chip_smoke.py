#!/usr/bin/env python3
"""chip_smoke.py: drive the served TSBS path once, end to end, on the chip.

The quickest proof that the system still starts on the accelerator. It
starts `python -m greptimedb_tpu.cli standalone start` as a child with
the DEFAULT configuration, loads TSBS devops `cpu-only` (table `cpu`,
`hostname` tag, 10 double fields, 10 s interval; source:
/root/reference/docs/benchmarks/tsbs/README.md:40-48) over the wire,
flushes and compacts, asks the TSBS query shapes over HTTP, compares
every answer with a plain NumPy computation on the same seeded arrays,
restarts the server on the same data home and asks again.

One process for each chip: THIS process never imports jax (nor anything
of greptimedb_tpu that does) — it only talks to the server over
sockets, so the server child is the one process that holds the device.

Exit code 0 and, as the LAST stdout line, exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
(the device as jax reports it in the server process) only when every
phase passed on a TPU. The line before it, `report: {...}`, carries the
rows, the per-phase wall times, the exec path of every step and the
compile-cache counts; the same document is written
to chiprun_out/chip_smoke_report.json. No accelerator, a query that
lands on a `host:*` path, a dead server child, a wrong answer or any
phase that raised: non-zero exit and no result line. `--cpu-rehearsal`
is the explicit CPU dry run of the same flow (every line then says
platform=cpu; it proves the control flow, never a device number).

    python chip_smoke.py                      # one chip, 4000 hosts x 12 h
    python chip_smoke.py --chips 4            # [mesh] on, 16384 hosts x 3 h
    python chip_smoke.py --cpu-rehearsal --hosts 512 --hours 2
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

FIELDS = [
    "usage_user", "usage_system", "usage_idle", "usage_nice",
    "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
    "usage_guest", "usage_guest_nice",
]
INTERVAL_MS = 10_000
CELLS_PER_HOUR = 3_600_000 // INTERVAL_MS
INFLUX_TAIL_CELLS = 60          # the last ten minutes ride line protocol
SOURCE_HOURS = 72               # the TSBS source runs 3 days
WALL_LIMIT_S = 1150             # the driver allows 1200 s

# Tolerances, fixed before any run. The server keeps cell states and
# folds in f32 (production runs with x64 off); the reference is f64.
# Selections (max / last_value / count) pick one f32-representable
# input value or an integer: they must match EXACTLY. Means sum n f32
# terms; the first-order worst case of a recursive f32 sum is n * 2^-24
# relative (4.3e-5 at the flagship's n = 360), blocked and pairwise
# folds stay far below it, so 1e-4 holds every averaged shape here.
RTOL_F32_MEAN = 1e-4
EXACT = 0.0


_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')   # name="value"


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------------
# the server child and its sockets
# ----------------------------------------------------------------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Server:
    """One `standalone start` child. Every wait checks that the child
    is still alive: a server that died is a failure, never a timeout."""

    def __init__(self, data_home: str, log_dir: str, *, config: str | None,
                 env: dict):
        self.data_home = data_home
        self.log_dir = log_dir
        self.config = config
        self.env = env
        self.http = f"127.0.0.1:{_free_port()}"
        self.flight_port = _free_port()
        self.proc: subprocess.Popen | None = None
        self.lives = 0
        self.log_path = ""

    def start(self) -> float:
        self.lives += 1
        self.log_path = os.path.join(
            self.log_dir, f"chip_smoke_server_life{self.lives}.log"
        )
        args = [sys.executable, "-m", "greptimedb_tpu.cli", "standalone",
                "start", "--data-home", self.data_home,
                "--http-addr", self.http,
                "--flight-addr", f"127.0.0.1:{self.flight_port}",
                "--mysql-addr", "", "--postgres-addr", ""]
        if self.config:
            args += ["-c", self.config]
        t0 = time.perf_counter()
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                args, cwd=HERE, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        deadline = time.time() + 300
        while True:
            self.alive()
            try:
                with urllib.request.urlopen(
                        f"http://{self.http}/health", timeout=2):
                    break
            except (urllib.error.URLError, OSError):
                check(time.time() < deadline,
                      "server never answered /health")
                time.sleep(0.2)
        return time.perf_counter() - t0

    def alive(self):
        check(self.proc is not None and self.proc.poll() is None,
              f"server child died (exit {self.proc.poll()}); "
              f"log tail:\n{self.log_tail()}")

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "(no log)"

    def stop(self):
        """SIGTERM and wait: the graceful shutdown a deployment does."""
        self.alive()
        self.proc.send_signal(signal.SIGTERM)
        code = self.proc.wait(timeout=300)
        check(code == 0, f"server exited {code} on SIGTERM; log tail:\n"
                         f"{self.log_tail()}")
        self.proc = None

    def kill(self):
        """Cleanup path: whatever is still running goes, group and all."""
        if self.proc is None or self.proc.poll() is not None:
            return
        try:
            os.killpg(self.proc.pid, signal.SIGTERM)
            self.proc.wait(timeout=20)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=20)

    # -- HTTP ----------------------------------------------------------
    def _open(self, req, timeout: float) -> bytes:
        self.alive()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.read()
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"HTTP {e.code} from {req.full_url}: "
                f"{e.read()[:600]!r}") from e

    def get(self, path: str, timeout: float = 120.0) -> bytes:
        return self._open(
            urllib.request.Request(f"http://{self.http}{path}"), timeout)

    def sql(self, sql: str, timeout: float = 900.0) -> list:
        """POST /v1/sql -> rows of the last result set."""
        req = urllib.request.Request(
            f"http://{self.http}/v1/sql",
            data=urllib.parse.urlencode({"sql": sql}).encode(),
            headers={"Content-Type": "application/x-www-form-urlencoded"},
        )
        doc = json.loads(self._open(req, timeout))
        check("output" in doc, f"no output for {sql[:80]!r}: {doc}")
        last = doc["output"][-1]
        if "records" in last:
            return last["records"]["rows"]
        return [[last.get("affectedrows", 0)]]

    def influx(self, body: str, timeout: float = 300.0):
        req = urllib.request.Request(
            f"http://{self.http}/v1/influxdb/write?precision=ms",
            data=body.encode(), method="POST",
        )
        self._open(req, timeout)

    def prom_range(self, query: str, start_s: int, end_s: int,
                   step_s: int, timeout: float = 900.0) -> list:
        qs = urllib.parse.urlencode({
            "query": query, "start": start_s, "end": end_s,
            "step": step_s,
        })
        doc = json.loads(self.get(
            f"/v1/prometheus/api/v1/query_range?{qs}", timeout))
        check(doc.get("status") == "success",
              f"promql {query!r} failed: {str(doc)[:400]}")
        return doc["data"]["result"]

    def device(self) -> dict:
        """The `device` check of /health?deep=1: identity and per-device
        bytes in use as jax reports them IN THE SERVER PROCESS."""
        doc = json.loads(self.get("/health?deep=1"))
        dev = doc["checks"]["device"]
        check(dev.get("ok"), f"device health check failed: {dev}")
        return dev

    def metrics(self) -> dict:
        """/metrics as {(family, ((label, value), ...)): float}."""
        out = {}
        for ln in self.get("/metrics").decode().splitlines():
            if not ln or ln.startswith("#"):
                continue
            head, _, val = ln.rpartition(" ")
            name, _, rest = head.partition("{")
            labels = _LABEL.findall(rest)
            try:
                out[(name, tuple(sorted(labels)))] = float(val)
            except ValueError:
                continue
        return out


# ----------------------------------------------------------------------
# data: TSBS cpu-only from --seed, and the plain reference over it
# ----------------------------------------------------------------------

def make_data(np, seed: int, hosts: int, cells: int):
    """(F, hosts, cells) float32: every value is exactly representable
    in f32, so the f64 the wire carries equals the f32 the device keeps
    and selections can be compared exactly."""
    rng = np.random.default_rng(seed)
    return np.stack([
        rng.random((hosts, cells), dtype=np.float32) * np.float32(100.0)
        for _ in FIELDS
    ])


def load(np, srv: Server, data, hostnames: list, say) -> dict:
    """Bulk rows as Arrow over Flight DoPut to the table path; the last
    ten minutes as InfluxDB line protocol (how TSBS itself loads
    GreptimeDB). Returns acknowledged row counts and wall times."""
    import pyarrow as pa
    import pyarrow.flight as flight

    n_fields, hosts, cells = data.shape
    bulk_cells = cells - INFLUX_TAIL_CELLS
    per_batch = max(1, 131_072 // hosts)
    names = pa.array(hostnames, pa.string())
    schema = pa.schema(
        [("hostname", pa.string()), ("ts", pa.timestamp("ms"))]
        + [(f, pa.float64()) for f in FIELDS]
    )
    t0 = time.perf_counter()
    client = flight.connect(f"grpc://127.0.0.1:{srv.flight_port}")
    writer, _ = client.do_put(
        flight.FlightDescriptor.for_path("cpu"), schema)
    sent = 0
    for c0 in range(0, bulk_cells, per_batch):
        c1 = min(c0 + per_batch, bulk_cells)
        w = c1 - c0
        host_idx = np.repeat(np.arange(hosts, dtype=np.int32), w)
        cols = [
            pa.DictionaryArray.from_arrays(
                pa.array(host_idx), names).cast(pa.string()),
            pa.array(np.tile(
                np.arange(c0, c1, dtype=np.int64) * INTERVAL_MS, hosts
            ), pa.timestamp("ms")),
        ]
        for f in range(n_fields):
            cols.append(pa.array(
                data[f, :, c0:c1].reshape(-1).astype(np.float64)))
        writer.write_batch(pa.record_batch(cols, schema=schema))
        sent += hosts * w
        srv.alive()
    # close() returns once the server has applied every batch of the
    # stream without error: that is the acknowledgement
    writer.close()
    client.close()
    acked_flight = sent
    flight_s = time.perf_counter() - t0
    say(f"load: {acked_flight} rows acknowledged over Flight DoPut in "
        f"{flight_s:.1f}s")
    # flush between the two wire paths: the tail then lands in an SST
    # of its own, so the last time window always holds >= 2 runs and
    # the compaction below has a merge to do at every scale
    t_f = time.perf_counter()
    srv.sql("ADMIN flush_table('cpu')")
    bulk_flush_s = time.perf_counter() - t_f

    t1 = time.perf_counter()
    acked_influx = 0
    lines: list[str] = []
    for c in range(bulk_cells, cells):
        ts = c * INTERVAL_MS
        # Python floats: repr() is the shortest text that parses back
        # to the same f64, so the line carries the value exactly
        block = data[:, :, c].astype(np.float64).tolist()  # [F][hosts]
        for h in range(hosts):
            vals = ",".join(
                f"{FIELDS[f]}={block[f][h]!r}" for f in range(n_fields)
            )
            lines.append(f"cpu,hostname={hostnames[h]} {vals} {ts}")
        if len(lines) >= 20_000 or c == cells - 1:
            srv.influx("\n".join(lines))      # 204 = acknowledged
            acked_influx += len(lines)
            lines = []
    influx_s = time.perf_counter() - t1
    say(f"load: {acked_influx} rows acknowledged over "
        f"POST /v1/influxdb/write in {influx_s:.1f}s")
    return {"acked_rows": acked_flight + acked_influx,
            "flight_rows": acked_flight, "influx_rows": acked_influx,
            "flight_s": flight_s, "bulk_flush_s": bulk_flush_s,
            "influx_s": influx_s}


def compare(np, name: str, got: dict, want: dict, rtol: float) -> float:
    """Exact row count and keys; values within rtol (0 = exact).
    got/want: {key: tuple of floats}. Returns the worst relative
    error seen."""
    check(len(got) == len(want),
          f"{name}: {len(got)} rows, reference has {len(want)}")
    missing = [k for k in want if k not in got]
    check(not missing, f"{name}: keys differ, e.g. missing {missing[:3]}")
    keys = list(want)
    g = np.asarray([got[k] for k in keys], np.float64)
    w = np.asarray([want[k] for k in keys], np.float64)
    check(g.shape == w.shape, f"{name}: value shape {g.shape} != {w.shape}")
    check(bool(np.isfinite(g).all()), f"{name}: non-finite values")
    err = np.abs(g - w) / np.maximum(np.abs(w), 1e-30)
    worst = float(err.max()) if err.size else 0.0
    if rtol == EXACT:
        bad = int((g != w).sum())
        check(bad == 0, f"{name}: {bad} values differ from the reference "
                        f"(exact match required), worst rel {worst:.3g}")
    else:
        check(worst <= rtol, f"{name}: worst relative error {worst:.3g} "
                             f"> {rtol:g}")
    return worst


class Workload:
    """The TSBS query shapes with their references.
    Each step yields (sql-or-promql, reference dict, rtol) for a cold
    variant and a fresh-literal warm variant: same program shapes, a
    different literal, so the session registry cannot answer it."""

    def __init__(self, np, data, hostnames, hours: int):
        self.np = np
        self.d = data                     # (F, hosts, cells) f32
        self.hostnames = hostnames
        self.hours = hours
        self.hosts = data.shape[1]
        self.cells = data.shape[2]
        self.end_ms = self.cells * INTERVAL_MS
        self.hid = {h: i for i, h in enumerate(hostnames)}

    # -- references ----------------------------------------------------
    def _hourly(self, f: int, op: str, hosts=None, hours=None):
        np = self.np
        hrs = self.hours if hours is None else hours
        x = self.d[f][:, :hrs * CELLS_PER_HOUR].astype(np.float64)
        if hosts is not None:
            x = x[hosts]
        x = x.reshape(x.shape[0], hrs, CELLS_PER_HOUR)
        return x.mean(axis=2) if op == "avg" else x.max(axis=2)

    def flagship(self, where: str = ""):
        items = ", ".join(f"avg({f}) RANGE '1h'" for f in FIELDS)
        sql = (f"SELECT ts, hostname, {items} FROM cpu {where} "
               f"ALIGN '1h' BY (hostname)")
        means = [self._hourly(f, "avg") for f in range(len(FIELDS))]
        want = {
            (k * 3_600_000, self.hostnames[h]):
                tuple(m[h, k] for m in means)
            for h in range(self.hosts) for k in range(self.hours)
        }
        return sql, want, RTOL_F32_MEAN

    def lastpoint(self, where: str = ""):
        sql = (f"SELECT ts, hostname, last_value(usage_user) RANGE "
               f"'{self.hours}h' FROM cpu {where} ALIGN '{self.hours}h' "
               f"TO '1970-01-01 00:00:00' BY (hostname)")
        want = {(0, self.hostnames[h]): (float(self.d[0, h, -1]),)
                for h in range(self.hosts)}
        return sql, want, EXACT

    def cpu_max_all_8(self, first_host: int):
        hrs = min(8, self.hours)
        hs = list(range(first_host, first_host + 8))
        inl = ", ".join(f"'{self.hostnames[h]}'" for h in hs)
        items = ", ".join(f"max({f}) RANGE '1h'" for f in FIELDS)
        sql = (f"SELECT ts, hostname, {items} FROM cpu WHERE hostname IN "
               f"({inl}) AND ts < {hrs * 3_600_000} ALIGN '1h' "
               f"BY (hostname)")
        mx = [self._hourly(f, "max", hosts=hs, hours=hrs)
              for f in range(len(FIELDS))]
        want = {(k * 3_600_000, self.hostnames[h]):
                tuple(m[i, k] for m in mx)
                for i, h in enumerate(hs) for k in range(hrs)}
        return sql, want, EXACT

    def single_groupby_5_8_1(self, first_host: int):
        np = self.np
        hs = list(range(first_host, first_host + 8))
        inl = ", ".join(f"'{self.hostnames[h]}'" for h in hs)
        items = ", ".join(f"max({f}) RANGE '1m'" for f in FIELDS[:5])
        lo = self.end_ms - 3_600_000
        sql = (f"SELECT ts, hostname, {items} FROM cpu WHERE hostname IN "
               f"({inl}) AND ts >= {lo} AND ts < {self.end_ms} "
               f"ALIGN '1m' BY (hostname)")
        x = self.d[:5][:, hs, self.cells - CELLS_PER_HOUR:].astype(
            np.float64)
        x = x.reshape(5, 8, 60, 6).max(axis=3)          # (5, 8, 60)
        want = {(lo + m * 60_000, self.hostnames[h]):
                tuple(x[:, i, m])
                for i, h in enumerate(hs) for m in range(60)}
        return sql, want, EXACT

    def groupby_hostname(self, where: str = ""):
        np = self.np
        sql = ("SELECT hostname, count(usage_user), avg(usage_user), "
               f"max(usage_system) FROM cpu {where} GROUP BY hostname")
        avg = self.d[0].astype(np.float64).mean(axis=1)
        mx = self.d[1].astype(np.float64).max(axis=1)
        want = {(self.hostnames[h],): (float(self.cells), avg[h], mx[h])
                for h in range(self.hosts)}
        return sql, want, RTOL_F32_MEAN

    def promql_max(self, extra_matcher: str = ""):
        """max_over_time over (t-1h, t]: PromQL windows are left-open."""
        np = self.np
        q = ('max by (hostname) (max_over_time(cpu{__field__='
             f'"usage_user"{extra_matcher}}}[1h]))')
        want = {}
        x = self.d[0].astype(np.float64)
        for k in range(1, self.hours + 1):
            lo, hi = (k - 1) * CELLS_PER_HOUR + 1, k * CELLS_PER_HOUR + 1
            m = x[:, lo:min(hi, self.cells)].max(axis=1)
            for h in range(self.hosts):
                want[(k * 3600, self.hostnames[h])] = (m[h],)
        return q, want, EXACT

    # -- cross-shard legs (folds that cross shards; chips > 1) ---------
    def fold_all(self, where: str = ""):
        """BY (): every series folds into one group — the cross-shard
        blocked sum (gather_blocks + left fold), extreme (pmax) and
        last-value winner extraction (staged pext + psum)."""
        np = self.np
        sql = ("SELECT ts, count(usage_user) RANGE '1h', "
               "avg(usage_user) RANGE '1h', max(usage_user) RANGE '1h', "
               f"last_value(usage_user) RANGE '1h' FROM cpu {where} "
               "ALIGN '1h' BY ()")
        x = self.d[0].astype(np.float64).reshape(
            self.hosts, self.hours, CELLS_PER_HOUR)
        want = {}
        for k in range(self.hours):
            want[(k * 3_600_000,)] = (
                float(self.hosts * CELLS_PER_HOUR),
                x[:, k].mean(), x[:, k].max(),
                # last ts of the hour; ties across hosts resolve to the
                # HIGHEST series id = the last host written
                x[self.hosts - 1, k, -1],
            )
        return sql, want, RTOL_F32_MEAN

    def promql_topk(self, extra_matcher: str = ""):
        np = self.np
        q = ('topk(5, avg_over_time(cpu{__field__="usage_user"'
             f'{extra_matcher}}}[1h]))')
        want = {}
        x = self.d[0].astype(np.float64)
        for k in range(1, self.hours + 1):
            lo, hi = (k - 1) * CELLS_PER_HOUR + 1, k * CELLS_PER_HOUR + 1
            m = x[:, lo:min(hi, self.cells)].mean(axis=1)
            for h in np.argsort(-m)[:5]:
                want[(k * 3600, self.hostnames[int(h)])] = (m[int(h)],)
        return q, want, RTOL_F32_MEAN


def sql_rows_to_dict(rows: list, n_keys: int) -> dict:
    return {tuple(r[:n_keys]): tuple(r[n_keys:]) for r in rows}


def prom_to_dict(result: list) -> dict:
    out = {}
    for series in result:
        host = series["metric"].get("hostname", "")
        for ts, val in series["values"]:
            out[(int(float(ts)), host)] = (float(val),)
    return out


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------

def cache_dir() -> str:
    """The rule of greptimedb_tpu.instance.enable_compile_cache, applied
    from outside: JAX_COMPILATION_CACHE_DIR when set, else the fixed
    <checkout>/.jax_cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")


def cache_entries() -> int:
    n = 0
    for _root, _dirs, files in os.walk(cache_dir()):
        n += len(files)
    return n


def build_native(say) -> str:
    """The line-protocol tokenizer is a build product (.so is
    git-ignored): build it from the committed lineproto.c, fail if that
    fails, and say which parser will serve the line-protocol slice."""
    nat = os.path.join(HERE, "greptimedb_tpu", "native")
    check(os.path.isfile(os.path.join(nat, "lineproto.c")),
          f"{nat}/lineproto.c not found: chip_smoke.py must run from "
          "the root of a checkout")
    p = subprocess.run(
        ["make", "-C", nat, f"PY={sys.executable}"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=300,
    )
    check(p.returncode == 0, f"make -C {nat} failed:\n{p.stdout[-2000:]}")
    # importable by the interpreter the server runs under (this one):
    # greptimedb_tpu.native is a plain package, no jax behind it
    sys.path.insert(0, HERE)
    from greptimedb_tpu.native import _lineproto

    parser = f"native({os.path.basename(_lineproto.__file__)})"
    say(f"build: make -C greptimedb_tpu/native ok; line protocol parser "
        f"= {parser}")
    return parser


def run(args) -> dict:
    import numpy as np

    t_run = time.perf_counter()
    chips = args.chips
    hosts = args.hosts or (16_384 if chips > 1 else 4_000)
    hours = args.hours or (3 if chips > 1 else 12)
    cells = hours * CELLS_PER_HOUR
    rows_total = hosts * cells
    tag = {"platform": "?", "kind": "?", "count": 0}

    def say(msg: str):
        print(f"[platform={tag['platform']} kind={tag['kind']!r} "
              f"n={tag['count']} t={time.perf_counter() - t_run:6.1f}s] "
              f"{msg}", flush=True)

    report: dict = {"rows": rows_total, "hosts": hosts, "hours": hours,
                    "seed": args.seed, "chips": chips,
                    "phases_s": {}, "steps": []}
    say(f"TSBS devops cpu-only: {hosts} hosts x {hours} h @ 10 s = "
        f"{rows_total} rows x {len(FIELDS)} fields; reduced: {hours}h of "
        f"3d (source runs --scale=4000 for {SOURCE_HOURS} h)"
        + ("" if hosts == 4_000 else
           f"; series axis set to {hosts} hosts (source scale is 4000"
           + (": widened so the planner shards it over the mesh)"
              if chips > 1 else ")")))

    parser = build_native(say)
    report["lineproto_parser"] = parser

    out_dir = os.path.join(os.getcwd(), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    work = tempfile.mkdtemp(prefix="gtpu_smoke_")
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if args.cpu_rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    config = None
    mesh_off_config = None
    if chips > 1:
        # the ONLY non-default setting, and only for the several-chip
        # run: the mesh is off by default. Native devices — virtual
        # host devices only in the CPU rehearsal.
        config = os.path.join(work, "mesh_on.toml")
        with open(config, "w") as f:
            f.write("[mesh]\nenabled = true\n")
            if args.cpu_rehearsal:
                f.write(f"force_host_device_count = {chips}\n")
        mesh_off_config = os.path.join(work, "mesh_off.toml")
        with open(mesh_off_config, "w") as f:
            f.write("[mesh]\nenabled = false\n")
    srv = Server(os.path.join(work, "data"), out_dir, config=config,
                 env=env)
    try:
        return _drive(np, args, srv, report, tag, say,
                      mesh_off_config=mesh_off_config, t_run=t_run)
    finally:
        srv.kill()
        shutil.rmtree(work, ignore_errors=True)


def _drive(np, args, srv: Server, report, tag, say, *, mesh_off_config,
           t_run):
    chips, hosts, hours = args.chips, report["hosts"], report["hours"]
    cells = hours * CELLS_PER_HOUR
    rows_total = report["rows"]
    phases = report["phases_s"]
    cache0 = cache_entries()
    # ---- start + identity -------------------------------------------
    phases["open"] = round(srv.start(), 3)
    dev = srv.device()
    tag.update(platform=dev["platform"], kind=dev["device_kind"],
               count=dev["count"])
    say(f"start: server up in {phases['open']}s; device as the server "
        f"reports it: {dev['platform']} / {dev['device_kind']} x "
        f"{dev['count']}")
    want_platform = "cpu" if args.cpu_rehearsal else "tpu"
    check(dev["platform"] == want_platform,
          f"the server runs on platform={dev['platform']!r}, not "
          f"{want_platform!r}: no accelerator (pass --cpu-rehearsal for "
          "the explicit CPU dry run)")
    check(dev["count"] == chips,
          f"the server sees {dev['count']} device(s), --chips is {chips}")

    # ---- load ---------------------------------------------------------
    hostnames = [f"host_{i}" for i in range(hosts)]
    t0 = time.perf_counter()
    data = make_data(np, args.seed, hosts, cells)
    phases["generate"] = round(time.perf_counter() - t0, 3)
    cols = ", ".join(f"{f} double" for f in FIELDS)
    srv.sql(f"create table cpu (ts timestamp time index, "
            f"hostname string primary key, {cols})")
    t0 = time.perf_counter()
    acked = load(np, srv, data, hostnames, say)
    phases["load"] = round(time.perf_counter() - t0, 3)
    report["load"] = {k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in acked.items()}
    check(acked["acked_rows"] == rows_total,
          f"acknowledged {acked['acked_rows']} rows, sent {rows_total}")

    # ---- flush + compact (device merge) ------------------------------
    m0 = srv.metrics()
    t0 = time.perf_counter()
    srv.sql("ADMIN flush_table('cpu')")
    phases["flush"] = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    srv.sql("ADMIN compact_table('cpu')", timeout=1000.0)
    phases["compact"] = round(time.perf_counter() - t0, 3)
    m1 = srv.metrics()
    merges = {p: m1.get(k, 0.0) - m0.get(k, 0.0)
              for k in m1 if k[0] == "gtpu_compaction_merge_total"
              for p in [dict(k[1]).get("path", "")]}
    report["compaction"] = {
        "merges_by_path": merges,
        # the fused Pallas merge-gather was removed in PR 21 (Mosaic
        # refused its one-lane blocks): the device merge is the XLA
        # sort/dedup program, there is no kernel variant to choose
        "kernel": "xla(no_kernel_variant)",
    }
    say(f"flush {phases['flush']}s, compact {phases['compact']}s: "
        f"merges by path {merges}, kernel decision "
        f"{report['compaction']['kernel']}")
    check(merges.get("device", 0) >= 1,
          f"no compaction merged on the device path: {merges}")
    check(merges.get("host", 0) == 0,
          f"a compaction merge fell to the host path: {merges}")

    # ---- guarantees: acknowledged == counted --------------------------
    t0 = time.perf_counter()
    counted = int(srv.sql("select count(*) from cpu")[0][0])
    phases["count"] = round(time.perf_counter() - t0, 3)
    say(f"count(*) = {counted} (acknowledged {rows_total})")
    check(counted == rows_total,
          f"count(*) {counted} != acknowledged {rows_total}")

    # ---- queries -------------------------------------------------------
    wl = Workload(np, data, hostnames, hours)
    steps = [
        ("double-groupby-all", "range", "sql", 2,
         wl.flagship(), wl.flagship("WHERE ts >= 0")),
        ("lastpoint", "range", "sql", 2,
         wl.lastpoint(), wl.lastpoint("WHERE ts >= 0")),
        ("cpu-max-all-8", "range", "sql", 2,
         wl.cpu_max_all_8(0), wl.cpu_max_all_8(8)),
        ("single-groupby-5-8-1", "range", "sql", 2,
         wl.single_groupby_5_8_1(0), wl.single_groupby_5_8_1(8)),
        ("groupby-hostname", "aggregate", "sql", 1,
         wl.groupby_hostname(), wl.groupby_hostname("WHERE ts >= 0")),
        ("promql-max-over-time", "promql", "promql", 2,
         wl.promql_max(), wl.promql_max(',hostname!="no_such_host"')),
    ]
    if chips > 1:
        steps += [
            ("fold-all", "range", "sql", 1,
             wl.fold_all(), wl.fold_all("WHERE ts >= 0")),
            ("topk", "promql", "promql", 2,
             wl.promql_topk(),
             wl.promql_topk(',hostname!="no_such_host"')),
        ]
    answers: dict = {}
    for name, kind, wire, n_keys, cold, warm in steps:
        rec = _run_step(np, srv, name, kind, wire, n_keys, cold, warm,
                        hours, say, answers)
        report["steps"].append(rec)
    phases["first_query"] = report["steps"][0]["cold_s"]
    warm_all = sorted(s["warm_s"] for s in report["steps"])
    phases["warm_query_median"] = warm_all[len(warm_all) // 2]

    # ---- mesh evidence (several chips) --------------------------------
    if chips > 1:
        plan = "\n".join(
            r[0] for r in srv.sql("EXPLAIN ANALYZE " + wl.fold_all()[0]))
        notes = _notes(plan)
        report["mesh"] = {k: notes.get(k) for k in (
            "mesh_decision_range", "mesh_devices", "exec_path_range")}
        say(f"mesh: {report['mesh']}")
        check(notes.get("mesh_decision_range") == "shard(large_grid)",
              f"range not sharded: {notes.get('mesh_decision_range')}")
        check(notes.get("mesh_devices") == str(chips),
              f"mesh_devices {notes.get('mesh_devices')} != {chips}")
        in_use = srv.device()["bytes_in_use"]
        report["mesh"]["bytes_in_use"] = in_use
        if all(b is not None for b in in_use):
            say(f"mesh: bytes in use per device {in_use}")
            check(min(in_use) > 0 and max(in_use) <= 1.25 * min(in_use),
                  f"per-device bytes in use not within 25%: {in_use}")

    # ---- restart -------------------------------------------------------
    flag_sql, flag_want, flag_rtol = wl.flagship()
    before = srv.sql(flag_sql)
    t0 = time.perf_counter()
    _wait_snapshot(srv)
    phases["snapshot_wait"] = round(time.perf_counter() - t0, 3)
    srv.stop()
    cache1 = cache_entries()
    check(cache1 > 0, f"the compile cache at {cache_dir()} is empty after "
                      "the first life of the server")
    phases["reopen"] = round(srv.start(), 3)
    t0 = time.perf_counter()
    plan = "\n".join(r[0] for r in srv.sql("EXPLAIN ANALYZE " + flag_sql))
    phases["first_query_after_restart"] = round(
        time.perf_counter() - t0, 3)
    notes = _notes(plan)
    say(f"restart: reopened in {phases['reopen']}s, first query "
        f"{phases['first_query_after_restart']}s, grid_cache="
        f"{notes.get('grid_cache')}, exec_path="
        f"{notes.get('exec_path_range')}")
    check(notes.get("exec_path_range") == "device",
          f"after restart the flagship ran on "
          f"{notes.get('exec_path_range')}")
    check(notes.get("grid_cache") in ("hit", "miss(restored)"),
          f"after restart the grid was {notes.get('grid_cache')}, not "
          f"restored from its snapshot")
    after = srv.sql(flag_sql)
    check(sorted(map(tuple, after)) == sorted(map(tuple, before)),
          "the flagship answer changed across the restart")
    compare(np, "double-groupby-all(after restart)",
            sql_rows_to_dict(after, 2), flag_want, flag_rtol)
    counted2 = int(srv.sql("select count(*) from cpu")[0][0])
    check(counted2 == rows_total,
          f"count(*) after restart {counted2} != acknowledged "
          f"{rows_total}")
    say(f"restart: identical flagship answer, count(*) = {counted2}")
    report["restart"] = {"grid_cache": notes.get("grid_cache"),
                         "count": counted2}

    srv.stop()
    cache2 = cache_entries()
    report["cache_entries"] = {"dir": cache_dir(), "before": cache0,
                               "after_first_life": cache1,
                               "after_second_life": cache2}
    say(f"compile cache {cache_dir()}: {cache0} entries before, "
        f"{cache1} after the first life, {cache2} after the second")
    check(cache2 == cache1,
          f"the second life of the server added {cache2 - cache1} "
          "compile cache entries")

    # ---- mesh off: the cross-shard legs against one device -------------
    if chips > 1:
        srv.config = mesh_off_config
        phases["reopen_mesh_off"] = round(srv.start(), 3)
        # equal under the same tolerance as against the reference.
        # Whether the two are also BIT-identical is reported, not
        # required: XLA's own f32 reductions pick their order from the
        # shard-local shape on a TPU (PR 21 saw the unfolded flagship
        # differ in the last bits between 4 chips and 1, while every
        # cross-shard leg was bit-identical)
        report["mesh"]["mesh_off"] = {}
        for name in ("double-groupby-all", "groupby-hostname",
                     "fold-all", "topk"):
            wire, query, n_keys, on_mesh, rtol = answers[name]
            t0 = time.perf_counter()
            got = _ask(srv, wire, query, hours, n_keys)
            worst = compare(np, f"{name}(mesh-off vs {chips} chips)",
                            got, on_mesh, rtol)
            same = got == on_mesh
            report["mesh"]["mesh_off"][name] = {
                "bit_identical": same, "worst_rel_diff": worst}
            say(f"mesh-off {name}: equal to the {chips}-chip answer "
                f"(worst rel diff {worst:.2g}, bit-identical: {same}; "
                f"{time.perf_counter() - t0:.1f}s)")
        srv.stop()
    report["device"] = {"platform": tag["platform"], "kind": tag["kind"],
                        "count": tag["count"]}
    report["wall_s"] = round(time.perf_counter() - t_run, 1)
    return report


def _notes(plan_text: str) -> dict:
    """`key: value` lines of an EXPLAIN ANALYZE plan."""
    out = {}
    for ln in plan_text.splitlines():
        k, sep, v = ln.strip().partition(": ")
        if sep and " " not in k:
            out[k] = v.strip()
    return out


def _ask(srv: Server, wire: str, query: str, hours: int, n_keys: int):
    if wire == "sql":
        return sql_rows_to_dict(srv.sql(query), n_keys)
    return prom_to_dict(srv.prom_range(query, 3600, hours * 3600, 3600))


def _run_step(np, srv, name, kind, wire, n_keys, cold, warm, hours, say,
              answers) -> dict:
    """Cold then fresh-literal warm; both must run on the device (the
    server's own gtpu_query_exec_path_total / gtpu_device_program_*
    counters, read before and after) and equal the reference."""
    m0 = srv.metrics()
    rec = {"step": name}
    for label, (query, want, rtol) in (("cold", cold), ("warm", warm)):
        t0 = time.perf_counter()
        got = _ask(srv, wire, query, hours, n_keys)
        rec[f"{label}_s"] = round(time.perf_counter() - t0, 3)
        rec[f"{label}_worst_rel_err"] = compare(
            np, f"{name}({label})", got, want, rtol)
        if label == "cold":
            answers[name] = (wire, query, n_keys, got, rtol)
    m1 = srv.metrics()

    def delta(fam, **match):
        tot = 0.0
        for k, v in m1.items():
            if k[0] != fam:
                continue
            lb = dict(k[1])
            if all(lb.get(a) == b for a, b in match.items()):
                tot += v - m0.get(k, 0.0)
        return tot

    sess = delta("gtpu_session_hits_total")
    check(sess == 0, f"{name}: the session registry answered a variant")
    if kind == "promql":
        # the fast path answered both (no generic-engine fallback) and
        # a device program at a `promql` site was dispatched for each
        calls = delta("gtpu_device_program_calls_total", site="promql") \
            + delta("gtpu_device_program_calls_total", site="topk")
        fallback = delta("greptime_promql_fast_path_total",
                         event="fallback")
        rec["exec_path"] = f"device(promql programs: {int(calls)})"
        check(calls >= 2 and fallback == 0,
              f"{name}: {calls} promql device dispatches, {fallback} "
              "fast-path fallbacks")
    else:
        on_dev = delta("gtpu_query_exec_path_total", kind=kind,
                       path="device")
        off_dev = {dict(k[1])["path"]: v - m0.get(k, 0.0)
                   for k, v in m1.items()
                   if k[0] == "gtpu_query_exec_path_total"
                   and dict(k[1]).get("path", "").startswith("host")
                   and v - m0.get(k, 0.0) > 0}
        rec["exec_path"] = "device" if on_dev >= 2 and not off_dev \
            else f"host:{off_dev}"
        check(on_dev >= 2 and not off_dev,
              f"{name}: exec paths device={on_dev} host={off_dev}")
    say(f"{name}: cold {rec['cold_s']}s warm {rec['warm_s']}s "
        f"exec_path={rec['exec_path']} "
        f"rows={len(cold[1])} worst_rel_err="
        f"{max(rec['cold_worst_rel_err'], rec['warm_worst_rel_err']):.2g}")
    return rec


def _wait_snapshot(srv: Server):
    """The grid snapshot is written by a background thread after the
    build; a deployment restarts long after it, this run restarts at
    once, so wait for the file (bounded) before SIGTERM."""
    deadline = time.time() + 300
    while True:
        for root, _dirs, files in os.walk(srv.data_home):
            if os.path.basename(root) == "device_cache" and any(
                    f.endswith(".gtdc") for f in files):
                return
        check(time.time() < deadline,
              "no grid snapshot appeared under the data home")
        srv.alive()
        time.sleep(0.5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--hosts", type=int, default=0,
                    help="TSBS --scale (default 4000; 16384 with "
                         "--chips 4)")
    ap.add_argument("--hours", type=int, default=0,
                    help="hours of data (default 12; 3 with --chips 4)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="explicit CPU dry run of the same flow")
    ap.add_argument("--wall-limit", type=int, default=WALL_LIMIT_S,
                    help="fail after this many seconds (the driver "
                         "allows 1200)")
    args = ap.parse_args(argv)

    def on_alarm(*_a):
        raise SmokeFailure(f"wall limit {args.wall_limit}s exceeded")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(args.wall_limit)
    try:
        report = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    report["ok"] = True
    path = os.path.join(os.getcwd(), "chiprun_out",
                        "chip_smoke_report.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    dev = report["device"]
    print(f"[platform={dev['platform']} kind={dev['kind']!r} "
          f"n={dev['count']}] report: "
          + json.dumps(report, separators=(",", ":")), flush=True)
    # the result line: these two keys and nothing else, last on stdout
    print(json.dumps({"ok": True, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"])}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
