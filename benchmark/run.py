#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Starts `python -m greptimedb_tpu.cli standalone start` with the DEFAULT
configuration as a child, talks to it over sockets only (this process
never imports jax), sets up, warms the cell's own shapes, measures one
window from the client's side, compares what the clients received with
the plain reference, prints the result line and leaves no process
behind. Cells, configurations, traffic mixes and per-layer metrics are
data, found by the names in BENCHMARK.json (see benchmark/README.md).

Every run builds its data from `--seed` in a fresh data home of its own
and removes it: nothing a run leaves but the compile cache is read by
the next. A run has 360 s from start to exit, so every phase prints one
timeline line (`t=...s phase`) to stderr, and a watchdog of the
harness's own ends a run that is about to overrun.

`--cpu-rehearsal` with `--scale key=value` overrides is the explicit dry
run: the device it names is the CPU, and no device metric is printed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import select
import shutil
import signal
import subprocess
import sys
import threading
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import loadgen  # noqa: E402
from benchmark.lib.compare import compare_rows  # noqa: E402
from benchmark.lib.files import (  # noqa: E402
    cell_files, load_json, module, reference,
)
from benchmark.readers import delta  # noqa: E402
from benchmark.lib.server import (  # noqa: E402
    BenchFailure, Server, adopt_orphans, check, split_cpus, sweep,
)

WATCHDOG_S = 335.0          # a run has 360 s from start to exit
TRACE_S = 4.0               # of the window's middle, in a `--trace 1` run
END_SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


class Timeline:
    """The run's phases: calling it prints one line to stderr and keeps
    it as [seconds since the process started, phase]."""

    def __init__(self):
        self.phases: list = []

    def __call__(self, msg: str):
        t = time.perf_counter() - T_PROCESS
        self.phases.append([round(t, 1), msg])
        print(f"t={t:6.1f}s {msg}", file=sys.stderr, flush=True)


def dir_bytes(top: str) -> int:
    total = 0
    for d, _dirs, files in os.walk(top):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(d, f))
            except OSError:
                pass
    return total


class Run:
    """One run of one cell. `hooks` lets a test break the timed path
    underneath (tests/benchmark): `before_window(run)` once the
    cell is warm, `before_check(run)` once the window has closed."""

    def __init__(self, args, hooks: dict | None = None):
        self.args = args
        self.hooks = hooks or {}
        self.phase = Timeline()
        self.tell = lambda line: None   # to the janitor, once there is one
        self.spare_pids: tuple = ()     # children the clean-up leaves alone
        self.platform_wanted = "cpu" if args.cpu_rehearsal else "tpu"
        self.tag = {"platform": "?", "kind": "?", "count": 0}
        self.state_dir = args.state_dir or os.path.join(HERE, ".run")
        self.srv: Server | None = None
        self.top: str | None = None  # the run's data home, removed at the end
        self.numbers: list = []      # [name, value, limit]
        self.notes: dict = {}
        self.mem_peak = 0
        self.peaks = None

    def number(self, name: str, value, limit):
        self.numbers.append([name, value, limit])

    # -- files -----------------------------------------------------------
    def load_files(self):
        a = self.args
        check(os.path.isdir(os.path.join(ROOT, "greptimedb_tpu")),
              "no greptimedb_tpu/ beside benchmark/: the benchmark runs "
              "from the root of a checkout of the program")
        manifest, self.cell, self.wl, self.cfg = cell_files(a.workload)
        cells = [w["name"] for w in manifest["workloads"]]
        self.scale = dict(self.cfg["scale"])
        for kv in a.scale or []:
            k, _, v = kv.partition("=")
            check(a.cpu_rehearsal, "--scale is for --cpu-rehearsal only")
            check(k in self.scale, f"the configuration has no scale {k!r}")
            self.scale[k] = int(v)
        self.datagen = module("datagen", self.cfg["datagen"])
        self.traffic = module("traffic", self.wl["generator"])
        self.e2e = [m for m in manifest["end_to_end"]
                    if a.workload in m.get("workloads", cells)]
        self.per_layer = [m for m in manifest["per_layer"]
                          if a.workload in m.get("workloads", cells)]
        self.peaks_table = load_json(HERE, "peaks.json")

    # -- set-up ------------------------------------------------------------
    def build_native(self):
        nat = os.path.join(ROOT, "greptimedb_tpu", "native")
        p = subprocess.run(
            ["make", "-C", nat, f"PY={sys.executable}"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            timeout=120)
        check(p.returncode == 0,
              f"make -C {nat} failed:\n{p.stdout[-2000:]}")

    def new_server(self, data_home: str):
        gen_cpus, srv_cpus = split_cpus()
        os.sched_setaffinity(0, gen_cpus)
        self.notes["cpus"] = {"generator": gen_cpus, "server": srv_cpus}
        log_dir = os.path.join(self.state_dir, "logs", self.args.workload)
        self.srv = Server(ROOT, data_home, log_dir,
                          platform=self.platform_wanted, cpus=srv_cpus,
                          on_spawn=lambda pid: self.tell(f"group {pid}"))

    def start_and_identify(self):
        srv = self.srv
        opened = srv.start()
        self.phase(f"server answers /health after {opened:.1f}s")
        dev = srv.device()
        self.tag.update(platform=dev["platform"], kind=dev["device_kind"],
                        count=dev["count"])
        self.phase(f"device: {dev['platform']} / {dev['device_kind']} x "
                   f"{dev['count']}")
        check(dev["platform"] == self.platform_wanted,
              f"the server runs on platform={dev['platform']!r}, not "
              f"{self.platform_wanted!r}: no accelerator (pass "
              "--cpu-rehearsal for the explicit CPU dry run)")
        check(dev["count"] >= self.cell["chips"],
              f"the server sees {dev['count']} device(s), the cell asks "
              f"for {self.cell['chips']}")
        if self.platform_wanted == "tpu":
            check(dev["device_kind"] in self.peaks_table["devices"],
                  f"device kind {dev['device_kind']!r} is not in "
                  "benchmark/peaks.json")
            self.peaks = self.peaks_table["devices"][dev["device_kind"]]
        self.sample_memory(dev)

    def sample_memory(self, dev: dict | None = None) -> dict:
        """The server reports bytes in use, not a peak of its own: the
        run keeps the largest reading it takes (after the load, after
        warm-up, in the traced window, after the window)."""
        dev = dev or self.srv.device()
        used = [b for b in dev.get("bytes_in_use", []) if b is not None]
        if used:
            self.mem_peak = max(self.mem_peak, max(used))
        return dev

    def hold_data(self, np, ds):
        """A query cell's held data, on the server that will serve the
        window: create, load, flush, build the configuration's grids,
        count. No restart and no compaction: the cells are served from
        the grid by the server that loaded the rows, and every
        acknowledged row is counted before the window."""
        srv, table = self.srv, self.cfg["table"]
        acked = self.datagen.load(np, srv, ds, self.phase)
        check(acked["acked_rows"] == ds.rows,
              f"acknowledged {acked['acked_rows']} rows of {ds.rows}")
        srv.sql(f"ADMIN flush_table('{table}')")
        self.phase("flushed")
        for q in self.cfg.get("grid_warm_sql", []):
            srv.sql(q)
        self.phase("grid built")
        counted = int(srv.sql(f"select count(*) from {table}")[0][0])
        self.number("rows_acked_not_counted", abs(counted - ds.rows), 0)
        self.phase(f"count(*) = {counted}")

    # -- the run -----------------------------------------------------------
    def run(self) -> dict:
        import numpy as np

        a = self.args
        self.load_files()
        wl, traffic = self.wl, self.traffic
        self.build_native()
        self.phase("native parser built")
        os.makedirs(self.state_dir, exist_ok=True)
        ds = self.datagen.make(np, a.seed, self.scale)
        ds.reference = reference(self.cfg)
        budget = int(wl["max_requests_per_s"] * a.seconds) + 64
        st = traffic.prepare(np, wl["params"], ds, a.seed, budget)
        requests = [traffic.request(st, i) for i in range(st.n)]
        self.phase(f"data and {st.n} requests made from the seed")
        # a fresh data home at a fixed path: what a killed run left
        # there goes now, and this run's goes when the run ends
        top = os.path.join(self.state_dir, f"home-{a.workload}")
        shutil.rmtree(top, ignore_errors=True)
        os.makedirs(top)
        self.top = top
        self.tell(f"home {top}")
        try:
            self.new_server(os.path.join(top, "home"))
            self.start_and_identify()
            if traffic.KIND == "query":
                self.hold_data(np, ds)
            return self.measure(np, st, requests)
        finally:
            self.close()

    def measure(self, np, st, requests) -> dict:
        a, wl, traffic, srv = self.args, self.wl, self.traffic, self.srv
        # -- warm-up: this cell's shapes and no others ----------------------
        if hasattr(traffic, "setup"):
            traffic.setup(np, st, srv, self.phase)
        n_warm = int(wl.get("warm_requests", 4))
        ok_status = tuple(wl.get("ok_status", [200]))
        warm_rec, _, _, _ = loadgen.run_window(
            srv.http, requests[:n_warm], workers=1, seconds=120,
            keep_body=lambda i: True, ok_status=ok_status)
        for r in warm_rec:
            check(r.status in ok_status,
                  f"warm-up request {r.i} answered {r.status}: "
                  f"{(r.body or b'')[:300]!r}")
        # a second pass at the window's own concurrency, so that every
        # worker's connection path and the server's threads are warm
        n_warm2 = n_warm + int(wl["workers"]) * int(
            wl.get("warm_rounds", 2))
        warm2, _, _, _ = loadgen.run_window(
            srv.http, requests[:n_warm2], workers=int(wl["workers"]),
            seconds=120, keep_body=lambda i: False, ok_status=ok_status,
            start_at=n_warm)
        self.phase(f"warm: {n_warm2} requests")
        if "before_window" in self.hooks:
            self.hooks["before_window"](self)
        os.sync()       # set-up's dirty pages go to disk in set-up
        self.sample_memory()
        m0 = srv.metrics()
        s0 = self.statement_sums()
        # -- the window ---------------------------------------------------
        tracer = None
        trace_box: dict = {}
        if a.trace:
            tracer = threading.Thread(
                target=self.capture_trace,
                args=(a.seconds * 0.4, min(TRACE_S, a.seconds / 3),
                      trace_box),
                daemon=True)
        setup_s = time.perf_counter() - T_PROCESS
        self.phase(f"window opens: {a.seconds:g}s, {wl['workers']} closed-loop "
                   f"clients; setup_s = {setup_s:.1f}")
        if tracer:
            tracer.start()
        gc.collect()
        gc.disable()        # no collector pause in the generator's window
        try:
            records, t0, t1, exhausted = loadgen.run_window(
                srv.http, requests, workers=int(wl["workers"]),
                seconds=a.seconds,
                keep_body=lambda i: True, ok_status=ok_status,
                start_at=n_warm2)
        finally:
            gc.enable()
        if tracer:
            tracer.join(timeout=60)
        m1 = srv.metrics()
        s1 = self.statement_sums()
        health = self.sample_memory()
        check(not exhausted,
              f"the window used up all {st.n} rendered requests: raise "
              "max_requests_per_s in the workload's file")
        # -- what the window did ---------------------------------------------
        done = [r for r in records if r.t_done <= t1]
        good = [r for r in done if r.status in ok_status]
        failed = [r for r in records if r.status not in ok_status]
        window_s = t1 - t0
        # a rate counts what completed inside the window; a latency is of
        # every request sent in it, the wait past the close counted
        answered = [r for r in records if r.status in ok_status]
        lat = sorted((r.t_done - r.t_send) * 1000.0 for r in answered)
        client = {
            # the server's counters are read once the late answers are
            # in, so what divides them counts those too
            "requests_answered": float(len(answered)),
            "request_seconds": sum(r.t_done - r.t_send for r in answered),
            "window_s": window_s,
            "generator_gap_share": 100.0 * loadgen.gap_share(
                done, t0, t1, int(wl["workers"])),
        }
        # where in the window the answers came, and the ten slowest with
        # the second they were sent in, so that a stall (a flush, a
        # collection) shows in a run's own line
        by_5s = [0] * (int(window_s // 5) + 1)
        for r in good:
            by_5s[int((r.t_done - t0) // 5)] += 1
        self.notes["answered_by_5s"] = by_5s
        # the same slices in time: p50 and p95 of the queries sent in
        # each, so that a slow stretch shows in a run's own line
        by_sent: list = [[] for _ in by_5s]
        for r in answered:
            by_sent[max(0, int((r.t_send - t0) // 5))].append(
                (r.t_done - r.t_send) * 1000.0)
        self.notes["p50_p95_by_5s"] = [
            [round(loadgen.percentile(sorted(v), 0.50), 3),
             round(loadgen.percentile(sorted(v), 0.95), 3)] if v else None
            for v in by_sent]
        self.notes["slowest_ms"] = [
            [round(r.t_send - t0, 3), (r.t_done - r.t_send) * 1000.0]
            for r in sorted(answered, key=lambda r: r.t_send - r.t_done)[:10]]
        e2e = traffic.end_to_end(st, good, lat, window_s)
        e2e["setup_s"] = setup_s
        self.phase(f"window closed: {len(records)} sent, {len(good)} answered "
                   f"in time, {len(failed)} failed; "
                   + ", ".join(f"{k}={v:.4g}" for k, v in e2e.items()))
        # -- in-window events that a warm-up must have taken ----------------
        ctx = {"m0": m0, "m1": m1, "client": client, "health": health,
               "shapes": traffic.shapes(st), "peaks": self.peaks,
               "s0": s0, "s1": s1, "trace": None}
        self.notes["in_window"] = {
            "compiles": s1.get("compile_count", 0.0)
            - s0.get("compile_count", 0.0),
            "programs_new": delta(ctx, "gtpu_device_programs") or 0.0,
            "compaction_merges": delta(
                ctx, "gtpu_compaction_merge_total") or 0.0,
        }   # the program counts no flushes: PERF.md, Open questions
        self.check_exec_path(ctx, len(records))
        # -- after the window: what a cell checks on the live server ---------
        if "before_check" in self.hooks:
            self.hooks["before_check"](self)
        if hasattr(traffic, "after_window"):
            traffic.after_window(np, st, srv, warm_rec + warm2 + records,
                                 self, ok_status)
            self.sample_memory()
            self.phase("read back")
        self.notes["home_bytes"] = dir_bytes(self.top)
        srv.stop()
        self.srv = None
        self.phase("server stopped")
        # -- the comparison, once the server's state is freed ------------------
        self.number("requests_failed", len(failed), 0)
        if failed:
            r = failed[0]
            self.phase(f"first failed request {r.i}: status {r.status} "
                       f"{(r.body or b'')[:300]!r}")
        self.compare_answers(np, st, records, ok_status)
        # -- per-layer ---------------------------------------------------------
        result_metrics = {}
        device = {"platform": self.tag["platform"],
                  "kind": self.tag["kind"], "count": self.tag["count"],
                  "memory_peak_bytes": self.mem_peak}
        out = {}
        if a.trace:
            ctx["trace"] = tr = self.reduce_trace(trace_box) or {}
            if tr.get("busy_s"):
                device["busy_s"] = tr["busy_s"]
                device["window_s"] = tr["window_s"]
                out["breakdown"] = {"device_ops": tr["device_ops"],
                                    "idle_gaps": tr["idle_gaps"]}
            else:
                check(self.platform_wanted == "cpu",
                      "the trace holds no operation on the device: "
                      f"{trace_box.get('error') or tr}")
            for m in self.per_layer:
                v = self.read_metric(m["name"], ctx)
                if v is not None:
                    result_metrics[m["name"]] = {
                        "value": v, "unit": m["unit"]}
            out["end_to_end_traced"] = e2e
            self.phase("trace reduced")
        else:
            for m in self.e2e:
                check(m["name"] in e2e,
                      f"the window gave no {m['name']}")
                result_metrics[m["name"]] = {
                    "value": e2e[m["name"]], "unit": m["unit"]}
        correct = all(v is not None and v <= lim
                      for _n, v, lim in self.numbers)
        self.notes["phases"] = self.phase.phases
        return {
            "correct": bool(correct), "attempted": len(records),
            "failed": len(failed), "metrics": result_metrics,
            "device": device, **out, "notes": self.notes,
            "compared": {n: {"value": v, "limit": lim}
                         for n, v, lim in self.numbers},
        }

    def statement_sums(self) -> dict:
        """/v1/stats/statements with every numeric field summed over
        the statements: `compile_count` is the dispatches that compiled
        (`compile="first_call"`), `queue_total_ms` the admission wait."""
        doc = json.loads(self.srv.get("/v1/stats/statements?limit=1000"))
        out: dict = {}
        for st in doc.get("statements", []):
            for k, v in st.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out[k] = out.get(k, 0.0) + v
        return out

    def check_exec_path(self, ctx, sent: int):
        ep = getattr(self.traffic, "EXEC_PATH", None)
        if not ep:
            return
        on_device = delta(ctx, ep["family"], {
            **ep["match"], ep.get("label", "path"): ep["device"]}) or 0.0
        total = delta(ctx, ep["family"], ep["match"]) or 0.0
        off = total - on_device
        self.number("queries_off_device", off + max(0.0, sent - total), 0)

    def compare_answers(self, np, st, records, ok_status):
        """Every answer of the window against the reference; the
        limits come from the workload's file."""
        traffic, wl = self.traffic, self.wl
        if not hasattr(traffic, "expected"):
            return
        kept = [r for r in records
                if r.status in ok_status and r.body is not None]
        t0 = time.perf_counter()
        worst = {"rows_missing": 0, "values_differing": 0,
                 "worst_rel_err": 0.0}
        unparsed = 0
        values = 0
        for r in kept:
            try:
                got = traffic.parse(np, st, r.i, r.body)
            except (ValueError, KeyError, IndexError, TypeError):
                unparsed += 1
                continue
            got_cmp = compare_rows(np, got, traffic.expected(np, st, r.i))
            values += got_cmp["values"]
            for k in worst:
                worst[k] = max(worst[k], got_cmp[k])
        self.notes["compared_answers"] = len(kept)
        self.notes["compared_values"] = values
        self.notes["compare_s"] = time.perf_counter() - t0
        self.number("answers_none_compared", int(not kept), 0)
        self.number("answers_unparsed", unparsed, 0)
        for k, lim in wl["limits"].items():
            self.number(k, worst[k], lim)
        self.phase(f"compared {len(kept)} answers, {values} values")

    def capture_trace(self, after_s: float, seconds: float, box: dict):
        time.sleep(after_s)
        out = os.path.join(self.top, "trace")
        os.makedirs(out, exist_ok=True)
        try:
            doc = json.loads(self.srv.get(
                f"/debug/prof/device/trace?seconds={seconds}&dir={out}",
                timeout=120))
            self.sample_memory()
            box["doc"] = doc
        except (BenchFailure, OSError, ValueError) as e:
            box["error"] = repr(e)

    def reduce_trace(self, box: dict) -> dict | None:
        doc = box.get("doc")
        if not doc:
            self.phase(f"trace: none captured ({box.get('error')})")
            return None
        pb = [f for f in doc["files"] if f.endswith(".xplane.pb")]
        if not pb:
            return None
        path = os.path.join(doc["trace_dir"], pb[0])
        out = path + ".reduced.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        argv = [sys.executable, os.path.join(HERE, "lib", "xplane.py"),
                path, out]
        t0 = time.perf_counter()
        p = subprocess.run(argv, env=env, stdout=subprocess.PIPE,
                           stderr=subprocess.STDOUT, text=True, timeout=120)
        check(p.returncode == 0, f"trace reduction failed:\n"
                                 f"{p.stdout[-2000:]}")
        red = load_json(out)
        self.notes["trace"] = {
            "bytes": os.path.getsize(path),
            "reduce_s": time.perf_counter() - t0,
            "programs": red.get("programs")}
        return red

    def read_metric(self, name: str, ctx):
        spec = load_json(HERE, "metrics", name + ".json")
        reader = module("readers", spec["reader"])
        if getattr(reader, "DEVICE", False) and (
                self.platform_wanted == "cpu"):
            return None
        return reader.read(spec, ctx)

    def close(self):
        """Every path out of a run ends here, and may come here twice:
        the server and its group are gone and waited for, then every
        other descendant of this process but the janitor, then the data
        home. No wait here is without a time limit."""
        if self.srv is not None:
            self.srv.kill()
            self.srv = None
        sweep(self.phase, spare=self.spare_pids)
        if self.top is not None:
            shutil.rmtree(self.top, ignore_errors=True)
            self.top = None


def print_result(doc: dict):
    compared = doc.pop("compared")
    notes = doc.pop("notes")
    # each number compared beside its limit: last on stderr, and in the
    # result line under a key of its own that comes last
    line = {**doc, "notes": notes, "compared": compared}
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, separators=(",", ":")), flush=True)


class WayOut:
    """The forced ways out of a run. A thread of its own waits on a
    pipe that the C-level signal handler writes to, so it also wakes
    while the main thread sits in a call that holds no interpreter: on
    SIGTERM, SIGINT or SIGHUP, or when the watchdog's time is up, it
    prints the timeline so far, stops everything the run started,
    removes the data home and exits non-zero. The janitor is what is
    left for a harness killed outright (benchmark/lib/janitor.py)."""

    def __init__(self, run: Run, watchdog_s: float):
        self.run = run
        self.deadline = T_PROCESS + watchdog_s
        self.lock = threading.Lock()
        self.over = False
        self.rfd, self.wfd = os.pipe()
        os.set_blocking(self.wfd, False)
        self.janitor = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "lib", "janitor.py")],
            stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
            start_new_session=True, text=True)
        run.tell = self.tell
        run.spare_pids = (self.janitor.pid,)
        for sig in END_SIGNALS:
            signal.signal(sig, lambda _s, _f: None)
        signal.set_wakeup_fd(self.wfd, warn_on_full_buffer=False)
        self.thread = threading.Thread(target=self.watch, daemon=True)
        self.thread.start()

    def tell(self, line: str):
        try:
            self.janitor.stdin.write(line + "\n")
            self.janitor.stdin.flush()
        except (OSError, ValueError):
            pass

    def watch(self):
        left = self.deadline - time.perf_counter()
        ready, _, _ = select.select([self.rfd], [], [], max(0.0, left))
        signum = os.read(self.rfd, 1)[0] if ready else 0
        with self.lock:
            if self.over:
                return
            # (a further signal only writes to the pipe: nothing reads)
            why = (f"ended by signal {signum}" if signum else
                   f"the watchdog's {self.deadline - T_PROCESS:.0f}s are up")
            phase = self.run.phase
            phase(f"FAILED: {why}; the run so far:")
            for t, msg in phase.phases[:-1]:
                print(f"    t={t:6.1f}s {msg}", file=sys.stderr)
            sys.stderr.flush()
            self.run.close()
            self.dismiss_janitor()
            os._exit(128 + signum if signum else 124)

    def dismiss_janitor(self):
        try:
            self.janitor.stdin.close()
        except OSError:
            pass
        try:
            self.janitor.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.janitor.kill()
            self.janitor.wait(timeout=5)

    def done(self):
        """The run ended by itself: from here on no signal ends it."""
        with self.lock:
            self.over = True
        for sig in END_SIGNALS:
            signal.signal(sig, signal.SIG_IGN)
        signal.set_wakeup_fd(-1)
        os.write(self.wfd, b"\0")       # lets the thread go
        self.run.close()
        self.dismiss_janitor()


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="explicit CPU dry run of the same flow")
    ap.add_argument("--scale", action="append", metavar="KEY=N",
                    help="override a scale of the configuration "
                         "(rehearsal only), e.g. --scale hosts=64")
    ap.add_argument("--state-dir", default=None,
                    help="where the run's data home and the server's log "
                         "live (default benchmark/.run)")
    return ap.parse_args(argv)


def main(argv=None, hooks: dict | None = None,
         watchdog_s: float = WATCHDOG_S) -> int:
    args = parse_args(argv)
    run = Run(args, hooks)
    adopt_orphans()
    way_out = WayOut(run, watchdog_s)
    doc = None
    try:
        doc = run.run()
    except BenchFailure as e:
        run.phase(f"FAILED: {e}")
    finally:
        way_out.done()
    if doc is None:
        return 1
    print_result(doc)
    return 0


if __name__ == "__main__":
    sys.exit(main())
