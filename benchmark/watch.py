#!/usr/bin/env python3
"""A run of the benchmark with the machine watched beside it.

    python3 benchmark/watch.py --out <file.jsonl> [--every 1.0] -- \
        python3 benchmark/run.py --workload <cell> --seed <n> ...
    python3 benchmark/watch.py --read <file.jsonl>

Starts the command as a child (its output is the command's own) and,
until it ends, appends one JSON line a tick to `--out`. A tick holds
what tells a slower clock from more work when a stretch of a window
reads slow (PERF.md section 6, PR 35):

  spin       for each CPU of the machine, a fixed piece of work (the
             same 40,000 steps of integer arithmetic) run there by a
             thread of this process: [cpu, wall us, thread-CPU us]. A
             core that runs slower (its sibling busy, its clock down)
             takes more thread-CPU time for the same work; a core that
             is taken away shows in wall time alone.
  spin_mem   the same idea for the memory system: one fixed gather of
             a million words from 64 MB, on the server's CPUs.
  spin_heap  and for an interpreter's heap: 40,000 steps along a chain
             of two million integer objects in shuffled order, each a
             cache miss, as a server's own objects are.
  spin_sys, spin_fault, spin_write
             and for the kernel under the processes (on the chip's
             machine a sandbox kernel in user space, whose /proc/stat
             and /proc/vmstat read nothing): 2,000 `getppid` calls; 4 MB
             mapped, every page touched, unmapped; 1 MB written to a
             file beside `--out` and truncated. Wall us each. A kernel
             that is busy (reclaiming, writing back, collecting) slows
             these and every request's system calls, and leaves the
             arithmetic alone.
  server     of the `standalone start` child, once it is there: CPU
             seconds (utime + stime) by thread name, resident bytes,
             page faults, context switches; and of its /metrics the counts and sums
             of the request histograms by path, every `gtpu_span_seconds`
             and the collector's pauses. CPU seconds over requests
             answered is the work a request took.
  harness    CPU seconds, resident bytes and page faults of the command.
  machine    /proc/stat by CPU (user, system, idle, iowait, irq,
             softirq, steal, in ticks), /proc/loadavg, the pressure
             files, and the counters of /proc/vmstat and /proc/meminfo
             that move when the kernel works for a process (write-back,
             compaction, huge pages, page migration).

`--read` prints a file's ticks as one table: a row a tick, its columns
named in the first line.

Nothing here is part of the benchmark: no cell runs it, and the run it
watches is the harness's own command, unchanged. The fixed work costs
each CPU some 3 ms a tick.
"""

from __future__ import annotations

import argparse
import json
import mmap
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

_TICK = os.sysconf("SC_CLK_TCK")
_PAGE = os.sysconf("SC_PAGE_SIZE")
_BLOCK = bytes(256 << 10)
_VMSTAT = re.compile(
    r"^(pgfault|pgmajfault|pgpgin|pgpgout|nr_dirty|nr_writeback|"
    r"thp_fault_alloc|thp_collapse_alloc|thp_split_page|compact_stall|"
    r"compact_migrate_scanned|numa_hint_faults|numa_pages_migrated|"
    r"pgmigrate_success|allocstall_\w+|pgscan_\w+|pgsteal_\w+|"
    r"pswpin|pswpout) (\d+)$", re.M)
_MEMINFO = re.compile(
    r"^(MemFree|Cached|Dirty|Writeback|AnonHugePages|Mapped):\s+(\d+)", re.M)
_METRIC = re.compile(
    r"^(greptime_servers_http_latency_seconds_(?:sum|count)\{[^}]*\}|"
    r"gtpu_span_seconds_(?:sum|count)\{[^}]*\}|"
    r"gtpu_runtime_gc_pause_seconds_(?:sum|count)\{[^}]*\}|"
    r"gtpu_grid_upkeep_total\{[^}]*\}|gtpu_grid_upkeep_rows_total) "
    r"(\S+)$", re.M)


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def spin_cpu() -> tuple:
    """The fixed work: (wall us, thread-CPU us)."""
    w0, c0 = time.perf_counter_ns(), time.thread_time_ns()
    x = 1
    for _ in range(40_000):
        x = (x * 31 + 7) & 0xFFFF
    return ((time.perf_counter_ns() - w0) / 1000.0,
            (time.thread_time_ns() - c0) / 1000.0)


def spin_kernel(scratch: str) -> dict:
    """Fixed work for the kernel: wall us of each kind."""
    out = {}
    t0 = time.perf_counter_ns()
    for _ in range(2000):
        os.getppid()
    out["spin_sys"] = round((time.perf_counter_ns() - t0) / 1000.0, 1)
    t0 = time.perf_counter_ns()
    with mmap.mmap(-1, 4 << 20) as m:
        for at in range(0, 4 << 20, _PAGE):
            m[at] = 1
    out["spin_fault"] = round((time.perf_counter_ns() - t0) / 1000.0, 1)
    t0 = time.perf_counter_ns()
    fd = os.open(scratch, os.O_WRONLY | os.O_CREAT, 0o600)
    try:
        for at in range(4):
            os.pwrite(fd, _BLOCK, at * len(_BLOCK))
        os.ftruncate(fd, 0)
    finally:
        os.close(fd)
    out["spin_write"] = round((time.perf_counter_ns() - t0) / 1000.0, 1)
    return out


class Spinner(threading.Thread):
    """Runs the fixed work on each CPU in turn when asked to: a thread
    of its own, because `sched_setaffinity(0, ...)` moves the calling
    thread alone and the command must inherit the whole machine."""

    def __init__(self, cpus: list, mem_cpus: list, scratch: str):
        super().__init__(daemon=True)
        self.cpus, self.mem_cpus, self.scratch = cpus, mem_cpus, scratch
        self.ask, self.done = threading.Event(), threading.Event()
        self.out: dict = {}
        import numpy as np

        rng = np.random.default_rng(1)
        self.chain = rng.permutation(1 << 21).tolist()
        self.words = np.zeros(16 << 20, np.int32)          # 64 MB
        self.idx = rng.integers(0, len(self.words), 1 << 20)

    def run(self):
        while True:
            self.ask.wait()
            self.ask.clear()
            spin = []
            for cpu in self.cpus:
                os.sched_setaffinity(0, [cpu])
                wall, cpu_us = spin_cpu()
                spin.append([cpu, round(wall, 1), round(cpu_us, 1)])
            os.sched_setaffinity(0, self.mem_cpus)
            w0, c0 = time.perf_counter_ns(), time.thread_time_ns()
            int(self.words[self.idx].sum())
            out = {"spin": spin, "spin_mem": [
                round((time.perf_counter_ns() - w0) / 1000.0, 1),
                round((time.thread_time_ns() - c0) / 1000.0, 1)]}
            chain, at = self.chain, 0
            w0 = time.perf_counter_ns()
            for _ in range(40_000):
                at = chain[at]
            out["spin_heap"] = round((time.perf_counter_ns() - w0) / 1000.0, 1)
            out.update(spin_kernel(self.scratch))
            self.out = out
            self.done.set()


def descendants(root: int) -> list:
    """[(pid, cmdline)] of every live descendant of `root`."""
    kids: dict = {}
    for name in os.listdir("/proc"):
        if name.isdigit():
            stat = _read(f"/proc/{name}/stat")
            if stat:
                kids.setdefault(
                    int(stat.rsplit(")", 1)[1].split()[1]), []).append(
                        int(name))
    out, todo = [], [root]
    while todo:
        for pid in kids.get(todo.pop(), []):
            todo.append(pid)
            out.append((pid, _read(f"/proc/{pid}/cmdline").replace(
                "\0", " ")))
    return out


def process(pid: int, threads: bool) -> dict:
    """CPU seconds, memory and faults of a process; with `threads`, CPU
    seconds by thread name."""
    stat = _read(f"/proc/{pid}/stat")
    if not stat:
        return {}
    f = stat.rsplit(")", 1)[1].split()
    out = {"cpu_s": (int(f[11]) + int(f[12])) / _TICK,
           "minflt": int(f[7]), "majflt": int(f[9]),
           "rss": int(f[21]) * _PAGE}
    for key in ("voluntary_ctxt_switches", "nonvoluntary_ctxt_switches"):
        m = re.search(key + r":\s+(\d+)", _read(f"/proc/{pid}/status"))
        if m:
            out[key] = int(m.group(1))
    if threads:
        by_name: dict = {}
        try:
            tids = os.listdir(f"/proc/{pid}/task")
        except OSError:
            tids = []
        for tid in tids:
            st = _read(f"/proc/{pid}/task/{tid}/stat")
            if not st:
                continue
            name = st.split("(", 1)[1].rsplit(")", 1)[0]
            g = st.rsplit(")", 1)[1].split()
            by_name[name] = round(by_name.get(name, 0.0) + (
                int(g[11]) + int(g[12])) / _TICK, 2)
        out["threads"] = by_name
    return out


def machine() -> dict:
    cpus = {}
    for ln in _read("/proc/stat").splitlines():
        if ln.startswith("cpu") and ln[3:4].isdigit():
            p = ln.split()
            # user+nice, system, idle, iowait, irq, softirq, steal
            cpus[p[0][3:]] = [int(p[1]) + int(p[2]), int(p[3]), int(p[4]),
                              int(p[5]), int(p[6]), int(p[7]), int(p[8])]
    out = {"stat": cpus, "loadavg": _read("/proc/loadavg").split()[:3],
           "vmstat": {k: int(v) for k, v in
                      _VMSTAT.findall(_read("/proc/vmstat"))},
           "meminfo_kb": {k: int(v) for k, v in
                          _MEMINFO.findall(_read("/proc/meminfo"))}}
    for what in ("cpu", "io", "memory"):
        text = _read(f"/proc/pressure/{what}")
        if text:
            out.setdefault("pressure", {})[what] = [
                int(m) for m in re.findall(r"total=(\d+)", text)]
    return out


def scrape(addr: str) -> dict:
    try:
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=2) as resp:
            text = resp.read().decode()
    except (OSError, ValueError):
        return {}
    return {k: float(v) for k, v in _METRIC.findall(text)}


_SQL = 'greptime_servers_http_latency_seconds_%s{path="/v1/sql"}'
_BODY = 'greptime_servers_http_latency_seconds_%s{path="/v1/influxdb/*"}'
_UPKEEP = 'gtpu_span_seconds_%s{name="grid.upkeep"}'


def summarize(path: str) -> None:
    """One row a tick, from one tick to the next: requests answered and
    their mean time by the server's own histograms, the server's CPU
    milliseconds a request, and beside them what the fixed work took."""
    with open(path) as f:
        ticks = [json.loads(ln) for ln in f if ln.strip()]
    print("t_s sql_n sql_ms body_n body_ms upkeep_ms srv_cpu_ms_per_req "
          "srv_cpu_per_s spin_median_us spin_worst_us spin_cpu_median_us "
          "spin_mem_us spin_heap_us spin_sys_us spin_fault_us spin_write_us "
          "steal_ticks "
          "srv_minflt srv_rss_mb dirty_kb")
    for a, b in zip(ticks, ticks[1:]):
        if "server" not in a or not b.get("server") or "spin" not in b:
            continue
        dt = b["t"] - a["t"]

        def d(key, m0=a.get("metrics", {}), m1=b.get("metrics", {})):
            return m1.get(key, 0.0) - m0.get(key, 0.0)

        sql_n, body_n = d(_SQL % "count"), d(_BODY % "count")
        cpu = b["server"]["cpu_s"] - a["server"].get("cpu_s", 0.0)
        wall = sorted(w for _c, w, _us in b["spin"])
        cpu_us = sorted(us for _c, _w, us in b["spin"])
        steal = sum(v[6] for v in b["machine"]["stat"].values()) - sum(
            v[6] for v in a["machine"]["stat"].values())
        up_n = d(_UPKEEP % "count")
        row = [b["t"] - ticks[0]["t"], sql_n,
               1e3 * d(_SQL % "sum") / sql_n if sql_n else 0.0, body_n,
               1e3 * d(_BODY % "sum") / body_n if body_n else 0.0,
               1e3 * d(_UPKEEP % "sum") / up_n if up_n else 0.0,
               1e3 * cpu / (sql_n + body_n) if sql_n + body_n else 0.0,
               cpu / dt, wall[len(wall) // 2], wall[-1],
               cpu_us[len(cpu_us) // 2], b.get("spin_mem", [0.0])[0],
               b.get("spin_heap", 0.0), b.get("spin_sys", 0.0),
               b.get("spin_fault", 0.0),
               b.get("spin_write", 0.0), steal,
               b["server"]["minflt"] - a["server"]["minflt"],
               b["server"]["rss"] / 1e6,
               b["machine"]["meminfo_kb"].get("Dirty", 0)]
        print(" ".join(f"{v:.3f}" if isinstance(v, float) else str(v)
                       for v in row))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--read", help="print a file's ticks as a table")
    ap.add_argument("--out")
    ap.add_argument("--every", type=float, default=1.0)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if args.read:
        summarize(args.read)
        return 0
    if not args.out:
        ap.error("--out or --read")
    command = args.command[1:] if args.command[:1] == ["--"] else args.command
    if not command:
        ap.error("no command after --")
    cpus = sorted(os.sched_getaffinity(0))
    child = subprocess.Popen(command)
    # the harness's own split (lib/server.py:split_cpus): the server
    # keeps all but the first quarter
    n_gen = min(4, max(1, len(cpus) // 4)) if len(cpus) > 1 else 0
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    spinner = Spinner(cpus, cpus[n_gen:], args.out + ".spin")
    spinner.start()
    server, addr = None, None
    with open(args.out, "w") as out, child:
        while child.poll() is None:
            t0 = time.time()
            spinner.done.clear()
            spinner.ask.set()
            if server is None or not os.path.exists(f"/proc/{server}"):
                server = addr = None
                for pid, cmd in descendants(child.pid):
                    m = re.search(r"greptimedb_tpu\.cli standalone start"
                                  r".*--http-addr (\S+)", cmd)
                    if m:
                        server, addr = pid, m.group(1)
            line = {"t": t0, "machine": machine(),
                    "harness": process(child.pid, threads=False)}
            if server is not None:
                line["server"] = process(server, threads=True)
                line["metrics"] = scrape(addr)
            spinner.done.wait(timeout=5)
            line.update(spinner.out)
            line["tick_s"] = round(time.time() - t0, 4)
            out.write(json.dumps(line, separators=(",", ":")) + "\n")
            out.flush()
            time.sleep(max(0.0, args.every - (time.time() - t0)))
    if os.path.exists(spinner.scratch):
        os.unlink(spinner.scratch)
    return child.returncode


if __name__ == "__main__":
    sys.exit(main())
