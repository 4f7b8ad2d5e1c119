"""`benchmark/watch.py`: a command run with the machine watched beside
it. A tick holds the fixed work's times and the machine's counters, the
command's exit code is the watcher's own, and `--read` turns a file of
ticks into one table."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WATCH = os.path.join(ROOT, "benchmark", "watch.py")
SQL = 'greptime_servers_http_latency_seconds_%s{path="/v1/sql"}'


def test_a_watched_command_leaves_a_tick_a_line_and_its_exit_code(tmp_path):
    out = tmp_path / "ticks.jsonl"
    p = subprocess.run(
        [sys.executable, WATCH, "--every", "0.3", "--out", str(out), "--",
         sys.executable, "-c", "import time, sys; time.sleep(1.2); "
                               "sys.exit(7)"],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 7, p.stderr[-2000:]
    ticks = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert len(ticks) >= 2
    for tick in ticks:
        assert {"t", "machine", "harness", "spin", "spin_heap", "spin_sys",
                "spin_fault", "spin_write", "tick_s"} <= set(tick)
        assert len(tick["spin"]) == len(os.sched_getaffinity(0))
        assert all(wall > 0 for _cpu, wall, _us in tick["spin"])
        assert "server" not in tick     # no `standalone start` below it
    assert ticks[-1]["harness"]["rss"] > 0
    assert not os.path.exists(str(out) + ".spin")


def _tick(t, sql_n, sql_s, cpu_s):
    return {"t": t, "tick_s": 0.1, "spin": [[0, 3000.0, 3000.0]],
            "spin_mem": [9000.0, 9000.0], "spin_heap": 11000.0,
            "spin_sys": 400.0, "spin_fault": 2500.0, "spin_write": 300.0,
            "machine": {"stat": {"0": [0] * 7}, "meminfo_kb": {"Dirty": 8}},
            "harness": {"cpu_s": 1.0},
            "server": {"cpu_s": cpu_s, "minflt": 0, "rss": 5e8},
            "metrics": {SQL % "count": sql_n, SQL % "sum": sql_s}}


def test_read_prints_a_row_a_tick(tmp_path):
    path = tmp_path / "ticks.jsonl"
    ticks = [_tick(0.0, 0, 0.0, 0.0), _tick(2.0, 250, 1.0, 2.0),
             _tick(4.0, 450, 2.0, 4.4)]
    path.write_text("".join(json.dumps(t) + "\n" for t in ticks))
    p = subprocess.run([sys.executable, WATCH, "--read", str(path)],
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    head, *rows = [ln.split() for ln in p.stdout.splitlines()]
    assert len(rows) == 2 and all(len(r) == len(head) for r in rows)
    row = dict(zip(head, map(float, rows[1])))
    # 200 queries in 2 s took 1 s of the server's time and 2.4 s of CPU
    assert row["sql_n"] == 200 and row["sql_ms"] == 5.0
    assert row["srv_cpu_ms_per_req"] == 12.0 and row["srv_cpu_per_s"] == 1.2
    assert row["spin_median_us"] == 3000.0 and row["spin_heap_us"] == 11000.0
