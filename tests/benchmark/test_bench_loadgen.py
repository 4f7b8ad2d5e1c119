"""The load generator and the end-to-end arithmetic: nearest-rank
percentiles, the generator's gap share, a closed loop that waits for a
late answer and records when it really came, and the control tool."""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.traffic import tsbs_range  # noqa: E402
from benchmark.lib import loadgen  # noqa: E402
from benchmark.lib.loadgen import Record  # noqa: E402


@pytest.mark.parametrize("vals,p,want", [
    ([1.0, 2.0, 3.0, 4.0], 0.50, 2.0),
    ([1.0, 2.0, 3.0, 4.0], 0.95, 4.0),
    (list(range(1, 101)), 0.95, 95),
    (list(range(1, 101)), 0.50, 50),
    ([7.0], 0.95, 7.0),
], ids=["p50-of-4", "p95-of-4", "p95-of-100", "p50-of-100", "one"])
def test_percentile_is_nearest_rank(vals, p, want):
    assert loadgen.percentile(vals, p) == want


def test_gap_share_counts_the_time_between_a_reply_and_the_next_send():
    # two workers over a 10 s window; worker 0 idles 1 s before its
    # first send and 2 s between its requests, worker 1 never idles and
    # its last answer comes after the close
    recs = [Record(0, 0, 1.0, 4.0, 200, None),
            Record(2, 0, 6.0, 10.0, 200, None),
            Record(1, 1, 0.0, 5.0, 200, None),
            Record(3, 1, 5.0, 12.0, 200, None)]
    assert loadgen.gap_share(recs, 0.0, 10.0, 2) == pytest.approx(3 / 20)
    assert loadgen.gap_share([], 0.0, 10.0, 2) == 0.0


def test_default_end_to_end_is_over_all_the_window():
    good = [Record(i, 0, 0.0, 0.0, 200, None) for i in range(40)]
    lat = sorted(float(i) for i in range(1, 41))
    e2e = tsbs_range.end_to_end(None, good, lat, 8.0)
    assert e2e == {"query_p50_ms": 20.0, "query_p95_ms": 38.0,
                   "queries_per_s": 5.0}
    assert tsbs_range.end_to_end(None, [], [], 8.0) == {}


class _Slow(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        body = self.rfile.read(int(self.headers["Content-Length"]))
        doc = json.loads(body)
        time.sleep(doc["sleep"])
        out = json.dumps({"echo": doc["i"]}).encode()
        self.send_response(doc.get("status", 200))
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def do_GET(self):       # the generator's first word on a connection
        self.send_response(200)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, *_a):
        pass


@pytest.fixture
def slow_server():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()


def _req(i, sleep, status=200):
    return ("POST", "/x", json.dumps(
        {"i": i, "sleep": sleep, "status": status}).encode(), {})


def test_closed_loop_waits_for_the_late_answer(slow_server):
    # one worker: three quick requests, then one that outlasts the close
    reqs = [_req(0, 0.0), _req(1, 0.0), _req(2, 0.0), _req(3, 0.6),
            _req(4, 0.0)]
    recs, t0, t1, exhausted = loadgen.run_window(
        slow_server, reqs, workers=1, seconds=0.3,
        keep_body=lambda i: i == 1, ok_status=(200,))
    assert not exhausted
    assert [r.i for r in recs] == [0, 1, 2, 3]      # 4 was never sent
    assert t1 - t0 == pytest.approx(0.3)
    late = recs[-1]
    assert late.status == 200 and late.t_done > t1  # late, not wrong
    assert late.t_done - late.t_send >= 0.6
    assert json.loads(recs[1].body) == {"echo": 1}
    assert recs[0].body is None and recs[2].body is None


def test_closed_loop_keeps_a_refused_answer_and_says_when_it_ran_dry(
        slow_server):
    reqs = [_req(0, 0.0), _req(1, 0.0, status=503), _req(2, 0.0)]
    recs, _t0, _t1, exhausted = loadgen.run_window(
        slow_server, reqs, workers=2, seconds=5,
        keep_body=lambda i: False, ok_status=(200,), start_at=1)
    assert exhausted
    assert [r.i for r in recs] == [1, 2]
    assert recs[0].status == 503 and b"echo" in recs[0].body
    assert {r.worker for r in recs} <= {0, 1}


def test_fifty_workers_open_their_connections_without_a_reset(
        slow_server):
    """The stdlib server listens with a backlog of 5: fifty connections
    opened at once overflow it and are reset."""
    reqs = [_req(i, 0.001) for i in range(400)]
    recs, _t0, _t1, _dry = loadgen.run_window(
        slow_server, reqs, workers=50, seconds=0.5,
        keep_body=lambda i: False, ok_status=(200,))
    assert len(recs) >= 50 and {r.status for r in recs} == {200}
    assert len({r.worker for r in recs}) == 50


@pytest.mark.parametrize("cell,differs", [
    ("tsbs-single-groupby-1-1-1", "values_differing"),
    ("tsbs-single-groupby-1-1-1-w50", "values_differing"),
    ("tsbs-load", "readback_avg_rel_err"),
    ("tsbs-load", "readback_rows_differing"),
])
def test_control_tool_fails_bfloat16_and_passes_float32(
        cell, differs, load_checkout):
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "control.py"),
         "--workload", cell, "--seeds", f"3,{2**31 + 9}", "--requests",
         "20", "--scale", "hosts=16", "--scale", "hours=2"],
        cwd=load_checkout, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    docs = [json.loads(ln) for ln in p.stdout.splitlines()]
    assert [d["seed"] for d in docs] == [3, 2**31 + 9]
    for d in docs:
        assert d["control_fails"] and d["stated_precision_passes"]
        assert d["bfloat16"][differs] > 3 * max(
            d["limits"][differs], d["float32"][differs])
        assert set(d["float32"]) == set(d["limits"])
