"""The load generator: closed-loop workers on keep-alive connections,
one thread each, all in this one process. A worker sends its next
request only when the last one has answered. Requests are rendered
before the window; the window only sends, receives and keeps bytes.
"""

from __future__ import annotations

import http.client
import itertools
import threading
import time


class Record:
    __slots__ = ("i", "worker", "t_send", "t_done", "status", "body")

    def __init__(self, i, worker, t_send, t_done, status, body):
        self.i = i
        self.worker = worker
        self.t_send = t_send
        self.t_done = t_done
        self.status = status
        self.body = body


def run_window(addr: str, requests: list, *, workers: int, seconds: float,
               keep_body, ok_status: tuple, timeout: float = 120.0,
               start_at: int = 0):
    """Drive `requests[start_at:]` closed-loop for `seconds`. Returns
    (records, t0, t1, exhausted). A request sent before the close is
    waited for and recorded with its real finish time, so the caller
    can tell late from in time. `keep_body(i)` says whether the bytes
    of answer i are kept for the comparison."""
    host, _, port = addr.partition(":")
    counter = itertools.count(start_at)
    out: list[list] = [[] for _ in range(workers)]
    exhausted = threading.Event()
    ready = threading.Barrier(workers + 1)
    box = {}
    # the connections are opened here, one after the other, and each
    # has answered once before a worker takes it: the stdlib server
    # listens with a backlog of 5 and resets what 50 workers opening
    # theirs at once would overflow, and its thread a connection is
    # started before the window, not in it
    conns = []
    for _ in range(workers):
        conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
        conn.request("GET", "/health")
        conn.getresponse().read()
        conns.append(conn)

    def work(w: int):
        conn = conns[w]
        mine = out[w]
        ready.wait()
        deadline = box["deadline"]
        while True:
            i = next(counter)
            if i >= len(requests):
                exhausted.set()
                break
            method, path, body, headers = requests[i]
            t_send = time.perf_counter()
            if t_send >= deadline:
                break
            try:
                conn.request(method, path, body=body, headers=headers)
                resp = conn.getresponse()
                data = resp.read()
                status = resp.status
            except (OSError, http.client.HTTPException) as e:
                t_done = time.perf_counter()
                mine.append(Record(i, w, t_send, t_done, -1,
                                   repr(e).encode()))
                conn.close()
                conn = http.client.HTTPConnection(
                    host, int(port), timeout=timeout)
                continue
            t_done = time.perf_counter()
            keep = status not in ok_status or keep_body(i)
            mine.append(Record(i, w, t_send, t_done, status,
                               data if keep else None))
        conn.close()

    threads = [threading.Thread(target=work, args=(w,), daemon=True)
               for w in range(workers)]
    for t in threads:
        t.start()
    box["deadline"] = time.perf_counter() + seconds + 0.05
    ready.wait()
    t0 = time.perf_counter()
    box["deadline"] = t0 + seconds
    for t in threads:
        t.join(timeout=seconds + timeout + 60)
    records = sorted((r for rs in out for r in rs), key=lambda r: r.i)
    return records, t0, t0 + seconds, exhausted.is_set()


def gap_share(records: list, t0: float, t1: float, workers: int) -> float:
    """Share of the window's worker-time in which a worker held a reply
    and had not yet sent its next request."""
    by_worker: dict = {}
    for r in records:
        by_worker.setdefault(r.worker, []).append(r)
    gap = 0.0
    for rs in by_worker.values():
        rs.sort(key=lambda r: r.t_send)
        prev = t0
        for r in rs:
            gap += max(0.0, min(r.t_send, t1) - prev)
            prev = min(r.t_done, t1)
    return gap / (workers * (t1 - t0))


def percentile(sorted_vals: list, p: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    n = len(sorted_vals)
    k = max(0, min(n - 1, int(-(-p * n // 1)) - 1))
    return sorted_vals[k]
