"""The fleet report: TSBS `double-groupby-all`, the mean of all ten
fields of every host by hour over everything the table holds, as the
benchmark's cell `tsbs-double-groupby-all` sends it (`benchmark/traffic/
tsbs_range.py`, `hosts: 0`, `span_hours: 0`). Served by `POST /v1/sql` on
a real server object from the plane program of `query/device_range.py`
and held to the plain reference that the configurations `tsbs-cpu-4000`
and `tsbs-cpu-4000-fleet` share (NumPy, float64), at a small size: 72
hosts (past `_ROWS_MAX`, so that no selection could take the rows
program even with a matcher) by 3 h.

Also the spans and counters the deployment added: `result.rows` and
`json.dumps` under `http.encode`, the answer's bytes and rows by route,
and `window=hit|miss` on `query.select_series`."""

import gc
import json
import os
import signal
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.datagen import tsbs_cpu as dg  # noqa: E402
from benchmark.lib.compare import compare_rows  # noqa: E402
from benchmark.lib.files import load_json, reference  # noqa: E402
from benchmark.traffic import tsbs_range  # noqa: E402
from greptimedb_tpu import cli  # noqa: E402
from greptimedb_tpu.instance import Standalone  # noqa: E402
from greptimedb_tpu.query import device_range as DR  # noqa: E402
from greptimedb_tpu.servers.http import HttpServer  # noqa: E402
from test_tracing import (  # noqa: E402
    _family, _finished_trace, _post_sql as post_sql, _render,
)

CFG = load_json(ROOT, "benchmark", "configs", "tsbs-cpu-4000-fleet.json")
CELL = load_json(ROOT, "benchmark", "workloads",
                 "tsbs-double-groupby-all.json")
REF = reference(CFG)
LIMIT = CELL["limits"]["worst_rel_err"]
SCALE = {"hosts": 72, "hours": 3}
HOUR_CELLS = dg.CELLS_PER_HOUR
HOUR_MS = 3_600_000
BIG_SEED = 2**31 + 3301


def counter(family: str, **labels) -> float:
    return _family(_render(), family, **labels) or 0.0


class Fleet:
    """A server holding the table `cpu` of one seed, and the cell's
    traffic over it. `mask` (hosts, cells) says which rows were ever
    written."""

    def __init__(self, home: str, seed: int, mask=None):
        self.ds = dg.make(np, seed, SCALE)
        self.ds.reference = REF
        self.mask = mask
        self.st = tsbs_range.prepare(np, CELL["params"], self.ds, seed, 64)
        self.inst = Standalone(home, prefer_device=True, warm_start=False)
        self.srv = HttpServer(self.inst, port=0).start()
        self.port = self.srv.port
        dg.create_table(self.inst)
        hosts, cells = self.ds.hosts, self.ds.cells
        host = np.repeat(np.arange(hosts), cells)
        keep = (np.ones(hosts * cells, bool) if mask is None
                else mask.reshape(-1))
        cols = {t: np.asarray(self.ds.tags[t], object)[host][keep]
                for t in dg.TAGS}
        cols["ts"] = (np.tile(np.arange(cells, dtype=np.int64), hosts)
                      * dg.INTERVAL_MS)[keep]
        for f, name in enumerate(dg.FIELDS):
            cols[name] = self.ds.values[f].reshape(-1).astype(
                np.float64)[keep]
        self.inst._write_columns(
            self.inst.catalog.table("public", "cpu"), cols, {})

    def close(self):
        self.srv.stop()
        self.inst.close()

    def answer(self, i: int, **headers) -> dict:
        return tsbs_range.parse(
            np, self.st, i, post_sql(self.port, tsbs_range.sql(self.st, i),
                                     **headers))

    def expected(self, mask=None) -> dict:
        """The reference's rows over every host and the whole span,
        counting the rows of `mask` (default: the rows written)."""
        mask = self.mask if mask is None else mask
        vals, present = REF.range_agg(
            np, self.ds.values, fields=list(range(10)), hosts=None, c_lo=0,
            c_hi=self.ds.cells, bucket_cells=HOUR_CELLS, op="avg", mask=mask)
        return REF.as_rows(vals, present, hostnames=self.ds.hostnames,
                           hosts=None, t_lo_ms=0, bucket_ms=HOUR_MS)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    pytest.importorskip("jax")
    f = Fleet(str(tmp_path_factory.mktemp("fleet")), BIG_SEED)
    yield f
    f.close()


def _held_to_reference(got: dict, want: dict):
    cmp = compare_rows(np, got, want)
    assert cmp["rows_missing"] == 0, cmp
    assert cmp["values"] == len(want) * 10
    assert cmp["worst_rel_err"] <= LIMIT, cmp


@pytest.mark.parametrize("seed", [1, 7, BIG_SEED + 1])
def test_every_host_every_field_every_hour_equals_the_reference(
        tmp_path, seed):
    pytest.importorskip("jax")
    f = Fleet(str(tmp_path / "home"), seed)
    try:
        got = f.answer(0)
        assert len(got) == SCALE["hosts"] * SCALE["hours"]
        assert {len(v) for v in got.values()} == {10}
        _held_to_reference(got, f.expected())
        # the traffic's own expectation is the same rows
        _held_to_reference(got, tsbs_range.expected(np, f.st, 0))
    finally:
        f.close()


def test_the_plane_program_answers_and_nothing_else(fleet):
    assert SCALE["hosts"] > DR._ROWS_MAX
    before = {k: counter("gtpu_range_selection_total", path=k)
              for k in ("plane", "rows")}
    dev = counter("gtpu_query_exec_path_total", kind="range", path="device")
    every = counter("gtpu_query_exec_path_total", kind="range")
    n = 3
    for i in range(n):
        assert "hostname IN" not in tsbs_range.sql(fleet.st, i)
        fleet.answer(i)
    assert counter("gtpu_range_selection_total",
                   path="plane") - before["plane"] == n
    assert counter("gtpu_range_selection_total",
                   path="rows") == before["rows"]
    assert counter("gtpu_query_exec_path_total", kind="range",
                   path="device") - dev == n
    assert counter("gtpu_query_exec_path_total", kind="range") - every == n


def _without_an_hour(ds, host: int, hour: int):
    mask = np.ones((ds.hosts, ds.cells), bool)
    mask[host, hour * HOUR_CELLS:(hour + 1) * HOUR_CELLS] = False
    return mask


@pytest.mark.parametrize("how", ["never_written", "deleted"])
def test_a_host_without_an_hour_has_no_row_for_it(tmp_path, how):
    """host_5 lacks its second hour: that (ts, hostname) row is absent
    from the answer as from the reference, every other row is there."""
    pytest.importorskip("jax")
    host, hour = 5, 1
    ds = dg.make(np, 11, SCALE)
    mask = _without_an_hour(ds, host, hour)
    f = Fleet(str(tmp_path / "home"), 11,
              mask if how == "never_written" else None)
    try:
        if how == "deleted":
            f.answer(0)        # the grid holds the rows that now go
            f.inst.sql(f"delete from cpu where hostname = 'host_{host}' "
                       f"and ts >= {hour * HOUR_MS} "
                       f"and ts < {(hour + 1) * HOUR_MS}")
        got = f.answer(1)
        want = f.expected(mask)
        assert (hour * HOUR_MS, f"host_{host}") not in want
        assert (hour * HOUR_MS, f"host_{host}") not in got
        assert len(got) == SCALE["hosts"] * SCALE["hours"] - 1
        _held_to_reference(got, want)
    finally:
        f.close()


def test_a_partly_filled_last_hour_is_the_mean_of_the_rows_present(
        tmp_path):
    """host_9 stops reporting 100 rows into the last hour, host_20 a
    single row into it: their last buckets are means over what is there,
    not over 360 cells."""
    pytest.importorskip("jax")
    ds = dg.make(np, 13, SCALE)
    mask = np.ones((ds.hosts, ds.cells), bool)
    last = (SCALE["hours"] - 1) * HOUR_CELLS
    mask[9, last + 100:] = False
    mask[20, last + 1:] = False
    f = Fleet(str(tmp_path / "home"), 13, mask)
    try:
        got = f.answer(0)
        _held_to_reference(got, f.expected())
        ts = (SCALE["hours"] - 1) * HOUR_MS
        for host, n in ((9, 100), (20, 1)):
            want = ds.values[:, host, last:last + n].astype(
                np.float64).mean(axis=1)
            assert np.allclose(got[(ts, f"host_{host}")], want,
                               rtol=LIMIT, atol=0)
        whole = ds.values[:, 9, last:].astype(np.float64).mean(axis=1)
        assert not np.allclose(got[(ts, "host_9")], whole, rtol=1e-4)
    finally:
        f.close()


def test_two_literals_one_answer_and_the_window_memo_misses(fleet):
    """Queries that differ only in the vacuous literal give the same
    rows. The window memo keys on the WHERE's own bounds
    (`device_range.py`: `win_key`), so each fresh literal is
    `window=miss` on a selection that is `memo=hit`; the same statement
    again is `window=hit`."""
    q = [tsbs_range.sql(fleet.st, i) for i in (4, 5)]
    assert q[0] != q[1]
    assert q[0].split("ts >=")[0] == q[1].split("ts >=")[0]
    raw = [json.loads(post_sql(fleet.port, s)) for s in q]
    rows = [d["output"][-1]["records"]["rows"] for d in raw]
    assert rows[0] == rows[1]
    seen = []
    for n, sql in enumerate((q[0], tsbs_range.sql(fleet.st, 6),
                             tsbs_range.sql(fleet.st, 6))):
        tid = f"{0xa0 + n:02x}" * 16
        post_sql(fleet.port, sql, traceparent=f"00-{tid}-{'cd' * 8}-01")
        sel = next(s for s in _finished_trace(tid)
                   if s["name"] == "query.select_series")
        seen.append((sel["attributes"]["memo"], sel["attributes"]["window"]))
    assert seen == [("hit", "hit"), ("hit", "miss"), ("hit", "hit")]


def test_rows_come_in_the_order_of_order_by_ts_hostname(fleet):
    sql = tsbs_range.sql(fleet.st, 8)
    plain = json.loads(post_sql(fleet.port, sql))
    ordered = json.loads(post_sql(fleet.port, sql + " ORDER BY ts, hostname"))
    rows = plain["output"][-1]["records"]["rows"]
    assert rows == ordered["output"][-1]["records"]["rows"]
    keys = [(r[0], r[1]) for r in rows]
    # hostnames order as strings: host_10 before host_2
    assert keys == sorted(keys) and len(set(keys)) == len(keys)
    assert keys[1][1] == "host_1" and keys[2][1] == "host_10"


def test_encode_has_its_two_children_and_they_take_time(fleet):
    tid = "b7" * 16
    post_sql(fleet.port, tsbs_range.sql(fleet.st, 9),
             traceparent=f"00-{tid}-{'cd' * 8}-01")
    spans = _finished_trace(tid)
    by_id = {s["span_id"]: s for s in spans}
    encode = next(s for s in spans if s["name"] == "http.encode")
    kids = {s["name"]: s for s in spans
            if s["name"] in ("result.rows", "json.dumps")}
    assert set(kids) == {"result.rows", "json.dumps"}
    for s in kids.values():
        assert by_id[s["parent_id"]] is encode
        assert s["duration_ms"] > 0
    assert sum(s["duration_ms"] for s in kids.values()) <= \
        encode["duration_ms"]


def test_span_families_and_answer_counters_move(fleet):
    families = {
        "rows_s": ("gtpu_span_seconds_sum", {"name": "result.rows"}),
        "rows_n": ("gtpu_span_seconds_count", {"name": "result.rows"}),
        "dumps_s": ("gtpu_span_seconds_sum", {"name": "json.dumps"}),
        "dumps_n": ("gtpu_span_seconds_count", {"name": "json.dumps"}),
        "bytes": ("gtpu_http_response_bytes_total", {"path": "/v1/sql"}),
        "rows": ("gtpu_query_rows_returned_total", {"path": "/v1/sql"}),
    }

    def read():
        text = _render()
        return {k: _family(text, fam, **labels) or 0.0
                for k, (fam, labels) in families.items()}

    a = read()
    tid = "b9" * 16
    body = post_sql(fleet.port, tsbs_range.sql(fleet.st, 10),
                    traceparent=f"00-{tid}-{'cd' * 8}-01")
    _finished_trace(tid)
    b = read()
    assert b["rows_n"] - a["rows_n"] == 1 and b["rows_s"] > a["rows_s"]
    assert b["dumps_n"] - a["dumps_n"] == 1 and b["dumps_s"] > a["dumps_s"]
    assert b["bytes"] - a["bytes"] == len(body)
    assert b["rows"] - a["rows"] == SCALE["hosts"] * SCALE["hours"]
    # a statement that returns no rows counts its bytes and no row
    body = post_sql(fleet.port, "delete from cpu where hostname = 'nobody'")
    c = read()
    assert c["bytes"] - b["bytes"] == len(body)
    assert c["rows"] == b["rows"]


def test_the_heap_of_start_up_is_out_of_the_collectors_reach():
    """Every role serves from `_serve_until_signal`, and it settles the
    heap first: what start-up made is frozen, so the full collections
    that 32,000 fresh row lists an answer bring on walk the requests'
    objects alone. (The fleet cell's answers fell into modes a full
    collection apart: PERF.md section 6, PR 33.)"""
    closed = []
    old = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    before = gc.get_freeze_count()
    timer = threading.Timer(0.3, os.kill, (os.getpid(), signal.SIGTERM))
    timer.start()
    try:
        assert cli._serve_until_signal([lambda: closed.append(1)]) == 0
        assert closed == [1]
        frozen = gc.get_freeze_count() - before
        assert frozen > 10_000, frozen
        # a full collection now walks what was made since, not the lot
        assert len(gc.get_objects()) < frozen / 4
    finally:
        timer.cancel()
        gc.unfreeze()
        for s, h in old.items():
            signal.signal(s, h)
    assert gc.get_freeze_count() == 0     # the interpreter's own few too
