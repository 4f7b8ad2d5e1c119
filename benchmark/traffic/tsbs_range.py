"""The general generator of TSBS range panels: `agg(field..) RANGE b
ALIGN b BY (hostname)` over some hosts and a span of hours, by
`POST /v1/sql`. One traffic mix is a file of parameters:

    agg         "max" | "avg"
    fields      how many of the ten fields, in TSBS's order
    hosts       how many hosts a query names; 0 = every host, no matcher
    span_hours  length of the queried span; 0 = all the data holds
    bucket_s    the bucket: 60 (by minute) or 3600 (by hour)

`tsbs-single-groupby-<fields>-<hosts>-<hours>`, `cpu-max-all-<hosts>` and
`double-groupby-<fields|all>` are all points of this family. Query i is
a pure function of (seed, i): the hosts and the start of the span are
drawn from the seed, no two queries of a run carry the same literals, so
neither the session registry nor a result cache can answer one. A query
over every host spans the whole data and carries a `WHERE ts >= <lit>`
whose literal, a different whole number of buckets before the data, is
what differs.
"""

from __future__ import annotations

import json
import urllib.parse

from benchmark.datagen.tsbs_cpu import FIELDS, INTERVAL_MS
from benchmark.lib.compare import compare_rows
from benchmark.lib.loadgen import percentile

KIND = "query"
EXEC_PATH = {"family": "gtpu_query_exec_path_total",
             "match": {"kind": "range"}, "device": "device"}
_HEADERS = {"Content-Type": "application/x-www-form-urlencoded"}


class State:
    pass


def prepare(np, params: dict, ds, seed: int, budget: int) -> State:
    st = State()
    st.agg = params["agg"]
    st.fields = list(range(int(params["fields"])))
    st.n_hosts = int(params["hosts"])
    st.span_cells = (int(params["span_hours"]) * 3_600_000 // INTERVAL_MS
                     or ds.cells)
    st.bucket_ms = int(params["bucket_s"]) * 1000
    st.bucket_cells = st.bucket_ms // INTERVAL_MS
    st.ds = ds
    if st.span_cells > ds.cells:
        raise ValueError("the span is longer than the data")
    rng = np.random.default_rng([seed, 0x51E7])
    n = int(budget)
    starts = (ds.cells - st.span_cells) // st.bucket_cells + 1
    if st.n_hosts:
        # distinct (first host, start) pairs, in the seed's order
        space = ds.hosts * starts
        draw = rng.integers(0, space, int(n * 1.3) + 16)
        # the first requests are the warm-up's: they take the spans at
        # both ends of the data and the first series, whose group ids
        # are the identity and compile a variant without the fold
        draw = np.concatenate([[0, space - 1], draw])
        _, first = np.unique(draw, return_index=True)
        draw = draw[np.sort(first)][:n]
        if len(draw) < n:
            raise ValueError("the traffic has fewer distinct queries "
                             "than the budget asks for")
        st.first_host = draw // starts
        st.start_bucket = draw % starts
        st.host_step = rng.integers(1, ds.hosts, n)
    else:
        st.start_bucket = np.zeros(n, np.int64)
        # a distinct literal each: k buckets before the first row
        st.lit_k = rng.permutation(n * 4)[:n] + 1
    st.n = n
    st.memo = {}
    return st


def _hosts(st, i):
    if not st.n_hosts:
        return None
    h0, step = int(st.first_host[i]), int(st.host_step[i])
    # n distinct hosts: an arithmetic walk modulo a host count
    return [(h0 + k * step) % st.ds.hosts for k in range(st.n_hosts)] \
        if st.n_hosts > 1 else [h0]


def sql(st, i) -> str:
    items = ", ".join(
        f"{st.agg}({FIELDS[f]}) RANGE '{st.bucket_ms // 1000}s'"
        for f in st.fields)
    lo = int(st.start_bucket[i]) * st.bucket_ms
    hi = lo + st.span_cells * INTERVAL_MS
    hosts = _hosts(st, i)
    if hosts is None:
        where = f"ts >= {-int(st.lit_k[i]) * st.bucket_ms}"
    else:
        inl = ", ".join(f"'{st.ds.hostnames[h]}'" for h in hosts)
        where = f"hostname IN ({inl}) AND ts >= {lo} AND ts < {hi}"
    return (f"SELECT ts, hostname, {items} FROM cpu WHERE {where} "
            f"ALIGN '{st.bucket_ms // 1000}s' BY (hostname)")


def request(st, i):
    body = urllib.parse.urlencode({"sql": sql(st, i)}).encode()
    return "POST", "/v1/sql", body, _HEADERS


def parse(np, st, i, raw: bytes) -> dict:
    doc = json.loads(raw)
    rows = doc["output"][-1]["records"]["rows"]
    return {(r[0], r[1]): tuple(r[2:]) for r in rows}


def expected(np, st, i, precision: str = "float64") -> dict:
    hosts = _hosts(st, i)
    c_lo = int(st.start_bucket[i]) * st.bucket_cells
    if hosts is None:
        # every query over every host asks for the same rows
        key = (c_lo, precision)
        if key not in st.memo:
            st.memo[key] = _expected(np, st, hosts, c_lo, precision)
        return st.memo[key]
    return _expected(np, st, hosts, c_lo, precision)


def _expected(np, st, hosts, c_lo, precision) -> dict:
    vals, present = st.ds.reference.range_agg(
        np, st.ds.values, fields=st.fields, hosts=hosts, c_lo=c_lo,
        c_hi=c_lo + st.span_cells, bucket_cells=st.bucket_cells,
        op=st.agg, precision=precision)
    return st.ds.reference.as_rows(vals, present, hostnames=st.ds.hostnames,
                       hosts=hosts, t_lo_ms=c_lo * INTERVAL_MS,
                       bucket_ms=st.bucket_ms)


def end_to_end(st, good, lat, window_s) -> dict:
    """`good`: the answers that came inside the window; `lat`: the
    ascending latencies (ms) of every query sent in it."""
    out = {}
    if lat:
        out["query_p50_ms"] = percentile(lat, 0.50)
        out["query_p95_ms"] = percentile(lat, 0.95)
    if window_s > 0 and good:
        out["queries_per_s"] = len(good) / window_s
    return out


def control(np, st, precision: str, n: int) -> dict:
    """The cell's numbers when the reference, computed in `precision`,
    stands in the program's place for `n` queries of the window's."""
    worst: dict = {}
    for i in range(n):
        cmp = compare_rows(np, expected(np, st, i, precision=precision),
                           expected(np, st, i))
        for k, v in cmp.items():
            worst[k] = max(worst.get(k, 0), v)
    return worst


def shapes(st) -> dict:
    """What one query makes the device touch, for the byte model."""
    return {"series": st.ds.hosts, "cells": st.ds.cells,
            "fields": len(st.fields),
            "hosts_selected": st.n_hosts or st.ds.hosts,
            "span_cells": st.span_cells,
            "buckets": st.span_cells // st.bucket_cells}
