"""The generator of one Prometheus histogram panel, asked as Grafana
asks it: `POST /v1/prometheus/api/v1/query_range` with

    histogram_quantile(<phi>, sum by (le) (rate(<metric>[<window>])))

`start` and `end` multiples of the step, `end - start` the panel's
range. One traffic mix is a file of parameters:

    phis       the quantiles a query is drawn from
    range_s    the panel's range (`end - start`)
    step_s     the step: the scrape interval
    window_s   the range selector's window (`[5m]` = 300)

`end` is drawn from the multiples of the step between (first sample +
window + range) and the last scrape. Query i is a pure function of
(seed, i): the (phi, end) pairs are dealt without replacement, and the
deal starts again, in a new seeded order, only when every pair has been
asked, so that neither the session registry nor a result cache answers
one before then.
"""

from __future__ import annotations

import json
import urllib.parse

from benchmark.datagen.prom_hist import BASE_MS, BOUNDS, METRIC
from benchmark.lib.compare import compare_rows
from benchmark.lib.loadgen import percentile

KIND = "query"
# every PromQL query the fast path saw counts one event: `hit` when the
# selector grid answered it on the device; a fallback to the host
# engine or a grid build inside the window is a query off the device
EXEC_PATH = {"family": "greptime_promql_fast_path_total", "match": {},
             "label": "event", "device": "hit"}
_HEADERS = {"Content-Type": "application/x-www-form-urlencoded"}


class State:
    pass


def prepare(np, params: dict, ds, seed: int, budget: int) -> State:
    st = State()
    st.ds = ds
    st.seed = seed
    st.phis = [float(p) for p in params["phis"]]
    st.range_ms = int(params["range_s"]) * 1000
    st.step_ms = int(params["step_s"]) * 1000
    st.window_ms = int(params["window_s"]) * 1000
    last = BASE_MS + ds.minutes * 60_000
    first_end = BASE_MS + st.window_ms + st.range_ms
    if first_end > last or (first_end - BASE_MS) % st.step_ms:
        raise ValueError("the data holds no whole panel")
    if st.window_ms % 60_000:
        raise ValueError("the window is written in whole minutes")
    st.ends = np.arange(first_end, last + 1, st.step_ms, dtype=np.int64)
    # every step any query can ask, for the reference's one pass
    st.all_steps = np.arange(first_end - st.range_ms, last + 1, st.step_ms,
                             dtype=np.int64)
    st.pairs = len(st.phis) * len(st.ends)
    st.n = int(budget)
    st.deals = {}
    st.memo = {}
    st.np = np
    return st


def pair(st, i: int) -> tuple:
    """(phi, end_ms) of query i: deal i // pairs, card i % pairs."""
    deal, card = divmod(i, st.pairs)
    if deal not in st.deals:
        st.deals[deal] = st.np.random.default_rng(
            [st.seed, 0x9A1F, deal]).permutation(st.pairs)
    k = int(st.deals[deal][card])
    return st.phis[k % len(st.phis)], int(st.ends[k // len(st.phis)])


def promql(phi: float, window_ms: int = 300_000) -> str:
    return (f"histogram_quantile({phi!r}, sum by (le) "
            f"(rate({METRIC}[{window_ms // 60_000}m])))")


def request(st, i):
    phi, end = pair(st, i)
    body = urllib.parse.urlencode({
        "query": promql(phi, st.window_ms),
        "start": (end - st.range_ms) // 1000,
        "end": end // 1000, "step": st.step_ms // 1000}).encode()
    return "POST", "/v1/prometheus/api/v1/query_range", body, _HEADERS


def parse(np, st, i, raw: bytes) -> dict:
    doc = json.loads(raw)
    if doc["status"] != "success":
        raise ValueError(doc)
    return {(round(float(t) * 1000), tuple(sorted(s["metric"].items()))):
            (float(v),)
            for s in doc["data"]["result"] for t, v in s["values"]}


def _bucket_rates(np, st, precision: str):
    """`sum by (le) (rate(..))` at every step a query can ask: one pass
    over the data a precision, some instances at a time so that the
    (instances, buckets, steps) intermediates stay small."""
    if precision not in st.memo:
        ref, ds = st.ds.reference, st.ds
        total, held = 0.0, False
        with np.errstate(all="ignore"):
            for lo in range(0, ds.instances, 256):
                rate, present = ref.extrapolated_rate(
                    np, ds.ts[lo:lo + 256], ds.values[lo:lo + 256],
                    st.all_steps, st.window_ms, precision)
                part, any_present = ref.sum_by_le(
                    np, rate, present, precision)
                total = ref.lower(np, total + part, precision)
                held = held | any_present
        st.memo[precision] = total, held
    return st.memo[precision]


def expected(np, st, i, precision: str = "float64") -> dict:
    phi, end = pair(st, i)
    buckets, present = _bucket_rates(np, st, precision)
    j0 = int((end - st.range_ms - st.all_steps[0]) // st.step_ms)
    j1 = j0 + st.range_ms // st.step_ms + 1
    with np.errstate(all="ignore"):
        got = st.ds.reference.histogram_quantile(
            np, BOUNDS, buckets[:, j0:j1], present[:, j0:j1], phi, precision)
    return {(int(st.all_steps[j0 + j]), ()): (v,) for j, v in got.items()}


def end_to_end(st, good, lat, window_s) -> dict:
    """`lat`: the ascending latencies (ms) of every query sent in the
    window."""
    if not lat:
        return {}
    return {"query_p50_ms": percentile(lat, 0.50),
            "query_p95_ms": percentile(lat, 0.95)}


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
    interval = int(st.ds.ts[0, 1] - st.ds.ts[0, 0])
    return {"series": st.ds.series,
            "span_cells": (st.range_ms + st.window_ms) // interval,
            "steps": st.range_ms // st.step_ms + 1}
