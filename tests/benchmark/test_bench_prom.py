"""The pieces of `prom-100k-defbuckets` and its cell
`prom-100k-histogram-quantile`: the data is a pure function of the seed
and has the shapes the configuration states, the remote-write bodies
decode with the program's own decoder, the generator deals distinct
(phi, end) pairs, the plain reference agrees with hand-worked cases,
the bfloat16 control fails the cell's limits where float32 passes, and
a CPU rehearsal of the cell reads every new per-layer metric."""

import os

import numpy as np
import pytest

from test_bench_rehearsal import _rehearse as rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.datagen import prom_hist as dg  # noqa: E402
from benchmark.lib import bytes_model_prom  # noqa: E402
from benchmark.lib.files import cell_files, load_json, reference  # noqa: E402
from benchmark.traffic import prom_range  # noqa: E402

CELL = "prom-100k-histogram-quantile"
SCALE = {"instances": 24, "minutes": 40}
BIG_SEED = 2**31 + 4321
_manifest, _cell, WL, CFG = cell_files(CELL)
ref = reference(CFG)


def make(seed, scale=SCALE):
    ds = dg.make(np, seed, scale)
    ds.reference = ref
    return ds


def test_the_files_say_what_the_issue_says():
    assert CFG["scale"]["instances"] * len(dg.LE) == 100_008
    assert CFG["schema"]["series"] == 100_008
    assert CFG["schema"]["buckets"] == dg.LE and len(dg.LE) == 12
    assert CFG["schema"]["tags"] == dg.TAGS
    assert CFG["schema"]["interval_s"] * 1000 == dg.INTERVAL_MS == 15_000
    assert CFG["reduced"] == ["minutes"]
    assert 35 <= CFG["scale"]["minutes"] <= CFG["source_scale"]["minutes"]
    assert CFG["source_scale"] == {"instances": 8334, "minutes": 120}
    assert dg.BOUNDS[:-1] == [.005, .01, .025, .05, .1, .25, .5, 1, 2.5, 5,
                              10] and dg.BOUNDS[-1] == float("inf")
    assert dg.BASE_MS % dg.INTERVAL_MS == 0
    assert WL["params"] == {"phis": [0.5, 0.9, 0.95, 0.99], "range_s": 1800,
                            "step_s": 15, "window_s": 300}
    assert WL["workers"] == 1 and WL["limits"]["rows_missing"] == 0
    assert 0 < WL["limits"]["worst_rel_err"] <= 1e-2
    # the warm query is the panel's own shape: 121 steps
    (warm,) = CFG["grid_warm_sql"]
    lo, hi = (dg.BASE_MS + 300_000) // 1000, (dg.BASE_MS + 2_100_000) // 1000
    assert warm.startswith(f"TQL EVAL ({lo}, {hi}, '15s') ")
    assert warm.endswith(prom_range.promql(0.5))


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_data_is_a_pure_function_of_the_seed(seed):
    a, b = dg.make(np, seed, SCALE), dg.make(np, seed, SCALE)
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.offsets, b.offsets) and a.names == b.names
    c = dg.make(np, seed + 1, SCALE)
    assert not np.array_equal(a.values, c.values)
    assert not np.array_equal(a.offsets, c.offsets)
    assert a.values.dtype == np.float32 and a.values.shape == (24, 12, 161)
    assert a.rows == 24 * 12 * 161 and a.series == 288
    # whole numbers under 2**24, cumulative in le
    assert np.array_equal(a.values, np.round(a.values))
    assert 0 <= a.values.min() and a.values.max() < 2**24
    assert (np.diff(a.values, axis=1) >= 0).all()
    # every bucket fills: each le series grows over the hour
    fleet = a.values.sum(axis=0)
    assert (np.diff(np.concatenate([[0], fleet[:, -1]])) > 0).all()
    # instance 0 at offset 0, every instance a phase of its own
    assert a.offsets[0] == 0 and len(set(a.offsets.tolist())) == 24
    assert 0 < a.offsets[1:].min() and a.offsets.max() < dg.INTERVAL_MS
    assert (np.diff(a.ts, axis=1) == dg.INTERVAL_MS).all()
    assert a.ts[0, 0] == dg.BASE_MS
    # restarts: all twelve counters of an instance fall at one scrape
    falls = np.diff(a.values, axis=2) < 0
    assert falls.any(axis=(1, 2)).sum() == 1
    assert (falls.any(axis=1) == falls.all(axis=1)).all()
    assert a.names[0] == "10.0.0.0:8080" and a.names[23] == "10.0.0.23:8080"


def test_remote_write_bodies_decode_with_the_programs_own_decoder():
    from greptimedb_tpu.servers import prom_store, snappy

    ds = make(11, {"instances": 400, "minutes": 35})
    k = ds.scrapes - 1
    bodies = dg.scrape_bodies(np, ds, k, np.arange(ds.instances))
    assert len(bodies) == -(-400 * 12 // dg.SAMPLES_PER_SEND) == 3
    series = []
    for body in bodies:
        got = prom_store.parse_write_request(snappy.decompress(body))
        assert len(got) <= dg.SAMPLES_PER_SEND
        series.extend(got)
    assert len(series) == 400 * 12
    for s, (labels, samples) in enumerate(series):
        i, b = divmod(s, 12)
        assert list(labels) == sorted(labels)   # as Prometheus sends them
        assert labels == {"__name__": dg.METRIC, "instance": ds.names[i],
                          "job": dg.JOB, "le": dg.LE[b]}
        assert samples == [(float(ds.values[i, b, k]), int(ds.ts[i, k]))]
    # a body longer than one literal element, and a short one
    for n in (1, 59, 60, 61, 65536, 65537, 200_000):
        raw = bytes(range(256)) * (n // 256 + 1)
        assert snappy.decompress(dg.snappy_block(raw[:n])) == raw[:n]


def test_pairs_are_distinct_until_the_deal_runs_out():
    ds = make(3)
    st = prom_range.prepare(np, WL["params"], ds, BIG_SEED, 200)
    # 40 minutes hold (40 - 35) * 4 + 1 ends, each with four quantiles
    assert st.pairs == 21 * 4 and st.n == 200
    pairs = [prom_range.pair(st, i) for i in range(200)]
    assert len(set(pairs[:84])) == 84 and len(set(pairs[84:168])) == 84
    assert pairs[:84] != pairs[84:168]      # a new order, the same cards
    assert set(pairs[:84]) == set(pairs[84:168])
    for phi, end in pairs:
        assert phi in (0.5, 0.9, 0.95, 0.99)
        assert (end - dg.BASE_MS) % 15_000 == 0
        assert dg.BASE_MS + 35 * 60_000 <= end <= dg.BASE_MS + 40 * 60_000
    again = prom_range.prepare(np, WL["params"], ds, BIG_SEED, 200)
    assert [prom_range.request(again, i) for i in range(200)] == [
        prom_range.request(st, i) for i in range(200)]
    other = prom_range.prepare(np, WL["params"], ds, BIG_SEED + 1, 200)
    assert [prom_range.pair(other, i) for i in range(84)] != pairs[:84]
    method, path, body, _headers = prom_range.request(st, 0)
    assert (method, path) == ("POST", "/v1/prometheus/api/v1/query_range")
    form = dict(kv.split("=") for kv in body.decode().split("&"))
    assert int(form["end"]) - int(form["start"]) == 1800
    assert form["step"] == "15" and int(form["start"]) % 15 == 0
    assert len(prom_range.expected(np, st, 0)) == 121
    with pytest.raises(ValueError):
        prom_range.prepare(np, WL["params"],
                           make(3, {"instances": 4, "minutes": 30}), 1, 10)


def test_rate_reference_against_hand_worked_cases():
    # one series that counts one a second, scraped every 15 s from
    # t = 0; the scrape at 15 s was missed; window 60 s
    ts = np.asarray([[0, 30_000, 45_000, 60_000, 75_000, 90_000]])
    v = (ts / 1000.0)[:, None, :]
    rate, present = ref.extrapolated_rate(np, ts, v, [60_000, 97_000], 60_000)
    assert present.all()
    # (0, 60]: the sample at 0 sits on the open end and is out; 30, 45,
    # 60 are in: 30 s sampled, 30 s to the start >= 1.1 * 15, so half an
    # interval is added; the end is exact
    assert rate[0, 0, 0] == pytest.approx(30.0 * (37.5 / 30.0) / 60.0)
    # (37, 97]: 45..90, 8 s to the start and 7 s to the end, both added
    assert rate[0, 0, 1] == pytest.approx(45.0 * (60.0 / 45.0) / 60.0)
    # a reset: 100, 110, 5, 15 reads as 100, 110, 115, 125
    ts = np.asarray([[15_000, 30_000, 45_000, 60_000]])
    v = np.asarray([[[100.0, 110.0, 5.0, 15.0]]])
    rate, _ = ref.extrapolated_rate(np, ts, v, [60_000], 60_000)
    assert rate[0, 0, 0] == pytest.approx(25.0 * (60.0 / 45.0) / 60.0)
    # a counter near zero is not extrapolated below it: 1, 11, 21, 31
    v = np.asarray([[[1.0, 11.0, 21.0, 31.0]]])
    rate, _ = ref.extrapolated_rate(np, ts, v, [60_000], 60_000)
    to_zero = 45.0 * (1.0 / 30.0)
    assert rate[0, 0, 0] == pytest.approx(30.0 * ((45.0 + to_zero) / 45.0)
                                          / 60.0)
    # fewer than two samples in the window: no rate
    _, present = ref.extrapolated_rate(np, ts, v, [20_000, 200_000], 10_000)
    assert not present.any()


def test_quantile_reference_against_hand_worked_cases():
    bounds = [0.1, 0.5, 1.0, float("inf")]
    b = np.asarray([[10.0], [60.0], [90.0], [100.0]])
    held = np.ones((4, 1), bool)

    def q(phi, buckets=b, present=held, le=bounds):
        return ref.histogram_quantile(np, le, buckets, present, phi)

    assert q(0.5) == {0: pytest.approx(0.1 + 0.4 * (40.0 / 50.0))}
    assert q(0.05) == {0: pytest.approx(0.1 * (5.0 / 10.0))}   # from 0
    assert q(0.95) == {0: 1.0}      # in +Inf: the highest finite bound
    assert q(0.9) == {0: pytest.approx(1.0)}
    # no +Inf bucket, or none present at a step: no answer
    assert q(0.5, b[:3], held[:3], bounds[:3]) == {}
    gone = held.copy()
    gone[3, 0] = False
    assert q(0.5, present=gone) == {}
    assert q(0.5, np.zeros((4, 1))) == {}       # no observations
    # forced monotonic: a bucket below its predecessor reads as it
    dip = np.asarray([[10.0], [60.0], [55.0], [100.0]])
    assert q(0.59, dip) == q(0.59, np.asarray([[10.0], [60.0], [60.0],
                                               [100.0]]))


@pytest.mark.parametrize("seed", [1, 2, BIG_SEED])
def test_control_in_bfloat16_fails_and_float32_passes(seed):
    """The configuration states float32; the control is the reference
    computed in bfloat16 and put in the program's place."""
    st = prom_range.prepare(np, WL["params"], make(seed), seed, 40)
    sound = prom_range.control(np, st, "float32", 30)
    control = prom_range.control(np, st, "bfloat16", 30)
    assert sound["rows_missing"] == control["rows_missing"] == 0
    lim = WL["limits"]["worst_rel_err"]
    assert sound["worst_rel_err"] <= lim / 3, sound
    assert control["worst_rel_err"] > 3 * lim, control


def test_byte_model_reads_shapes_not_programs():
    st = prom_range.prepare(np, WL["params"], make(5), 5, 10)
    shapes = prom_range.shapes(st)
    assert shapes == {"series": 288, "span_cells": 140, "steps": 121}
    assert bytes_model_prom.histogram_quantile_bytes(shapes) == (
        288 * 140 * 9 + 121 * 4)
    full = dict(shapes, series=100_008)
    assert bytes_model_prom.histogram_quantile_bytes(full) == 126_010_564
    assert bytes_model_prom.histogram_quantile_bytes.__code__.co_argcount == 1


NEW_METRICS = {
    "http_server_ms.promql", "promql_parse_ms_per_query",
    "promql_resolve_ms_per_query", "promql_plan_ms_per_query",
    "hist_device_call_ms_per_query", "promql_assemble_ms_per_query",
    "hist_dispatches_per_query", "setup_remote_write_s",
    # twins of the latency cell's metrics, the same readers and keys:
    # test_bench_stage_metrics.py holds those entries to one cell
    "http_read_ms_per_query.promql", "encode_ms_per_query.promql",
    "send_ms_per_query.promql", "gc_pause_ms_per_s.promql",
    "background_ms_per_s.promql", "compiles_per_query.promql",
    "setup_flight_decode_s.promql", "setup_tag_columns_s.promql",
    "setup_intern_s.promql", "setup_wal_append_s.promql",
    "setup_memtable_s.promql", "setup_flush_s.promql",
    "setup_grid_build_s.promql", "setup_compile_s.promql",
}
NEW_DEVICE_METRICS = {"hist_roofline", "hist_device_busy_ms_per_query"}
REHEARSAL = ["--scale", "instances=64", "--scale", "minutes=40"]


def _rehearse(state, *, trace, hooks_code=None):
    p, line = rehearse(CELL, state, REHEARSAL, seconds=3, trace=trace,
                       hooks_code=hooks_code)
    assert p.returncode == 0, p.stderr[-3000:]
    return p, line


def test_cpu_rehearsal_of_the_cell_reads_every_new_metric(tmp_path):
    p, line = _rehearse(str(tmp_path), trace=1)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] >= 3
    listed = {m["name"] for m in _manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert NEW_METRICS | NEW_DEVICE_METRICS <= listed
    got = set(line["metrics"])
    assert NEW_METRICS <= got <= listed
    assert not NEW_DEVICE_METRICS & got     # no device metric on the CPU
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["hist_dispatches_per_query"] == 1.0
    assert m["compiles_per_query.promql"] == 0.0
    assert m["readback_bytes_per_query"] == 2 * 121 * 8
    assert m["setup_grid_build_s.promql"] > 0
    assert m["setup_remote_write_s"] > 0
    for name in NEW_METRICS:
        if name.endswith(".promql") and name != "http_server_ms.promql":
            twin = name[:-len(".promql")]
            twin += ".query" if twin.endswith("_per_s") else ""
            assert load_json(ROOT, "benchmark", "metrics", name + ".json") \
                == load_json(ROOT, "benchmark", "metrics", twin + ".json")
    for name in ("promql_parse_ms_per_query", "promql_resolve_ms_per_query",
                 "promql_plan_ms_per_query", "hist_device_call_ms_per_query",
                 "promql_assemble_ms_per_query"):
        assert 0 < m[name] < m["http_server_ms.promql"], name
    c = {k: v["value"] for k, v in line["compared"].items()}
    assert c["rows_acked_not_counted"] == 0 and c["queries_off_device"] == 0
    assert c["rows_missing"] == 0
    assert 0 < c["worst_rel_err"] < WL["limits"]["worst_rel_err"] / 3
    assert line["notes"]["compared_answers"] == line["attempted"]
    said = " ".join(ln for ln in p.stderr.splitlines() if ln.startswith("t="))
    for word in ("Flight DoPut", "POST /v1/prometheus/write", "flushed",
                 "grid built", f"count(*) = {64 * 12 * 161}", "warm: 3"):
        assert word in said, word
    # untraced: the cell's end-to-end metrics and no others
    _p, line = _rehearse(str(tmp_path), trace=0)
    assert set(line["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                    "setup_s"}
    assert line["correct"] is True


WRITE_BEFORE_THE_WINDOW = """
def late_scrape(run):
    # a sample written once the cell is warm drops the selector grid:
    # the window's first query builds it again, which is a query that
    # the device did not answer from a held grid
    import numpy as np
    from benchmark.datagen import prom_hist as dg
    ds = dg.make(np, run.args.seed, run.scale)
    ds.ts[:, -1] += dg.INTERVAL_MS
    for body in dg.scrape_bodies(np, ds, ds.scrapes - 1, np.arange(2)):
        run.srv.post("/v1/prometheus/write", body, dg._RW_HEADERS)
HOOKS = {"before_window": late_scrape}
"""


def test_a_grid_build_inside_the_window_comes_out_not_correct(tmp_path):
    _p, line = _rehearse(str(tmp_path), trace=0,
                         hooks_code=WRITE_BEFORE_THE_WINDOW)
    assert line["correct"] is False
    assert line["compared"]["queries_off_device"]["value"] >= 1
    assert line["compared"]["requests_failed"]["value"] == 0


def test_roofline_reader_counts_the_calls_the_capture_spans():
    """Three events of a 2.1 s program in a 3.8 s capture are not three
    calls: the reader takes the calls the capture's span holds from the
    window's own rate (24 calls in 51 s)."""
    from benchmark.readers import trace_hist_roofline as reader

    spec = load_json(ROOT, "benchmark", "metrics", "hist_roofline.json")
    key = ("gtpu_device_program_calls_total",
           (("program", "p"), ("site", "promql_histogram")))
    other = ("gtpu_device_program_calls_total",
             (("program", "q"), ("site", "range")))
    ctx = {"m0": {key: 3.0, other: 1.0}, "m1": {key: 27.0, other: 9.0},
           "client": {"window_s": 51.0, "requests_answered": 24.0},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "shapes": {"series": 100_008, "span_cells": 140, "steps": 121},
           "trace": {"window_s": 3.8, "busy_s": 3.6, "programs": {
               "jit__fused_hist_query": {"seconds": 3.6, "calls": 3},
               "jit_program": {"seconds": 0.1, "calls": 7}}}}
    per_call_s = 3.6 / (24 / 51.0 * 3.8)
    assert reader.read(spec, ctx) == pytest.approx(
        100.0 * (126_010_564 / 819e9) / per_call_s)
    # nothing of the program in the capture, or no capture: nothing read
    ctx["trace"]["programs"].pop("jit__fused_hist_query")
    assert reader.read(spec, ctx) is None
    assert reader.read(spec, dict(ctx, trace=None)) is None
    assert reader.read(spec, dict(ctx, trace={})) is None
