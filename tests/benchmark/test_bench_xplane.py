"""The reduction from a profiler trace to busy time, idle share,
per-program sums and named idle gaps."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.lib import xplane  # noqa: E402
from benchmark.readers import trace_busy, trace_idle  # noqa: E402
from benchmark.readers import trace_program_roofline  # noqa: E402

US = 1000.0
BUSY = {"programs": ["jit_program", "jit_prelude"], "scale": 1000.0,
        "calls": {"family": "gtpu_device_program_calls_total"}}


def _planes():
    ops = [("%fusion.2 = s32[9,4680]{1,0} fusion(...)", 100 * US, 10 * US,
            "jit_program"),
           ("%fusion.3 = f32[4680,1]{1,0} fusion(...)", 105 * US, 10 * US,
            "jit_program"),                       # overlaps the first
           ("copy-done", 500 * US, 20 * US, "")]  # module from enclosure
    mods = [("jit_program(123)", 100 * US, 15 * US, ""),
            ("jit_prelude(7)", 495 * US, 30 * US, "")]
    host = [("$server.py:10 serve_forever", 0.0, 600 * US, ""),
            ("$parser.py:75 at_kw", 250 * US, 120 * US, ""),
            ("$parser.py:66 peek", 290 * US, 20 * US, "")]
    waiting = [("$threading.py:637 wait", 0.0, 600 * US, "")]
    copies = [("%copy-start = (s32[4000,4320]) copy-start(...)",
               480 * US, 25 * US, "")]       # 480..505: 15 us not in ops
    return [
        {"name": "/host:metadata", "lines": []},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": copies}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host},
            {"name": "", "events": waiting}]},
    ]


def test_busy_is_the_union_and_programs_sum():
    red = xplane.reduce_planes(_planes())
    assert red["device_planes"] == 1
    # 100..115 and 480..520 (the copy joins the op it overlaps)
    assert red["busy_s"] == pytest.approx(55e-6)
    # the capture's own span: the device tracer's first event to its
    # last (100..525), not the Python tracer's longer one (0..600)
    assert red["window_s"] == pytest.approx(425e-6)
    assert red["programs"]["jit_program"] == {
        "seconds": pytest.approx(15e-6), "calls": 1}
    assert red["programs"]["jit_prelude"]["calls"] == 1
    ops = dict(red["device_ops"])
    assert ops["jit_program/fusion.2_s32_9_4680_"] == pytest.approx(10e-6)
    assert ops["jit_prelude/copy-done"] == pytest.approx(20e-6)
    # the one gap between operations (115..480 us) is named after the
    # innermost frame that was working at its middle, not after a
    # waiting thread; the ends of the trace are the profiler's own
    assert len(red["idle_gaps"]) == 1
    label, seconds = red["idle_gaps"][0]
    assert label == "host:parser.py_66_peek"
    assert seconds == pytest.approx(365e-6)


def test_union_length_merges_overlaps_and_touching():
    total, merged = xplane.union_length([(5, 7), (0, 2), (1, 3), (3, 4)])
    assert total == 6 and merged == [[0, 4], [5, 7]]
    assert xplane.union_length([]) == (0, [])


def test_readers_return_nothing_without_a_device_trace():
    ctx = {"trace": None, "client": {"requests_answered": 10.0},
           "m0": {}, "m1": {}}
    assert trace_idle.read({}, ctx) is None
    assert trace_busy.read(BUSY, ctx) is None
    no_dev = xplane.reduce_planes(
        [p for p in _planes() if not p["name"].startswith("/device")])
    assert no_dev["device_planes"] == 0 and "busy_s" not in no_dev
    assert trace_idle.read({}, {"trace": no_dev}) is None


def test_readers_on_a_reduced_trace():
    """The requests a capture holds are counted from the trace: its
    two module calls over the two dispatches a query made in the window
    (the program's counter over the answers) are one query, whatever
    the client's clock says of the capture's 4 seconds."""
    red = xplane.reduce_planes(_planes())
    key = ("gtpu_device_program_calls_total", (("site", "range"),))
    ctx = {"trace": red, "client": {"requests_answered": 500.0},
           "m0": {key: 40.0}, "m1": {key: 1040.0},
           "shapes": {"hosts_selected": 1, "span_cells": 360, "fields": 1,
                      "buckets": 60},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert trace_idle.read({}, ctx) == pytest.approx(100 * (1 - 55 / 425))
    assert trace_busy.read(BUSY, ctx) == pytest.approx(0.055)
    share = trace_program_roofline.read(
        {**BUSY, "bytes": "range_query_bytes"}, ctx)
    # 2100 bytes at 819 GB/s over 45 us of program time
    assert share == pytest.approx(100 * (2100 / 819e9) / 45e-6)
    assert trace_program_roofline.read(
        {**BUSY, "programs": ["jit_absent"], "bytes": "range_query_bytes"},
        ctx) is None
    # half the dispatches a query: the same capture holds two queries
    ctx["m1"] = {key: 540.0}
    assert trace_busy.read(BUSY, ctx) == pytest.approx(0.0275)


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "v5e_panel.xplane.pb.gz")


def test_reduction_of_a_recorded_v5e_trace(tmp_path):
    """0.17 s of `tsbs-single-groupby-1-1-1` captured on a TPU v5e
    through /debug/prof/device/trace (my chip run, PR 25)."""
    import gzip

    raw = tmp_path / "v5e_panel.xplane.pb"
    with gzip.open(RECORDED, "rb") as f:
        raw.write_bytes(f.read())
    planes = xplane.read_planes(str(raw))
    assert "/device:TPU:0" in [p["name"] for p in planes]
    red = xplane.reduce_planes(planes)
    assert red["device_planes"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["window_s"] == pytest.approx(0.1736, abs=2e-3)
    # the panel's two programs, each dispatched once a query
    assert {"jit_program", "jit_prelude"} <= set(red["programs"])
    calls = red["programs"]["jit_program"]["calls"]
    assert calls >= 1
    assert abs(red["programs"]["jit_prelude"]["calls"] - calls) <= 8  # workers
    per_call = red["programs"]["jit_program"]["seconds"] / calls
    assert 1e-5 < per_call < 5e-3
    assert red["busy_s"] >= sum(
        p["seconds"] for p in red["programs"].values()) * 0.5
    ops = dict(red["device_ops"])
    assert any(k.startswith("jit_program/") for k in ops)
    assert all(label.startswith("host:") and seconds > 0
               for label, seconds in red["idle_gaps"])
