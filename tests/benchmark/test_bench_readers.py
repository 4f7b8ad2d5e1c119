"""The counter, histogram, ratio, clock and gauge readers over a
hand-made pair of scrapes: a reader that finds nothing returns None."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.lib.server import parse_metrics  # noqa: E402
from benchmark.readers import (  # noqa: E402
    client_clock, counter_delta, health_gauge, histogram_mean,
    ratio_of_deltas,
)

BEFORE = """# HELP x
lat_seconds_sum{path="/v1/sql"} 1.0
lat_seconds_count{path="/v1/sql"} 10
lat_seconds_sum{path="/metrics"} 5.0
lat_seconds_count{path="/metrics"} 1
calls_total{site="range",program="a"} 4
readback_total{mode="full"} 100
"""
AFTER = """lat_seconds_sum{path="/v1/sql"} 3.0
lat_seconds_count{path="/v1/sql"} 30
lat_seconds_sum{path="/metrics"} 9.0
lat_seconds_count{path="/metrics"} 2
calls_total{site="range",program="a"} 24
calls_total{site="range_prelude",program="b"} 20
readback_total{mode="full"} 4100
"""


@pytest.fixture
def ctx():
    return {"m0": parse_metrics(BEFORE), "m1": parse_metrics(AFTER),
            "client": {"requests_answered": 20.0, "request_seconds": 4.0,
                       "generator_gap_share": 0.5},
            "health": {"bytes_in_use": [7, None, 9], "platform": "tpu"}}


def test_parse_metrics_reads_labels_and_skips_comments():
    m = parse_metrics(BEFORE)
    assert m[("lat_seconds_count", (("path", "/v1/sql"),))] == 10.0
    assert m[("calls_total", (("program", "a"), ("site", "range")))] == 4.0
    assert len(m) == 6


@pytest.mark.parametrize("reader,spec,want", [
    (counter_delta, {"family": "readback_total"}, 4000.0),
    (counter_delta, {"family": "calls_total",
                     "labels": {"site": "range"}}, 20.0),
    (counter_delta, {"family": "absent_total"}, None),
    (histogram_mean, {"family": "lat_seconds", "scale": 1000.0,
                      "labels": {"path": ["/v1/sql", "/v1/other"]}}, 100.0),
    (histogram_mean, {"family": "lat_seconds",
                      "labels": {"path": "/nowhere"}}, None),
    (ratio_of_deltas, {"num": {"family": "calls_total"},
                       "den": {"client": "requests_answered"}}, 2.0),
    (ratio_of_deltas, {"num": {"family": "lat_seconds_sum",
                               "labels": {"path": ["/v1/sql"]}},
                       "den": {"client": "request_seconds"},
                       "scale": 100.0}, 50.0),
    (ratio_of_deltas, {"num": {"family": "absent_total"},
                       "den": {"client": "requests_answered"}}, None),
    (client_clock, {"what": "generator_gap_share"}, 0.5),
    (client_clock, {"what": "absent"}, None),
    (health_gauge, {"field": "bytes_in_use"}, 9.0),
    (health_gauge, {"field": "absent"}, None),
], ids=lambda v: getattr(v, "__name__", None) and v.__name__.rsplit(
    ".", 1)[-1])
def test_reader(reader, spec, want, ctx):
    got = reader.read(spec, ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_device_readers_are_marked_so_a_rehearsal_never_prints_them():
    from benchmark.readers import trace_busy, trace_idle
    from benchmark.readers import trace_program_roofline

    for mod in (trace_busy, trace_idle, trace_program_roofline,
                health_gauge):
        assert mod.DEVICE is True
    for mod in (counter_delta, histogram_mean, ratio_of_deltas,
                client_clock):
        assert not getattr(mod, "DEVICE", False)
