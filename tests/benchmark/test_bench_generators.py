"""The yardstick's own pieces: each generator is a pure function of the
seed, each plain reference agrees with a hand-worked case, the control
(the reference in the next lower precision) fails the comparison, and
the byte model reads the same work whatever program implements it."""

import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark.datagen import tsbs_cpu as dg  # noqa: E402
from benchmark.lib import bytes_model  # noqa: E402
from benchmark.lib.compare import compare_rows  # noqa: E402
from benchmark.lib.files import load_json, reference  # noqa: E402
from benchmark.traffic import tsbs_load, tsbs_range  # noqa: E402

SCALE = {"hosts": 16, "hours": 2}
PANEL = {"agg": "max", "fields": 1, "hosts": 1, "span_hours": 1,
         "bucket_s": 60}
FLEET = {"agg": "avg", "fields": 10, "hosts": 0, "span_hours": 0,
         "bucket_s": 3600}
BIG_SEED = 2**31 + 12345
ref = reference(load_json(ROOT, "benchmark", "configs",
                          "tsbs-cpu-4000.json"))


def make(seed, scale=SCALE):
    ds = dg.make(np, seed, scale)
    ds.reference = ref
    return ds


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_tsbs_data_is_a_pure_function_of_the_seed(seed):
    a, b = dg.make(np, seed, SCALE), dg.make(np, seed, SCALE)
    assert np.array_equal(a.values, b.values) and a.tags == b.tags
    c = dg.make(np, seed + 1, SCALE)
    assert not np.array_equal(a.values, c.values)
    assert a.values.dtype == np.float32
    assert a.values.shape == (10, 16, 720)
    # every value is a multiple of 1/64 in [0, 100): exact in float32
    assert np.array_equal(a.values * 64, np.round(a.values * 64))
    assert a.values.min() >= 0 and a.values.max() < 100
    assert list(a.tags) == dg.TAGS and len(a.tags["region"]) == 16
    assert all(d.startswith(r) for r, d in
               zip(a.tags["region"], a.tags["datacenter"]))


@pytest.mark.parametrize("params", [PANEL, FLEET],
                         ids=["panel", "fleet"])
def test_range_traffic_is_a_pure_function_of_the_seed(params):
    ds = make(3)
    a = tsbs_range.prepare(np, params, ds, BIG_SEED, 200)
    b = tsbs_range.prepare(np, params, ds, BIG_SEED, 200)
    sa = [tsbs_range.sql(a, i) for i in range(200)]
    assert sa == [tsbs_range.sql(b, i) for i in range(200)]
    assert len(set(sa)) == 200, "two queries of a run share their literals"
    c = tsbs_range.prepare(np, params, ds, BIG_SEED + 1, 200)
    assert sa != [tsbs_range.sql(c, i) for i in range(200)]


def test_range_reference_against_a_hand_worked_case():
    # 1 field, 2 hosts, 12 cells (2 minutes): by minute
    v = np.zeros((10, 2, 12), np.float32)
    v[0, 0] = [1, 2, 3, 4, 5, 6, 60, 50, 40, 30, 20, 10]
    v[0, 1] = [0.5] * 6 + [0.25] * 6
    mx, present = ref.range_agg(np, v, fields=[0], hosts=[1, 0], c_lo=0,
                                c_hi=12, bucket_cells=6, op="max")
    assert mx.tolist() == [[[0.5, 0.25], [6.0, 60.0]]]
    assert present.all()
    av, _ = ref.range_agg(np, v, fields=[0], hosts=None, c_lo=6, c_hi=12,
                          bucket_cells=6, op="avg")
    assert av.tolist() == [[[35.0], [0.25]]]
    rows = ref.as_rows(mx, present, hostnames=["a", "b"], hosts=[1, 0],
                       t_lo_ms=0, bucket_ms=60_000)
    assert rows == {(0, "b"): (0.5,), (60_000, "b"): (0.25,),
                    (0, "a"): (6.0,), (60_000, "a"): (60.0,)}


def test_masked_reference_counts_only_acknowledged_rows():
    v = np.zeros((10, 1, 6), np.float32)
    v[0, 0] = [10, 20, 30, 40, 50, 60]
    mask = np.array([[True, True, False, False, False, False]])
    av, present = ref.range_agg(np, v, fields=[0], hosts=None, c_lo=0,
                                c_hi=6, bucket_cells=3, op="avg", mask=mask)
    assert av[0, 0, 0] == 15.0 and present.tolist() == [[True, False]]
    mx, _ = ref.range_agg(np, v, fields=[0], hosts=None, c_lo=0, c_hi=6,
                          bucket_cells=3, op="max", mask=mask)
    assert mx[0, 0, 0] == 20.0


def test_traffic_expected_matches_its_own_sql_shape():
    ds = make(5)
    st = tsbs_range.prepare(np, PANEL, ds, 11, 10)
    want = tsbs_range.expected(np, st, 3)
    assert len(want) == 60                      # 1 host x 60 minutes
    q = tsbs_range.sql(st, 3)
    (ts0, host), _ = next(iter(want.items()))
    assert f"'{host}'" in q and f"ts >= {ts0} " in q
    st = tsbs_range.prepare(np, FLEET, ds, 11, 10)
    assert len(tsbs_range.expected(np, st, 0)) == 16 * 2
    assert "ts >= -" in tsbs_range.sql(st, 0)


@pytest.mark.parametrize("traffic,params,limits", [
    (tsbs_range, PANEL, {"values_differing": 0}),
    (tsbs_range, FLEET, {"worst_rel_err": 1e-6}),
    (tsbs_load, {"batch_lines": 24},
     {"readback_avg_rel_err": 1e-6, "readback_max_differing": 0,
      "readback_rows_differing": 0}),
], ids=["panel-exact", "fleet-f32-mean", "load-readback"])
@pytest.mark.parametrize("seed", [1, 2, BIG_SEED])
def test_control_in_the_next_lower_precision_fails(traffic, params, limits,
                                                   seed):
    """The configuration states float32; the control is the reference
    computed in bfloat16 and put in the program's place. The same
    reference in float32 (what a sound program gives) passes."""
    st = traffic.prepare(np, params, make(seed), seed, 40)
    sound = traffic.control(np, st, "float32", 30)
    control = traffic.control(np, st, "bfloat16", 30)
    for k, lim in limits.items():
        assert sound[k] <= lim, (k, sound)
        assert control[k] > 3 * max(lim, sound[k]), (k, control)


def test_compare_reads_missing_rows_and_wrong_values():
    want = {(0, "a"): (1.0, 2.0), (1, "a"): (3.0, 4.0)}
    same = compare_rows(np, dict(want), want)
    assert same["rows_missing"] == 0 and same["values_differing"] == 0
    assert compare_rows(np, {(0, "a"): (1.0, 2.0)}, want)[
        "rows_missing"] == 1
    off = compare_rows(np, {(0, "a"): (1.0, 2.5), (1, "a"): (3.0, 4.0)},
                       want)
    assert off["values_differing"] == 1 and off["worst_rel_err"] == 0.25
    nan = compare_rows(np, {(0, "a"): (float("nan"), 2.0),
                            (1, "a"): (3.0, 4.0)}, want)
    assert nan["worst_rel_err"] == float("inf")


def test_load_bodies_are_the_time_major_stream_of_the_seed():
    ds = make(9, {"hosts": 16, "hours": 1})
    st = tsbs_load.prepare(np, {"batch_lines": 24}, ds, 9, 5)
    again = tsbs_load.prepare(np, {"batch_lines": 24}, ds, 9, 5)
    assert st.bodies == again.bodies and len(st.bodies) == 5
    lines = b"".join(st.bodies).decode().splitlines()
    assert len(lines) == 5 * 24
    # line 17 is host 1 at the second timestamp, full width
    head, fields, ts = lines[17].split(" ")
    assert head.startswith("cpu,hostname=host_1,region=")
    assert head.count(",") == 10 and fields.count(",") == 9
    assert int(ts) == 10_000
    assert float(fields.split(",")[0].split("=")[1]) == float(
        ds.values[0, 1, 1])
    renderer = dg.LineRenderer(np, ds)
    assert [ln + "\n" for ln in lines[:32]] == \
        renderer.cell(0) + renderer.cell(1)


def test_byte_model_reads_shapes_not_programs():
    shapes = {"series": 4000, "cells": 4320, "fields": 10,
              "hosts_selected": 4000, "span_cells": 4320, "buckets": 12}
    need = bytes_model.range_query_bytes(shapes)
    assert need == 4000 * 4320 * 10 * 5 + 4000 * 12 * 10 * 5
    one = dict(shapes, fields=1, hosts_selected=1, span_cells=360,
               buckets=60)
    assert bytes_model.range_query_bytes(one) == 360 * 5 + 60 * 5
    # nothing but the shapes goes in: no program, no cost analysis
    assert bytes_model.range_query_bytes.__code__.co_argcount == 1
