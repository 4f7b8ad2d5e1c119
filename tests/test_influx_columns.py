"""Line-protocol bodies become tables a column at a time
(servers/influx.py:_write_measurement): what each shape of body leaves in
the table, read back through SQL, and what the conversion costs in
Python-level calls."""

import os
import subprocess
import sys

import numpy as np
import pytest

from greptimedb_tpu.instance import Standalone
from greptimedb_tpu.servers import influx
from greptimedb_tpu.servers.influx import LineProtocolError, write_lines
from greptimedb_tpu.telemetry import tracing
from greptimedb_tpu.telemetry.metrics import global_registry


@pytest.fixture()
def inst(tmp_path):
    inst = Standalone(str(tmp_path / "d"), warm_start=False)
    yield inst
    inst.close()


def _columns(inst, table):
    """[(name, SQL type, semantic)] in the table's own order."""
    return [(r[0], r[1], r[5])
            for r in inst.sql(f"DESC TABLE {table}").rows()]


# (bodies written in turn at precision=ms, {statement: rows expected})
CASES = {
    "all_floats": (
        ["m,h=a u=1.5,v=2 1000\nm,h=b u=3.25,v=4e1 2000"],
        {"SELECT h, u, v, ts FROM m ORDER BY h":
            [["a", 1.5, 2.0, 1000], ["b", 3.25, 40.0, 2000]],
         "DESC m": [("h", "STRING", "TAG"), ("u", "DOUBLE", "FIELD"),
                    ("v", "DOUBLE", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "int_then_float_widens": (
        ["m,h=a f=1i 1000\nm,h=b f=1.5 2000"],
        {"SELECT h, f FROM m ORDER BY h": [["a", 1.0], ["b", 1.5]],
         "DESC m": [("h", "STRING", "TAG"), ("f", "DOUBLE", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "floats_then_int": (
        ["m,h=a f=0.5 1000\nm,h=b f=2.25 2000\nm,h=c f=7i 3000"],
        {"SELECT h, f FROM m ORDER BY h":
            [["a", 0.5], ["b", 2.25], ["c", 7.0]],
         "DESC m": [("h", "STRING", "TAG"), ("f", "DOUBLE", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "ints_stay_int64": (
        ["m,h=a n=1i 1000\nm,h=b n=-9007199254740993i 2000"],
        {"SELECT h, n FROM m ORDER BY h":
            [["a", 1], ["b", -9007199254740993]],
         "DESC m": [("h", "STRING", "TAG"), ("n", "BIGINT", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "field_missing_in_some_rows": (
        ["m,h=a u=1,v=10 1000\nm,h=b u=2 2000\nm,h=c u=3,v=30 3000\n"
         "m,h=d u=4 4000"],
        {"SELECT h, u, v FROM m ORDER BY h":
            [["a", 1.0, 10.0], ["b", 2.0, None], ["c", 3.0, 30.0],
             ["d", 4.0, None]],
         "SELECT count(u), count(v), sum(v) FROM m": [[4, 2, 40.0]]},
    ),
    "field_first_seen_in_last_row": (
        ["m,h=a u=1 1000\nm,h=b u=2 2000",
         "m,h=c u=3 3000\nm,h=d u=4,late=9i 4000"],
        {"SELECT h, u, late FROM m ORDER BY h":
            [["a", 1.0, None], ["b", 2.0, None], ["c", 3.0, None],
             ["d", 4.0, 9]],
         "SELECT count(late) FROM m": [[1]],
         "DESC m": [("h", "STRING", "TAG"), ("u", "DOUBLE", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP"),
                    ("late", "BIGINT", "FIELD")]},
    ),
    "field_first_seen_in_last_row_of_first_body": (
        ["m,h=a u=1 1000\nm,h=b u=2,late=t 2000"],
        {"SELECT h, u, late FROM m ORDER BY h":
            [["a", 1.0, None], ["b", 2.0, True]]},
    ),
    "tag_missing_in_some_rows": (
        ["m,h=a,dc=east u=1 1000\nm,h=b u=2 2000\nm,dc=west u=3 3000"],
        {"SELECT h, dc, u FROM m ORDER BY u":
            [["a", "east", 1.0], ["b", "", 2.0], ["", "west", 3.0]]},
    ),
    "tags_in_another_order_a_row": (
        ["m,b=1,a=2 u=1 1000\nm,a=3,c=4,b=5 u=2 2000"],
        {"SELECT b, a, c, u FROM m ORDER BY u":
            [["1", "2", "", 1.0], ["5", "3", "4", 2.0]],
         "DESC m": [("b", "STRING", "TAG"), ("a", "STRING", "TAG"),
                    ("c", "STRING", "TAG"), ("u", "DOUBLE", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "fields_in_another_order_a_row": (
        ["m,h=a v=1,u=2 1000\nm,h=b u=3,w=4,v=5 2000"],
        {"SELECT h, v, u, w FROM m ORDER BY h":
            [["a", 1.0, 2.0, None], ["b", 5.0, 3.0, 4.0]],
         "DESC m": [("h", "STRING", "TAG"), ("v", "DOUBLE", "FIELD"),
                    ("u", "DOUBLE", "FIELD"), ("w", "DOUBLE", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "booleans": (
        ["m,h=a ok=t 1000\nm,h=b ok=false 2000\nm,h=c ok=TRUE 3000"],
        {"SELECT h, ok FROM m ORDER BY h":
            [["a", True], ["b", False], ["c", True]],
         "DESC m": [("h", "STRING", "TAG"), ("ok", "BOOLEAN", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "string_field": (
        ['m,h=a msg="hello, world" 1000\nm,h=b msg="" 2000'],
        {"SELECT h, msg FROM m ORDER BY h":
            [["a", "hello, world"], ["b", ""]],
         "DESC m": [("h", "STRING", "TAG"), ("msg", "STRING", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "string_field_with_a_missing_value": (
        ['m,h=a msg="up",u=1 1000\nm,h=b u=2 2000\nm,h=c msg="down",u=3 '
         '3000'],
        {"SELECT h, msg, u FROM m ORDER BY h":
            [["a", "up", 1.0], ["b", None, 2.0], ["c", "down", 3.0]],
         "SELECT count(msg) FROM m": [[2]]},
    ),
    "two_measurements_interleaved": (
        ["cpu,h=a u=1 1000\nmem,h=a used=5i 1000\ncpu,h=b u=2 2000\n"
         "mem,h=b used=6i,free=1.5 2000\ncpu,h=c u=3 3000"],
        {"SELECT h, u FROM cpu ORDER BY h":
            [["a", 1.0], ["b", 2.0], ["c", 3.0]],
         "SELECT h, used, free FROM mem ORDER BY h":
            [["a", 5, None], ["b", 6, 1.5]]},
    ),
    "int64_column_takes_an_integral_float": (
        ["m,h=a n=1i 1000", "m,h=b n=2.0 2000\nm,h=c n=-3e2 3000"],
        {"SELECT h, n FROM m ORDER BY h": [["a", 1], ["b", 2], ["c", -300]],
         "DESC m": [("h", "STRING", "TAG"), ("n", "BIGINT", "FIELD"),
                    ("ts", "TIMESTAMP(3)", "TIMESTAMP")]},
    ),
    "second_body_with_one_field_fewer": (
        ["m,h=a u=1,v=10 1000\nm,h=b u=2,v=20 2000",
         "m,h=c u=3 3000\nm,h=d u=4 4000"],
        {"SELECT h, u, v FROM m ORDER BY h":
            [["a", 1.0, 10.0], ["b", 2.0, 20.0], ["c", 3.0, None],
             ["d", 4.0, None]],
         "SELECT count(u), count(v) FROM m": [[4, 2]]},
    ),
    # kinds a column's type does not take: value by value, as before
    "bool_among_ints": (
        ["m,h=a n=5i 1000\nm,h=b n=t 2000\nm,h=c n=f 3000"],
        {"SELECT h, n FROM m ORDER BY h": [["a", 5], ["b", 1], ["c", 0]]},
    ),
    "number_into_a_string_column": (
        ['m,h=a s="x" 1000', "m,h=b s=1.5 2000\nm,h=c s=7i 3000\n"
                             "m,h=d s=t 4000"],
        {"SELECT h, s FROM m ORDER BY h":
            [["a", "x"], ["b", "1.5"], ["c", "7"], ["d", "True"]]},
    ),
    "int_and_float_into_an_int64_column": (
        ["m,h=a n=1i 1000", "m,h=b n=9007199254740993i 2000\nm,h=c n=4.0 "
                            "3000"],
        {"SELECT h, n FROM m ORDER BY h":
            [["a", 1], ["b", 9007199254740993], ["c", 4]]},
    ),
}


def _check(inst, expected):
    for stmt, rows in expected.items():
        if stmt.startswith("DESC "):
            assert _columns(inst, stmt[5:]) == rows, stmt
        else:
            assert inst.sql(stmt).rows() == rows, stmt


@pytest.mark.parametrize("case", list(CASES))
def test_body_to_table(inst, case):
    bodies, expected = CASES[case]
    for body in bodies:
        assert write_lines(inst, body, precision="ms") == len(
            body.splitlines())
    _check(inst, expected)


# a body the table's types refuse: (bodies that create the table, the
# refused body, the error, what its text holds)
REFUSED = {
    "fraction_into_int64": (
        ["m,h=a n=1i 1000"], "m,h=b n=2.0 2000\nm,h=c n=2.5 3000\n"
                             "m,h=d n=3.5 4000",
        LineProtocolError, "field 'n' is int64 but got non-integral "
                           "value 2.5"),
    "fraction_among_ints_into_int64": (
        ["m,h=a n=1i 1000"], "m,h=b n=2i 2000\nm,h=c n=0.25 3000",
        LineProtocolError, "field 'n' is int64 but got non-integral "
                           "value 0.25"),
    "nan_into_int64": (
        ["m,h=a n=1i 1000"], "m,h=b n=nan 2000",
        LineProtocolError, "field 'n' is int64"),
    "out_of_range_into_int64": (
        ["m,h=a n=1i 1000"], "m,h=b n=1e300 2000",
        LineProtocolError, "field 'n' is int64"),
    "text_into_float64": (
        ["m,h=a u=1 1000"], 'm,h=b u=2 2000\nm,h=c u="high" 3000',
        ValueError, "could not convert string to float"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_refused_body_writes_nothing(inst, case):
    bodies, refused, exc, text = REFUSED[case]
    for body in bodies:
        write_lines(inst, body, precision="ms")
    with pytest.raises(exc, match=text):
        write_lines(inst, refused, precision="ms")
    assert inst.sql("SELECT count(*) FROM m").rows() == [[1]]


PAYLOAD = (
    'cpu,host=h\\ 1,dc=east u=1.5,n=3i,ok=t,msg="a, \\"b\\"" 1000\n'
    "# a comment\n"
    "cpu,dc=west,host=h2 u=2,n=4i 2000\n"
    "\n"
    "mem,host=h2 used=7i 2000\n"
    "cpu,host=h3 u=3i,extra=1e-3 3000\n"
)


def test_python_and_native_parser_give_the_same_table(tmp_path, monkeypatch):
    if influx._native_lineproto is None:
        pytest.skip("native tokenizer not built")
    seen = []
    for parser in ("native", "python"):
        if parser == "python":
            monkeypatch.setattr(influx, "_native_lineproto", None)
        inst = Standalone(str(tmp_path / parser), warm_start=False)
        try:
            assert write_lines(inst, PAYLOAD, precision="ms") == 4
            seen.append((
                _columns(inst, "cpu"), _columns(inst, "mem"),
                inst.sql("SELECT * FROM cpu ORDER BY ts").rows(),
                inst.sql("SELECT * FROM mem ORDER BY ts").rows()))
        finally:
            inst.close()
    assert seen[0] == seen[1]
    assert seen[0][2] == [
        ["h 1", "east", 1.5, 3, True, 'a, "b"', None, 1000],
        ["h2", "west", 2.0, 4, None, None, None, 2000],
        ["h3", "", 3.0, None, None, None, 0.001, 3000]]


def test_flows_are_told_the_columns_written(inst, monkeypatch):
    told = []
    monkeypatch.setattr(
        inst, "_notify_flows",
        lambda db, name, table, data, valid: told.append(
            (db, name, table.name, data, valid)))
    write_lines(inst, "m,h=a u=1,v=10 1000\nm,h=b u=2 2000",
                precision="ms")
    (db, name, tname, data, valid), = told
    assert (db, name, tname) == ("public", "m", "m")
    assert list(data) == ["ts", "h", "u", "v"]
    assert data["ts"].dtype == np.int64 and data["ts"].tolist() == [1000,
                                                                    2000]
    assert data["h"].dtype == object and data["h"].tolist() == ["a", "b"]
    assert data["u"].dtype == np.float64 and data["u"].tolist() == [1., 2.]
    assert data["v"].tolist() == [10.0, 0.0]
    assert list(valid) == ["v"] and valid["v"].tolist() == [True, False]


# ----------------------------------------------------------------------
# a count, not a speed
# ----------------------------------------------------------------------

FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice"]


def _tsbs_body(tick: int, hosts: int = 400) -> str:
    return "\n".join(
        f"cpu,hostname=host_{h},region=eu-west-1,datacenter=eu-west-1a,"
        f"rack={h % 100},os=Ubuntu16.04LTS,arch=x64,team=NYC,"
        f"service={h % 20},service_version=1,service_environment=test "
        + ",".join(f"{k}={(h * 7 + j * 13 + tick) % 6400 / 64}"
                   for j, k in enumerate(FIELDS))
        + f" {1_700_000_000_000 + tick * 10_000}"
        for h in range(hosts))


def test_a_tsbs_body_costs_calls_by_the_column_not_the_value(inst):
    write_lines(inst, _tsbs_body(0), precision="ms")   # creates the table
    table = inst.catalog.table("public", "cpu")

    code = influx._write_measurement.__code__
    write_code = type(table).write.__code__
    calls = []
    depth = {"inside": 0, "below_write": 0}

    def prof(frame, event, arg):
        # Python-level calls only: a C function raises c_call, not call
        if event == "call":
            if frame.f_code is code:
                depth["inside"] += 1
            elif depth["inside"]:
                if frame.f_code is write_code:
                    depth["below_write"] += 1
                elif not depth["below_write"]:
                    calls.append(frame.f_code.co_qualname)
        elif event == "return":
            if frame.f_code is code:
                depth["inside"] -= 1
            elif frame.f_code is write_code and depth["inside"]:
                depth["below_write"] -= 1

    body = _tsbs_body(1)
    sys.setprofile(prof)
    try:
        assert write_lines(inst, body, precision="ms") == 400
    finally:
        sys.setprofile(None)
    # 4,000 values: the parent made over 12,000 calls here
    assert len(calls) < 300, (len(calls), sorted(set(calls)))
    assert calls.count("ConcreteDataType.float64") == len(FIELDS)
    assert inst.sql("SELECT count(*), count(usage_guest_nice) FROM cpu"
                    ).rows() == [[800, 800]]


# ----------------------------------------------------------------------
# spans and the path counter
# ----------------------------------------------------------------------

def _exported(series: str) -> float:
    """One series of the text /metrics serves; 0 where it is not there."""
    for line in global_registry.render().splitlines():
        if line.startswith(series + " "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def _span_seconds(name):
    return _exported(f'gtpu_span_seconds_sum{{name="{name}"}}')


def _column_paths():
    return {path: _exported(
        f'gtpu_influx_field_columns_total{{path="{path}"}}')
        for path in ("typed", "mixed")}


def test_a_traced_body_holds_parse_and_columns_once_each(inst):
    before = _span_seconds("influx.columns"), _span_seconds("influx.parse")
    with tracing.span("http /v1/influxdb/*") as root:
        write_lines(inst, _tsbs_body(0, hosts=40), precision="ms")
    spans = tracing.global_traces.trace(root.trace_id)
    names = [s["name"] for s in spans]
    for stage in ("influx.parse", "influx.columns", "write.intern",
                  "wal.append", "memtable.append"):
        assert names.count(stage) == 1, (stage, names)
    by_name = {s["name"]: s for s in spans}
    root_id = by_name["http /v1/influxdb/*"]["span_id"]
    for stage in ("influx.parse", "influx.columns", "write.intern"):
        assert by_name[stage]["parent_id"] == root_id, stage
    # flat: parsed, then the columns built, then the write
    order = ["influx.parse", "influx.columns", "write.intern"]
    for a, b in zip(order, order[1:]):
        a, b = by_name[a], by_name[b]
        assert a["start_ms"] + a["duration_ms"] <= b["start_ms"] + 0.002
    assert _span_seconds("influx.columns") > before[0]
    assert _span_seconds("influx.parse") > before[1]


def test_path_counter_reads_zero_in_a_fresh_process():
    # a fresh process: nothing has written a body in it
    out = subprocess.run(
        [sys.executable, "-c",
         "from greptimedb_tpu.servers import influx\n"
         "from greptimedb_tpu.telemetry.metrics import global_registry\n"
         "print(global_registry.render())"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert 'gtpu_influx_field_columns_total{path="typed"} 0' in out.stdout
    assert 'gtpu_influx_field_columns_total{path="mixed"} 0' in out.stdout


def test_path_counter_counts_typed_and_mixed_columns(inst):
    p0 = _column_paths()
    write_lines(inst, _tsbs_body(0, hosts=8), precision="ms")
    p1 = _column_paths()
    assert p1["typed"] - p0["typed"] == len(FIELDS)
    assert p1["mixed"] - p0["mixed"] == 0
    # a bool among ints is no column of one kind; its neighbour is
    write_lines(inst, "m,h=a n=1i,u=1 1000\nm,h=b n=t,u=2 2000",
                precision="ms")
    p2 = _column_paths()
    assert p2["typed"] - p1["typed"] == 1
    assert p2["mixed"] - p1["mixed"] == 1
