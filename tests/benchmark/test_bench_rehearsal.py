"""CPU rehearsals of the benchmark's one command at a tiny size: the
result line's keys, no device metric under platform=cpu, data that
every run builds from the seed in a home it removes, the timed path
broken underneath (`correct` comes out false), and a cell, a
configuration and a counter metric added as files only in a temporary
copy. (How a run that is ended from outside leaves things is in
test_bench_exits.py.)"""

import json
import os
import subprocess
import sys

import pytest

from conftest import copy_checkout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join("benchmark", "run.py")
DEVICE_METRICS = {"range_roofline", "hbm_bytes_in_use",
                  "device_idle_share.query", "device_busy_ms_per_query",
                  "device_idle_share.throughput"}
QUERY_SCALE = ["--scale", "hosts=64", "--scale", "hours=2"]
LOAD_SCALE = ["--scale", "hosts=64", "--scale", "hours=8"]


@pytest.fixture(scope="module")
def state(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_state"))


def _rehearse(workload, state, extra, *, seed=2**31 + 77, seconds=2,
              trace=0, cwd=ROOT, hooks_code=None, timeout=400):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--cpu-rehearsal",
            "--state-dir", state] + extra
    if hooks_code is None:
        cmd = [sys.executable, RUN] + argv
    else:
        # the same command, with the timed path broken underneath
        cmd = [sys.executable, "-c",
               "import sys; sys.path.insert(0, '.')\n"
               "from benchmark import run\n"
               f"{hooks_code}\n"
               f"sys.exit(run.main({argv!r}, hooks=HOOKS))"]
    p = subprocess.run(cmd, cwd=cwd, capture_output=True, text=True,
                       timeout=timeout)
    line = None
    if p.returncode == 0:
        line = json.loads(p.stdout.strip().splitlines()[-1])
    return p, line


def _check_line(line, names):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert not DEVICE_METRICS & set(line["metrics"])
    assert "breakdown" not in line
    for name, m in line["metrics"].items():
        assert name in names, name
        assert isinstance(m["value"], float) and m["unit"]
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}


def _names(kind, cell, root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        m = json.load(f)
    cells = [w["name"] for w in m["workloads"]]
    return {e["name"] for e in m[kind] if cell in e.get("workloads", cells)}


def test_query_cell_builds_its_data_in_every_run(state):
    cell = "tsbs-single-groupby-1-1-1"
    p, first = _rehearse(cell, state, QUERY_SCALE, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    _check_line(first, _names("per_layer", cell))
    assert first["correct"] is True and first["failed"] == 0
    assert first["attempted"] > 30
    assert {"stmt_ms", "dispatches_per_query", "http_server_ms.query",
            "readback_bytes_per_query"} <= set(first["metrics"])
    assert first["notes"]["in_window"]["compiles"] == 0
    # a timeline line a phase, then each number compared beside its limit
    lines = p.stderr.splitlines()
    for ln in lines:
        assert ln.startswith(("t=", "compared ")), ln
    assert lines[-1].startswith("compared ")
    said = " ".join(ln for ln in lines if ln.startswith("t="))
    for word in ("server answers", "load:", "grid built", "count(*)",
                 "warm:", "window opens", "window closed",
                 "server stopped", "compared", "trace reduced"):
        assert word in said, word
    assert first["notes"]["phases"][-1][0] < 240
    # served by the server that loaded the rows, each of them counted,
    # and every answer of the window compared; the home is gone
    assert first["compared"]["rows_acked_not_counted"] == {
        "value": 0, "limit": 0}
    assert first["notes"]["compared_answers"] == first["attempted"]
    assert not os.path.exists(os.path.join(state, "home-" + cell))
    # a second run of the same seed builds again: one regime of set-up
    p, second = _rehearse(cell, state, QUERY_SCALE, trace=0)
    assert p.returncode == 0, p.stderr[-3000:]
    _check_line(second, _names("end_to_end", cell))
    assert set(second["metrics"]) == _names("end_to_end", cell)
    assert second["correct"] is True
    phases = [[" ".join(m.split()[:2]) for _t, m in line["notes"]["phases"]]
              for line in (first, second)]
    assert phases[0][:11] == phases[1][:11] and "grid built" in phases[1]
    assert not os.path.exists(os.path.join(state, "home-" + cell))


ALTER_AN_ANSWER = """
def alter(run):
    # an answer altered where it is produced: acknowledged rows are
    # overwritten behind the reference's back
    run.srv.sql("insert into cpu (ts, hostname, region, datacenter, rack, "
                "os, arch, team, service, service_version, "
                "service_environment, usage_user) select ts, hostname, "
                "region, datacenter, rack, os, arch, team, service, "
                "service_version, service_environment, usage_user + 1 "
                "from cpu")
HOOKS = {"before_window": alter}
"""

LOSE_A_ROW = """
def lose(run):
    # an acknowledged row that is not there when it is read back
    run.srv.sql("delete from cpu where hostname = 'host_3' and ts = 0")
HOOKS = {"before_check": lose}
"""


def test_altered_answer_comes_out_not_correct(state):
    p, line = _rehearse("tsbs-single-groupby-1-1-1", state, QUERY_SCALE,
                        hooks_code=ALTER_AN_ANSWER)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    assert line["compared"]["values_differing"]["value"] > 0
    assert line["compared"]["rows_missing"]["value"] == 0


def test_throughput_cell_drives_fifty_clients(state):
    cell = "tsbs-single-groupby-1-1-1-w50"
    p, line = _rehearse(cell, state, QUERY_SCALE, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    _check_line(line, _names("per_layer", cell))
    assert line["correct"] is True and line["failed"] == 0
    assert {"generator_gap_share", "stmt_ms.throughput",
            "http_server_ms.throughput"} == set(line["metrics"])
    assert "50 closed-loop clients" in p.stderr
    assert line["notes"]["compared_answers"] == line["attempted"] > 50
    p, line = _rehearse(cell, state, QUERY_SCALE, seconds=1)
    assert p.returncode == 0, p.stderr[-3000:]
    assert set(line["metrics"]) == {"queries_per_s", "setup_s"}


def test_load_cell_counts_and_reads_back_what_was_acknowledged(
        state, load_checkout):
    cell = "tsbs-load"
    p, line = _rehearse(cell, state, LOAD_SCALE, trace=1, cwd=load_checkout)
    assert p.returncode == 0, p.stderr[-3000:]
    _check_line(line, _names("per_layer", cell, load_checkout))
    assert line["correct"] is True, line["compared"]
    rb = line["notes"]["readback"]
    assert rb["rows_acked"] == rb["counted"] > 0
    # all ten fields by host and hour, and one host's rows at full width
    assert rb["groups"] >= 64 and rb["rows_of_host"] > 0
    assert {"readback_avg_rel_err", "readback_max_differing",
            "readback_rows_differing", "readback_rows_missing",
            "readback_off_device"} <= set(line["compared"])
    assert rb["rows_acked"] == 3000 * (
        line["attempted"] + 2 + 12)       # the warm-up's bodies count too
    assert {"http_server_ms.ingest", "write_server_share"} <= set(
        line["metrics"])
    assert 0 < line["metrics"]["write_server_share"]["value"] <= 100
    assert not os.path.exists(os.path.join(state, "home-" + cell))


def test_lost_acknowledged_row_comes_out_not_correct(state, load_checkout):
    p, line = _rehearse("tsbs-load", state, LOAD_SCALE,
                        hooks_code=LOSE_A_ROW, cwd=load_checkout)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    assert line["compared"]["rows_acked_not_counted"]["value"] == 1


WRONG_FIELD = """
def wrong(run):
    # a field altered where it is stored: not one of the two that a
    # narrow read-back would look at
    run.srv.sql("insert into cpu (ts, hostname, region, datacenter, rack, "
                "os, arch, team, service, service_version, "
                "service_environment, usage_guest) select ts, hostname, "
                "region, datacenter, rack, os, arch, team, service, "
                "service_version, service_environment, usage_guest + 1 "
                "from cpu")
HOOKS = {"before_check": wrong}
"""


def test_a_field_outside_the_first_two_altered_comes_out_not_correct(
        state, load_checkout):
    p, line = _rehearse("tsbs-load", state, LOAD_SCALE,
                        hooks_code=WRONG_FIELD, cwd=load_checkout)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is False
    assert line["compared"]["readback_rows_differing"]["value"] > 0
    assert line["compared"]["readback_max_differing"]["value"] > 0


def test_a_cell_a_configuration_and_a_metric_are_files_only(tmp_path):
    """A later PR adds `tsbs-single-groupby-5-8-1` over a configuration
    of its own and a counter metric, touching no file that exists
    (BENCHMARK.json gets entries appended)."""
    root = tmp_path / "checkout"
    manifest = copy_checkout(str(root))
    before = {}
    for d, _dirs, files in os.walk(root / "benchmark"):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                before[path] = fh.read()
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "tsbs-cpu-4000.json").read_text())
    cfg.update(name="tsbs-cpu-100", scale={"hosts": 100, "hours": 4},
               reference="tsbs-cpu-4000",
               source="TSBS devops cpu-only --scale=100 (the TSBS README's "
                      "own example scale)")
    (bench / "configs" / "tsbs-cpu-100.json").write_text(json.dumps(cfg))
    cell = {
        "name": "tsbs100-single-groupby-5-8-1", "config": "tsbs-cpu-100",
        "traffic": "single-groupby-5-8-1", "chips": 1,
        "why": "max of 5 fields of 8 hosts over 1 hour by minute",
    }
    (bench / "workloads" / (cell["name"] + ".json")).write_text(json.dumps({
        "generator": "tsbs_range",
        "params": {"agg": "max", "fields": 5, "hosts": 8, "span_hours": 1,
                   "bucket_s": 60},
        "workers": 2, "max_requests_per_s": 400,
        "limits": {"rows_missing": 0, "values_differing": 0}}))
    metric = {
        "name": "tag_index_lookups_per_query", "unit": "1/query",
        "better": "lower", "source": "program_counter",
        "layer": "index/tag_index.py", "moves": "query_p50_ms",
        "workloads": [cell["name"]]}
    (bench / "metrics" / (metric["name"] + ".json")).write_text(
        json.dumps({"reader": "ratio_of_deltas",
                    "num": {"family": "gtpu_index_lookups_total"},
                    "den": {"client": "requests_answered"}}))
    manifest["configs"].append({
        "name": cfg["name"], "source": cfg["source"],
        "file": "benchmark/configs/tsbs-cpu-100.json",
        "reduced": cfg["reduced"], "why": "a small fleet"})
    manifest["workloads"].append(cell)
    for m in manifest["end_to_end"]:
        if m["name"] in ("query_p50_ms", "query_p95_ms"):
            m["workloads"].append(cell["name"])
    manifest["per_layer"].append(metric)
    for m in manifest["per_layer"]:
        if m["name"] == "stmt_ms":
            m["workloads"].append(cell["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    p, line = _rehearse(cell["name"], str(tmp_path / "state"),
                        ["--scale", "hosts=64", "--scale", "hours=2"],
                        trace=1, cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    assert line["correct"] is True, line["compared"]
    assert line["metrics"]["tag_index_lookups_per_query"]["value"] > 0
    assert "stmt_ms" in line["metrics"]
    assert line["notes"]["compared_values"] >= 5 * 8 * 60
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, f"{path} had to be edited"
