"""chip_smoke.py's contract on a machine without a chip, and the compile
cache rule it checks (ISSUE 21).

The served path itself is driven by the CPU rehearsal (`slow`: it
starts a real server twice and loads 368,640 rows); tier-1 keeps the
two cases that must FAIL: no accelerator without the explicit flag, and
a directory that holds chip_smoke.py and nothing else of the repo.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, *, script=SMOKE, cache=False, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)      # the server sees ONE cpu device
    if cache:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run(
        [sys.executable, script, *args], cwd=tmp_path, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=timeout,
    )


def _result_line(stdout: str):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    if not lines:
        return None
    try:
        doc = json.loads(lines[-1])
    except ValueError:
        return None
    return doc if isinstance(doc, dict) and "ok" in doc else None


def test_no_accelerator_without_flag_fails_and_prints_no_result(tmp_path):
    p = _run(["--hosts", "8", "--hours", "1"], tmp_path)
    assert p.returncode != 0
    assert _result_line(p.stdout) is None
    assert "platform='cpu'" in p.stderr and "no accelerator" in p.stderr
    # nothing was loaded before the platform was known
    assert "load:" not in p.stdout


def test_alone_in_a_directory_fails(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone / "chip_smoke.py")
    p = _run([], alone, script=str(alone / "chip_smoke.py"), timeout=60)
    assert p.returncode != 0
    assert _result_line(p.stdout) is None
    assert os.listdir(alone) == ["chip_smoke.py"]


@pytest.mark.slow
def test_cpu_rehearsal_drives_the_served_path(tmp_path):
    p = _run(["--cpu-rehearsal", "--hosts", "512", "--hours", "2"],
             tmp_path, cache=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    # the last line: "ok" and "device" and nothing else
    assert _result_line(p.stdout) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    # the line before it carries the report (also written to a file)
    head, sep, body = p.stdout.splitlines()[-2].partition("] report: ")
    assert sep and "platform=cpu" in head
    doc = json.loads(body)
    with open(tmp_path / "chiprun_out" / "chip_smoke_report.json") as f:
        assert json.load(f) == doc
    assert doc["ok"] is True
    assert doc["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert doc["rows"] == 512 * 720
    assert doc["load"]["acked_rows"] == doc["rows"]
    assert doc["restart"]["grid_cache"] in ("hit", "miss(restored)")
    assert doc["restart"]["count"] == doc["rows"]
    assert doc["compaction"]["merges_by_path"] == {"device": 1.0}
    assert [s["step"] for s in doc["steps"]] == [
        "double-groupby-all", "lastpoint", "cpu-max-all-8",
        "single-groupby-5-8-1", "groupby-hostname",
        "promql-max-over-time",
    ]
    assert all(s["exec_path"].startswith("device") for s in doc["steps"])
    # every line of a rehearsal says which platform it ran on
    assert all("platform=" in ln
               for ln in p.stdout.splitlines()[:-1] if ln.strip())
    # the cache was placed from outside, and the second life of the
    # server added nothing to it
    ce = doc["cache_entries"]
    assert ce["dir"] == str(tmp_path / "jax_cache")
    assert ce["after_first_life"] == ce["after_second_life"] > 0
    assert len(os.listdir(tmp_path / "jax_cache")) > 0


# ----------------------------------------------------------------------
# the compile cache rule (instance.enable_compile_cache)
# ----------------------------------------------------------------------

@pytest.fixture()
def cache_rule(monkeypatch):
    """enable_compile_cache with its once-per-process latch reset and
    jax.config.update recorded instead of applied."""
    import jax

    from greptimedb_tpu import instance

    updates = {}
    monkeypatch.setattr(instance, "_compile_cache_dir", None)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    return instance.enable_compile_cache, updates


def test_cache_dir_from_env_is_used_and_not_set_in_code(
        cache_rule, monkeypatch, tmp_path):
    enable, updates = cache_rule
    placed = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert enable() == placed
    assert "jax_compilation_cache_dir" not in updates
    # the thresholds are lowered in this case too
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_cache_dir_default_is_fixed_inside_the_checkout(
        cache_rule, monkeypatch, tmp_path):
    enable, updates = cache_rule
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert enable() == fixed
    assert updates["jax_compilation_cache_dir"] == fixed
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_cache_is_never_under_a_data_home(cache_rule, monkeypatch,
                                          tmp_path):
    from greptimedb_tpu.instance import Standalone

    enable, updates = cache_rule
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    home = tmp_path / "data_home"
    inst = Standalone(str(home), warm_start=False)
    try:
        inst.execute_sql("create table t (ts timestamp time index, "
                         "v double)")
    finally:
        inst.close()
    assert not str(updates["jax_compilation_cache_dir"]).startswith(
        str(tmp_path))
    for root, dirs, _files in os.walk(home):
        assert ".xla_cache" not in dirs and ".jax_cache" not in dirs, root
