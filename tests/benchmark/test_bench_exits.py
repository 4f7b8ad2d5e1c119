"""A run that is ended from outside leaves no process and no data
home: by SIGTERM (a time limit), SIGINT, SIGKILL of the harness (where
only the parent-death signal and the janitor are left to act) and by
the harness's own watchdog. A harness that only cleans up on its own
way out fails each of these. Also the two ways a run refuses to start.
CPU rehearsals at a tiny size."""

import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join("benchmark", "run.py")
CELL = "tsbs-single-groupby-1-1-1"
ARGV = ["--workload", CELL, "--seed", "7", "--seconds", "20",
        "--trace", "0", "--cpu-rehearsal", "--scale", "hosts=64",
        "--scale", "hours=24"]


def _processes_naming(path: str) -> list:
    """(pid, command line) of every live process that names `path`."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == os.getpid():
            continue
        try:
            with open(f"/proc/{name}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(f"/proc/{name}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except (OSError, IndexError):
            continue
        if path in cmd and state != "Z":
            out.append((int(name), cmd))
    return out


def _children_of(pid: int) -> list:
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
        except (OSError, IndexError):
            continue
        if int(ppid) == pid and state != "Z":
            out.append(int(name))
    return out


def _wait_for_server(p, state):
    deadline = time.time() + 120
    while not any("standalone" in c for _p, c in _processes_naming(state)):
        assert p.poll() is None, "the run ended before its server was up"
        assert time.time() < deadline, "no server came up"
        time.sleep(0.1)
    time.sleep(1.0)


def _nothing_left(state, pids):
    """Within a few seconds of the harness's end: no process of the run
    (server, janitor, anything they started) and no data home."""
    home = os.path.join(state, "home-" + CELL)
    deadline = time.time() + 10
    while time.time() < deadline and (
            _processes_naming(state) or os.path.exists(home)
            or any(os.path.exists(f"/proc/{pid}") for pid in pids)):
        time.sleep(0.1)
    assert _processes_naming(state) == []
    assert [pid for pid in pids if os.path.exists(f"/proc/{pid}")] == []
    assert not os.path.exists(home)


@pytest.mark.parametrize("how", [signal.SIGTERM, signal.SIGKILL,
                                 signal.SIGINT],
                         ids=["time_limit_sigterm", "killed_outright",
                              "interrupt"])
def test_a_run_that_is_ended_leaves_nothing(tmp_path, how):
    state = str(tmp_path / "st")
    p = subprocess.Popen(
        [sys.executable, RUN] + ARGV + ["--state-dir", state],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        _wait_for_server(p, state)
        assert os.path.isdir(os.path.join(state, "home-" + CELL))
        pids = _children_of(p.pid)      # the server and the janitor
        assert len(pids) >= 2
        p.send_signal(how)
        out, err = p.communicate(timeout=90)
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()
    assert p.returncode != 0 and out.strip() == b""
    if how != signal.SIGKILL:
        # the timeline so far is what a run that is cut leaves to read
        assert b"FAILED: ended by signal" in err
        assert b"requests made from the seed" in err.split(b"FAILED")[1]
    _nothing_left(state, pids)


def test_the_watchdog_ends_a_run_that_overruns(tmp_path):
    state = str(tmp_path / "st")
    argv = ARGV + ["--state-dir", state]
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.')\n"
         "from benchmark import run\n"
         f"sys.exit(run.main({argv!r}, watchdog_s=12))"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "FAILED: the watchdog's 12s are up" in p.stderr
    # the timeline so far follows, whatever phase a loaded machine reached
    assert "native parser built" in p.stderr.split("FAILED")[1]
    _nothing_left(state, [])


def test_a_home_that_a_killed_run_left_is_removed_at_the_next_start(
        tmp_path):
    state = tmp_path / "st"
    stale = state / ("home-" + CELL) / "home" / "wal"
    stale.mkdir(parents=True)
    (stale / "left").write_text("x")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3",
         "--seconds", "1", "--trace", "0", "--cpu-rehearsal", "--scale",
         "hosts=64", "--scale", "hours=2", "--state-dir", str(state)],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert not (state / ("home-" + CELL)).exists()


def test_no_accelerator_without_the_flag_fails_and_prints_no_result(
        tmp_path):
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0", "--state-dir",
         str(tmp_path / "st")],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
    _nothing_left(str(tmp_path / "st"), [])


def test_alone_in_a_directory_fails_and_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0", "--cpu-rehearsal"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
