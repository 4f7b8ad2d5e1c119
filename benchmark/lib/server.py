"""The system under test: one `standalone start` child and its sockets.

A copy of `chip_smoke.py`'s `Server` (PR 21), kept with the benchmark so
that no later PR can change the yardstick. The harness process never
imports jax nor anything of `greptimedb_tpu` that does: the server child
is the one process that holds the chip. The child takes the DEFAULT
configuration; what the benchmark fixes around it (CPU set, hash seed,
buffering) is environment of the process, not an option of the program.
"""

from __future__ import annotations

import ctypes
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')   # name="value"
_LIBC = ctypes.CDLL(None, use_errno=True)   # loaded before any fork
_PR_SET_PDEATHSIG = 1
_PR_SET_CHILD_SUBREAPER = 36


class BenchFailure(Exception):
    """The run cannot produce a result: no device, a dead child, a
    refused set-up step. Exit non-zero, print no result line."""


def check(cond, msg):
    if not cond:
        raise BenchFailure(msg)


def adopt_orphans():
    """Make this process the reaper of all its descendants: whatever a
    child leaves behind (a compiler under `make`, a helper of the
    server) becomes a child of the harness, where `sweep` finds it."""
    _LIBC.prctl(_PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0)


def children_of(pid: int) -> list:
    """[(pid, command)] of the live processes whose parent is `pid`."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                state, ppid = f.read().rsplit(")", 1)[1].split()[:2]
            if int(ppid) != pid:
                continue
            with open(f"/proc/{name}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except (OSError, ValueError, IndexError):
            continue        # gone between the listing and the read
        out.append((int(name), f"[{state}] {cmd.strip()[:200]}"))
    return out


def sweep(say=None, spare: tuple = ()) -> int:
    """Kill and reap every child this process still has (with
    `adopt_orphans`, every descendant) but those in `spare`, until none
    is left. Called last on every path out of a run; returns how many
    it found."""
    found = 0
    deadline = time.time() + 30
    while True:
        kids = [k for k in children_of(os.getpid()) if k[0] not in spare]
        if not kids or time.time() > deadline:
            return found
        for pid, cmd in kids:
            found += 1
            if say:
                say(f"sweep: killing leftover process {pid}: {cmd}")
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        for pid, _cmd in kids:
            try:
                os.waitpid(pid, 0)
            except (ChildProcessError, OSError):
                pass
        time.sleep(0.05)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def split_cpus() -> tuple[list, list]:
    """(generator cpus, server cpus): disjoint, the same in every run on
    the same machine. The generator keeps the first quarter (at least
    one, at most four), the server the rest."""
    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) < 2:
        return cpus, cpus
    n_gen = min(4, max(1, len(cpus) // 4))
    return cpus[:n_gen], cpus[n_gen:]


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {(family, ((label, value), ...)): float}."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        head, _, val = ln.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = _LABEL.findall(rest)
        try:
            out[(name, tuple(sorted(labels)))] = float(val)
        except ValueError:
            continue
    return out


class Server:
    """One `standalone start` child. Every wait checks that the child
    is still alive: a server that died is a failure, never a timeout."""

    def __init__(self, root: str, data_home: str, log_dir: str, *,
                 platform: str, cpus: list | None = None, on_spawn=None):
        self.root = root
        self.on_spawn = on_spawn    # told the child's pid (= its group)
        self.data_home = data_home
        self.log_dir = log_dir
        self.cpus = cpus
        self.http = f"127.0.0.1:{free_port()}"
        self.flight_port = free_port()
        self.proc: subprocess.Popen | None = None
        self.log_path = ""
        env = dict(os.environ)
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        env["PYTHONHASHSEED"] = "0"
        # libtpu's own log goes beside the server's, not to /tmp/tpu_logs
        env.setdefault("TPU_LOG_DIR", os.path.join(log_dir, "tpu_logs"))
        if platform == "cpu":
            # the rehearsal only: no accelerator, and the device path
            # also below the 262,144 rows at which the default gates
            # pick it, so that a tiny size drives the same code
            env["JAX_PLATFORMS"] = "cpu"
            env["GREPTIMEDB_TPU__QUERY__PREFER_DEVICE"] = "true"
        self.env = env

    def start(self) -> float:
        shutil.rmtree(self.log_dir, ignore_errors=True)  # the last run's
        os.makedirs(self.log_dir)
        self.log_path = os.path.join(self.log_dir, "server.log")
        args = [sys.executable, "-m", "greptimedb_tpu.cli", "standalone",
                "start", "--data-home", self.data_home,
                "--http-addr", self.http,
                "--flight-addr", f"127.0.0.1:{self.flight_port}",
                "--mysql-addr", "", "--postgres-addr", ""]
        cpus = self.cpus
        parent = os.getpid()

        def pin():
            # the server never outlives the harness: if the harness is
            # killed outright (a time limit's SIGKILL), the kernel sends
            # the child SIGKILL. `start` runs on the main thread only:
            # the signal follows the thread that forked
            _LIBC.prctl(_PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0)
            if os.getppid() != parent:
                os._exit(1)
            if cpus:
                os.sched_setaffinity(0, cpus)

        t0 = time.perf_counter()
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                args, cwd=self.root, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
                preexec_fn=pin,
            )
        if self.on_spawn:
            self.on_spawn(self.proc.pid)
        deadline = time.time() + 120
        while True:
            self.alive()
            try:
                with urllib.request.urlopen(
                        f"http://{self.http}/health", timeout=2):
                    break
            except (urllib.error.URLError, OSError):
                check(time.time() < deadline,
                      "server never answered /health")
                time.sleep(0.1)
        return time.perf_counter() - t0

    def alive(self):
        check(self.proc is not None and self.proc.poll() is None,
              f"server child died (exit "
              f"{self.proc.poll() if self.proc else None}); "
              f"log tail:\n{self.log_tail()}")

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "(no log)"

    def stop(self):
        """SIGTERM and wait: the graceful shutdown a deployment does.
        Whatever else is left in the server's own session goes too."""
        self.alive()
        self.proc.send_signal(signal.SIGTERM)
        code = self.proc.wait(timeout=60)
        self._end_group()
        check(code == 0, f"server exited {code} on SIGTERM; log tail:\n"
                         f"{self.log_tail()}")

    def kill(self):
        """Cleanup path: whatever is still running goes, group and all,
        at once: nobody needs this shutdown. Safe to call again after a
        call that was interrupted."""
        if self.proc is not None:
            self._end_group()

    def _end_group(self):
        """SIGKILL the server's process group (the child leads a session
        of its own), reap the leader, and wait until the group is empty.
        With `adopt_orphans` the group's other members are children of
        the harness once the leader is gone, so they can be reaped."""
        pgid = self.proc.pid

        def killpg() -> bool:
            try:
                os.killpg(pgid, signal.SIGKILL)
                return True
            except (ProcessLookupError, PermissionError):
                return False    # nothing left in the group

        killpg()
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        deadline = time.time() + 30
        while killpg() and time.time() < deadline:
            try:
                while os.waitpid(-pgid, os.WNOHANG)[0]:
                    pass
            except ChildProcessError:
                pass
            time.sleep(0.05)
        self.proc = None

    # -- HTTP ----------------------------------------------------------
    def _open(self, req, timeout: float) -> bytes:
        self.alive()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.read()
        except urllib.error.HTTPError as e:
            raise BenchFailure(
                f"HTTP {e.code} from {req.full_url}: "
                f"{e.read()[:600]!r}") from e

    def get(self, path: str, timeout: float = 120.0) -> bytes:
        return self._open(
            urllib.request.Request(f"http://{self.http}{path}"), timeout)

    def sql(self, sql: str, timeout: float = 900.0) -> list:
        """POST /v1/sql -> rows of the last result set."""
        req = urllib.request.Request(
            f"http://{self.http}/v1/sql",
            data=urllib.parse.urlencode({"sql": sql}).encode(),
            headers={"Content-Type": "application/x-www-form-urlencoded"},
        )
        doc = json.loads(self._open(req, timeout))
        check("output" in doc, f"no output for {sql[:80]!r}: {doc}")
        last = doc["output"][-1]
        if "records" in last:
            return last["records"]["rows"]
        return [[last.get("affectedrows", 0)]]

    def post(self, path: str, body: bytes, headers: dict | None = None,
             timeout: float = 300.0) -> bytes:
        req = urllib.request.Request(
            f"http://{self.http}{path}", data=body, method="POST",
            headers=headers or {})
        return self._open(req, timeout)

    def device(self) -> dict:
        """The `device` check of /health?deep=1: identity and per-device
        bytes in use as jax reports them IN THE SERVER PROCESS."""
        doc = json.loads(self.get("/health?deep=1"))
        dev = doc["checks"]["device"]
        check(dev.get("ok"), f"device health check failed: {dev}")
        return dev

    def metrics(self) -> dict:
        return parse_metrics(self.get("/metrics").decode())
