#!/usr/bin/env python3
"""What outlives a harness that is killed outright: a small process in
a session of its own, holding the read end of a pipe whose write end
only the harness has. The harness writes it lines as the run goes:

    home <dir>      the run's directory, to be removed
    group <pgid>    a process group the run started, to be killed

When the pipe closes, by a clean exit or by SIGKILL of the harness, it
kills every group it was told of, waits (bounded) until each is empty,
removes every directory it was told of and exits. After a clean exit
there is nothing left to do and it ends at once. Imports nothing of the
benchmark nor of the program.
"""

import os
import shutil
import signal
import sys
import time


def group_alive(pgid: int) -> bool:
    """Whether a process of the group is still running; one that has
    ended and only waits to be reaped holds no file and counts as gone."""
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                state, _ppid, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
        except (OSError, ValueError, IndexError):
            continue
        if int(pgrp) == pgid and state != "Z":
            return True
    return False


def main() -> int:
    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, signal.SIG_IGN)   # only the pipe ends it
    homes, groups = [], []
    for line in sys.stdin:
        kind, _, arg = line.strip().partition(" ")
        if kind == "home" and arg:
            homes.append(arg)
        elif kind == "group" and arg.isdigit():
            groups.append(int(arg))
    for pgid in groups:
        try:
            os.killpg(pgid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    deadline = time.time() + 20
    while any(group_alive(g) for g in groups) and time.time() < deadline:
        time.sleep(0.05)
    for home in homes:
        for _ in range(3):      # a dying writer may still make a file
            shutil.rmtree(home, ignore_errors=True)
            if not os.path.exists(home):
                break
            time.sleep(0.2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
