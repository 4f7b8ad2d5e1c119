"""What the two resident grids share to follow their tables under
writes: the range grid (query/device_range.py `_upkeep`) and the PromQL
selector grid (promql/fast.py `_upkeep`). Both bring an entry forward by
the rows written since its stamp (`Table.appended_since`, which reads
them from the region by sequence), scatter them into planes they donate
to one program, and so need:

- a gate (`PlaneGate`) that shuts queries out of the planes while the
  upkeep donates them, and out of filing what they read from planes
  that the upkeep has since replaced (`file_unless_appended`);
- one rule for how many cells a time axis is given: whole lane tiles
  (`lane_cells`), and a quarter more where it is to take appends
  (`cell_capacity`).
"""

from __future__ import annotations

import contextlib
import time

from greptimedb_tpu import concurrency
from greptimedb_tpu.telemetry.metrics import global_registry

# time spent held at an entry's `PlaneGate`, stamped only by a caller
# that actually waits: a query behind the upkeep's donation ("shared"),
# the upkeep behind the queries reading the planes or another upkeep
# ("exclusive"). One connection never contends; many will.
GATE_WAIT = global_registry.counter(
    "gtpu_plane_gate_wait_seconds_total",
    "seconds callers waited at a grid entry's plane gate, by the side "
    "they asked for (shared: a query; exclusive: the upkeep)",
    labels=("side",),
)
GATE_WAIT_SHARED = GATE_WAIT.labels("shared")
GATE_WAIT_EXCLUSIVE = GATE_WAIT.labels("exclusive")

LANE = 128      # the chip tiles an array's last axis by this many


class PlaneGate:
    """Who may touch an entry's planes and what is filed about them:
    any number of queries, from reading the plane references to the
    return of their dispatch, and again to file a window's record or a
    session's buffer (`shared`), or the one upkeep that donates the
    planes to its program, and so deletes the arrays a query might
    still hold, and forgets the windows it wrote to (`exclusive`)."""

    def __init__(self):
        self._cv = concurrency.Condition()
        self._readers = 0
        self._writer = False

    @contextlib.contextmanager
    def shared(self):
        with self._cv:
            if self._writer:
                t0 = time.monotonic()
                while self._writer:
                    self._cv.wait()
                GATE_WAIT_SHARED.inc(time.monotonic() - t0)
            self._readers += 1
        try:
            yield
        finally:
            with self._cv:
                self._readers -= 1
                if not self._readers:
                    self._cv.notify_all()

    @contextlib.contextmanager
    def exclusive(self):
        with self._cv:
            t0 = (time.monotonic() if self._writer or self._readers
                  else None)
            while self._writer:
                self._cv.wait()
            self._writer = True
            while self._readers:
                self._cv.wait()
            if t0 is not None:
                GATE_WAIT_EXCLUSIVE.inc(time.monotonic() - t0)
        try:
            yield
        finally:
            with self._cv:
                self._writer = False
                self._cv.notify_all()


def lane_cells(n: int) -> int:
    """`n` cells as the chip lays a time axis out: past one lane tile,
    rounded up to a whole number of tiles (the chip pads the last axis
    to one anyway, and a whole-plane fusion runs faster over whole
    tiles than over a ragged last one); within one tile, `n` itself."""
    return -(-n // LANE) * LANE if n > LANE else n


def cell_capacity(nb: int, fits) -> int:
    """Cells to give a time axis whose data spans `nb`: a quarter more
    (a geometric rule from the entry's own span: a table that holds
    days has hours to spare, one that holds minutes has minutes), in
    whole lane tiles (`lane_cells`), so that appended ticks change no
    shape; `nb` itself where `fits(cells)` refuses that (the cell cap,
    the byte budget): an entry that fits without spare cells is still
    built, and rebuilds when it grows."""
    cap = lane_cells(nb + max(nb // 4, 1))
    return cap if fits(cap) else nb


def file_unless_appended(entry, seen: int, put, *args) -> None:
    """File what a query learned from `entry`'s planes as it read them
    when `entry.appends` stood at `seen`: not if a batch was applied
    since, whose forgetting of the windows it wrote to has run and
    would have missed it (a later query, sent after that batch's rows
    were acknowledged, would be answered without them)."""
    with entry.gate.shared():
        if entry.appends == seen:
            put(*args)
