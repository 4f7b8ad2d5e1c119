"""TSBS devops `cpu-only` whose fleet keeps reporting: the stream of
`tsbs_cpu` (same schema, tags and values, made from the seed), cut in
two. The first `hours` are held when the window opens: they are the
dataset's `values` and `rows`, loaded and counted as `tsbs_cpu` loads
them. The next `live_minutes` of the same stream ride beside them as
`live`, for the traffic to write inside the window; `stream` is both,
what the reference answers over. A pure function of (seed, hosts,
hours, live_minutes).

The configuration guarantees `grid_kept_up`, and the counter that holds
a run to it is `gtpu_grid_upkeep_total{outcome}`, which a program that
keeps its range grid up under writes exports from start-up with every
label at 0. A program without the family cannot be held to the
guarantee (a missing counter would read as "no rebuild" while the grid
is rebuilt after every body): `load` refuses it before a row is sent,
so the run ends in seconds with no result line and exit code 1.
"""

from __future__ import annotations

from benchmark.datagen import tsbs_cpu
from benchmark.datagen.tsbs_cpu import (
    CELLS_PER_HOUR, INTERVAL_MS, Dataset, make_tags, make_values,
)
from benchmark.lib.server import check

UPKEEP_FAMILY = "gtpu_grid_upkeep_total"

__all__ = ["make", "load"]


def make(np, seed: int, scale: dict) -> Dataset:
    hosts, hours = int(scale["hosts"]), int(scale["hours"])
    held = hours * CELLS_PER_HOUR
    live = int(scale["live_minutes"]) * 60_000 // INTERVAL_MS
    stream = make_values(np, seed, hosts, held + live)
    ds = Dataset(stream[:, :, :held], make_tags(np, seed, hosts), hours)
    ds.stream = stream
    ds.live = stream[:, :, held:]
    ds.live_cells = live
    return ds


def load(np, srv, ds: Dataset, say) -> dict:
    check(any(k[0] == UPKEEP_FAMILY for k in srv.metrics()),
          f"the server exports no {UPKEEP_FAMILY}: this program does not "
          "keep a range grid up under writes, so it cannot serve "
          "tsbs-cpu-4000-live, whose guarantee grid_kept_up that counter "
          "holds (it would rebuild the grid after every body and the "
          "missing counter would read 0)")
    return tsbs_cpu.load(np, srv, ds, say)
