"""A program's share of the memory roofline: the bytes the work has to
move (from the cell's shapes, by `benchmark/lib/bytes_model.py`, never
from a program's own cost analysis) over the chip's peak bandwidth
(`benchmark/peaks.json`, by device kind), over the device time of the
named programs in the trace. Memory-bound by construction: these
programs fold and select, they multiply no matrices."""
from benchmark.lib import bytes_model
from benchmark.readers import requests_in_trace

DEVICE = True


def read(spec, ctx):
    tr = ctx.get("trace")
    n = requests_in_trace(spec, ctx)
    if not tr or not n:
        return None
    seconds = sum(rec["seconds"] for name, rec in tr["programs"].items()
                  if any(name.startswith(p) for p in spec["programs"]))
    if seconds <= 0:
        return None
    need = getattr(bytes_model, spec["bytes"])(ctx["shapes"])
    peak = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * (need * n / peak) / seconds
