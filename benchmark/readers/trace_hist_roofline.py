"""The PromQL programs' share of the memory roofline: the bytes a query
has to move by its shapes (`benchmark/lib/bytes_model_prom.py`) over
the chip's peak bandwidth, over the device time a query takes in the
trace. A query of this path runs for seconds, so a capture of four
holds parts of calls: counting the program's events as whole calls
(`requests_in_trace`) would read the time a call high or low by where
the capture's edges fell. The queries that the capture's span holds are
taken from the window's own rate instead: requests answered a second of
the window, times the calls a request makes, times the capture's span.
For calls much shorter than the capture the two counts agree."""
from benchmark.lib import bytes_model_prom
from benchmark.readers import delta

DEVICE = True


def read(spec, ctx):
    tr, client = ctx.get("trace"), ctx["client"]
    if not tr or not tr.get("window_s") or not client["window_s"]:
        return None
    seconds = sum(rec["seconds"] for name, rec in tr["programs"].items()
                  if any(name.startswith(p) for p in spec["programs"]))
    calls = delta(ctx, spec["calls"]["family"], spec["calls"].get("labels"))
    if seconds <= 0 or not calls:
        return None
    held = calls / client["window_s"] * tr["window_s"]
    need = getattr(bytes_model_prom, spec["bytes"])(ctx["shapes"])
    return 100.0 * (need * held / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
