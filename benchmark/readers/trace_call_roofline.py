"""A program's share of the memory roofline, by the calls of it that the
capture holds: the bytes one call's work has to move (`spec["bytes"]`
of `benchmark/lib/<spec["model"]>.py`, from the cell's shapes) times
those calls, over the chip's peak bandwidth, over their device time.
For a cell whose requests are not all of one kind, where the capture's
requests cannot be counted from `client.requests_answered`: one upkeep
is one call of the append program, one dispatched panel one call of the
rows program."""
import importlib

from benchmark.readers.trace_program_call_ms import programs

DEVICE = True


def read(spec, ctx):
    seconds, calls = programs(spec, ctx)
    if seconds <= 0 or not calls:
        return None
    model = importlib.import_module("benchmark.lib." + spec["model"])
    need = getattr(model, spec["bytes"])(ctx["shapes"])
    return 100.0 * (need * calls / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
