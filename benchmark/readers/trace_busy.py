"""Device busy time in the capture over the requests it holds (counted
from the trace's own program calls), times `scale`."""
from benchmark.readers import requests_in_trace

DEVICE = True


def read(spec, ctx):
    tr = ctx.get("trace")
    n = requests_in_trace(spec, ctx)
    if not tr or not tr.get("busy_s") or not n:
        return None
    return tr["busy_s"] / n * spec.get("scale", 1.0)
