"""after - before of a counter over the window, times `scale`."""
from benchmark.readers import delta


def read(spec, ctx):
    d = delta(ctx, spec["family"], spec.get("labels"))
    return None if d is None else d * spec.get("scale", 1.0)
