"""Mean of a histogram over the window: delta of `_sum` over delta of
`_count`, times `scale` (1000 for seconds -> ms)."""
from benchmark.readers import delta


def read(spec, ctx):
    s = delta(ctx, spec["family"] + "_sum", spec.get("labels"))
    n = delta(ctx, spec["family"] + "_count", spec.get("labels"))
    if s is None or not n:
        return None
    return s / n * spec.get("scale", 1.0)
