"""delta(num) / delta(den) * scale. `den` is a counter family or, as
{"client": "requests_answered" | "request_seconds" | "window_s"}, a count or a
time of the load generator's own."""
from benchmark.readers import client, delta


def _side(side, ctx):
    if "client" in side:
        return client(ctx, side["client"])
    return delta(ctx, side["family"], side.get("labels"))


def read(spec, ctx):
    num, den = _side(spec["num"], ctx), _side(spec["den"], ctx)
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1.0)
