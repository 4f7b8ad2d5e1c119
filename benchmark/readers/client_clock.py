"""A number the load generator measured on its own clock."""
from benchmark.readers import client


def read(spec, ctx):
    if spec["what"] not in ctx["client"]:
        return None
    return client(ctx, spec["what"]) * spec.get("scale", 1.0)
