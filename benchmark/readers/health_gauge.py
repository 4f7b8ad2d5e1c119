"""A field of `/health?deep=1` `checks.device`, read after the window:
`bytes_in_use` is the fullest device's."""
DEVICE = True


def read(spec, ctx):
    v = ctx["health"].get(spec["field"])
    if isinstance(v, list):
        v = [x for x in v if x is not None]
        v = max(v) if v else None
    return None if v is None else float(v)
