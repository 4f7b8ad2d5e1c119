"""100 * (1 - busy / window) from the device trace, the window being
the capture's own span."""
DEVICE = True


def read(spec, ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
