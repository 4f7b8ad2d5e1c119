"""Per-layer metric readers, one module a kind, found by the name in a
metric's file (`"reader": "<kind>"`). A reader is `read(spec, ctx)` and
returns a number, or None where it finds nothing to read: the harness
then leaves the metric out of the line. `DEVICE = True` marks a reader
of the device, which a CPU rehearsal never prints."""


def delta(ctx, family: str, labels: dict | None = None) -> float | None:
    """Sum over the label sets of `family` that match `labels` (a value
    may be a list of alternatives) of after - before; None when the
    family did not appear after the window."""
    total, seen = 0.0, False
    for (name, lbs), v in ctx["m1"].items():
        if name != family:
            continue
        lb = dict(lbs)
        ok = True
        for k, want in (labels or {}).items():
            alts = want if isinstance(want, list) else [want]
            if lb.get(k) not in alts:
                ok = False
                break
        if ok:
            seen = True
            total += v - ctx["m0"].get((name, lbs), 0.0)
    return total if seen else None


def client(ctx, what: str) -> float:
    """A count or a time the load generator took itself."""
    return float(ctx["client"][what])


def requests_in_trace(spec, ctx) -> float | None:
    """How many requests the capture holds, from the trace itself: the
    calls it recorded of the programs `spec["programs"]` names, over
    the calls a request makes (`spec["calls"]`, a counter family, over
    the requests answered in the window)."""
    tr = ctx.get("trace") or {}
    in_trace = sum(rec["calls"] for name, rec in tr.get("programs", {}).items()
                   if any(name.startswith(p) for p in spec["programs"]))
    calls = delta(ctx, spec["calls"]["family"], spec["calls"].get("labels"))
    n = ctx["client"]["requests_answered"]
    if not in_trace or not calls or not n:
        return None
    return in_trace / (calls / n)
