"""Device time of one call of the named programs, from the capture
alone: their seconds over their calls, times `scale`. (`trace_busy`
divides the whole capture's busy time by the requests it holds, which
is every program's time; this is one program's.)"""

DEVICE = True


def programs(spec, ctx) -> tuple[float, int]:
    """(seconds, calls) of the programs `spec` names, in the capture."""
    tr = ctx.get("trace") or {}
    mine = [rec for name, rec in tr.get("programs", {}).items()
            if any(name.startswith(p) for p in spec["programs"])]
    return (sum(rec["seconds"] for rec in mine),
            sum(rec["calls"] for rec in mine))


def read(spec, ctx):
    seconds, calls = programs(spec, ctx)
    if seconds <= 0 or not calls:
        return None
    return seconds / calls * spec.get("scale", 1.0)
