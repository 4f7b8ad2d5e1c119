"""The value of a family as the window opens, summed over the label
sets that match `labels` (a value may be a list of alternatives), times
`scale`. Every run starts a fresh server, so what a counter or a
histogram's `_sum` holds at the window's opening is what set-up put
there."""


def read(spec, ctx):
    total, seen = 0.0, False
    for (name, lbs), v in ctx["m0"].items():
        if name != spec["family"]:
            continue
        lb = dict(lbs)
        if all(lb.get(k) in (want if isinstance(want, list) else [want])
               for k, want in (spec.get("labels") or {}).items()):
            seen = True
            total += v
    return total * spec.get("scale", 1.0) if seen else None
