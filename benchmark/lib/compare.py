"""The comparison that decides `correct`: what the clients received in
the timed window against the plain reference. A copy of
`chip_smoke.py`'s `compare`, returning readings instead of raising."""

from __future__ import annotations


def compare_rows(np, got: dict, want: dict) -> dict:
    """got/want: {key: tuple of floats}. Returns the readings:
    `rows_missing` (keys of the reference that the answer lacks, plus
    keys it has too many), `values_differing` (count of values not
    bit-equal) and `worst_rel_err` (largest |got - want| / |want|)."""
    missing = sum(1 for k in want if k not in got)
    extra = sum(1 for k in got if k not in want)
    keys = [k for k in want if k in got]
    if not keys:
        return {"rows_missing": missing + extra, "values_differing": 0,
                "worst_rel_err": 0.0, "values": 0}
    g = np.asarray([got[k] for k in keys], np.float64)
    w = np.asarray([want[k] for k in keys], np.float64)
    if g.shape != w.shape:
        return {"rows_missing": missing + extra + len(keys),
                "values_differing": 0, "worst_rel_err": 0.0, "values": 0}
    bad = ~np.isfinite(g)
    err = np.abs(g - w) / np.maximum(np.abs(w), 1e-30)
    err = np.where(bad, np.inf, err)
    return {"rows_missing": missing + extra,
            "values_differing": int((g != w).sum()),
            "worst_rel_err": float(err.max()),
            "values": int(g.size)}
