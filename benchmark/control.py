#!/usr/bin/env python3
"""The control of a cell's comparison, at the cell's own size: the plain
reference computed in the next precision below the one the
configuration states (bfloat16 for float32), put in the program's place
and compared as an answer of the program would be. It has to fail one
of the cell's limits. Also reads the reference at the stated precision
itself, which has to pass. NumPy only: no server, no device.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.files import cell_files, module, reference  # noqa: E402

NEXT_LOWER = {"float64": "float32", "float32": "bfloat16"}


def read(np, workload: str, seed: int, n: int, scale: dict) -> dict:
    _manifest, _cell, wl, cfg = cell_files(workload)
    datagen = module("datagen", cfg["datagen"])
    traffic = module("traffic", wl["generator"])
    ds = datagen.make(np, seed, {**cfg["scale"], **scale})
    ds.reference = reference(cfg)
    st = traffic.prepare(np, wl["params"], ds, seed, n)
    stated, limits = cfg["precision"], wl["limits"]
    out = {"seed": seed, "limits": limits}
    for precision in (stated, NEXT_LOWER[stated]):
        got = traffic.control(np, st, precision, n)
        out[precision] = {k: got[k] for k in limits}
    out["control_fails"] = any(
        out[NEXT_LOWER[stated]][k] > lim for k, lim in limits.items())
    out["stated_precision_passes"] = all(
        out[stated][k] <= lim for k, lim in limits.items())
    return out


def main(argv=None) -> int:
    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=480,
                    help="how many of the window's requests to compare")
    ap.add_argument("--scale", action="append", default=[])
    a = ap.parse_args(argv)
    scale = {k: int(v) for k, _, v in (s.partition("=") for s in a.scale)}
    ok = True
    for seed in a.seeds.split(","):
        doc = read(np, a.workload, int(seed), a.requests, scale)
        print(json.dumps(doc), flush=True)
        ok = ok and doc["control_fails"] and doc["stated_precision_passes"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
