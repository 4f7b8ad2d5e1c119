"""How the benchmark's files find each other, by the names that
BENCHMARK.json and the data files give (benchmark/README.md)."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

from benchmark.lib.server import check

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by the name a data file gives."""
    check(name.replace("_", "").isalnum(), f"bad {kind} name {name!r}")
    check(os.path.isfile(os.path.join(BENCH, kind, name + ".py")),
          f"no benchmark/{kind}/{name}.py")
    return importlib.import_module(f"benchmark.{kind}.{name}")


def cell_files(workload: str) -> tuple:
    """(manifest, the cell's entry, its workload file, its
    configuration's file) for a cell named in BENCHMARK.json."""
    manifest = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    check(workload in cells, f"BENCHMARK.json has no workload {workload!r}")
    cell = cells[workload]
    return (manifest, cell,
            load_json(BENCH, "workloads", workload + ".json"),
            load_json(BENCH, "configs", cell["config"] + ".json"))


def reference(cfg: dict):
    """A configuration's plain reference: the module beside its file,
    `configs/<name>.reference.py`, `name` being the configuration's own
    or the one its `reference` key gives."""
    name = cfg.get("reference", cfg["name"])
    path = os.path.join(BENCH, "configs", name + ".reference.py")
    check(os.path.isfile(path), f"no benchmark/configs/{name}.reference.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + "".join(
            c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
