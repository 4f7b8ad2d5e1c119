"""The benchmark's tests import `benchmark.*` from the root of the
checkout."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.dirname(os.path.abspath(__file__))


def copy_checkout(root) -> dict:
    """A checkout's worth of the benchmark under `root`: a copy of
    benchmark/ and BENCHMARK.json, the program linked. Returns the
    manifest, for a test to append to and write back."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    os.symlink(os.path.join(ROOT, "greptimedb_tpu"),
               os.path.join(root, "greptimedb_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def load_checkout(tmp_path_factory):
    """`tsbs-load` is no cell of BENCHMARK.json (PERF.md, Open
    questions: its window runs nothing on the device). Its generator
    stays, and is rehearsed in a copy of the benchmark to which the
    cell is added as the files under data/tsbs-load/ and nothing else."""
    root = str(tmp_path_factory.mktemp("load") / "checkout")
    manifest = copy_checkout(root)
    extra = os.path.join(HERE, "data", "tsbs-load")
    shutil.copytree(extra, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("manifest.json"),
                    dirs_exist_ok=True)
    with open(os.path.join(extra, "manifest.json")) as f:
        for key, entries in json.load(f).items():
            manifest[key].extend(entries)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root
