"""Paths and loaders shared by tests/perfbench. The harness is imported by
path: perfbench/ is a directory of files found by name, not a package."""
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERFBENCH = os.path.join(ROOT, "perfbench")
# as when `python3 perfbench/run.py` is the script: readers import flops.py
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)


def load_by_path(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(PERFBENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]
