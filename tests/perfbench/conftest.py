"""tests/perfbench: the benchmark's own tests (CPU, tier-1)."""
import json
import os

import pytest

from perfbench_helpers import ROOT, load_by_path


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def harness():
    return load_by_path("perfbench_run", "run.py")


@pytest.fixture(scope="session")
def reducer():
    return load_by_path("perfbench_trace_reduce", "trace", "reduce.py")
