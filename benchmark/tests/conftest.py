"""Tests of the benchmark itself: `pytest benchmark/tests -q`, by hand, on
the CPU. Not part of the repo's tier-1 suite. Nothing here loads libtpu at
import time: JAX is pinned to the CPU before any test imports it, and the
compile cache goes under pytest's temporary directory, not the checkout's
`.xla_cache` (whose sidecars change which programs a chip run compiles)."""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

os.environ["JAX_PLATFORMS"] = "cpu"
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session", autouse=True)
def _cache_outside_the_checkout(tmp_path_factory):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("xla_cache"))


@pytest.fixture(scope="session")
def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="session")
def run_py():
    """benchmark/run.py as a module (its main() takes an argv)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def last_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
