"""Fixtures of the benchmark's own tests."""

import json
import os

import pytest

import benchhelp


@pytest.fixture(scope="session")
def bench_manifest():
    with open(os.path.join(benchhelp.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
