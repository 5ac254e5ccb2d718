"""The configuration `uservisits-q2` and its cell `uservisits.agg`, held to
what test_manifest.py holds the older configurations to, and to what is its
own: a `load` that stops a program which keeps byte strings on the host,
and the per-layer metric that reads the strings that crossed the host."""

import json
import os

import numpy as np
import pytest

from benchhelp import ROOT, run_cell  # noqa: E402

from perf.lib import manifest  # noqa: E402

CELL = "uservisits.agg"
CONFIG = "uservisits-q2"


@pytest.fixture(scope="module")
def entry_and_config(bench_manifest):
    entry = next(c for c in bench_manifest["configs"]
                 if c["name"] == CONFIG)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return entry, json.load(f)


@pytest.fixture(scope="module")
def job(entry_and_config):
    return manifest.load_module(
        manifest.job_module_path(entry_and_config[1]["job_module"]))


@pytest.mark.parametrize("key,other", [
    ("source_ip_distribution", {"kind": "dotted_quad", "octets": {
        "kind": "zipf", "s": 1.0}}),
    ("ad_revenue_distribution", {"kind": "exponential", "scale": 1.0})])
def test_a_distribution_the_job_module_lacks_is_an_error(
        entry_and_config, job, key, other):
    config = dict(entry_and_config[1])
    params = {"rows_per_job": 4096, "resident_partitions": 1}
    job.make_data(config, params, 1, 1)
    config[key] = other
    with pytest.raises(ValueError):
        job.make_data(config, params, 1, 1)


def test_reduced_names_keys_of_the_configuration_file(entry_and_config):
    entry, config = entry_and_config
    assert entry["reduced"] and set(entry["reduced"]) <= set(config)
    assert set(entry["reduced"]) == set(config["reduced_why"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    for key in ("assumed", "guarantees", "tolerance", "schema"):
        assert config[key], key


def test_the_traffic_fits_the_configuration(entry_and_config,
                                            bench_manifest):
    config = entry_and_config[1]
    cell = next(w for w in bench_manifest["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    with open(manifest.traffic_path(cell["traffic"])) as f:
        params = json.load(f)
    resident = params["resident_partitions"] * params["rows_per_job"] \
        * config["schema"]["row_bytes"]
    assert resident <= config["resident_bytes_per_chip_max"]
    assert resident >= 0.9 * config["resident_bytes_per_chip_max"]
    assert params["rows_per_job"] <= config["rows_per_job_max"]


def test_data_is_seeded_and_as_the_file_says(entry_and_config, job):
    config = entry_and_config[1]
    params = {"rows_per_job": 8192, "resident_partitions": 2}
    big = 3000000019                    # the driver's seeds pass 2**31
    a = job.make_data(config, params, big, 1)
    b = job.make_data(config, params, big, 1)
    c = job.make_data(config, params, big + 1, 1)
    ip, revenue = a["parts"][0]
    assert ip.dtype == np.dtype("S16") and revenue.dtype == np.float32
    assert (ip == b["parts"][0][0]).all() and (ip != c["parts"][0][0]).any()
    assert (ip != a["parts"][1][0]).any()
    assert 0.0 <= revenue.min() and revenue.max() < 1.0
    texts = ip.tolist()
    assert {len(t) for t in texts} <= set(range(7, 16))
    octets = np.array([[int(x) for x in t.split(b".")] for t in texts])
    assert octets.shape == (8192, 4)
    assert octets.min() == 0 and octets.max() == 255
    assert job.reference(a, 0, "q2c", "count") \
        == len({t[:12] for t in texts})


_PATCHED = """
import sys
sys.path.insert(0, %r)
from perf.lib import runner
load = runner.load_job_module
def patched(name):
    mod = load(name)
    %s
    return mod
runner.load_job_module = patched
sys.exit(runner.main(sys.argv[1:]))
"""

# float() of a traced value cannot be traced: the load's identity map
# leaves the array path, as a program without byte strings on the device
# leaves it at the S16 column
_HOST_LOAD = """
    def untraceable(r):
        return (r[0], float(r[1]))
    mod.resident = untraceable
"""


def test_load_stops_a_program_that_leaves_the_array_path():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace",
         "0", "--rehearse", "64"], code=_PATCHED % (ROOT, _HOST_LOAD.strip()))
    assert rc != 0 and line is None
    assert "left the array path" in err


def test_no_string_crosses_the_host_in_a_job():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse", "64"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["bytes_host_rows_per_job"] \
        == {"value": 0.0, "unit": "count"}
    assert line["metrics"]["window_compiles"]["value"] == 0.0
