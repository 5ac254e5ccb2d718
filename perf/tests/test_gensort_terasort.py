"""The configuration `gensort-terasort` and its cell `sort.4chip`, and the
control cell `agg.highcard`: held to what test_manifest.py holds the older
configurations to, and to what is their own: seeded gensort-shaped
records, a numpy reference that equals the `local` master, a `load` that
stops a program which cannot sort byte strings on the device, and the two
per-layer metrics that read the bounds sample."""

import json
import os

import numpy as np
import pytest

from benchhelp import ROOT, run_cell  # noqa: E402

from perf.lib import manifest  # noqa: E402

CELL = "sort.4chip"
CONFIG = "gensort-terasort"
CONTROL = "agg.highcard"


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
    ("key_distribution", {"kind": "gensort_skewed"}),
    ("record_layout", "gensort_ascii")])
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
    assert config["architecture"] is None       # no model: a deployment
    for key in ("assumed", "guarantees", "schema", "source_scale"):
        assert config[key], key
    assert all("confidence" in text for text in config["assumed"].values())
    # the source's widths, never cut
    assert config["schema"]["row_bytes"] == {"host": 100, "device": 112}


def test_the_traffic_fits_the_configuration(entry_and_config, job,
                                            bench_manifest):
    config = entry_and_config[1]
    cell = next(w for w in bench_manifest["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["config"] == CONFIG
    with open(manifest.traffic_path(cell["traffic"])) as f:
        params = json.load(f)
    assert params["rows_per_job"] <= config["rows_per_job_max"]
    rows = params["rows_per_job"] * params["resident_partitions"]
    assert rows == config["table_rows"]["records"]
    assert rows * job.DEVICE_ROW_BYTES / cell["chips"] \
        <= config["resident_bytes_per_chip_max"]
    assert [(e["query"], e["action"], e["setup_actions"])
            for e in params["jobs"]] \
        == [("terasort", "sample", ["count", "dense_sample"])]
    assert set(params["jobs"][0]["setup_actions"]) | {"sample"} \
        == set(job.QUERIES["terasort"])


def test_the_control_cell_is_the_four_chip_mix_on_one_chip(bench_manifest):
    cells = {w["name"]: w for w in bench_manifest["workloads"]}
    control, four = cells[CONTROL], cells["agg.highcard.4chip"]
    assert control["chips"] == 1 and four["chips"] == 4
    assert control["config"] == four["config"]
    with open(manifest.traffic_path(control["traffic"])) as f:
        one = json.load(f)
    with open(manifest.traffic_path(four["traffic"])) as f:
        assert one == json.load(f)
    # 2 of the 8 cells ask for four chips
    assert sum(w["chips"] == 4 for w in cells.values()) == 2


def test_data_is_seeded_and_as_the_file_says(entry_and_config, job):
    config = entry_and_config[1]
    params = {"rows_per_job": 8192, "resident_partitions": 2}
    a = job.make_data(config, params, 2 ** 31 + 5, 1)
    b = job.make_data(config, params, 2 ** 31 + 5, 1)
    c = job.make_data(config, params, 2 ** 31 + 6, 1)
    assert all((x == y).all() for pa, pb in zip(a["parts"], b["parts"])
               for x, y in zip(pa, pb))
    assert not (a["parts"][0][0] == c["parts"][0][0]).all()
    keys, payload = a["parts"][0]
    assert keys.dtype == "S10" and payload.dtype == "S90"
    raw = job.key_bytes(keys)
    # uniform over all 256 byte values: half the keys begin 0x80-0xff
    assert 0.45 < (raw[:, 0] >= 0x80).mean() < 0.55
    assert len(np.unique(raw)) == 256
    assert len(set(payload.tolist())) == len(payload)   # the record number
    assert job.input_rows(a) == 8192 and job.n_partitions(a) == 2
    assert job.resident_bytes(a) == 2 * 8192 * 112
    assert job.least(config, params, a, 4, "terasort") == {
        "hbm_bytes": 2 * 2048 * 100.0, "ici_bytes": 2048 * 100 * 0.75}
    assert job.least(config, params, a, 1, "terasort")["ici_bytes"] == 0.0


@pytest.fixture(scope="module")
def local():
    from dpark_tpu import DparkContext
    ctx = DparkContext("local")
    ctx.start()
    yield ctx
    ctx.stop()


def _host_safe(job, action):
    """The job's predicates index byte 9 of a key; on the local master a
    key that ends in NUL is a shorter `bytes`, so pad it there."""
    keep = job.QUERIES["terasort"][action]
    return lambda kv: keep((kv[0].ljust(10, b"\0"), kv[1]))


@pytest.mark.parametrize("action", ["sample", "dense_sample", "count"])
def test_the_reference_equals_the_local_master(entry_and_config, job,
                                               local, action):
    from dpark_tpu import Columns
    config = entry_and_config[1]
    data = job.make_data(config, {"rows_per_job": 16384,
                                  "resident_partitions": 1}, 11, 1)
    keys, payload = data["parts"][0]
    ordered = local.parallelize(Columns(keys, payload), 4) \
        .map(job.resident).sortByKey(numSplits=4)
    want = job.reference(data, 0, "terasort", action)
    if action == "count":
        assert ordered.count() == want == 16384
        return
    got = ordered.filter(_host_safe(job, action)).collect()
    assert len(got) == len(want[0]) > 0
    assert job.verdict(got, want, action)
    assert not job.verdict(got[1:], want, action)
    assert not job.verdict(got[::-1], want, action)


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

# a U (unicode) key column has no device form: the chain leaves the array
# path at the probe, as on a program that cannot sort byte strings
_U_KEYS = """
    make = mod.make_data
    def unicode_keys(*a):
        data = make(*a)
        import numpy as np
        data["parts"] = [(np.array([x.hex() for x in k.tolist()]), v)
                         for k, v in data["parts"]]
        return data
    mod.make_data = unicode_keys
    mod.reference = lambda *a: None
"""


def test_load_stops_a_program_that_leaves_the_array_path():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace",
         "0", "--rehearse", "64"], code=_PATCHED % (ROOT, _U_KEYS.strip()))
    assert rc != 0 and line is None
    assert "left the array path" in err and "string leaf" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_the_sort_cell_rehearses_and_names_its_metrics(trace):
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--rehearse", "64"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"throughput", "job_s", "setup_s"}
        return
    assert metrics["sort_sample_rows_per_job"] == {"value": 2000.0,
                                                   "unit": "count"}
    assert metrics["sort_sample_job_ms"]["unit"] == "ms"
    assert metrics["sort_sample_job_ms"]["value"] > 0
    assert metrics["window_compiles"]["value"] == 0.0
    assert metrics["stores_released_per_job"]["value"] == 1.0


@pytest.mark.parametrize("trace", [0, 1])
def test_the_control_cell_rehearses(trace):
    rc, line, err = run_cell(
        ["--workload", CONTROL, "--seed", "2900000011", "--seconds", "2",
         "--trace", str(trace), "--rehearse", "64"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 1
    assert "sort_sample_rows_per_job" not in line["metrics"]
    if trace:
        assert line["metrics"]["window_compiles"]["value"] == 0.0
