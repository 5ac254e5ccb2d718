"""The configuration `tpch-q1` and its cell `tpch.q1`: held to what the
other configurations' tests hold theirs to, and to what is its own: a
seeded lineitem whose groups are TPC-H's, a numpy reference that equals
Python's integers, a verdict that refuses a sum off by one hundredth, a
`load` whose probe stops a program whose query plane does not scan a
resident table on the device, and the four per-layer metrics that read
the query plane's spans and counters."""

import json
import os

import numpy as np
import pytest

from benchhelp import ROOT, run_cell  # noqa: E402

from perf.lib import manifest  # noqa: E402

CELL = "tpch.q1"
CONFIG = "tpch-q1"
READERS = ("query_plan_job_ms", "query_finish_job_ms",
           "scan_device_rows_per_job", "scan_host_rows_per_job")
# count_order of SF1's validation answer, as remembered, over 6,001,215
SF1_SHARES = {(b"A", b"F"): 1478493 / 6001215,
              (b"N", b"F"): 38854 / 6001215,
              (b"N", b"O"): 2920374 / 6001215,
              (b"R", b"F"): 1478870 / 6001215}


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


def test_reduced_names_keys_of_the_configuration_file(entry_and_config):
    entry, config = entry_and_config
    assert entry["reduced"] and set(entry["reduced"]) <= set(config)
    assert set(entry["reduced"]) == set(config["reduced_why"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert config["architecture"] is None       # no model: a deployment
    for key in ("assumed", "guarantees", "schema", "source_scale",
                "departures", "tolerance"):
        assert config[key], key
    assert all("confidence" in text for text in config["assumed"].values())
    assert all("confidence" in text
               for text in config["source_scale"].values())
    assert config["schema"]["row_bytes"] == 38
    assert len(config["columns_resident"]) == 7


def test_the_traffic_fits_the_configuration(entry_and_config, job,
                                            bench_manifest):
    config = entry_and_config[1]
    cell = next(w for w in bench_manifest["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    with open(manifest.traffic_path(cell["traffic"])) as f:
        params = json.load(f)
    assert params["jobs"] == [{"query": "q1", "action": "collect",
                               "weight": 1}]
    tables = params["resident_partitions"] * params["rows_per_job"] \
        * config["schema"]["device_row_bytes"]
    # the issue's four partitions; a job's one store holds four rows
    # and is registered at their capacity class (8 slots of two key
    # words and six accumulator leaves), not at its input's capacity
    assert params["resident_partitions"] == 4
    assert tables + 8 * 8 * 8 <= config["resident_bytes_per_chip_max"]
    assert params["rows_per_job"] <= config["rows_per_job_max"]
    assert params["resident_partitions"] * params["rows_per_job"] \
        == config["table_rows"]
    for m in bench_manifest["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]


def test_a_distribution_the_job_module_lacks_is_an_error(entry_and_config,
                                                         job):
    config = dict(entry_and_config[1])
    params = {"rows_per_job": 4096, "resident_partitions": 1}
    job.make_data(config, params, 1, 1)
    config["distributions"] = dict(config["distributions"], l_quantity={
        "kind": "zipf", "s": 1.0})
    with pytest.raises(ValueError):
        job.make_data(config, params, 1, 1)


def test_data_is_seeded_and_its_groups_are_the_sources(entry_and_config,
                                                       job):
    config = entry_and_config[1]
    params = {"rows_per_job": 1 << 20, "resident_partitions": 2}
    big = 3000000019                    # the driver's seeds pass 2**31
    a = job.make_data(config, params, big, 1)
    b = job.make_data(config, params, big, 4)
    assert b["rows"] == (1 << 20) // 4
    c = job.make_data(config, {"rows_per_job": 4096,
                               "resident_partitions": 1}, big + 1, 1)
    again = job.lineitem_partition(big, 0, 1 << 20)
    cols = a["parts"][0]
    assert all(np.array_equal(x, y) for x, y in zip(cols, again))
    assert not np.array_equal(cols[1], a["parts"][1][1])
    assert not np.array_equal(cols[1][:4096], c["parts"][0][1])
    assert [x.dtype.str for x in cols] == [
        "<i8", "<i8", "<i8", "<i8", "|S1", "|S1", "<i4"]
    quantity, price, discount, tax, flag, status, ship = cols
    assert quantity.min() == 100 and quantity.max() == 5000
    assert not (quantity % 100).any()
    assert 90000 <= price.min() and price.max() <= 10495000
    assert (discount.min(), discount.max()) == (0, 10)
    assert (tax.min(), tax.max()) == (0, 8)
    assert job.START_DATE + 1 <= ship.min()
    assert ship.max() <= job.LAST_ORDER + 121
    assert job.CUTOFF == 10471 and job.CURRENT_DATE == 9298
    # of ALL rows, the qualifying groups' shares are SF1's answer's
    rows = job.q1_rows(cols)
    assert [r[:2] for r in rows] == sorted(SF1_SHARES)
    for r in rows:
        assert abs(r[9] / len(ship) - SF1_SHARES[r[:2]]) < 0.003, r[:2]
    assert abs(sum(r[9] for r in rows) / len(ship) - 0.986) < 0.003
    # generation order: the lines of an order (four on average) are
    # neighbours and ship within 120 days of one another
    near = np.abs(np.diff(ship.astype(np.int64))) <= 120
    assert 0.70 < near.mean() < 0.85


def test_the_reference_equals_pythons_integers(job):
    cols = job.lineitem_partition(7, 3, 20000)
    assert job.q1_rows(cols) == job.q1_rows_python(cols)
    rows = job.q1_rows(cols)
    assert all(type(v) is int for r in rows for v in r[2:6] + (r[9],))
    # the worst row's charge times the cell's rows stays inside int64,
    # which is what lets numpy (and the device) hold the sums exactly
    assert 10495000 * 100 * 108 * 8388608 < 2 ** 63


def test_the_verdict_refuses_a_sum_off_by_one_hundredth(job):
    cols = job.lineitem_partition(11, 0, 8192)
    ref = job.q1_rows(cols)
    assert job.verdict([tuple(r) for r in ref], ref, "collect")
    for col in (2, 3, 4, 5, 9):
        bad = [list(r) for r in ref]
        bad[1][col] += 1
        assert not job.verdict([tuple(r) for r in bad], ref, "collect")
    for col in (6, 7, 8):
        near = [list(r) for r in ref]
        near[2][col] = float(np.nextafter(near[2][col], np.inf))
        assert job.verdict([tuple(r) for r in near], ref, "collect")
        far = [list(r) for r in ref]
        far[2][col] = far[2][col] * (1 + 1e-12)
        assert not job.verdict([tuple(r) for r in far], ref, "collect")
    assert not job.verdict([tuple(r) for r in ref[:-1]], ref, "collect")
    assert not job.verdict([tuple(r) for r in reversed(ref)], ref,
                           "collect")
    floats = [r[:2] + tuple(float(v) for v in r[2:6]) + r[6:] for r in ref]
    assert not job.verdict(floats, ref, "collect")


_PATCHED = """
import sys
sys.path.insert(0, %r)
from perf.lib import runner
load = runner.load_job_module
def patched(name):
    # the program before the counter (jax is up by now: the runner has
    # set the rehearsal's platform)
    from dpark_tpu.backend.tpu.executor import JAXExecutor
    def absent(self):
        raise AttributeError("scan_rows_host")
    JAXExecutor.scan_rows_host = property(absent, lambda self, v: None)
    return load(name)
runner.load_job_module = patched
sys.exit(runner.main(sys.argv[1:]))
"""


def test_load_stops_a_program_without_the_counter():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace",
         "0", "--rehearse", "4096"], code=_PATCHED % ROOT)
    assert rc != 0 and line is None
    assert "no counter scan_rows_host" in err


_ON_THE_HOST = """
import sys
sys.path.insert(0, %r)
from dpark_tpu import table
table.TableRDD._resident = lambda self: None    # no resident Scan source
from perf.lib import runner
sys.exit(runner.main(sys.argv[1:]))
"""


def test_load_stops_a_program_that_scans_on_the_host():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace",
         "0", "--rehearse", "4096"], code=_ON_THE_HOST % ROOT)
    assert rc != 0 and line is None
    assert "cannot run the configuration" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_names_its_metrics(trace):
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--rehearse", "2048"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 1
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"throughput", "job_s", "setup_s"}
        return
    assert set(READERS) <= set(metrics)
    assert metrics["scan_device_rows_per_job"] == {"value": 4096.0,
                                                   "unit": "count"}
    assert metrics["scan_host_rows_per_job"] == {"value": 0.0,
                                                 "unit": "count"}
    # S1 keys ride as key words and come back as bytes in the planner:
    # no byte string crosses layout's host form
    assert metrics["bytes_host_rows_per_job"] == {"value": 0.0,
                                                  "unit": "count"}
    assert metrics["query_plan_job_ms"]["unit"] == "ms"
    assert metrics["query_plan_job_ms"]["value"] > 0
    assert metrics["query_finish_job_ms"]["value"] > 0
    assert metrics["window_compiles"]["value"] == 0.0
    assert metrics["launches_per_job"]["value"] == 2.0
    assert metrics["dispatches_per_job"]["value"] == 2.0
    assert metrics["prereduced_stores_per_job"]["value"] == 1.0
    # every metric that lists no cells reads a number here too
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    device_only = {m["name"] for m in per_layer
                   if m["source"] == "device_trace"} | {"exec_host_job_ms",
                                                        "peak_hbm_GB"}
    assert {m["name"] for m in per_layer if "workloads" not in m} \
        - device_only <= set(metrics)


def test_the_readers_read_nothing_from_a_program_without_the_plane():
    obs = {"jobs": [{"spans": [{"name": "stage.exec", "dur": 0.01}]}],
           "counters": {"host_reads": 3}}
    for name in READERS:
        reader = manifest.load_module(
            manifest.reader_path("per_layer", name))
        assert reader.read(obs) is None
    obs = {"jobs": [{"spans": [{"name": "query.plan", "dur": 0.002},
                               {"name": "query.finish", "dur": 0.0005}]},
                    {"spans": [{"name": "query.plan", "dur": 0.004},
                               {"name": "query.plan", "dur": 0.002},
                               {"name": "query.finish", "dur": 0.0015}]}],
           "counters": {"scan_rows_device": 16, "scan_rows_host": 0}}
    values = [manifest.load_module(
        manifest.reader_path("per_layer", name)).read(obs)
        for name in READERS]
    assert values == [4.0, 1.0, 8.0, 0.0]
