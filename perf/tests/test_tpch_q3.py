"""The configuration `tpch-q3` and its cell `tpch.q3`: held to what the
other configurations' tests hold theirs to, and to what is its own:
three seeded tables whose populations are TPC-H's, a numpy reference
that equals Python's integers, a verdict that accepts any order of the
groups that tie across the tenth place and nothing else, a `load` whose
probe stops a program that does not lower the two joins onto the device,
and the per-layer metric that reads the joins' counter."""

import json
import os

import numpy as np
import pytest

from benchhelp import ROOT, run_cell  # noqa: E402

from perf.lib import manifest  # noqa: E402

CELL = "tpch.q3"
CONFIG = "tpch-q3"


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


@pytest.fixture(scope="module")
def tables(job):
    return job.make_tables(7, 0.02)


def test_reduced_names_keys_of_the_configuration_file(entry_and_config):
    entry, config = entry_and_config
    assert set(entry["reduced"]) == set(config["reduced_why"]) \
        == set(config["reduced"]) and set(entry["reduced"]) <= set(config)
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert config["architecture"] is None       # no model: a deployment
    for key in ("assumed", "guarantees", "schema", "source_scale",
                "departures", "tolerance"):
        assert config[key], key
    assert all("confidence" in text for text in config["assumed"].values())
    assert config["columns_resident"] == {
        "customer": ["c_custkey", "c_mktsegment"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                   "o_shippriority"],
        "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                     "l_shipdate"]}


def test_the_traffic_fits_the_configuration(entry_and_config, job,
                                            bench_manifest):
    config = entry_and_config[1]
    cell = next(w for w in bench_manifest["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    with open(manifest.traffic_path(cell["traffic"])) as f:
        params = json.load(f)
    assert params["jobs"] == [{"query": "q3", "action": "collect",
                               "weight": 1,
                               "setup_actions": ["all_groups"]}]
    assert params["resident_partitions"] == 1
    sf = params["scale_factor"]
    assert sf in (1, 2, 4)
    assert params["rows_per_job"] == 7650000 * sf
    assert params["rows_per_job"] <= config["rows_per_job_max"]
    # 24, 32 and 32 B a row: 243.6 MB a scale factor
    assert sf * (150000 * 24 + 1500000 * 32 + 6000000 * 32) \
        <= config["resident_bytes_per_chip_max"]
    assert config["queries"] == job.QUERIES


def test_a_distribution_the_job_module_lacks_is_an_error(entry_and_config,
                                                         job):
    config = dict(entry_and_config[1])
    params = {"scale_factor": 0.001, "rows_per_job": 7650}
    job.make_data(config, params, 1, 1)
    config["distributions"] = dict(config["distributions"],
                                   o_shippriority=1)
    with pytest.raises(ValueError):
        job.make_data(config, params, 1, 1)


def test_the_tables_follow_the_population_rules(job, tables):
    ckey, seg = tables["customer"]
    okey, ocust, odate, oprio = tables["orders"]
    lkey, price, disc, ship = tables["lineitem"]
    assert len(ckey) == 3000 and len(okey) == 30000
    assert np.array_equal(ckey, np.arange(1, 3001))
    # c_mktsegment: five segments, each 20% +- 0.5 at 100,000 customers
    big = job.make_tables(8, 0.667)["customer"][1]
    shares = {s: float((big == s).mean()) for s in job.SEGMENTS}
    assert all(abs(v - 0.2) <= 0.005 for v in shares.values()), shares
    # sparse order keys: the first 8 of every 32, increasing
    assert np.all(np.diff(okey) > 0)
    assert set(((okey - 1) % 32).tolist()) == set(range(8))
    assert np.all(ocust % 3 != 0) and ocust.min() >= 1 \
        and ocust.max() <= 3000
    assert odate.min() >= job.tpch_q1.START_DATE \
        and odate.max() <= job.tpch_q1.LAST_ORDER
    assert not oprio.any()
    # 1..7 lines an order, shipped 1..121 days after it
    lines = np.bincount(np.searchsorted(okey, lkey))
    assert lines.min() >= 1 and lines.max() <= 7
    lag = ship - np.repeat(odate, lines)
    assert lag.min() >= 1 and lag.max() <= 121
    assert disc.min() >= 0 and disc.max() <= 10
    assert price.max() <= 10495000


def test_the_filters_pass_the_shares_the_source_gives(job, tables):
    seg = tables["customer"][1]
    assert abs(float((seg == job.SEGMENT).mean()) - 0.2) < 0.03
    odate = tables["orders"][2]
    assert abs(float((odate < job.DATE).mean()) - 1169 / 2406) < 0.015
    ship = tables["lineitem"][3]
    assert abs(float((ship > job.DATE).mean()) - 0.54) < 0.02


@pytest.mark.parametrize("sf", [0.1, 0.2])
def test_the_group_count_grows_with_the_scale_factor(job, sf):
    """About 11,322 groups a scale factor: 1,500,000 orders x 20% from a
    BUILDING customer x the sum over the 121 days before the date of
    1/2,406 x P(one of 1..7 lines ships after it)."""
    groups = len(job.q3_rows(job.make_tables(11, sf)))
    assert abs(groups / sf - 11322) < 0.08 * 11322, groups


def test_the_reference_equals_pythons_integers(job, tables):
    rows = job.q3_rows(tables)
    assert rows == job.q3_rows_python(tables)
    assert len(rows) > 100
    assert all(type(v) is int for r in rows for v in r)
    # revenue past 2**53: where a float64 sum would round, int64 holds
    big = {t: tuple(c.copy() for c in cols) for t, cols in tables.items()}
    big["lineitem"][1][:] = 2 ** 40 + 1
    assert job.q3_rows(big) == job.q3_rows_python(big)


def test_the_verdict_takes_any_order_of_a_tie_and_nothing_else(job):
    rows = [(k, 1000 - k, 9000, 0) for k in range(1, 9)] \
        + [(20, 500, 9001, 0), (21, 500, 9001, 0), (22, 500, 9001, 0),
           (30, 100, 9000, 0)]
    ref = {"rows": rows, "set": frozenset(rows)}
    assert job.verdict(rows[:10], ref, "collect")
    # three groups tie on (revenue, date) across the tenth place: any
    # two of them, in either order
    assert job.verdict(rows[:8] + [rows[10], rows[8]], ref, "collect")
    for wrong in (rows[:8] + [rows[8], rows[8]],           # a row twice
                  rows[:8] + [rows[8], (21, 501, 9001, 0)],  # revenue
                  rows[:8] + [rows[8], (21, 500, 9001, 1)],  # priority
                  rows[:8] + [rows[8], (21, 500.0, 9001, 0)],  # a float
                  rows[1:10] + [rows[0]],                 # out of order
                  rows[:9],                               # one short
                  rows[:8] + [rows[8], rows[11]]):        # not a tie
        assert not job.verdict(wrong, ref, "collect"), wrong
    assert job.verdict(rows[:8] + [rows[10], rows[9], rows[8]]
                       + rows[11:], ref, "all_groups")
    assert not job.verdict(rows[:-1], ref, "all_groups")
    assert not job.verdict(rows[:10], ref, "all_groups")


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
        raise AttributeError("query_joins_device")
    JAXExecutor.query_joins_device = property(absent, lambda self, v: None)
    return load(name)
runner.load_job_module = patched
sys.exit(runner.main(sys.argv[1:]))
"""


def test_load_stops_a_program_without_the_counter():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace",
         "0", "--rehearse", "4096"], code=_PATCHED % ROOT)
    assert rc != 0 and line is None
    assert "no counter query_joins_device" in err


_ON_THE_HOST = """
import sys
sys.path.insert(0, %r)
from dpark_tpu import table
table.TableRDD._resident = lambda self: None    # no resident Scan source
from perf.lib import runner
sys.exit(runner.main(sys.argv[1:]))
"""


def test_load_stops_a_program_that_joins_on_the_host():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace",
         "0", "--rehearse", "4096"], code=_ON_THE_HOST % ROOT)
    assert rc != 0 and line is None
    assert "cannot run the configuration" in err


def test_the_cell_rehearses_and_reads_two_device_joins():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse", "256"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["device_joins_per_job"] == {"value": 2.0,
                                               "unit": "count"}
    assert metrics["scan_host_rows_per_job"]["value"] == 0.0
    assert metrics["masked_filters_per_job"]["value"] == 3.0
    assert metrics["window_compiles"]["value"] == 0.0
    assert metrics["join_job_ms"]["value"] > 0
    assert metrics["query_finish_job_ms"]["value"] > 0


def test_the_reader_reads_nothing_from_a_program_without_the_counter():
    reader = manifest.load_module(
        manifest.reader_path("per_layer", "device_joins_per_job"))
    assert reader.read({"jobs": [{}], "counters": {"host_reads": 3}}) \
        is None
    assert reader.read({"jobs": [{}, {}],
                        "counters": {"query_joins_device": 4}}) == 2.0
