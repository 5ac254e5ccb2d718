"""The configuration `uservisits-q3` and its cell `uservisits.join`, held to
what test_manifest.py holds the older configurations to, and to what is its
own: a numpy reference that equals the `local` master, a `load` that stops
a program which keeps wide byte strings on the host, and the two per-layer
metrics that read the join."""

import json
import os

import numpy as np
import pytest

from benchhelp import ROOT, run_cell  # noqa: E402

from perf.lib import manifest  # noqa: E402

CELL = "uservisits.join"
CONFIG = "uservisits-q3"


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
    ("page_url_distribution", {"kind": "words", "length": 60}),
    ("page_rank_distribution", {"kind": "uniform", "low": 1, "high": 10}),
    ("dest_url_distribution", {"kind": "zipf_over_pages", "exponent": 0.5}),
    ("visit_date_distribution", {"kind": "uniform", "low": 0, "high": 99}),
    ("source_ip_distribution", {"kind": "dotted_quad", "octets": {
        "kind": "zipf", "s": 1.0}}),
    ("ad_revenue_distribution", {"kind": "exponential", "scale": 1.0}),
    ("pages_per_visit", {"pages": 1, "visits": 1})])
def test_a_distribution_the_job_module_lacks_is_an_error(
        entry_and_config, job, key, other):
    config = dict(entry_and_config[1])
    params = {"rows_per_job": 2048, "resident_partitions": 1}
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
    for key in ("assumed", "guarantees", "tolerance", "schema",
                "source_scale"):
        assert config[key], key
    assert all("confidence" in text for text in config["assumed"].values())


def test_the_traffic_fits_the_configuration(entry_and_config, job,
                                            bench_manifest):
    config = entry_and_config[1]
    cell = next(w for w in bench_manifest["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    with open(manifest.traffic_path(cell["traffic"])) as f:
        params = json.load(f)
    visits = params["resident_partitions"] * params["rows_per_job"]
    assert visits == config["table_rows"]["uservisits"]
    assert round(visits * 18 / 155) == config["table_rows"]["rankings"]
    resident = visits * config["schema"]["row_bytes"]["uservisits"] \
        + config["table_rows"]["rankings"] \
        * config["schema"]["row_bytes"]["rankings"]
    assert resident <= config["resident_bytes_per_chip_max"]
    assert params["rows_per_job"] <= config["rows_per_job_max"]
    assert (job.VISIT_BYTES, job.PAGE_BYTES) == (124, 104)
    assert params["jobs"] == [{"query": "q3c", "action": "top1",
                               "weight": 1,
                               "setup_actions": ["collect_sample"]}]


def test_data_is_seeded_and_as_the_file_says(entry_and_config, job):
    config = entry_and_config[1]
    params = {"rows_per_job": 4096, "resident_partitions": 2}
    big = 3000000019                    # the driver's seeds pass 2**31
    a = job.make_data(config, params, big, 1)
    b = job.make_data(config, params, big, 1)
    c = job.make_data(config, params, big + 1, 1)
    dest, ip, date, revenue = a["parts"][0]
    urls, ranks = a["pages"]
    assert len(urls) == round(2 * 4096 * 18 / 155) == 951
    assert urls.dtype == dest.dtype == np.dtype("S100")
    assert ip.dtype == np.dtype("S16")
    assert date.dtype == ranks.dtype == np.int32
    assert revenue.dtype == np.float32
    assert (dest == b["parts"][0][0]).all() and (urls == b["pages"][0]).all()
    assert (dest != c["parts"][0][0]).any() and (urls != c["pages"][0]).any()
    assert (dest != a["parts"][1][0]).any()
    texts = urls.tolist()
    assert len(set(texts)) == len(texts)            # distinct
    assert {len(t) for t in texts} <= set(range(20, 101))
    assert max(len(t) for t in texts) > 90 and min(len(t) for t in texts) < 30
    assert all(t.startswith(b"http://") for t in texts)
    assert all(97 <= ch <= 122 or ch in b":/" for t in texts for ch in t)
    assert np.isin(dest, urls).all()        # every visit has its page
    assert 1 <= ranks.min() and ranks.max() == 10000
    assert 0 <= date.min() and date.max() < 15930
    inside = ((date >= 3652) & (date <= 14610)).mean()
    assert 0.65 < inside < 0.73                     # 68.8% of the days
    # Zipf over the pages: the hottest page by far the most visited, and
    # it carries the largest pageRank
    page, hits = np.unique(dest, return_counts=True)
    hottest = page[hits.argmax()]
    assert hits.max() > 0.03 * len(dest)
    assert ranks[urls == hottest][0] == 10000


def test_the_reference_equals_the_local_master(entry_and_config, job):
    """The numpy reference (over the bytes) against the dpark chain on the
    `local` master, 4,096 visits x 476 pages."""
    from dpark_tpu import Columns, DparkContext
    config = entry_and_config[1]
    data = job.make_data(config, {"rows_per_job": 4096,
                                  "resident_partitions": 1}, 7, 1)
    assert (data["rows"], len(data["pages"][0])) == (4096, 476)
    local = DparkContext("local")
    local.start()
    try:
        tables = {"parts": [local.parallelize(Columns(*data["parts"][0]),
                                              1)],
                  "pages": local.parallelize(Columns(*data["pages"]), 1)}
        top = job.run(local, tables, 0, "q3c", "top1", 1)
        sample = job.run(local, tables, 0, "q3c", "collect_sample", 1)
        everything = tables["parts"][0].filter(job.in_dates) \
            .map(job.by_url).join(tables["pages"]).map(job.by_ip) \
            .reduceByKey(job.add3, 1).collect()
    finally:
        local.stop()
    expected = job.reference(data, 0, "q3c", "top1")
    keys, ranks, counts, sums, _ = expected
    assert sorted(k for k, _ in everything) == keys.tolist()
    by_key = dict(everything)
    assert [by_key[k][0] for k in keys.tolist()] == ranks.tolist()
    assert [by_key[k][1] for k in keys.tolist()] == counts.tolist()
    np.testing.assert_allclose([by_key[k][2] for k in keys.tolist()], sums,
                               rtol=1e-12)
    assert counts.sum() == int(((data["parts"][0][2] >= 3652)
                                & (data["parts"][0][2] <= 14610)).sum())
    assert job.verdict(top, expected, "top1")
    assert top[0][0] == keys[sums.argmax()]
    assert job.verdict(sample, job.reference(data, 0, "q3c",
                                             "collect_sample"),
                       "collect_sample")
    # and a wrong answer is one: another group's row, a rank off by one
    k, v = top[0]
    assert not job.verdict([(k, (v[0] + 1, v[1], v[2]))], expected, "top1")
    worst = keys[sums.argmin()]
    assert not job.verdict([(worst, by_key[worst])], expected, "top1")
    assert not job.verdict(sample[1:], job.reference(
        data, 0, "q3c", "collect_sample"), "collect_sample")


def test_least_counts_both_tables_once(entry_and_config, job):
    config = entry_and_config[1]
    data = job.make_data(config, {"rows_per_job": 4096,
                                  "resident_partitions": 1}, 7, 1)
    least = job.least(config, None, data, 1, "q3c")
    groups = len(job.reference(data, 0, "q3c", "top1")[0])
    assert least == {"hbm_bytes": 4096 * 124 + 476 * 104 + groups * 36.0,
                     "ici_bytes": 0.0}
    assert job.input_rows(data) == 4096 + 476
    assert job.resident_bytes(data) == 4096 * 124 + 476 * 104


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
# leaves the array path, as a program that holds no S100 column on the
# device leaves it there
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
    assert "left the array path" in err and "rankings slice" in err


def test_the_join_metrics_read_in_a_rehearsal():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse", "64"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["join_host_rows_per_job"] == {"value": 1.0,
                                                 "unit": "count"}
    assert metrics["join_job_ms"]["unit"] == "ms"
    assert metrics["join_job_ms"]["value"] > 0
    assert metrics["window_compiles"]["value"] == 0.0
    assert metrics["readbacks_per_job"]["value"] <= 12
    assert metrics["launches_per_job"]["value"] <= 9


def test_the_int_join_reports_its_join_span_too():
    rc, line, err = run_cell(
        ["--workload", "join.resident", "--seed", "11", "--seconds", "1",
         "--trace", "1", "--rehearse", "64"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    assert line["metrics"]["join_job_ms"]["value"] > 0
    assert "join_host_rows_per_job" not in line["metrics"]
