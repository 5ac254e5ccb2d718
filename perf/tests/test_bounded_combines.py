"""`bounded_combines_per_job`: its reader on fabricated observations, its
manifest entry, and the counter it reads in rehearsals of `tpch.q1` (the
query's two CHAR(1) keys bound its groups at 180 a partition, so its map
program packs the combine to 256 slots of its input's 4,096) and of
`agg.small` (RDD code: no bound)."""

import pytest

import benchhelp
from perf.lib import manifest

NAME = "bounded_combines_per_job"


def read(obs):
    return manifest.load_module(
        manifest.reader_path("per_layer", NAME)).read(obs)


@pytest.mark.parametrize("counters,jobs,want", [
    ({"combines_bounded": 31}, 31, 1.0),
    ({"combines_bounded": 0}, 7, 0.0),
    ({"combines_bounded": 6, "filters_masked": 9}, 3, 2.0),
    ({"filters_masked": 9}, 9, None),       # a program before the counter
    ({"combines_bounded": 4}, 0, None),     # no job completed
], ids=["one-a-job", "none", "two-a-job", "no-counter", "no-jobs"])
def test_the_reader_divides_the_counter_by_the_jobs(counters, jobs, want):
    obs = {"counters": counters, "jobs": [{"index": i} for i in range(jobs)]}
    assert read(obs) == want


def test_the_manifest_entry(bench_manifest):
    (entry,) = [m for m in bench_manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "stage programs",
                     "moves": "job_s"}


@pytest.mark.parametrize("cell,rehearse,want", [("tpch.q1", "2048", 1.0),
                                                ("agg.small", "64", 0.0)])
def test_a_rehearsal_reads_the_counter(cell, rehearse, want):
    rc, line, err = benchhelp.run_cell(
        ["--workload", cell, "--seed", "3600000041", "--seconds", "2",
         "--trace", "1", "--rehearse", rehearse])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][NAME] == {"value": want, "unit": "count"}
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    if cell == "tpch.q1":
        # the store leaves the map program at 256 slots: under the
        # budget, so `_trim_combined` reads no `store.counts`
        assert line["metrics"]["readbacks_per_job"]["value"] == 11.0
