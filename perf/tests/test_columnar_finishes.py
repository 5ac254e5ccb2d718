"""`columnar_finishes_per_job`: its reader on fabricated observations, its
manifest entry, and the counter it reads in rehearsals of `tpch.q1` and
`tpch.q3` (each job's grouped finish runs over columns)."""

import pytest

import benchhelp
from perf.lib import manifest

NAME = "columnar_finishes_per_job"


def read(obs):
    return manifest.load_module(
        manifest.reader_path("per_layer", NAME)).read(obs)


@pytest.mark.parametrize("counters,jobs,want", [
    ({"query_finishes_columnar": 31}, 31, 1.0),
    ({"query_finishes_columnar": 0}, 7, 0.0),
    ({"scan_rows_device": 9}, 9, None),     # a program before the counter
    ({"query_finishes_columnar": 4}, 0, None),     # no job completed
], ids=["one-a-job", "none", "no-counter", "no-jobs"])
def test_the_reader_divides_the_counter_by_the_jobs(counters, jobs, want):
    obs = {"counters": counters, "jobs": [{"index": i} for i in range(jobs)]}
    assert read(obs) == want


def test_the_manifest_entry(bench_manifest):
    (entry,) = [m for m in bench_manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "driver",
                     "moves": "job_s", "workloads": ["tpch.q1", "tpch.q3"]}


@pytest.mark.parametrize("cell,rehearse", [("tpch.q1", "2048"),
                                           ("tpch.q3", "256")])
def test_a_rehearsal_reads_one_a_job(cell, rehearse):
    rc, line, err = benchhelp.run_cell(
        ["--workload", cell, "--seed", "3700000013", "--seconds", "2",
         "--trace", "1", "--rehearse", rehearse])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][NAME] == {"value": 1.0, "unit": "count"}
    assert line["metrics"]["window_compiles"]["value"] == 0.0
