"""`masked_filters_per_job`: its reader on fabricated observations, its
manifest entry, and the counter it reads in rehearsals of `tpch.q1` (the
query's date filter folds into the combining write of its one job) and of
`agg.small` (no filter)."""

import pytest

import benchhelp
from perf.lib import manifest

NAME = "masked_filters_per_job"


def read(obs):
    return manifest.load_module(
        manifest.reader_path("per_layer", NAME)).read(obs)


@pytest.mark.parametrize("counters,jobs,want", [
    ({"filters_masked": 31}, 31, 1.0),
    ({"filters_masked": 0}, 7, 0.0),
    ({"filters_masked": 6, "scan_rows_device": 9}, 3, 2.0),
    ({"scan_rows_device": 9}, 9, None),     # a program before the counter
    ({"filters_masked": 4}, 0, None),       # no job completed
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
        ["--workload", cell, "--seed", "3600000011", "--seconds", "2",
         "--trace", "1", "--rehearse", rehearse])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][NAME] == {"value": want, "unit": "count"}
    assert line["metrics"]["window_compiles"]["value"] == 0.0
