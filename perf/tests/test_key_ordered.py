"""`key_ordered_stores_per_job`: its reader on fabricated observations, its
manifest entry, and the counter it reads in rehearsals of the one-chip join
cells (each of `join.resident`'s two int-keyed sides is marked, none of
`uservisits.join`'s byte-string sides is)."""

import pytest

import benchhelp
from perf.lib import manifest

NAME = "key_ordered_stores_per_job"


def read(obs):
    return manifest.load_module(
        manifest.reader_path("per_layer", NAME)).read(obs)


@pytest.mark.parametrize("counters,jobs,want", [
    ({"stores_key_ordered": 24}, 12, 2.0),
    ({"stores_key_ordered": 0}, 7, 0.0),
    ({"stores_key_ordered": 8, "stores_pre_reduced": 2}, 2, 4.0),
    ({"stores_pre_reduced": 9}, 9, None),   # a program before the counter
    ({"stores_key_ordered": 4}, 0, None),   # no job completed
], ids=["two-a-job", "none", "four-a-job", "no-counter", "no-jobs"])
def test_the_reader_divides_the_counter_by_the_jobs(counters, jobs, want):
    obs = {"counters": counters, "jobs": [{"index": i} for i in range(jobs)]}
    assert read(obs) == want


def test_the_manifest_entry(bench_manifest):
    (entry,) = [m for m in bench_manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "stage programs",
                     "moves": "job_s",
                     "workloads": ["join.resident", "uservisits.join",
                                   "tpch.q3"]}


@pytest.mark.parametrize("cell,rehearse,want", [
    ("join.resident", "64", 2.0),
    ("uservisits.join", "64", 0.0),
    ("tpch.q3", "256", 4.0)])
def test_a_rehearsal_reads_the_counter(cell, rehearse, want):
    rc, line, err = benchhelp.run_cell(
        ["--workload", cell, "--seed", "3800000017", "--seconds", "2",
         "--trace", "1", "--rehearse", rehearse])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][NAME] == {"value": want, "unit": "count"}
    assert line["metrics"]["window_compiles"]["value"] == 0.0
