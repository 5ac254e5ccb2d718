"""`prereduced_stores_per_job`: its reader on fabricated observations, its
manifest entry, and the counter it reads in rehearsals of a one-chip cell
(every job's one combining store is marked) and of the four-chip cell (none
is)."""

import pytest

import benchhelp
from perf.lib import manifest

NAME = "prereduced_stores_per_job"


def read(obs):
    return manifest.load_module(
        manifest.reader_path("per_layer", NAME)).read(obs)


@pytest.mark.parametrize("counters,jobs,want", [
    ({"stores_pre_reduced": 12}, 12, 1.0),
    ({"stores_pre_reduced": 0}, 7, 0.0),
    ({"stores_pre_reduced": 3, "stores_released": 9}, 3, 1.0),
    ({"stores_released": 9}, 9, None),      # a program before the counter
    ({"stores_pre_reduced": 4}, 0, None),   # no job completed
], ids=["one-a-job", "none", "a-third-of-the-stores", "no-counter",
        "no-jobs"])
def test_the_reader_divides_the_counter_by_the_jobs(counters, jobs, want):
    obs = {"counters": counters, "jobs": [{"index": i} for i in range(jobs)]}
    assert read(obs) == want


def test_the_manifest_entry(bench_manifest):
    (entry,) = [m for m in bench_manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "stage programs",
                     "moves": "throughput"}
    assert bench_manifest["per_layer"][-1] is entry


@pytest.mark.parametrize("cell,chips,want", [("agg.small", 1, 1.0),
                                             ("agg.highcard.4chip", 4, 0.0)])
def test_a_rehearsal_reads_the_counter(cell, chips, want):
    rc, line, err = benchhelp.run_cell(
        ["--workload", cell, "--seed", "3600000011", "--seconds", "2",
         "--trace", "1", "--rehearse", "64"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == chips
    assert line["metrics"][NAME] == {"value": want, "unit": "count"}
    assert line["metrics"]["window_compiles"]["value"] == 0.0
