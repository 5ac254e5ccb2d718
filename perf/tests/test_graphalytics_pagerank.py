"""The configuration `graphalytics-pagerank` and its cell
`pagerank.graph500`: held to what test_gensort_terasort.py holds its
configuration to, and to what is their own: a seeded Graph500 generator
that gives a simple undirected graph with the generator's degree skew, a
numpy reference that equals `run_pregel` on the `local` master, a `load`
whose probe stops a program without the resident graph, and the four
per-layer metrics that read device Pregel's span and counters."""

import json
import os

import numpy as np
import pytest

from benchhelp import ROOT, run_cell  # noqa: E402

from perf.lib import manifest  # noqa: E402

CELL = "pagerank.graph500"
CONFIG = "graphalytics-pagerank"
READERS = ("pregel_superstep_ms", "pregel_supersteps_per_job",
           "pregel_msgs_per_job", "pregel_graph_loads_per_job")


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


def _params(config, partitions=1):
    return {"rows_per_job": 17 << config["graph500_scale"],
            "resident_partitions": partitions}


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
    # the source's shapes, never cut: generator, damping, the rule
    assert config["generator"]["initiator"] \
        == {"a": 0.57, "b": 0.19, "c": 0.19, "d": 0.05}
    assert config["generator"]["edge_factor"] == 16
    assert config["algorithm"] == {"name": "PR", "damping": 0.85,
                                   "iterations": 10, "epsilon": 0.0001}
    assert 16 <= config["graph500_scale"] <= 22


def test_the_traffic_fits_the_configuration(entry_and_config, job,
                                            bench_manifest):
    config = entry_and_config[1]
    cell = next(w for w in bench_manifest["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    with open(manifest.traffic_path(cell["traffic"])) as f:
        params = json.load(f)
    assert params["rows_per_job"] == 17 << config["graph500_scale"] \
        == config["rows_per_job_max"]
    assert params["resident_partitions"] == 4
    assert (params["loop"], params["draw"]) == ("closed", "uniform")
    assert [(e["query"], e["action"], e.get("setup_actions"))
            for e in params["jobs"]] == [("pagerank", "ranks", None)]
    assert set(job.QUERIES["pagerank"]) == {"ranks"}
    for name in READERS:
        metric = next(m for m in bench_manifest["per_layer"]
                      if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "job_s"


@pytest.mark.parametrize("change", [
    lambda c: c["generator"].update(edge_factor=8),
    lambda c: c["generator"]["initiator"].update(a=0.25),
    lambda c: c.update(graph500_scale=c["graph500_scale"] + 1)])
def test_a_generator_the_job_module_lacks_is_an_error(entry_and_config, job,
                                                      change):
    config = json.loads(json.dumps(entry_and_config[1]))
    params = _params(config)
    job.make_data(config, params, 1, 1 << 12)
    change(config)
    with pytest.raises(ValueError):
        job.make_data(config, params, 1, 1 << 12)


def test_data_is_seeded_simple_undirected_and_skewed(entry_and_config, job):
    config = entry_and_config[1]
    params = _params(config, 2)
    divisor = 1 << (config["graph500_scale"] - 12)      # scale 12
    a = job.make_data(config, params, 2 ** 31 + 5, divisor)
    b = job.make_data(config, params, 2 ** 31 + 5, divisor)
    c = job.make_data(config, params, 2 ** 31 + 6, divisor)
    assert a["scale"] == 12 and job.n_partitions(a) == 2
    for ga, gb in zip(a["graphs"], b["graphs"]):
        assert all(np.array_equal(ga[k], gb[k]) for k in ga)
    assert not np.array_equal(a["graphs"][0]["lo"], c["graphs"][0]["lo"])
    assert not np.array_equal(a["graphs"][0]["lo"], a["graphs"][1]["lo"])
    g = a["graphs"][0]
    ids, lo, hi = g["ids"], g["lo"], g["hi"]
    assert np.all(lo < hi)                              # no self-loop
    assert len(np.unique((lo << 12) | hi)) == len(lo)   # no duplicate
    assert np.array_equal(ids, np.unique(np.concatenate([lo, hi])))
    assert ids.min() >= 0 and ids.max() < 1 << 12
    # the published shape: graph500-22 keeps 57% of its labels and 96% of
    # its edges; a smaller scale loses more edges to duplicates
    assert 0.5 < len(ids) / (1 << 12) < 0.85
    assert 0.6 < len(lo) / (16 << 12) <= 1.0
    degree = np.bincount(np.searchsorted(ids, np.concatenate([lo, hi])))
    assert degree.min() >= 1                            # none isolated
    assert degree.max() > 20 * np.median(degree)        # the skew
    assert job.input_rows(a) == sum(
        len(x["ids"]) + len(x["lo"]) for x in a["graphs"]) // 2
    assert job.resident_bytes(a) == 2 * (20 * (1 << 17) + 8 * (1 << 12))
    arcs, vertices = (sum(2 * len(x["lo"]) for x in a["graphs"]) / 2,
                      sum(len(x["ids"]) for x in a["graphs"]) / 2)
    assert job.least(config, params, a, 1, "pagerank") == {
        "hbm_bytes": 10 * (8 * arcs + 12 * vertices), "ici_bytes": 0.0}


def test_a_rehearsal_never_goes_under_the_probe_scale(entry_and_config, job):
    config = entry_and_config[1]
    data = job.make_data(config, _params(config), 3, 1 << 20)
    assert data["scale"] == job.PROBE_SCALE == 8


@pytest.fixture(scope="module")
def local():
    from dpark_tpu import DparkContext
    ctx = DparkContext("local")
    ctx.start()
    yield ctx
    ctx.stop()


def test_the_reference_equals_the_local_master(entry_and_config, job, local):
    """The float64 reference against run_pregel's numpy loop in float64
    (far inside the rule), and the rule against what breaks it."""
    from dpark_tpu.bagel import run_pregel
    config = entry_and_config[1]
    data = job.make_data(config, _params(config), 11,
                         1 << (config["graph500_scale"] - 10))
    graph = data["graphs"][0]
    want = job.reference(data, 0, "pagerank", "ranks")
    assert abs(want["ranks"].sum() - 1.0) < 1e-12
    src, dst = job.arcs(graph)
    n = len(graph["ids"])
    compute, send, aggregator = job.pagerank_functions(
        data["damping"], data["iterations"])
    ids, (ranks, _), active = run_pregel(
        local, graph["ids"][::-1], (np.full(n, 1.0 / n), np.zeros(n, bool)),
        (src, dst), compute, send, combine="add", aggregator=aggregator)
    assert not active.any()
    worst, off = job.rank_errors((ids, ranks), want)
    assert worst < 1e-6 and off < 1e-6
    assert job.verdict((ids, ranks), want, "ranks")
    ranks = np.asarray(ranks, np.float64)
    assert not job.verdict((ids[:-1], ranks[:-1]), want, "ranks")
    assert not job.verdict((ids[::-1], ranks), want, "ranks")
    one_off = ranks.copy()
    one_off[7] *= 1.0 + 3e-4
    assert not job.verdict((ids, one_off), want, "ranks")
    # the nearest precision below the configuration's float32
    import jax.numpy as jnp
    rounded = np.asarray(jnp.asarray(ranks, jnp.bfloat16), np.float64)
    assert job.rank_errors((ids, rounded), want)[0] > 10 * want["epsilon"]
    assert not job.verdict((ids, rounded), want, "ranks")


_PATCHED = """
import sys
sys.path.insert(0, %r)
import dpark_tpu.bagel
del dpark_tpu.bagel.PregelGraph     # the program before the resident graph
from perf.lib import runner
sys.exit(runner.main(sys.argv[1:]))
"""


def test_load_stops_a_program_without_the_resident_graph():
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "5", "--seconds", "1", "--trace",
         "0", "--rehearse", "512"], code=_PATCHED % ROOT)
    assert rc != 0 and line is None
    assert "ImportError" in err and "PregelGraph" in err


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_names_its_metrics(trace):
    rc, line, err = run_cell(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--rehearse", "512"])
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 1
    metrics = line["metrics"]
    if not trace:
        assert set(metrics) == {"throughput", "job_s", "setup_s"}
        return
    assert set(READERS) <= set(metrics)
    assert metrics["pregel_supersteps_per_job"] == {"value": 11.0,
                                                    "unit": "count"}
    assert metrics["pregel_graph_loads_per_job"]["value"] == 0.0
    assert metrics["pregel_msgs_per_job"]["value"] > 10 * 2 * 1000
    assert metrics["pregel_superstep_ms"]["unit"] == "ms"
    assert metrics["pregel_superstep_ms"]["value"] > 0
    assert metrics["window_compiles"]["value"] == 0.0
    assert metrics["launches_per_job"]["value"] == 22.0
    assert metrics["readbacks_per_job"]["value"] == 28.0
    # every metric that lists no cells reads a number here too
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    device_only = {m["name"] for m in per_layer
                   if m["source"] == "device_trace"} | {"exec_host_job_ms",
                                                        "peak_hbm_GB"}
    assert {m["name"] for m in per_layer if "workloads" not in m} \
        - device_only <= set(metrics)


def test_the_readers_read_nothing_from_a_program_without_pregel():
    obs = {"jobs": [{"spans": [{"name": "stage.exec", "dur": 0.01}]}],
           "counters": {"host_reads": 3}}
    for name in READERS:
        reader = manifest.load_module(
            manifest.reader_path("per_layer", name))
        assert reader.read(obs) is None
    obs = {"jobs": [{"spans": [{"name": "pregel.superstep", "dur": d}
                               for d in (0.002, 0.004, 0.009)]},
                    {"spans": [{"name": "pregel.superstep", "dur": 0.006}]}],
           "counters": {"pregel_supersteps": 4, "pregel_messages": 10,
                        "pregel_graph_loads": 0}}
    values = [manifest.load_module(
        manifest.reader_path("per_layer", name)).read(obs)
        for name in READERS]
    assert values == [5.0, 2.0, 5.0, 0.0]
