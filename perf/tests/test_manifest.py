"""BENCHMARK.json against the files under perf/: every name resolves by
name, names and units use the permitted characters, and a new cell,
configuration, traffic mix and per-layer metric are files plus manifest
entries and nothing else."""

import json
import os

import pytest

from benchhelp import ROOT, run_cell  # noqa: E402

from perf.lib import manifest, traffic  # noqa: E402


def test_every_name_resolves(bench_manifest):
    m = bench_manifest
    assert m["paths"] == ["perf"]
    e2e = {x["name"] for x in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for w in m["workloads"]:
        r = manifest.resolve(m, w["name"])
        traffic.validate(r["traffic"])
        job = manifest.load_module(
            manifest.job_module_path(r["config"]["job_module"]))
        for entry in r["traffic"]["jobs"]:
            assert entry["query"] in job.QUERIES
        assert r["config"]["guarantees"]
        assert sorted(r["config"]["reduced"]) \
            == sorted(r["config_entry"]["reduced"])
        assert w["chips"] in (1, 4)
    for kind in ("end_to_end", "per_layer"):
        for metric in m[kind]:
            reader = manifest.load_module(
                manifest.reader_path(kind, metric["name"]))
            assert callable(reader.read)
            assert set(metric.get("workloads", ())) <= cells
    for metric in m["per_layer"]:
        assert metric["moves"] in e2e
    assert "setup_s" in e2e
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}


def test_names_and_units_use_permitted_characters(bench_manifest):
    m = bench_manifest
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in m[key]]
    names += [w["traffic"] for w in m["workloads"]]
    names += [k for c in m["configs"] for k in c["reduced"]]
    for name in names:
        assert manifest.NAME_RE.match(name), name
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in m[key]]
        assert len(got) == len(set(got)), key
    for metric in m["end_to_end"] + m["per_layer"]:
        assert manifest.UNIT_RE.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in manifest.SOURCES
    for metric in m["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    layers = {x["layer"] for x in m["per_layer"]}
    assert all(1 <= len(l) <= 200 and "\n" not in l for l in layers)
    for dirpath, _, files in os.walk(os.path.join(ROOT, "perf")):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            rel = os.path.relpath(os.path.join(dirpath, fn), ROOT)
            assert all(manifest.NAME_RE.match(p) for p in rel.split("/")), rel


_DUMMY_READER = '''"""A dummy per-layer metric: jobs in the window (an open
loop's, so each carries its latency from arrival)."""


def read(obs):
    assert all(j["latency_s"] >= j["wall_s"] for j in obs["jobs"])
    return len(obs["jobs"])
'''


@pytest.fixture
def checkout_with_a_dummy_cell(tmp_path):
    """A copy of what a checkout holds of the benchmark (BENCHMARK.json,
    perf/, the program by symlink) with a new configuration, traffic mix
    and per-layer reader added as NEW files and manifest entries, as a
    later PR would add them.  Nothing is written into this checkout."""
    import shutil
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perf"), root / "perf",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dpark_tpu"), root / "dpark_tpu")
    m = manifest.load()
    config = json.load(open(os.path.join(ROOT, m["configs"][0]["file"])))
    config["name"] = "dummy-config"
    params = json.load(open(manifest.traffic_path("small")))
    params.update(resident_partitions=3, profile_jobs=2, draw="zipf",
                  draw_exponent=1.2, loop="open", rate_per_s=20.0)
    new = {"perf/configs/dummy-config.json": json.dumps(config),
           "perf/traffic/dummy_traffic.json": json.dumps(params),
           "perf/layer_metrics/dummy_jobs.py": _DUMMY_READER}
    m["configs"].append(dict(m["configs"][0], name="dummy-config",
                             file="perf/configs/dummy-config.json"))
    m["workloads"].append({"name": "dummy.cell", "config": "dummy-config",
                           "traffic": "dummy_traffic", "chips": 1,
                           "why": "test"})
    m["per_layer"].append({"name": "dummy_jobs", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "driver", "moves": "throughput",
                           "workloads": ["dummy.cell"]})
    for rel, text in new.items():
        assert not (root / rel).exists()
        (root / rel).write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return str(root)


def test_a_new_cell_is_files_and_entries_only(checkout_with_a_dummy_cell):
    rc, line, err = run_cell(["--workload", "dummy.cell", "--seed", "3",
                              "--seconds", "1", "--trace", "1",
                              "--rehearse", "16"],
                             root=checkout_with_a_dummy_cell)
    assert rc == 0, err[-2000:]
    assert line["correct"] is True
    assert line["metrics"]["dummy_jobs"]["unit"] == "count"
    # an open loop at 20 jobs a second for 1 s, well under capacity
    assert 5 <= line["metrics"]["dummy_jobs"]["value"] <= 40
    assert "job_p95_ms" not in line["metrics"]      # agg.small's alone


def test_the_runner_has_no_option_beyond_the_contract():
    rc, line, err = run_cell(["--manifest", "x.json", "--workload",
                              "agg.small", "--seed", "1", "--seconds", "1",
                              "--trace", "0", "--rehearse", "16"])
    assert rc != 0 and line is None


@pytest.mark.parametrize("draw", traffic.DRAWS)
def test_every_draw_is_seeded_and_in_range(draw):
    import itertools
    params = json.load(open(manifest.traffic_path("small")))
    params["draw"] = draw
    traffic.validate(params)
    first = [p for _, p in itertools.islice(
        traffic.schedule(params, 7, 5), 600)]
    again = [p for _, p in itertools.islice(
        traffic.schedule(params, 7, 5), 600)]
    assert first == again and set(first) == set(range(5))
    others = [[p for _, p in itertools.islice(
        traffic.schedule(params, seed, 5), 600)] for seed in range(8, 14)]
    assert any(other != first for other in others)


def test_an_unknown_draw_is_refused():
    params = json.load(open(manifest.traffic_path("small")))
    params["draw"] = "bursty"
    with pytest.raises(ValueError):
        traffic.validate(params)


@pytest.mark.parametrize("entry", ["intpairs-reducebykey", "intpairs-join"])
def test_a_distribution_the_job_module_lacks_is_an_error(entry,
                                                         bench_manifest):
    """A configuration that names Zipfian keys gets Zipfian keys or an
    error, never uniform ones silently."""
    centry = next(c for c in bench_manifest["configs"]
                  if c["name"] == entry)
    config = json.load(open(os.path.join(ROOT, centry["file"])))
    job = manifest.load_module(manifest.job_module_path(config["job_module"]))
    params = {"rows_per_job": 4096, "resident_partitions": 1}
    job.make_data(config, params, 1, 1)
    config["key_distribution"] = {"kind": "zipf", "s": 1.0}
    with pytest.raises(ValueError):
        job.make_data(config, params, 1, 1)


def test_reduced_names_keys_of_the_configuration_file(bench_manifest):
    for centry in bench_manifest["configs"]:
        config = json.load(open(os.path.join(ROOT, centry["file"])))
        assert centry["reduced"] and set(centry["reduced"]) <= set(config)
        assert set(centry["reduced"]) == set(config["reduced_why"])
        assert centry["source"] == config["source"]


def test_unknown_names_are_refused(tmp_path):
    rc, line, err = run_cell(["--workload", "no.such.cell", "--seed", "1",
                              "--seconds", "1", "--trace", "0",
                              "--rehearse", "16"])
    assert rc != 0 and line is None
    assert "no workload named" in err
