"""BENCHMARK.json and the files its names resolve to.

Every name in the manifest finds a file by name alone, so a later PR adds
a cell, a configuration, a traffic mix or a per-layer metric as new files
plus manifest entries and edits nothing that is here:

    configuration  <name>  ->  its manifest entry's "file" (a JSON object)
    traffic        <name>  ->  perf/traffic/<name>.json
    per-layer      <name>  ->  perf/layer_metrics/<name>.py  (read(obs))
    end-to-end     <name>  ->  perf/end_to_end/<name>.py  (read(obs))
    job module     <name>  ->  perf/jobs/<name>.py  (named by the config)
"""

import importlib.util
import json
import os
import re

PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """The manifest or a file it names is missing or malformed."""


def _read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError("cannot read %s: %s" % (path, e))


def load(path=None):
    return _read_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError("no %s named %r in the manifest" % (what, name))


def traffic_path(name):
    return os.path.join(PERF_DIR, "traffic", name + ".json")


READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def reader_path(kind, metric):
    return os.path.join(PERF_DIR, READER_DIRS[kind], metric + ".py")


def job_module_path(name):
    return os.path.join(PERF_DIR, "jobs", name + ".py")


def load_module(path):
    """Import one file of the benchmark by path (no package needed, so a
    new file is found without touching an __init__)."""
    if not os.path.isfile(path):
        raise ManifestError("no file %s" % path)
    name = "perf_file_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def in_cell(metric, cell_name):
    """Does this metric exist in this cell?  (A metric with no
    "workloads" list exists in every cell.)"""
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(manifest, workload):
    """One cell: its manifest entry, configuration (entry + file content),
    traffic parameters, and the end-to-end and per-layer metrics that
    exist in it."""
    cell = _by_name(manifest["workloads"], workload, "workload")
    centry = _by_name(manifest["configs"], cell["config"], "configuration")
    config = _read_json(os.path.join(ROOT, centry["file"]))
    traffic = _read_json(traffic_path(cell["traffic"]))
    return {
        "cell": cell,
        "config_entry": centry,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in manifest["end_to_end"]
                       if in_cell(m, workload)],
        "per_layer": [m for m in manifest["per_layer"]
                      if in_cell(m, workload)],
    }
