"""Every cell end to end on the CPU through --rehearse (one and four
virtual devices): the contract's last line, every metric of the manifest,
and a wrong answer or a left device path turned into `failed`."""

import pytest

from benchhelp import CONTRACT_KEYS, ROOT, run_cell  # noqa: E402

from perf.lib import manifest  # noqa: E402

CELLS = [w["name"] for w in manifest.load()["workloads"]]
# the CPU of a rehearsal reports no memory statistics
CPU_HAS_NONE = {"peak_hbm_GB"}


def _args(cell, trace, seconds="3"):
    return ["--workload", cell, "--seed", "5", "--seconds", seconds,
            "--trace", str(trace), "--rehearse", "64"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace, bench_manifest):
    rc, line, err = run_cell(_args(cell, trace))
    assert rc == 0, err[-2000:]
    # a rehearsal's trace is of the CPU: it goes under its own key and
    # never under `breakdown`, `device` or a device metric's name
    want_keys = CONTRACT_KEYS | ({"rehearsal_only"} if trace else set())
    assert set(line) == want_keys
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 3
    chips = next(w["chips"] for w in bench_manifest["workloads"]
                 if w["name"] == cell)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert line["device"]["rehearse"] == 64      # the rehearsal's mark
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"]: m["unit"] for m in bench_manifest[kind]
            if manifest.in_cell(m, cell) and m["source"] != "device_trace"
            and m["name"] not in CPU_HAS_NONE
            and m["name"] != "exec_host_job_ms"}  # stage.exec - device time
    got = {name: m["unit"] for name, m in line["metrics"].items()}
    assert got == want
    for name, m in line["metrics"].items():
        assert isinstance(m["value"], float), name
    if trace:
        assert not {"busy_s", "window_s"} & set(line["device"])
        only = line["rehearsal_only"]
        assert 0 < only["busy_s"] <= only["window_s"]
        for key in ("device_ops", "idle_gaps"):
            assert 0 < len(only["breakdown"][key]) <= 10
    else:
        assert all(m["value"] > 0 for m in line["metrics"].values())


def test_no_accelerator_no_result():
    """Without --rehearse and without a TPU: non-zero, no result line."""
    rc, line, err = run_cell(["--workload", "agg.small", "--seed", "1",
                              "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert line is None


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

_WRONG_REFERENCE = """
    ref = mod.reference
    def wrong(data, part, query, action):
        keys, sums = ref(data, part, query, action)
        sums = sums.copy(); sums[0] += 1
        return keys, sums
    mod.reference = wrong
"""

# int() of a traced value cannot be traced: the map stage leaves the array
# path with a fallback reason, and the answer is still right
_FALLBACK_QUERY = """
    def untraceable(kv):
        return (int(kv[0]) >> 24, kv[1])
    mod.QUERIES = dict(mod.QUERIES, prefix_24=(untraceable, 8))
"""


@pytest.mark.parametrize("patch", [_WRONG_REFERENCE, _FALLBACK_QUERY],
                         ids=["wrong_reference", "fallback_reason"])
def test_bad_job_counts_as_failed(patch):
    rc, line, err = run_cell(_args("agg.small", 0, "1"),
                             code=_PATCHED % (ROOT, patch.strip()))
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    assert line["attempted"] > 0
    assert line["failed"] == line["attempted"]


# agg.small at 1/16 holds 64 x 64 KiB resident and makes one 64 KiB store
# a job: with this budget the stores reach it some ten jobs into the
# window, and every job from there on spills
_SMALL_BUDGET = """
    from dpark_tpu import conf
    conf.SHUFFLE_HBM_BUDGET = (64 + 13) * 65536
"""
_HOOK_GONE = _SMALL_BUDGET + """
    runner.SpillWatch.HOOK = "_a_routine_the_program_renamed"
"""


def test_a_regime_flip_inside_the_window_counts_as_failed():
    """Jobs that meet the spill when the warm-up did not (or the other
    way round) are failed jobs: two runs would not agree on them."""
    rc, line, err = run_cell(
        ["--workload", "agg.small", "--seed", "5", "--seconds", "3",
         "--trace", "1", "--rehearse", "16"],
        code=_PATCHED % (ROOT, _SMALL_BUDGET.strip()))
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    assert 0 < line["failed"] < line["attempted"]
    assert line["metrics"]["spill_job_ms"]["value"] > 0


def test_evicting_unseen_stops_the_run():
    """A program that still evicts but has lost the routine the runner
    watches gets no result line, instead of a spill_job_ms of 0."""
    rc, line, err = run_cell(
        ["--workload", "agg.small", "--seed", "5", "--seconds", "3",
         "--trace", "0", "--rehearse", "16"],
        code=_PATCHED % (ROOT, _HOOK_GONE.strip()))
    assert rc != 0 and line is None
    assert "evicted shuffle stores" in err
