"""The reduction from a profiler trace to busy time, idle share, per-job
device time and collective time: the interval arithmetic on hand-made
intervals, and the whole reduction on a small .xplane.pb recorded on the
chip, against values checked by hand."""

import glob
import os

import pytest

from perf.lib import xplane

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def test_union_merges_nested_and_clips():
    pieces = xplane.union([(10, 20), (12, 15), (18, 30), (40, 50), (5, 6)],
                          lo=8, hi=45)
    assert pieces == [[10, 30], [40, 45]]
    assert xplane.total(pieces) == 25
    assert xplane.complement(pieces, 8, 45) == [(8, 10), (30, 40)]


def test_self_times_do_not_charge_a_parent_its_children():
    events = [(0, 100, "while"), (10, 30, "sort"), (30, 50, "gather"),
              (60, 70, "sort"), (120, 130, "copy")]
    assert xplane.self_times(events) == {
        "while": 50, "sort": 30, "gather": 20, "copy": 10}


def test_label_gaps_splits_a_gap_over_what_the_host_did():
    gaps = [(0, 10), (20, 50), (90, 100)]
    segments = [(5, 25, "stage a"), (25, 40, "driver")]
    labelled = dict(xplane.label_gaps(gaps, segments, "between"))
    assert labelled == {"stage a": 10e-9, "driver": 15e-9,
                        "between": 25e-9}


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """The traced run of agg.highcard.4chip on four TPU v5 lite chips
    (PR 22, seed 101, the two profiled jobs), trimmed at the protobuf
    wire level to what the reducer reads (the device planes' `XLA Ops`,
    `XLA Modules` and `Async XLA Ops` lines and the host's perf.job
    marks) and gzipped."""
    import gzip
    packed = glob.glob(os.path.join(FIXTURES, "highcard4chip_2jobs*.gz"))
    if not packed:
        pytest.skip("no recorded trace")
    path = tmp_path_factory.mktemp("trace") / "chip.xplane.pb"
    with gzip.open(packed[0], "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_reduce_matches_hand_checked_values(chip_trace):
    """The expected values come from a separate +1/-1 sweep over the raw
    ProfileData events, written for this check and sharing no code with
    perf/lib/xplane.py."""
    r = xplane.reduce(chip_trace, "tpu")
    assert r["n_devices"] == 4
    assert [j["index"] for j in r["jobs"]] == [28, 29]
    assert r["window_s"] == pytest.approx(2.210014952, rel=1e-9)
    assert r["busy_s"] == pytest.approx(2.16901175525, rel=1e-9)
    idle_pct = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert idle_pct == pytest.approx(1.855335716751283, rel=1e-6)
    assert [j["device_s"] for j in r["jobs"]] == pytest.approx(
        [1.08437919525, 1.08463256], rel=1e-9)
    assert [j["collective_s"] for j in r["jobs"]] == pytest.approx(
        [0.00029975625, 0.00029953125], rel=1e-9)
    # the gaps are the window's complement of the busy union
    idle_s = sum(e - s for s, e in r["gaps_ns"]) / 1e9
    assert idle_s <= r["window_s"] - r["busy_s"] + 1e-9
    name, seconds = r["device_ops"][0]
    assert name == "jit_per_device#0450/fusion.10 fusion:kCustom u32[2228224]"
    assert seconds == pytest.approx(1.230869799, rel=1e-9)


def test_a_tpu_run_without_device_planes_reduces_to_nothing(tmp_path):
    """Host events never stand in for the device's clock: a trace with no
    /device:TPU plane (here a CPU run's) gives None when the run was on a
    TPU, and is read only when the caller says it is a rehearsal."""
    import glob as g
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.JOB_MARK, index=0):
        jnp.sort(jnp.arange(4096)[::-1]).block_until_ready()
    jax.profiler.stop_trace()
    found = g.glob(str(tmp_path / "plugins" / "profile" / "*" /
                       "*.xplane.pb"))
    assert found
    assert xplane.reduce(found[0], "tpu") is None
    rehearsal = xplane.reduce(found[0], "cpu")
    assert rehearsal is None or rehearsal["busy_s"] > 0


def test_an_operand_named_all_to_all_is_not_a_collective():
    assert xplane.is_collective(
        "%all_to_all.19 = s32[4,1,8]{2,1,0} all-to-all(s32[4,1,8] %p)")
    assert xplane.is_collective("all-to-all")
    assert not xplane.is_collective(
        "%reduce.1 = s32[4]{0} reduce(s32[4,1,1]{2,1,0} %all_to_all.16)")
