"""perf/lib/selftime.py and the thirteen readers over it, on hand-built
spans: a three-deep tree, siblings that abut, a span the job's wall
clips, the `[host]` line's identity, a program from before the driver's
spans, and the three idle readers against hostspans' unattributed part on
the recorded chip trace."""

import glob
import gzip
import os

import pytest

import benchhelp  # noqa: F401  (puts the checkout on sys.path)
from perf.lib import hostspans, manifest, selftime, xplane

SPAN_READERS = ("preflight_job_ms", "job_begin_ms", "store_release_job_ms",
                "stage_self_job_ms", "job_loop_ms", "job_finish_ms",
                "driver_outside_job_ms", "exec_self_job_ms",
                "egest_rows_job_ms", "device_wait_job_ms")
IDLE_READERS = ("idle_driver_job_ms", "idle_exec_self_job_ms",
                "idle_unspanned_job_ms")
T0 = 1_700_000_000.0        # a job's t0_wall: the ring's clock is the epoch
MARK = 5_000_000_000        # its annotation's start on the trace's clock
MS = 1_000_000
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def read(name, obs):
    return manifest.load_module(
        manifest.reader_path("per_layer", name)).read(obs)


def span(name, start_ms, dur_ms, tid=1, t0=T0, **args):
    """A ring record `start_ms` after the job's t0_wall, rounded as the
    ring rounds."""
    return {"name": name, "cat": "x", "ts": round(t0 + start_ms / 1e3, 6),
            "dur": round(dur_ms / 1e3, 6), "job": 7, "tid": tid,
            "args": args}


def job(index, spans, wall_ms=100.0, t0=T0):
    return {"index": index, "wall_s": wall_ms / 1e3, "t0_wall": t0,
            "spans": spans}


def observation(jobs, profiled=(), prof=None):
    return {"jobs": list(jobs), "profiled_jobs": list(profiled),
            "profile": prof, "counters": {}}


# one job of 100 ms, as a collect of a two-stage chain leaves it:
# 0-2 chain building, preflight 2-5, job.begin 5-8 (a store.release
# 6-7), job 8-90 with two stage.run (10-40, 42-88), job.finish 90-93,
# 93-100 the action's own Python
JOB = [
    span("preflight", 2, 3, mode="warn"),
    span("job.begin", 5, 3, stages=2),
    span("store.release", 6, 1, stores=1, bytes=4096),
    span("job", 8, 82, state="done"),
    span("stage.run", 10, 30, tasks=1, shuffle=True),
    span("plan", 11, 4, ok=True),
    span("stage.exec", 16, 22, source="cached"),
    span("eager", 17, 3, site="keycheck"),
    span("readback", 20, 2, site="keycheck", bytes=1, wait_s=0.0015),
    span("launch", 23, 5, program="narrow"),
    span("stage.run", 42, 46, tasks=1, shuffle=False),
    span("plan", 43, 2, ok=True),
    span("stage.exec", 46, 40, source="hbm"),
    span("launch", 47, 3, program="reduce"),
    span("egest", 52, 30, rows=8, bytes=64),
    span("readback", 53, 20, site="egest.counts", bytes=8, wait_s=0.019),
    span("readback", 74, 2, site="egest.col", bytes=64, wait_s=0.0),
    span("readback", 86.5, 1, site="exchange.real_rows", bytes=8,
         wait_s=0.0002),
    span("job.finish", 90, 3),
    # records that overlap the tree instead of nesting in it, an instant
    # event, and a read on the bridge's thread: none of them is laid
    span("stage", 9.9, 31, rdd="MappedRDD"),
    span("task", 9.9, 31, status="success"),
    span("dispatch", 23, 0, program="narrow"),
    span("readback", 30, 50, tid=2, site="bridge.export", bytes=1),
]


def test_self_times_of_a_three_deep_tree():
    own = selftime.self_s(job(0, JOB))
    ms = {k: pytest.approx(v * 1e3, abs=1e-3) for k, v in own.items()}
    assert ms == {
        "outside": 2 + 7,
        "preflight": 3, "job.begin": 2, "store.release": 1,
        "job": 82 - 30 - 46, "stage.run": (30 - 4 - 22) + (46 - 2 - 40 - 1),
        "plan": 6, "stage.exec": (22 - 3 - 2 - 5) + (40 - 3 - 30),
        "eager": 3, "launch": 8, "egest": 30 - 22, "readback": 2 + 22 + 1,
        "job.finish": 3}
    assert sum(own.values()) == pytest.approx(0.100, abs=1e-12)


def test_readers_take_self_and_whole_times_per_job_and_the_median():
    quiet = [span("job.begin", 0, 1, stages=1), span("job", 1, 8),
             span("stage.run", 2, 6, tasks=1, shuffle=False),
             span("stage.exec", 3, 4, source="hbm"),
             span("job.finish", 9, 1)]
    obs = observation([job(0, JOB), job(1, quiet, 10), job(2, quiet, 10)])
    got = {name: read(name, obs) for name in SPAN_READERS}
    assert got == {k: pytest.approx(v, abs=1e-3) for k, v in {
        "preflight_job_ms": 0.0, "job_begin_ms": 1.0,
        "store_release_job_ms": 0.0, "stage_self_job_ms": 2.0,
        "job_loop_ms": 2.0, "job_finish_ms": 1.0,
        "driver_outside_job_ms": 0.0, "exec_self_job_ms": 4.0,
        "egest_rows_job_ms": 0.0, "device_wait_job_ms": 0.0}.items()}
    obs = observation([job(0, JOB)])
    got = {name: read(name, obs) for name in SPAN_READERS}
    assert got == {k: pytest.approx(v, abs=1e-3) for k, v in {
        "preflight_job_ms": 3.0, "job_begin_ms": 3.0,
        "store_release_job_ms": 1.0, "stage_self_job_ms": 7.0,
        "job_loop_ms": 6.0, "job_finish_ms": 3.0,
        "driver_outside_job_ms": 9.0, "exec_self_job_ms": 19.0,
        "egest_rows_job_ms": 8.0,
        "device_wait_job_ms": 1.5 + 19.0 + 0.2}.items()}


def test_siblings_that_abut_are_siblings_whatever_the_rounding():
    # each start and each duration is rounded on its own, so a span can
    # seem to end a microsecond after the next one starts
    t0 = 1_700_000_000.1234564
    spans = [span("job.begin", 0.0004, 1.0006, t0=t0),
             span("job", 1.0006, 3.0, t0=t0),
             span("stage.run", 1.0006, 1.4996, t0=t0),
             span("stage.run", 2.5002, 1.5004, t0=t0),
             span("job.finish", 4.0006, 0.5, t0=t0)]
    order, parent, pieces = selftime.lay(spans, t0, 0.005)
    assert [s["name"] for s in order] == [
        "job.begin", "job", "stage.run", "stage.run", "job.finish"]
    assert parent == [None, None, 1, 1, None]
    assert all(a < b for a, b, _ in pieces)
    assert all(x[1] == y[0] for x, y in zip(pieces, pieces[1:]))
    assert pieces[0][0] == 0.0 and pieces[-1][1] == 0.005
    own = selftime.self_s(job(0, spans, 5.0, t0=t0))
    assert own["job"] == pytest.approx(0.0, abs=3e-6)
    assert own["stage.run"] == pytest.approx(0.003, abs=3e-6)


def test_a_span_the_wall_clips_counts_for_what_lies_inside():
    spans = [span("preflight", -4, 6, mode="warn"),     # 2 ms inside
             span("job", 2, 90),
             span("stage.run", 3, 80, tasks=1, shuffle=False),
             span("job.finish", 92, 20),                # 8 ms inside
             span("stage.run", 150, 5, tasks=1, shuffle=False)]
    own = selftime.self_s(job(0, spans))
    assert {k: v * 1e3 for k, v in own.items()} == pytest.approx({
        "outside": 0, "preflight": 2, "job": 10, "stage.run": 80,
        "job.finish": 8}, abs=1e-3)
    assert selftime.whole_s(job(0, spans), "job") * 1e3 \
        == pytest.approx(90, abs=1e-3)


def test_the_host_line_adds_up_to_the_wall(capsys):
    cols = selftime.columns(job(0, JOB))
    assert list(cols)[:13] == list(selftime.COLUMNS)
    assert {k: v * 1e3 for k, v in cols.items()} == pytest.approx({
        "outside": 9, "preflight": 3, "begin": 3, "loop": 6,
        "stage self": 7, "plan": 6, "exec self": 19, "launch": 8,
        "eager": 3, "egest rows": 8, "read copy": 25 - 20.7,
        "device wait": 20.7, "finish": 3}, abs=1e-3)
    assert sum(cols.values()) == pytest.approx(0.100, abs=1e-12)
    # a span outside the fixed terms brings its own, and the sum holds
    more = JOB + [span("join", 48, 1, rows_out=3),
                  span("launch", 48.2, 0.5, program="join_count")]
    cols = selftime.columns(job(0, more))
    assert cols["join"] * 1e3 == pytest.approx(0.5, abs=1e-3)
    assert sum(cols.values()) == pytest.approx(0.100, abs=1e-12)
    # the wall minus stage.exec is the driver's seven parts, plan, the
    # two children of stage.run, and what else nests in a stage.run:
    # here the accounting's read
    more = JOB + [span("adapt.path", 15.2, 0.5, step="choose"),
                  span("result.rows", 87.6, 0.3, tasks=1, rows=8)]
    cols = selftime.columns(job(0, more))
    assert selftime.stray_s(job(0, more)) * 1e3 \
        == pytest.approx(1.0, abs=1e-3)
    driver = sum(cols[c] for c in (
        "outside", "preflight", "begin", "loop", "stage self", "plan",
        "finish", "adapt.path", "result.rows"))
    assert (0.100 - selftime.whole_s(job(0, more), "stage.exec") - driver
            ) * 1e3 == pytest.approx(1.0, abs=1e-3)
    assert cols["stage self"] * 1e3 == pytest.approx(7 - 0.8, abs=1e-3)
    slow = job(1, [span("job", 0, 300),
                   span("stage.run", 1, 298, tasks=1, shuffle=False)], 300)
    read("driver_outside_job_ms", observation(
        [job(0, JOB), slow, job(2, JOB)]))
    line = capsys.readouterr().out
    assert line.startswith("[host] median job 2 wall 100.000 ms = "
                           "outside 9.000 + preflight 3.000 + begin 3.000")
    assert "; slowest job 1 wall 300.000 ms = outside 0.000 + " in line
    assert "stage self 298.000" in line
    assert line.rstrip().endswith(
        "reads under stage.run outside stage.exec: median 1.000, at most "
        "1.000 ms a job over 3 jobs")


def test_a_wait_longer_than_its_read_is_clipped_in_the_line_only():
    spans = [span("job", 0, 10),
             span("stage.run", 1, 8, tasks=1, shuffle=False),
             span("readback", 2, 1, site="t", bytes=1, wait_s=0.002)]
    cols = selftime.columns(job(0, spans, 10))
    assert cols["device wait"] * 1e3 == pytest.approx(1.0, abs=1e-3)
    assert cols["read copy"] == 0.0
    assert selftime.wait_s(job(0, spans, 10)) == 0.002


def test_a_program_without_the_driver_spans_reports_none_of_them(capsys):
    old = [span("stage.exec", 0, 100, source="hbm"),
           span("plan", 1, 2, ok=True),
           span("readback", 5, 5, site="egest.counts", bytes=8),
           span("job", 0, 100)]
    obs = observation([job(0, old)], [job(9, old)],
                      {"jobs": [{"index": 9, "start_ns": MARK,
                                 "end_ns": MARK + 100 * MS}],
                       "gaps_ns": [(MARK, MARK + 10 * MS)]})
    assert [read(n, obs) for n in SPAN_READERS + IDLE_READERS] \
        == [None] * 13
    assert capsys.readouterr().out == ""
    assert read("idle_unattributed_job_ms", obs) == pytest.approx(
        3.0, abs=1e-3)


def test_idle_readers_need_a_device_trace():
    obs = observation([job(0, JOB)], [job(9, JOB)], prof=None)
    assert [read(n, obs) for n in IDLE_READERS] == [None] * 3
    assert read("job_loop_ms", obs) == pytest.approx(6.0, abs=1e-3)


def test_idle_is_split_by_who_owned_the_host():
    # idle 0-9 (chain building, preflight 2-5, job.begin 5-8, job from
    # 8), 15-17 (plan 11-15, then stage.run's own, stage.exec from 16,
    # the guard from 17), 28-47 (narrow's launch ended at 28), 82-100
    gaps = [(0, 9), (15, 17), (28, 47), (82, 100)]
    # (hostspans charges a gap to a read on whatever thread)
    spans = [s for s in JOB if s["tid"] == 1]
    obs = observation([job(0, spans)], [job(9, spans)],
                      {"jobs": [{"index": 9, "start_ns": MARK,
                                 "end_ns": MARK + 100 * MS}],
                       "gaps_ns": [(MARK + a * MS, MARK + b * MS)
                                   for a, b in gaps]})
    (parts,) = selftime.idle_parts(obs)
    assert {k: v / MS for k, v in parts.items()} == pytest.approx({
        # 2-9; 15-16; 38-43, 45-46; 86-86.5, 87.5-93
        "driver": 7 + 1 + 5 + 1 + 0.5 + 5.5,
        # 16-17; 28-38, 46-47; 82-86
        "exec_self": 1 + 10 + 1 + 4,
        # 0-2; 93-100
        "unspanned": 2 + 7}, abs=1e-2)
    ((by_part, _),) = hostspans.idle_by_part(obs)
    assert sum(parts.values()) == pytest.approx(
        by_part[hostspans.UNATTRIBUTED], abs=1)
    assert read("idle_unspanned_job_ms", obs) == pytest.approx(9, abs=1e-2)


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """test_xplane_reduce.py's recording: two profiled jobs of
    agg.highcard.4chip on four chips."""
    packed = glob.glob(os.path.join(FIXTURES, "highcard4chip_2jobs*.gz"))
    if not packed:
        pytest.skip("no recorded trace")
    path = tmp_path_factory.mktemp("trace") / "chip.xplane.pb"
    with gzip.open(packed[0], "rb") as f:
        path.write_bytes(f.read())
    return xplane.reduce(str(path), "tpu")


# the recording's device is idle in the first 0.8% of a job, around 55%
# (between its two stages) and in the last 0.4%: spans in percent of the
# job's wall that put a piece of each part under those gaps
CHIP_JOB = [
    ("job.begin", 0.05, 0.25), ("job", 0.3, 99.6),
    ("stage.run", 0.4, 54.5), ("plan", 0.45, 0.15),
    ("stage.exec", 0.7, 54.1), ("launch", 0.72, 0.08),
    ("stage.run", 54.95, 44.75), ("plan", 55.0, 0.2),
    ("stage.exec", 55.25, 44.4), ("readback", 60.0, 39.62),
    ("job.finish", 99.9, 0.05)]


def test_the_three_idle_parts_add_up_on_the_chips_trace(chip_trace):
    """The recorded gaps of the device under hand-built spans: job by job
    the three parts are hostspans' unattributed part, to the
    nanosecond."""
    profiled = []
    for i, mark in enumerate(chip_trace["jobs"]):
        wall_ms = (mark["end_ns"] - mark["start_ns"]) / 1e6
        spans = [span(name, a * wall_ms / 100, d * wall_ms / 100, t0=T0 + i)
                 for name, a, d in CHIP_JOB]
        profiled.append(job(mark["index"], spans, wall_ms, t0=T0 + i))
    obs = observation(profiled, profiled, chip_trace)
    ours = selftime.idle_parts(obs)
    theirs = hostspans.idle_by_part(obs)
    assert len(ours) == len(theirs) == 2
    for parts, (by_part, _) in zip(ours, theirs):
        assert all(v > 0 for v in parts.values()), parts
        assert sum(parts.values()) == pytest.approx(
            by_part[hostspans.UNATTRIBUTED], abs=1)
    total = sum(read(n, obs) for n in IDLE_READERS)
    # medians of two jobs are means, and means add
    assert total == pytest.approx(read("idle_unattributed_job_ms", obs),
                                  abs=1e-6)


@pytest.mark.parametrize("name", SPAN_READERS + IDLE_READERS)
def test_the_manifest_names_each_reader(bench_manifest, name):
    (entry,) = [m for m in bench_manifest["per_layer"]
                if m["name"] == name]
    assert "workloads" not in entry
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
    idle = name in IDLE_READERS
    assert entry["moves"] == ("throughput" if idle else "job_s")
    assert entry["source"] == ("device_trace" if idle else "program_span")
    assert entry["layer"] == (
        "device" if idle else "executor host side"
        if name in ("store_release_job_ms", "exec_self_job_ms",
                    "egest_rows_job_ms", "device_wait_job_ms")
        else "driver")
