"""perf/lib/hostspans.py and the eleven readers over it, on fabricated
observations: nested spans, a span that straddles the job's annotation, a
job with no spans, a run with no device trace, and a program from before
the spans."""

import pytest

import benchhelp  # noqa: F401  (puts the checkout on sys.path)
from perf.lib import hostspans, manifest

READERS = ("readbacks_per_job", "launches_per_job", "readback_job_ms",
           "egest_job_ms", "launch_job_ms", "plan_job_ms",
           "spill_span_job_ms", "idle_spill_job_ms", "idle_readback_job_ms",
           "idle_launch_job_ms", "idle_unattributed_job_ms")
IDLE = [r for r in READERS if r.startswith("idle_")]
T0 = 1000.0                 # a job's t0_wall, seconds on the ring's clock
MARK = 5_000_000_000        # its annotation's start on the trace's clock
MS = 1_000_000


def read(name, obs):
    return manifest.load_module(
        manifest.reader_path("per_layer", name)).read(obs)


def span(name, start_ms, dur_ms, **args):
    """A ring record `start_ms` after the job's t0_wall."""
    return {"name": name, "cat": "exec", "ts": T0 + start_ms / 1e3,
            "dur": dur_ms / 1e3, "job": 7, "stage": 1, "args": args}


def job(index, spans, wall_ms=100.0, spill_s=0.0):
    return {"index": index, "wall_s": wall_ms / 1e3, "t0_wall": T0,
            "spill_s": spill_s, "spans": spans}


def profile(marks, gaps_ms):
    """marks: {index: length in ms}, each starting at MARK; gaps in ms
    after MARK."""
    return {"jobs": [{"index": i, "start_ns": MARK,
                      "end_ns": MARK + int(n * MS)}
                     for i, n in marks.items()],
            "gaps_ns": [(MARK + int(a * MS), MARK + int(b * MS))
                        for a, b in gaps_ms]}


def observation(jobs, profiled=(), prof=None, counters=None):
    return {"jobs": list(jobs), "profiled_jobs": list(profiled),
            "profile": prof, "counters": dict(counters or {})}


# one job of 100 ms: a plan holding a readback, a launch, a control
# readback, an egest holding two readbacks, a spill holding one, and
# stage.exec around all of it
NESTED = [
    span("stage.exec", 0, 100, source="hbm"),
    span("plan", 1, 3, ok=True),
    span("readback", 2, 1, site="plan.rows", bytes=8),
    span("launch", 5, 5, program="reduce"),
    span("readback", 12, 8, site="exchange.counts", bytes=16),
    span("egest", 30, 20, rows=8, bytes=64),
    span("readback", 31, 4, site="egest.counts", bytes=4),
    span("readback", 36, 6, site="egest.col", bytes=32),
    span("hbm.spill", 60, 30, sid=3, bytes=4096),
    span("readback", 61, 10, site="bridge.export", bytes=4096),
]


def test_span_metrics_sum_own_spans_and_nested_readbacks_go_to_the_outer():
    obs = observation([job(0, NESTED)])
    assert read("readback_job_ms", obs) == pytest.approx(8.0)
    assert read("egest_job_ms", obs) == pytest.approx(20.0)
    assert read("launch_job_ms", obs) == pytest.approx(5.0)
    assert read("plan_job_ms", obs) == pytest.approx(3.0)
    assert read("spill_span_job_ms", obs) == pytest.approx(30.0)


def test_span_metrics_take_the_median_and_the_spill_the_mean():
    quiet = [span("plan", 1, 1, ok=True),
             span("launch", 5, 2, program="narrow")]
    obs = observation([job(0, NESTED), job(1, quiet), job(2, quiet)])
    assert read("launch_job_ms", obs) == pytest.approx(2.0)
    assert read("egest_job_ms", obs) == 0.0
    assert read("spill_span_job_ms", obs) == pytest.approx(10.0)


def test_spill_span_agrees_with_the_runner_clock_around_the_same_routine():
    jobs = [job(i, [span("plan", 0, 1, ok=True),
                    span("hbm.spill", 10, ms, sid=i, bytes=1)],
                spill_s=ms / 1e3) for i, ms in enumerate((700, 900, 800))]
    obs = observation(jobs)
    assert read("spill_span_job_ms", obs) == pytest.approx(
        read("spill_job_ms", obs))
    assert read("spill_span_job_ms", obs) == pytest.approx(800.0)


def test_counts_are_the_window_delta_per_job():
    obs = observation([job(0, NESTED), job(1, NESTED)],
                      counters={"host_reads": 10, "program_launches": 4})
    assert read("readbacks_per_job", obs) == 5.0
    assert read("launches_per_job", obs) == 2.0


def test_zero_is_reported_as_zero_once_the_program_has_the_spans():
    obs = observation([job(0, [span("plan", 1, 2, ok=True)])],
                      counters={"host_reads": 0, "program_launches": 0})
    assert read("readbacks_per_job", obs) == 0.0
    assert read("launches_per_job", obs) == 0.0
    for name in ("readback_job_ms", "egest_job_ms", "launch_job_ms",
                 "spill_span_job_ms"):
        assert read(name, obs) == 0.0


def test_a_program_without_the_spans_reports_none_of_the_eleven():
    old = [span("stage.exec", 0, 100, source="hbm"),
           {"name": "dispatch", "ts": T0, "dur": 0.0, "job": 7,
            "args": {"program": "narrow"}}]
    obs = observation([job(0, old)], [job(9, old)],
                      profile({9: 100}, [(0, 10)]),
                      counters={"exchange_wire_bytes": 0})
    assert [read(name, obs) for name in READERS] == [None] * 11


def test_idle_metrics_need_a_device_trace_and_span_metrics_do_not():
    obs = observation([job(0, NESTED)], [job(9, NESTED)], prof=None)
    assert [read(name, obs) for name in IDLE] == [None] * 4
    assert read("launch_job_ms", obs) == pytest.approx(5.0)


def test_idle_is_charged_to_the_outermost_span_and_adds_up(capsys):
    # idle: 0-11 (plan 1-4, launch 5-10, else nothing), 12-20 (the
    # control readback), 30-52 (egest 30-50, then nothing), 58-95 (spill
    # 60-90)
    gaps = [(0, 11), (12, 20), (30, 52), (58, 95)]
    obs = observation([job(0, NESTED)], [job(9, NESTED)],
                      profile({9: 100}, gaps))
    assert read("idle_launch_job_ms", obs) == pytest.approx(3.0 + 5.0)
    assert read("idle_readback_job_ms", obs) == pytest.approx(8.0 + 20.0)
    assert read("idle_spill_job_ms", obs) == pytest.approx(30.0)
    # 0-1, 4-5, 10-11, 50-52, 58-60, 90-95
    assert read("idle_unattributed_job_ms", obs) == pytest.approx(12.0)
    in_job = sum(b - a for a, b in gaps)
    assert sum(read(name, obs) for name in IDLE) == pytest.approx(in_job)
    log = capsys.readouterr().out
    assert "after hbm.spill, before job end: 5.000" in log
    assert "after launch reduce, before readback exchange.counts" in log
    assert "after job start, before plan: 1.000" in log


def test_idle_under_plan_ingest_and_eager_is_the_launch_part():
    spans = [span("plan", 0, 10, ok=True),
             span("ingest", 10, 4, rows=9),
             span("eager", 14, 6, site="keycheck"),
             span("launch", 20, 10, program="narrow")]
    obs = observation([job(0, spans)], [job(9, spans)],
                      profile({9: 50}, [(0, 25), (40, 50)]))
    ((parts, loose),) = hostspans.idle_by_part(obs)
    assert {p: ns / MS for p, ns in parts.items()} == pytest.approx(
        {"spill": 0, "readback": 0, "launch": 25, "unattributed": 10})
    assert loose == [(10 * MS, "launch narrow", "job end")]
    assert read("idle_launch_job_ms", obs) == pytest.approx(25.0)
    assert read("idle_unattributed_job_ms", obs) == pytest.approx(10.0)
    # an eager span is no launch of a compiled program
    assert read("launch_job_ms", obs) == pytest.approx(10.0)


def test_a_span_straddling_the_annotation_is_clipped_to_it():
    spans = [span("readback", -5, 10, site="keycheck", bytes=1),
             span("egest", 90, 30, rows=1, bytes=8)]
    obs = observation([job(0, spans)], [job(9, spans)],
                      profile({9: 100}, [(-20, 8), (80, 140)]))
    ((parts, _),) = hostspans.idle_by_part(obs)
    # in-job idle is 0-8 and 80-100: 28 ms
    assert sum(parts.values()) == 28 * MS
    assert parts["readback"] == (5 + 10) * MS
    assert parts["unattributed"] == (3 + 10) * MS


def test_a_profiled_job_with_no_spans_is_all_unattributed():
    obs = observation([job(0, NESTED)],
                      [job(8, NESTED), job(9, [])],
                      profile({8: 100, 9: 100}, [(10, 30)]))
    jobs = hostspans.idle_by_part(obs)
    assert jobs[1][0]["unattributed"] == 20 * MS
    assert jobs[1][1] == [(20 * MS, "job start", "job end")]
    assert sum(jobs[0][0].values()) == 20 * MS


def test_segments_do_not_overlap_and_keep_the_outermost():
    mark = {"start_ns": MARK, "end_ns": MARK + 100 * MS}
    segs = hostspans.segments(job(9, NESTED), mark)
    # the readbacks inside plan, egest and hbm.spill are those spans'
    assert [s[2] for s in segs] == ["plan", "launch", "readback", "egest",
                                    "hbm.spill"]
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    assert segs[1][3] == "launch reduce"
    assert segs[3][:2] == (MARK + 30 * MS, MARK + 50 * MS)


@pytest.mark.parametrize("name", READERS)
def test_the_manifest_names_each_reader(bench_manifest, name):
    (entry,) = [m for m in bench_manifest["per_layer"]
                if m["name"] == name]
    assert "workloads" not in entry and entry["better"] == "lower"
    assert entry["moves"] == ("throughput" if name in IDLE else "job_s")
    assert entry["source"] == (
        "device_trace" if name in IDLE else
        "program_counter" if name.endswith("_per_job") else "program_span")
