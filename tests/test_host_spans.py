"""What the host does inside stage.exec (ISSUE 24): the `plan`, `launch`,
`eager`, `readback`, `egest`, `ingest` and `hbm.spill` spans of the
array path, the always-on counters beside them (host_reads,
program_launches), and the TraceAnnotation every open span holds.  Counts are exact on the CPU: they are what ROADMAP S4 asks to be
counted per job.  Device jobs run on a 2-device sliced mesh ("tpu:2")
except where the test says otherwise."""

import ast
import glob
import os

import numpy as np
import pytest

from dpark_tpu import Columns, DparkContext, conf, trace
from dpark_tpu.analysis import concurrency
from dpark_tpu.backend.tpu import layout

PROGRAMS = {"narrow", "reduce", "exchange", "minmax", "join_count",
            "join_expand", "wave_sort", "wave_prereduce", "distinct"}


@pytest.fixture(autouse=True)
def _plane_off():
    trace.configure("off")
    yield
    trace.configure("off")


@pytest.fixture()
def tctx2():
    c = DparkContext("tpu:2")
    c.start()
    yield c
    c.stop()


def _add(a, b):
    return a + b


def _same(kv):
    return kv


def _mod8(kv):
    return (kv[0] % 8, kv[1])


def _product_mod8(kv):
    key, (left, right) = kv
    return (key % 8, left * right)


def _cached(c, keys, values, ndev):
    """A table resident in HBM, as the benchmark's cells load theirs."""
    rdd = c.parallelize(Columns(keys, values), ndev).map(_same).cache()
    assert rdd.count() == len(keys)
    return rdd


def _agg_job(c, hold=None):
    """`hold`, a list, keeps every job's chain: a store lives as long
    as the RDD that shuffled it, and only a held store can spill."""
    ndev = c.scheduler.executor.ndev
    keys = np.arange(4096, dtype=np.int64) % 37
    table = _cached(c, keys, np.ones(4096, np.int64), ndev)

    def job():
        rdd = table.map(_mod8).reduceByKey(_add, ndev)
        if hold is not None:
            hold.append(rdd)
        return sorted(rdd.collect())
    return job


def _join_job(c):
    ndev = c.scheduler.executor.ndev
    fact = _cached(c, np.arange(2048, dtype=np.int64) % 64,
                   np.full(2048, 3, np.int64), ndev)
    dim = _cached(c, np.arange(32, dtype=np.int64),
                  np.arange(32, dtype=np.int64) + 1, ndev)
    return lambda: sorted(fact.join(dim, ndev).map(_product_mod8)
                          .reduceByKey(_add, ndev).collect())


JOBS = {"agg": _agg_job, "join": _join_job}


def _count_job(c):
    ndev = c.scheduler.executor.ndev
    keys = np.arange(4096, dtype=np.int64) % 37
    table = _cached(c, keys, np.ones(4096, np.int64), ndev)
    return lambda: table.map(_mod8).reduceByKey(_add, ndev).count()


def _sort_job(c):
    ndev = c.scheduler.executor.ndev
    keys = (np.arange(4096, dtype=np.int64) * 7919) % 4096
    table = _cached(c, keys, np.ones(4096, np.int64), ndev)
    return lambda: table.sortByKey(numSplits=ndev).collect()


# a collect, a count, a join and a sortByKey: every way a cell of the
# benchmark enters and leaves a job
DRIVER_JOBS = dict(JOBS, count=_count_job, sort=_sort_job)


def _run_traced(c, job):
    """One warm run untraced, then one with the ring on: (answer, the
    job's record, its ring records)."""
    job()
    trace.configure("ring")
    answer = job()
    record = c.scheduler.history[-1]
    spans = [r for r in trace.snapshot() if r.get("job") == record["id"]]
    return answer, record, spans


def _inside(inner, outer, slack=0.0):
    return (outer["ts"] - slack <= inner["ts"]
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + slack)


# ---------------------------------------------------------------------------
# (a) the spans a job leaves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(JOBS))
def test_job_leaves_host_spans_with_job_and_stage(tctx2, kind):
    _, record, spans = _run_traced(tctx2, JOBS[kind](tctx2))
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    (job_span,) = by_name["job"]
    stage_ids = {s["stage"] for s in by_name["stage"]}
    for name in ("plan", "launch", "readback", "egest"):
        assert by_name.get(name), "no %r span in %s" % (
            name, sorted(by_name))
        for s in by_name[name]:
            assert s["cat"] == "exec"
            assert s["job"] == record["id"]
            assert s["stage"] in stage_ids
            assert _inside(s, job_span, slack=2e-6), (s, job_span)
    assert {s["args"]["program"] for s in by_name["launch"]} <= PROGRAMS
    assert all(s["args"]["ok"] is True for s in by_name["plan"])
    assert len(by_name["plan"]) == record["stages"]
    assert all(s["args"]["bytes"] > 0 for s in by_name["readback"])
    (egest,) = by_name["egest"]
    assert egest["args"]["rows"] == 8 and egest["args"]["bytes"] > 0
    # the benchmark's dispatches_per_job still finds its event
    assert by_name["dispatch"][0]["args"]["program"] == "narrow"


@pytest.mark.parametrize("kind", sorted(JOBS))
def test_host_spans_nest_as_documented(tctx2, kind):
    _, _, spans = _run_traced(tctx2, JOBS[kind](tctx2))
    execs = [s for s in spans if s["name"] == "stage.exec"]
    (egest,) = [s for s in spans if s["name"] == "egest"]
    for s in spans:
        if s["name"] in ("launch", "eager", "egest", "ingest"):
            assert any(_inside(s, e, 2e-6) for e in execs), s
        if s["name"] == "plan":
            # planning is the driver's, before the stage runs
            assert not any(_inside(s, e) for e in execs), s
        if s["name"] == "readback":
            site = s["args"]["site"]
            assert site, "a readback with no site: %r" % s
            assert site.startswith("egest.") == _inside(s, egest, 2e-6)
    # an egest is its readbacks plus row building, nothing else blocks
    inner = sum(s["dur"] for s in spans if s["name"] == "readback"
                and _inside(s, egest, 2e-6))
    assert 0 < inner <= egest["dur"] + 1e-5


def test_cached_key_guard_is_an_eager_span_and_no_launch(tctx2):
    """The guard's eager jnp operations are several XLA dispatches and
    no compiled stage program: a span of their own, outside the count
    of launches; its verdict is read after it, not inside."""
    ex = tctx2.scheduler.executor
    job = _agg_job(tctx2)
    job()
    trace.configure("ring")
    launches = ex.program_launches
    job()
    spans = trace.snapshot()
    (guard,) = [s for s in spans if s["name"] == "eager"]
    assert guard["cat"] == "exec" and guard["args"] == {"site": "keycheck"}
    assert guard["job"] == tctx2.scheduler.history[-1]["id"]
    (read,) = [s for s in spans if s["name"] == "readback"
               and s["args"]["site"] == "keycheck"]
    assert read["ts"] >= guard["ts"] + guard["dur"] - 2e-6
    assert ex.program_launches - launches == sum(
        1 for s in spans if s["name"] == "launch")


def test_ingest_span_around_a_host_numpy_source(tctx2):
    keys = np.arange(1000, dtype=np.int64)
    trace.configure("ring")
    got = tctx2.parallelize(Columns(keys % 5, keys), 2) \
        .reduceByKey(_add, 2).collect()
    assert len(got) == 5
    (ingest,) = [s for s in trace.snapshot() if s["name"] == "ingest"]
    assert ingest["args"] == {"rows": 1000}
    assert ingest["job"] == tctx2.scheduler.history[-1]["id"]


def test_one_chip_identity_exchange_metric_read_is_a_readback():
    """On one device nothing crosses a wire and the row metric's read is
    deferred to the scheduler's per-stage accounting, after stage.exec:
    a blocking read nobody could see before."""
    c = DparkContext("tpu:1")
    c.start()
    try:
        _, _, spans = _run_traced(c, _agg_job(c))
    finally:
        c.stop()
    sites = [s["args"]["site"] for s in spans if s["name"] == "readback"]
    assert "exchange.real_rows" in sites
    assert "exchange.counts" not in sites


# ---------------------------------------------------------------------------
# (a') the driver's way in and out of a job (ISSUE 35)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(DRIVER_JOBS))
def test_driver_spans_cover_the_job_from_preflight_to_finish(tctx2, kind):
    answer, record, spans = _run_traced(tctx2, DRIVER_JOBS[kind](tctx2))
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    (job_span,) = by_name["job"]
    (pre,) = by_name["preflight"]
    (begin,) = by_name["job.begin"]
    (finish,) = by_name["job.finish"]
    runs = by_name["stage.run"]
    driver = [job_span, pre, begin, finish] + runs
    assert all(s["cat"] == "sched" and s["job"] == record["id"]
               for s in driver)
    assert len({s["tid"] for s in driver + by_name["plan"]
                + by_name["stage.exec"]}) == 1
    assert pre["args"]["mode"] == "warn"
    assert begin["args"]["stages"] >= record["stages"] >= 1
    # preflight, then the way in, then the job, then the way out
    assert pre["ts"] + pre["dur"] <= begin["ts"] + 2e-6
    assert 0 <= job_span["ts"] - (begin["ts"] + begin["dur"]) < 1e-3
    assert abs(finish["ts"] - (job_span["ts"] + job_span["dur"])) < 1e-3
    # one stage.run a stage, inside the job; the stage's plan and
    # stage.exec inside it, in that order
    assert len(runs) == record["stages"]
    assert sum(s["args"]["shuffle"] for s in runs) == len(runs) - 1
    for run in runs:
        assert _inside(run, job_span, 2e-6)
        assert run["args"]["tasks"] == 2
        (plan,) = [s for s in by_name["plan"] if _inside(s, run, 2e-6)]
        (ex,) = [s for s in by_name["stage.exec"]
                 if _inside(s, run, 2e-6)]
        assert plan["stage"] == ex["stage"] == run["stage"]
        assert plan["ts"] + plan["dur"] <= ex["ts"] + 2e-6
        # the cost model's two steps, either side of the stage's run
        choose, observe = [s for s in by_name["adapt.path"]
                           if _inside(s, run, 2e-6)]
        assert (choose["args"], observe["args"]) \
            == ({"step": "choose"}, {"step": "observe"})
        assert choose["ts"] + choose["dur"] <= ex["ts"] + 2e-6
        assert ex["ts"] + ex["dur"] <= observe["ts"] + 2e-6
    # the rows of a collect go through the action's function once, in
    # the result stage's stage.run; a count brings no rows
    rows = by_name.get("result.rows", [])
    assert len(rows) == (0 if kind == "count" else 1)
    for s in rows:
        assert s["cat"] == "sched" and s["job"] == record["id"]
        assert _inside(s, runs[-1], 2e-6)
        assert s["args"] == {"tasks": 2, "rows": len(answer)}
    # the job span is what the record rounds to the millisecond
    assert abs(job_span["dur"] - record["seconds"]) <= 5e-4 + 1e-9
    # a read says how long it waited for the device
    assert by_name["readback"]
    for s in by_name["readback"]:
        assert 0 <= s["args"]["wait_s"] <= s["dur"], s


def test_the_job_span_keeps_the_microseconds(tctx2):
    job = _agg_job(tctx2)
    job()
    trace.configure("ring")
    for _ in range(3):
        job()
    durs = [s["dur"] for s in trace.snapshot() if s["name"] == "job"]
    assert len(durs) == 3
    # one whole millisecond in a thousand is chance; three are rounding
    assert any(round(d * 1e3, 3) != round(d * 1e3) for d in durs), durs


def test_a_dropped_chain_is_released_by_the_next_job(tctx2):
    """A job's chain is let go after its action, as a cell's is: the
    next job's way in frees its store, under one `store.release`."""
    job = _agg_job(tctx2)
    ex = tctx2.scheduler.executor
    trace.configure("ring")
    job()
    first = tctx2.scheduler.history[-1]["id"]
    assert not [s for s in trace.snapshot()
                if s["name"] == "store.release"]
    released = ex.stores_released
    job()
    second = tctx2.scheduler.history[-1]["id"]
    snap = trace.snapshot()
    (rel,) = [s for s in snap if s["name"] == "store.release"]
    assert rel["cat"] == "exec" and rel["job"] == second != first
    assert rel["args"]["stores"] == ex.stores_released - released >= 1
    assert rel["args"]["bytes"] > 0
    (begin,) = [s for s in snap if s["name"] == "job.begin"
                and s["job"] == second]
    assert _inside(rel, begin, 2e-6)
    # the events the HBM accounts fold say the same
    dropped = [s for s in snap if s["name"] == "hbm.release"
               and s["args"]["reason"] == "unreachable"]
    assert sum(s["args"]["bytes"] for s in dropped) == rel["args"]["bytes"]


@pytest.mark.parametrize("kind", sorted(DRIVER_JOBS))
def test_plane_off_waits_for_nothing_and_answers_the_same(
        tctx2, kind, monkeypatch):
    import jax
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(1) or real(x))
    job = DRIVER_JOBS[kind](tctx2)
    untraced = job()
    assert trace.snapshot() == [] and not waits
    trace.configure("ring")
    traced = job()
    reads = [s for s in trace.snapshot() if s["name"] == "readback"]
    assert reads and len(waits) == len(reads)
    trace.configure("off")
    assert job() == traced == untraced
    assert trace.snapshot() == [] and len(waits) == len(reads)


# ---------------------------------------------------------------------------
# (b) the spill of held stores (the store of a dropped chain is released)
# ---------------------------------------------------------------------------

def test_spill_spans_match_the_release_events(tctx2):
    """One `hbm.spill` span a spilled store, beside the `hbm.release`
    event (reason `spill`) the HBM accounts already fold: same stores,
    same bytes."""
    held = []
    job = _agg_job(tctx2, hold=held)
    expected = job()
    trace.configure("ring")
    old = conf.SHUFFLE_HBM_BUDGET
    conf.SHUFFLE_HBM_BUDGET = 1
    try:
        assert job() == expected
        assert job() == expected
    finally:
        conf.SHUFFLE_HBM_BUDGET = old
    snap = trace.snapshot()
    spills = [s for s in snap if s["name"] == "hbm.spill"]
    assert spills, "a 1-byte budget spilled nothing"
    released = [s for s in snap if s["name"] == "hbm.release"
                and s["args"]["reason"] == "spill"]
    assert sorted((s["args"]["sid"], s["args"]["bytes"]) for s in spills) \
        == sorted((s["args"]["sid"], s["args"]["bytes"]) for s in released)
    assert all(s["args"]["bytes"] > 0 for s in spills)
    for s, r in zip(spills, released):
        assert _inside(r, s, 2e-6)
    # the spill's own reads go through the host bridge, inside its span,
    # and carry the job that paid for the spill
    bridge = [s for s in snap if s["name"] == "readback"
              and s["args"]["site"] == "bridge.export"]
    assert bridge
    for s in bridge:
        (outer,) = [o for o in spills if _inside(s, o, 2e-6)]
        assert s["job"] == outer["job"]


# ---------------------------------------------------------------------------
# (c), (d) counters: exact, repeatable, the same traced or not
# ---------------------------------------------------------------------------

def _deltas(ex, job):
    reads, launches = ex.host_reads, ex.program_launches
    answer = job()
    return answer, ex.host_reads - reads, ex.program_launches - launches


@pytest.mark.parametrize("kind", sorted(JOBS))
def test_counters_exact_repeatable_and_blind_to_the_plane(tctx2, kind):
    ex = tctx2.scheduler.executor
    job = JOBS[kind](tctx2)
    job()                                           # compile
    off = [_deltas(ex, job) for _ in range(3)]
    assert trace.snapshot() == []
    trace.configure("ring")
    seen = len(trace.snapshot())
    on = []
    for _ in range(3):
        on.append(_deltas(ex, job))
        new = trace.snapshot()[seen:]
        seen += len(new)
        assert on[-1][1] == sum(1 for s in new if s["name"] == "readback")
        assert on[-1][2] == sum(1 for s in new if s["name"] == "launch")
    assert len({(reads, launches) for _, reads, launches in off + on}) == 1
    assert all(answer == off[0][0] for answer, _, _ in off + on)
    assert off[0][1] > 0 and off[0][2] >= 2


def test_plane_off_leaves_no_records_and_the_same_answer(tctx2):
    job = _join_job(tctx2)
    untraced = job()
    assert trace.snapshot() == []
    trace.configure("ring")
    traced = job()
    assert {s["name"] for s in trace.snapshot()} >= {
        "plan", "launch", "readback", "egest"}
    trace.configure("off")
    assert job() == traced == untraced
    assert trace.snapshot() == []


def test_host_read_of_a_list_is_one_read():
    import jax.numpy as jnp
    before = layout.HOST_READS
    trace.configure("ring")
    got = layout.host_read([jnp.arange(3), jnp.ones((2, 2))], site="t")
    assert layout.HOST_READS == before + 1
    assert isinstance(got, list) and got[0].tolist() == [0, 1, 2]
    assert isinstance(got[1], np.ndarray) and got[1].shape == (2, 2)
    (span,) = [s for s in trace.snapshot() if s["name"] == "readback"]
    assert span["args"]["site"] == "t"
    assert span["args"]["bytes"] == (jnp.arange(3).nbytes
                                     + jnp.ones((2, 2)).nbytes)


# ---------------------------------------------------------------------------
# (e) the exchange's reads on the full mesh
# ---------------------------------------------------------------------------

@pytest.mark.mesh
def test_full_mesh_job_counts_the_exchange_readbacks():
    c = DparkContext("tpu")
    c.start()
    try:
        ex = c.scheduler.executor
        assert ex.ndev == 8
        job = _agg_job(c)
        expected = job()
        trace.configure("ring")
        answer, reads, launches = _deltas(ex, job)
    finally:
        c.stop()
    assert answer == expected
    snap = trace.snapshot()
    sites = [s["args"]["site"] for s in snap if s["name"] == "readback"]
    assert sites.count("exchange.counts") >= 1
    # one min/max probe read per int64 leaf of the exchanged rows
    assert sites.count("narrow.minmax") == 2
    programs = [s["args"]["program"] for s in snap if s["name"] == "launch"]
    assert sorted(programs) == ["exchange", "minmax", "narrow", "reduce"]
    assert (reads, launches) == (len(sites), len(programs))


# ---------------------------------------------------------------------------
# the profiler's clock, and what a site costs with the plane off
# ---------------------------------------------------------------------------

def test_open_span_stands_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    trace.configure("ring")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("launch", "exec", program="narrow", shape=(2, 3)):
            trace.event("dispatch", "exec", program="narrow")
            trace.emit("phase.export", "phase", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events]
    (mark,) = [e for e in events if e.name == "launch"]
    stats = dict(mark.stats)
    assert stats["program"] == "narrow" and stats["shape"] == "(2, 3)"
    # instant events and retroactive spans are ring-only
    assert not [e for e in events
                if e.name in ("dispatch", "phase.export")]
    (span,) = [s for s in trace.snapshot() if s["name"] == "launch"]
    assert abs(mark.duration_ns / 1e9 - span["dur"]) < 1e-3


def test_span_in_a_process_with_jax_half_imported(monkeypatch):
    import sys
    import types
    monkeypatch.setitem(sys.modules, "jax", types.ModuleType("jax"))
    trace.configure("ring")
    with trace.span("readback", "exec", site="t") as sp:
        assert sp.mark is None
    (span,) = [s for s in trace.snapshot() if s["name"] == "readback"]
    assert span["args"] == {"site": "t"}


def test_sites_read_no_clock_and_build_no_span_when_off(tctx2, monkeypatch):
    import jax.numpy as jnp
    ex = tctx2.scheduler.executor

    def refuse(*a, **kw):
        raise AssertionError("the plane is off")

    monkeypatch.setattr(trace, "span", refuse)
    monkeypatch.setattr(trace, "_annotation", refuse)
    reads, launches = ex.host_reads, ex.program_launches
    assert layout.host_read(jnp.arange(4), site="t").tolist() == [0, 1, 2, 3]
    assert ex._launch("narrow", lambda a, b: a + b, 2, 3) == 5
    batch = layout.ingest(ex.mesh, [[1, 2], [3]],
                          *layout.record_spec(1))
    assert layout.egest(batch) == [[1, 2], [3]]
    assert ex.host_reads - reads == 3 and ex.program_launches - launches == 1


SEAMS = [s for s in concurrency.PLANE_SEAMS if s[2] == "trace._PLANE"]


def test_every_host_span_site_is_a_checked_seam():
    assert {(f, q) for f, q, _ in SEAMS} == {
        ("backend/tpu/__init__.py", "TPUScheduler._analyze"),
        ("backend/tpu/executor.py", "JAXExecutor._launch"),
        ("backend/tpu/executor.py", "JAXExecutor._source_outs"),
        ("backend/tpu/executor.py", "JAXExecutor._spill_shuffle_to_disk"),
        ("backend/tpu/executor.py", "JAXExecutor._check_cached_keys"),
        ("backend/tpu/layout.py", "host_read"),
        ("backend/tpu/layout.py", "egest"),
        ("context.py", "DparkContext.runJob"),
        ("schedule.py", "DAGScheduler._begin_job"),
        ("schedule.py", "DAGScheduler._run_tasks"),
        ("schedule.py", "DAGScheduler._finish_job"),
        ("backend/tpu/__init__.py", "TPUScheduler._drain_unreachable"),
        ("backend/tpu/__init__.py", "TPUScheduler._adapt_span"),
        ("backend/tpu/__init__.py", "TPUScheduler._run_array_stage"),
        ("query/planner.py", "plan_query"),
        ("query/planner.py", "PlannedQuery._run")}


@pytest.mark.parametrize("relfile,qualname,dotted", SEAMS)
def test_site_guards_on_is_none_first(relfile, qualname, dotted):
    pkg = os.path.dirname(os.path.abspath(trace.__file__))
    with open(os.path.join(pkg, relfile)) as f:
        source = f.read()
    assert concurrency.check_plane_seam(
        ast.parse(source), qualname, dotted) is None
    # the same site with its guard turned into real work on the off path
    # is what the rule exists to refuse
    broken = source.replace("plane = trace._PLANE",
                            "plane = trace._PLANE or trace.configure()")
    assert concurrency.check_plane_seam(
        ast.parse(broken), qualname, dotted) is not None
