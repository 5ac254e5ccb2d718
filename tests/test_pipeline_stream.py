"""Overlapped wave pipeline (ISSUE 2 tentpole): the streamed-shuffle
wave loop double-buffers device ingest, donates dead buffers to the
per-wave programs, defers host readback one wave, and spills through a
background writer — results must be BIT-IDENTICAL with the pipeline
and donation on vs off, cancellation mid-stream must not leak the
pipeline threads, and the per-wave metrics must show the overlap.

Runs on a 2-device sliced mesh ("tpu:2") so the suite works on small
containers where the full 8-device collective mesh wedges (see the
`mesh` marker in conftest)."""

import threading
import time

import numpy as np
import pytest

from dpark_tpu import Columns, conf


@pytest.fixture()
def tctx2():
    from dpark_tpu import DparkContext
    c = DparkContext("tpu:2")
    c.start()
    yield c
    c.stop()


@pytest.fixture()
def tiny_waves():
    old = (conf.STREAM_CHUNK_ROWS, conf.STREAM_PIPELINE_DEPTH,
           conf.DONATE_BUFFERS, conf.SPILL_WRITER)
    conf.STREAM_CHUNK_ROWS = 500
    yield
    (conf.STREAM_CHUNK_ROWS, conf.STREAM_PIPELINE_DEPTH,
     conf.DONATE_BUFFERS, conf.SPILL_WRITER) = old


def _pipeline_modes():
    # (depth, donate, spill_writer): full pipeline vs the serial
    # pre-pipeline configuration
    return [(1, True, True), (0, False, False)]


def _set_mode(depth, donate, writer):
    conf.STREAM_PIPELINE_DEPTH = depth
    conf.DONATE_BUFFERS = donate
    conf.SPILL_WRITER = writer


def _last_pipeline(ctx):
    best = None
    for rec in getattr(ctx.scheduler, "history", []):
        for st in rec.get("stage_info", []):
            if st.get("pipeline"):
                best = st["pipeline"]
    return best


def _mkdata(n=20000):
    i = np.arange(n, dtype=np.int64)
    return (i * 2654435761) % 997, i % 11


def test_streamed_combine_parity_pipeline_on_off(tctx2, tiny_waves):
    """Monoid reduceByKey through the combine stream: identical results
    (integer data: bit-identical) with the pipeline + donation on vs
    the serial loop."""
    keys, vals = _mkdata()
    got = {}
    for depth, donate, writer in _pipeline_modes():
        _set_mode(depth, donate, writer)
        rdd = tctx2.parallelize(Columns(keys, vals), 2) \
            .reduceByKey(lambda a, b: a + b, 2)   # held: so is its store
        got[depth] = dict(rdd.collect())
        ex = tctx2.scheduler.executor
        assert any(s.get("pre_reduced")
                   for s in ex.shuffle_store.values()), "did not stream"
    assert got[1] == got[0]
    expect = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        expect[k] = expect.get(k, 0) + v
    assert got[1] == expect


def test_streamed_nocombine_parity_pipeline_on_off(tctx2, tiny_waves):
    """sortByKey through the spilled-run stream (r > mesh: the rid
    column rides the exchange): identical row ORDER and content with
    the pipeline on vs off."""
    rng = np.random.RandomState(17)
    keys = rng.randint(-10**6, 10**6, 20000).astype(np.int64)
    vals = np.arange(20000, dtype=np.int64)
    got = {}
    for depth, donate, writer in _pipeline_modes():
        _set_mode(depth, donate, writer)
        rdd = tctx2.parallelize(Columns(keys, vals), 2) \
            .sortByKey(numSplits=8)               # held: so is its store
        got[depth] = rdd.collect()
        ex = tctx2.scheduler.executor
        assert any("host_runs" in s
                   for s in ex.shuffle_store.values()), "did not spill"
    assert got[1] == got[0]
    assert [k for k, _ in got[1]] == sorted(keys.tolist())


def test_pipeline_overlap_beats_serial(tctx2, tiny_waves):
    """The acceptance observable at test scale: the pipelined run's
    host-observed device-idle fraction is strictly below the serial
    run's on the same workload, and the per-wave metrics are
    populated."""
    rng = np.random.RandomState(23)
    keys = rng.randint(0, 10**6, 24000).astype(np.int64)
    vals = np.arange(24000, dtype=np.int64)
    idle = {}
    for depth, donate, writer in _pipeline_modes():
        _set_mode(depth, donate, writer)
        tctx2.parallelize(Columns(keys, vals), 2) \
            .sortByKey(numSplits=8).collect()
        pipe = _last_pipeline(tctx2)
        assert pipe is not None
        assert pipe["waves"] > 1
        assert pipe["pipeline_depth"] == depth
        assert pipe["donated"] == donate
        for field in ("ingest_ms", "compute_ms", "exchange_ms",
                      "spill_ms", "device_idle_frac"):
            assert field in pipe
        idle[depth] = pipe["device_idle_frac"]
    assert idle[1] < idle[0], idle


def test_premerge_runs_in_background(tctx2, tiny_waves):
    """After a spilled stream finishes, the export premerger collapses
    every partition's runs into one key-sorted run without waiting for
    the first reduce fetch."""
    keys = np.arange(15000, dtype=np.int64) % 97
    vals = np.arange(15000, dtype=np.int64) % 13
    rdd = tctx2.parallelize(Columns(keys, vals), 2) \
        .groupByKey(8)                            # held: so is its store
    got = {k: sorted(v) for k, v in rdd.collect()}
    expect = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        expect.setdefault(k, []).append(v)
    assert got == {k: sorted(v) for k, v in expect.items()}
    ex = tctx2.scheduler.executor
    stores = [s for s in ex.shuffle_store.values() if "host_runs" in s]
    assert stores and stores[0].get("premerge") is not None
    pm = stores[0]["premerge"]
    if pm._thread is not None:
        pm._thread.join(timeout=10)
    for rid, paths in enumerate(stores[0]["host_runs"]):
        assert len(paths) <= 1, (rid, paths)
        got_paths, presorted = pm.ensure(rid)
        assert presorted


def _dpark_pipeline_threads():
    names = ("dpark-wave-prefetch", "dpark-wave-ingest",
             "dpark-spill-writer")
    return [t for t in threading.enumerate() if t.name in names]


def test_cancellation_mid_stream_shuts_down_threads(tctx2, tiny_waves):
    """A wave that fails mid-stream (here: a key colliding with the
    device padding sentinel, surfacing in the INGEST thread) must
    unwind the whole pipeline — tokenize prefetch, ingest thread,
    spill writer — without leaking threads or the spool directory,
    and the job must still answer through the object-path fallback."""
    import os
    from dpark_tpu.backend.tpu.layout import KEY_SENTINEL
    from dpark_tpu.env import env
    keys = np.arange(8000, dtype=np.int64) % 53
    keys[6500] = KEY_SENTINEL          # wave ~13 of 16 fails at ingest
    vals = np.ones(8000, dtype=np.int64)
    rdd = tctx2.parallelize(Columns(keys, vals), 2) \
        .groupByKey(8)            # held: a streamed store would stay
    got = {k: sorted(v) for k, v in rdd.collect()}
    # object fallback computed the right answer (sentinel key included)
    assert got[int(KEY_SENTINEL)] == [1]
    assert sum(len(v) for v in got.values()) == 8000
    # no streamed store registered for the aborted array attempt
    ex = tctx2.scheduler.executor
    assert not any("host_runs" in s for s in ex.shuffle_store.values())
    # the aborted run's spool dir was cleaned up
    spool_root = os.path.join(env.workdir, "hbmruns")
    assert not os.path.isdir(spool_root) or not os.listdir(spool_root)
    # pipeline threads wind down (bounded poll: the prefetch stop
    # timeout is 0.5s per stage)
    deadline = time.time() + 8
    while time.time() < deadline and _dpark_pipeline_threads():
        time.sleep(0.1)
    assert not _dpark_pipeline_threads(), \
        [t.name for t in _dpark_pipeline_threads()]


def test_spill_writer_error_propagates():
    """A writer-thread failure surfaces on the wave loop's next put()
    or at finish(), never silently."""
    from dpark_tpu.backend.tpu.executor import _SpillWriter

    def bad_write(path, cols):
        raise OSError("disk gone")

    w = _SpillWriter(bad_write)
    w.put("/tmp/x1", [np.arange(3)])
    with pytest.raises(OSError):
        # the first write may still be in flight: poll put/finish
        for _ in range(50):
            w.put("/tmp/x2", [np.arange(3)])
            time.sleep(0.02)
        w.finish()
    w.abort()
    deadline = time.time() + 5
    while time.time() < deadline and w._thread.is_alive():
        time.sleep(0.05)
    assert not w._thread.is_alive()


def test_spill_writer_writes_and_finishes(tmp_path):
    from dpark_tpu.backend.tpu.executor import JAXExecutor, _SpillWriter
    w = _SpillWriter(JAXExecutor._write_run)
    paths = []
    for i in range(10):
        p = str(tmp_path / ("run-%d" % i))
        w.put(p, [np.arange(i + 1), np.ones(i + 1)])
        paths.append(p)
    w.finish()
    for i, p in enumerate(paths):
        cols = JAXExecutor._read_run(p)
        assert len(cols[0]) == i + 1
