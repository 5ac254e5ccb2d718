"""A shuffle store lives as long as its ShuffleDependency (ISSUE 25).

The contracts under test:

* RELEASE — a chain the caller drops dies by REFERENCE COUNT (every
  test here runs with the cyclic collector off), and the next job
  minted drops its stores, its stages and its map-output locations,
  with one `hbm.release` event of reason `unreachable` a store.
* KEEP — an RDD that is held keeps its store: a second action re-runs
  no map stage, and under HBM pressure the store spills to disk and
  reads back, exactly as before.
* EVERY EXIT — a job that raises and a take() that abandons its
  generator release too; so does a service job function.
* THE FINALIZER takes no lock: it only queues the shuffle id.
"""

import gc
import operator

import numpy as np
import pytest

from dpark_tpu import Columns, DparkContext, conf, ledger, locks, trace
from dpark_tpu.env import env


@pytest.fixture(autouse=True)
def _no_cyclic_collector():
    """The release has to be exact, by reference count: a cycle that
    holds a dependency would leave it to a collection nobody times."""
    trace.configure("off")
    gc.collect()
    gc.disable()
    yield
    gc.enable()
    trace.configure("off")


@pytest.fixture()
def tctx2():
    c = DparkContext("tpu:2")
    c.start()
    yield c
    c.stop()


def _mod8(kv):
    return (kv[0] % 8, kv[1])


def _product_mod8(kv):
    key, (a, b) = kv
    return (key % 8, a * b)


def _resident(kv):
    return kv


def _cached(c, keys, vals):
    rdd = c.parallelize(Columns(keys, vals), 2).map(_resident).cache()
    assert rdd.count() == len(keys)
    return rdd


def _shuffle_ids(rdd, acc=None):
    """Every shuffle id of an RDD's lineage."""
    acc = set() if acc is None else acc
    for dep in rdd.dependencies:
        if dep.is_shuffle:
            acc.add(dep.shuffle_id)
        _shuffle_ids(dep.rdd, acc)
    return acc


def _next_job(c):
    """Any job at all: minting its record drains the queue."""
    assert c.parallelize([1, 2, 3], 2).collect() == [1, 2, 3]


def _forgotten(c, sids):
    sched = c.scheduler
    return not any(sid in sched.executor.shuffle_store
                   or sid in sched.shuffle_to_stage
                   or env.map_output_tracker.get_outputs(sid) is not None
                   for sid in sids)


def _released_events(sids):
    return sorted((r["args"]["sid"], r["args"]["bytes"])
                  for r in trace.snapshot()
                  if r["name"] == "hbm.release"
                  and r["args"]["reason"] == "unreachable"
                  and r["args"]["sid"] in sids)


KEYS = np.arange(4096, dtype=np.int64) % 37
AGG_ANSWER = [(k, int((KEYS % 8 == k).sum())) for k in range(8)]


def _agg_table(c):
    return _cached(c, KEYS, np.ones(len(KEYS), np.int64))


def _agg_chain(c, table):
    return table.map(_mod8).reduceByKey(operator.add, 2)


def _dropped_job(c, make):
    """Build a chain, run it, let it go: (answer, {sid: store bytes})."""
    ex = c.scheduler.executor
    rdd = make()
    answer = sorted(rdd.collect())
    sizes = {sid: ex.shuffle_store[sid]["nbytes"]
             for sid in _shuffle_ids(rdd)}
    return answer, sizes


# ---------------------------------------------------------------------------
# (a), (b) a dropped chain's stores go when the next job starts
# ---------------------------------------------------------------------------

def test_dropped_chain_is_released_at_the_next_job(tctx2):
    assert not gc.isenabled()
    ex = tctx2.scheduler.executor
    table = _agg_table(tctx2)
    trace.configure("ring")
    bytes0, released0 = ex._store_bytes, ex.stores_released
    answer, sizes = _dropped_job(tctx2, lambda: _agg_chain(tctx2, table))
    assert answer == AGG_ANSWER
    (sid,) = sizes
    # the chain is gone, its store waits for the next drain
    assert sid in ex.shuffle_store and sid in tctx2.scheduler._unreachable
    assert ex._store_bytes == bytes0 + sizes[sid] > bytes0
    _next_job(tctx2)
    assert _forgotten(tctx2, sizes)
    assert ex._store_bytes == bytes0
    assert ex.stores_released == released0 + 1
    assert _released_events(sizes) == [(sid, sizes[sid])]
    assert not tctx2.scheduler._unreachable


def test_join_releases_its_three_stores(tctx2):
    ex = tctx2.scheduler.executor
    fact = _cached(tctx2, np.arange(2048, dtype=np.int64) % 8,
                   np.full(2048, 3, np.int64))
    dim = _cached(tctx2, np.arange(8, dtype=np.int64),
                  np.arange(8, dtype=np.int64) + 1)
    trace.configure("ring")
    bytes0, released0 = ex._store_bytes, ex.stores_released
    answer, sizes = _dropped_job(
        tctx2, lambda: fact.join(dim, 2).map(_product_mod8)
        .reduceByKey(operator.add, 2))
    assert answer == [(k, 256 * 3 * (k + 1)) for k in range(8)]
    assert len(sizes) == 3
    kinds = [st["kind"] for st in tctx2.scheduler.history[-1]["stage_info"]]
    assert all(k.startswith("array") for k in kinds), kinds
    _next_job(tctx2)
    assert _forgotten(tctx2, sizes)
    assert ex._store_bytes == bytes0
    assert ex.stores_released == released0 + 3
    assert _released_events(sizes) == sorted(sizes.items())


def test_steady_loop_holds_one_job_of_stores(tctx2):
    """What the benchmark's closed loop sees: each job frees what the
    job before it left, before it registers its own."""
    ex = tctx2.scheduler.executor
    table = _agg_table(tctx2)
    seen = []
    for _ in range(4):
        _dropped_job(tctx2, lambda: _agg_chain(tctx2, table))
        seen.append((len(ex.shuffle_store), ex._store_bytes,
                     len(tctx2.scheduler.shuffle_to_stage)))
    assert len(set(seen)) == 1 and seen[0][0] == 1, seen
    assert ex.stores_released == 3


# ---------------------------------------------------------------------------
# (c), (d) a held RDD keeps its store, in HBM or on disk
# ---------------------------------------------------------------------------

def test_held_rdd_reuses_its_store(tctx2):
    ex = tctx2.scheduler.executor
    table = _agg_table(tctx2)
    rdd = _agg_chain(tctx2, table)
    first = sorted(rdd.collect())
    assert first == AGG_ANSWER
    assert [st["shuffle"] for st
            in tctx2.scheduler.history[-1]["stage_info"]] == [True, False]
    (sid,) = _shuffle_ids(rdd)
    released0 = ex.stores_released
    _next_job(tctx2)
    assert sorted(rdd.collect()) == first
    record = tctx2.scheduler.history[-1]
    # the map stage did not run again: one stage, the result's
    assert record["stages"] == 1
    assert [st["shuffle"] for st in record["stage_info"]] == [False]
    assert sid in ex.shuffle_store and sid in tctx2.scheduler.shuffle_to_stage
    assert ex.stores_released == released0
    assert not tctx2.scheduler._unreachable


def test_held_store_still_spills_and_reads_back(tctx2):
    ex = tctx2.scheduler.executor
    r1 = tctx2.parallelize([(i % 4, 1) for i in range(4000)], 2) \
        .reduceByKey(operator.add, 2)
    assert dict(r1.collect()) == {k: 1000 for k in range(4)}
    (sid,) = _shuffle_ids(r1)
    nbytes = ex.shuffle_store[sid]["nbytes"]
    trace.configure("ring")
    old = conf.SHUFFLE_HBM_BUDGET
    conf.SHUFFLE_HBM_BUDGET = 1
    try:
        r2 = tctx2.parallelize([(i % 3, 2) for i in range(900)], 2) \
            .reduceByKey(operator.add, 2)
        assert dict(r2.collect()) == {k: 600 for k in range(3)}
    finally:
        conf.SHUFFLE_HBM_BUDGET = old
    spilled = [(r["args"]["sid"], r["args"]["bytes"])
               for r in trace.snapshot() if r["name"] == "hbm.release"
               and r["args"]["reason"] == "spill"]
    assert spilled == [(sid, nbytes)]
    assert sid not in ex.shuffle_store
    locs = env.map_output_tracker.get_outputs(sid)
    assert locs and not any(str(l).startswith("hbm://") for l in locs)
    assert dict(r1.collect()) == {k: 1000 for k in range(4)}
    record = tctx2.scheduler.history[-1]
    assert record["stages"] == 1
    assert record.get("resubmits", 0) == 0 and record.get("recomputes", 0) == 0
    assert ex.stores_released == 0


# ---------------------------------------------------------------------------
# (e) every way out of a job
# ---------------------------------------------------------------------------

def _boom(kv):
    raise RuntimeError("boom")


def test_job_that_raises_releases_its_store(tctx2):
    ex = tctx2.scheduler.executor

    def job():
        rdd = tctx2.parallelize([(i % 7, i) for i in range(1000)], 2) \
            .reduceByKey(operator.add, 2).map(_boom)
        with pytest.raises(Exception, match="boom"):
            rdd.collect()
        return _shuffle_ids(rdd)

    sids = job()
    assert tctx2.scheduler.history[-1]["state"] == "aborted"
    assert len(sids) == 1 and sids <= set(ex.shuffle_store)
    _next_job(tctx2)
    assert _forgotten(tctx2, sids)
    assert ex._store_bytes == 0 and ex.stores_released == 1


def test_abandoned_take_releases_its_store(tctx2):
    ex = tctx2.scheduler.executor

    def job():
        rdd = tctx2.parallelize([(i % 7, i) for i in range(1000)], 2) \
            .reduceByKey(operator.add, 2)
        assert len(rdd.take(1)) == 1
        return _shuffle_ids(rdd)

    sids = job()
    assert tctx2.scheduler.history[-1]["state"] == "partial"
    assert len(sids) == 1 and sids <= set(ex.shuffle_store)
    _next_job(tctx2)
    assert _forgotten(tctx2, sids)
    assert ex._store_bytes == 0 and ex.stores_released == 1


# ---------------------------------------------------------------------------
# (f) the resident service
# ---------------------------------------------------------------------------

def _service_job(ctx):
    return sorted(ctx.parallelize([(i % 5, 1) for i in range(1000)], 2)
                  .reduceByKey(operator.add, 2).collect())


def _drained(ex, next_job, seconds=20.0):
    """A slot thread may hold a finished job's last stage for a moment
    after the driver has its answer, so a chain can die just after a
    drain: the contract is `by the next job`.  Bounded: jobs until the
    executor holds no store."""
    import time
    deadline = time.time() + seconds
    while ex.shuffle_store and time.time() < deadline:
        next_job()
        time.sleep(0.02)
    return not ex.shuffle_store


def _service_idle(ctx):
    return ctx.parallelize([1, 2, 3], 1).collect()


def test_service_job_function_leaves_no_store():
    from dpark_tpu import service
    service.shutdown()
    ledger.configure("on")
    trace.configure("ring")
    framed = service.serve("127.0.0.1:0", master="tpu:2")
    try:
        srv = service.get_server()
        ex = srv.scheduler.executor
        base = ledger.snapshot()["hbm_live_bytes"]
        client = service.ServiceClient("%s:%d" % framed.bind_address,
                                       client="tenant-a")
        for n in (1, 2):
            assert client.run(_service_job) == [(k, 200) for k in range(5)]
            # as a rule gone when the function has returned (serve()
            # drains there), and always by the session's next job
            assert _drained(ex, lambda: client.run(_service_idle))
            assert ex._store_bytes == 0
            assert not srv.scheduler.shuffle_to_stage
            assert ex.stores_released == n
            assert ledger.snapshot()["hbm_live_bytes"] == base
        stored = [r for r in trace.snapshot() if r["name"] == "hbm.store"]
        freed = [r for r in trace.snapshot() if r["name"] == "hbm.release"]
        assert len(stored) == len(freed) == 2
        assert all(r["args"]["reason"] == "unreachable" for r in freed)
        # the byte-seconds settled on the tenant that stored them
        snap = ledger.snapshot()
        accrued = sum(d.get("hbm_byte_s", 0.0)
                      for d in list(snap["accounts"].values())
                      + list(snap["archive"].values()))
        assert accrued > 0, snap
    finally:
        framed.stop()
        service.shutdown()
        ledger.configure("on")


# ---------------------------------------------------------------------------
# (g) the finalizer: an append, nothing else
# ---------------------------------------------------------------------------

def test_finalizer_takes_no_lock(tctx2, monkeypatch):
    from dpark_tpu.backend.tpu import executor as executor_mod
    rdd = tctx2.parallelize([(i % 7, i) for i in range(1000)], 2) \
        .reduceByKey(operator.add, 2)
    assert len(rdd.collect()) == 7
    (sid,) = _shuffle_ids(rdd)
    ex = tctx2.scheduler.executor
    taken, unraisable = [], []

    def refuse(self, *a, **kw):
        taken.append(getattr(self, "name", "executor.mesh"))
        raise AssertionError("a lock inside the finalizer")

    monkeypatch.setattr("sys.unraisablehook", unraisable.append)
    monkeypatch.setattr(locks._NamedLock, "__enter__", refuse)
    monkeypatch.setattr(locks._NamedLock, "acquire", refuse)
    monkeypatch.setattr(executor_mod._MeshLock, "__enter__", refuse)
    del rdd                     # the last reference: the finalizer runs
    monkeypatch.undo()
    assert not taken and not unraisable
    # it queued the id and touched nothing
    assert list(tctx2.scheduler._unreachable) == [sid]
    assert sid in ex.shuffle_store and ex.stores_released == 0
    _next_job(tctx2)
    assert _forgotten(tctx2, {sid}) and ex.stores_released == 1


# ---------------------------------------------------------------------------
# concurrent drivers: the queue and the store accounting lose nothing
# ---------------------------------------------------------------------------

def test_concurrent_drivers_release_every_store():
    """More driver threads than cores' worth of work, a short switch
    interval: chains die on any thread, the drain runs under whichever
    thread holds the mesh, and in the end every store is accounted for."""
    import sys
    import threading
    from dpark_tpu import service
    service.shutdown()
    c = DparkContext("service:tpu:2")
    c.start()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    drivers, jobs = 6, 4
    wrong = []

    def driver(n):
        for j in range(jobs):
            got = dict(c.parallelize([(i % (3 + n), 1) for i in range(600)],
                                     2).reduceByKey(operator.add, 2)
                       .collect())
            if got != {k: 600 // (3 + n) + (1 if k < 600 % (3 + n) else 0)
                       for k in range(3 + n)}:
                wrong.append((n, j, got))

    try:
        threads = [threading.Thread(target=driver, args=(n,))
                   for n in range(drivers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not wrong
        sched = service.get_server().scheduler
        ex = sched.executor
        _next_job(c)
        assert _drained(ex, lambda: _next_job(c))
        assert not sched._unreachable and ex._store_bytes == 0
        assert not sched.shuffle_to_stage
        assert ex.stores_released == drivers * jobs
    finally:
        sys.setswitchinterval(old)
        c.stop()
        service.shutdown()

