"""On ONE device a combined shuffle write is the reduce's answer (ISSUE 36).

A combining shuffle write sorts the device's whole batch by (dst, key),
merges equal keys and packs.  With one device every row's dst is 0, so the
store holds each key once, in key order: what the reduce stage's identity
exchange and reduce program would hand on.  The executor registers such a
store `pre_reduced` and the stage that reads it runs its narrow tail alone.

The contracts under test:

* PARITY — reduceByKey / combineByKey over an int64, a tuple and a byte-
  string key, with add, min / max, a traced non-monoid merge, an untraceable
  merge and float32 sums, equal the local master on one device and on four.
* THE MARK — a store is `pre_reduced` exactly when the mesh has one device
  AND the write combined on the device; groupByKey, join, sortByKey, raw
  combiners and every write past one device keep the exchange + reduce.
  `stores_pre_reduced` counts the marks, `hbm.store` says `pre_reduced`.
* EXACT — a composite key orders by its true columns on one device: two
  keys of one 32-bit hash, interleaved, come out once each IN THE STORE.
* THE STORE'S OTHER READERS — a second action, a cache()d result after the
  store is gone, the export bridge (rows and columns), the spill.
"""

import operator

import numpy as np
import pytest

from dpark_tpu import Columns, DparkContext, conf, trace
from dpark_tpu.backend.tpu import fuse
from dpark_tpu.env import env
from dpark_tpu.utils.phash import portable_hash

N = 900


@pytest.fixture(scope="module")
def masters():
    ctxs = {}
    for name in ("local", "tpu:1", "tpu:4"):
        ctxs[name] = DparkContext(name)
        ctxs[name].start()
    yield ctxs
    for c in ctxs.values():
        c.stop()


def _shuffle_ids(rdd, acc=None):
    acc = set() if acc is None else acc
    for dep in rdd.dependencies:
        if dep.is_shuffle:
            acc.add(dep.shuffle_id)
        _shuffle_ids(dep.rdd, acc)
    return acc


def _stores(ctx, rdd):
    ex = ctx.scheduler.executor
    return [ex.shuffle_store[sid] for sid in sorted(_shuffle_ids(rdd))
            if sid in ex.shuffle_store]


def _resident(r):
    return r


def _tuple_key(r):
    return ((r[0] % 7, r[0] % 5), r[1])


def _ips():
    rng = np.random.default_rng(5)
    octets = rng.integers(0, 256, (N, 4)) % np.array([256, 3, 2, 2])
    return np.array([b"%d.%d.%d.%d" % tuple(o) for o in octets],
                    dtype="S16")


I = np.arange(N, dtype=np.int64)
INT_KEYS = (I * 7 % 53) * (1 << 33) - (1 << 35)    # past 32 bits, signed
INT_VALS = (I * 31 % 1009).astype(np.int64)
FLOAT_VALS = np.random.default_rng(3).random(N)


def _keyed(ctx, key, ndev, vals=INT_VALS):
    """(key, value) records over the three kinds of key."""
    if key == "bytes":
        return ctx.parallelize(Columns(_ips(), vals), ndev).map(_resident)
    rows = ctx.parallelize(Columns(INT_KEYS if key == "int64" else I, vals),
                           ndev).map(_resident)
    return rows.map(_tuple_key) if key == "tuple" else rows


def _mod_add(a, b):
    return (a + b) % 1000003          # associative; no monoid proves it


def _branchy(a, b):
    if a > b:                         # a tracer has no truth value
        return a + b
    return b + a


def _sum_count(v):
    return (v, 1)


def _sum_count_value(c, v):
    return (c[0] + v, c[1] + 1)


def _sum_count_merge(a, b):
    return (a[0] + b[0], a[1] + b[1])


# name -> (rdd -> shuffled rdd, values, does the write combine on the device)
def _reduce(f):
    return lambda rdd, n: rdd.reduceByKey(f, n)


MERGES = {
    "add": (_reduce(operator.add), INT_VALS, True),
    "min": (_reduce(min), INT_VALS, True),        # a monoid's own scan
    "max": (_reduce(max), INT_VALS, True),
    "traced": (_reduce(_mod_add), INT_VALS, True),
    "untraceable": (_reduce(_branchy), INT_VALS, False),
    "float32": (_reduce(operator.add), FLOAT_VALS, True),
    "combine": (lambda rdd, n: rdd.combineByKey(
        _sum_count, _sum_count_value, _sum_count_merge, n), INT_VALS, True),
}


def _same(got, want, floats):
    got, want = sorted(got), sorted(want)
    assert [r[0] for r in got] == [r[0] for r in want]
    if floats:
        np.testing.assert_allclose([r[1] for r in got],
                                   [r[1] for r in want], rtol=1e-5)
    else:
        assert [r[1] for r in got] == [r[1] for r in want]


@pytest.mark.parametrize("merge", list(MERGES))
@pytest.mark.parametrize("key", ["int64", "tuple", "bytes"])
@pytest.mark.parametrize("ndev", [1, 4])
def test_combining_shuffles_equal_the_local_master(masters, ndev, key,
                                                   merge):
    build, vals, combines = MERGES[merge]
    want = build(_keyed(masters["local"], key, ndev, vals), ndev).collect()
    tctx = masters["tpu:%d" % ndev]
    ex = tctx.scheduler.executor
    marked0 = ex.stores_pre_reduced
    shuffled = build(_keyed(tctx, key, ndev, vals), ndev)
    _same(shuffled.collect(), want, merge == "float32")
    (store,) = _stores(tctx, shuffled)
    marked = ndev == 1 and combines
    assert bool(store.get("pre_reduced")) is marked, (ndev, key, merge)
    assert ex.stores_pre_reduced - marked0 == int(marked)
    if marked:
        # the write's combine is exact: every key once in the store
        assert int(np.asarray(store["counts"]).sum()) == len(want)
        assert np.asarray(store["counts"]).shape == (1, 1)
    # count() reads the same store again
    assert shuffled.count() == len(want)
    assert ex.stores_pre_reduced - marked0 == int(marked)


def _group(rdd, n):
    # bare: groupByKey().mapValues(len) is rewritten into a combiner
    return rdd.groupByKey(n)


def _join(rdd, n):
    return rdd.join(rdd.mapValue(lambda v: v + 1), n) \
        .mapValue(lambda ab: ab[0] * ab[1])


def _sort(rdd, n):
    return rdd.sortByKey(numSplits=n)


def _partition(rdd, n):
    return rdd.partitionBy(n)


@pytest.mark.parametrize("chain", [_group, _join, _sort, _partition],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("ndev", [1, 4])
def test_writes_that_combine_nothing_keep_the_reduce(masters, ndev, chain):
    want = chain(_keyed(masters["local"], "int64", ndev), ndev).collect()
    tctx = masters["tpu:%d" % ndev]
    ex = tctx.scheduler.executor
    marked0 = ex.stores_pre_reduced
    rdd = chain(_keyed(tctx, "int64", ndev), ndev)
    got = rdd.collect()
    if chain is _sort:
        assert [r[0] for r in got] == [r[0] for r in want]
    if chain is _group:
        got, want = ([(k, sorted(v)) for k, v in rows]
                     for rows in (got, want))
    assert sorted(got) == sorted(want)
    assert not any(s.get("pre_reduced") for s in _stores(tctx, rdd))
    assert ex.stores_pre_reduced == marked0


# two tuple keys of ONE 32-bit composite hash (found by search; the assert
# below guards the hash): by the hashed order equal hashes keep the input's
# order, so A B A B ... stays interleaved and no two equal keys meet
KEY_A, KEY_B = (2, 458), (399, 168)


@pytest.mark.parametrize("ndev", [1, 4])
def test_keys_of_one_hash_come_out_once(masters, ndev):
    assert portable_hash(KEY_A) == portable_hash(KEY_B)
    rows = [(KEY_A if i % 2 == 0 else KEY_B, i) for i in range(64)]
    tctx = masters["tpu:%d" % ndev]
    # one partition's rows stay interleaved on their device
    shuffled = tctx.parallelize(rows, ndev).reduceByKey(operator.add, ndev)
    want = {KEY_A: sum(range(0, 64, 2)), KEY_B: sum(range(1, 64, 2))}
    got = shuffled.collect()
    assert len(got) == 2 and dict(got) == want
    (store,) = _stores(tctx, shuffled)
    assert bool(store.get("pre_reduced")) is (ndev == 1)
    if ndev == 1:
        assert int(np.asarray(store["counts"]).sum()) == 2
        keys = [np.asarray(l)[0, :2].tolist() for l in store["leaves"][:2]]
        assert list(zip(*keys)) == [KEY_A, KEY_B]       # in key order


def test_the_mark_rides_the_hbm_store_event(masters):
    trace.configure("ring")
    try:
        tctx = masters["tpu:1"]
        held = _keyed(tctx, "int64", 1).reduceByKey(operator.add, 1)
        held.count()
        held_group = _keyed(tctx, "int64", 1).groupByKey(1)
        held_group.count()
        events = {r["args"]["sid"]: r["args"] for r in trace.snapshot()
                  if r["name"] == "hbm.store"}
    finally:
        trace.configure("off")
    (sid,) = _shuffle_ids(held)
    (gid,) = _shuffle_ids(held_group)
    assert events[sid]["pre_reduced"] is True
    assert events[gid]["pre_reduced"] is False


def test_two_actions_read_one_marked_store(masters):
    tctx = masters["tpu:1"]
    ex = tctx.scheduler.executor
    want = sorted(_keyed(masters["local"], "tuple", 1)
                  .reduceByKey(operator.add, 1).collect())
    marked0, launches0 = ex.stores_pre_reduced, ex.program_launches
    shuffled = _keyed(tctx, "tuple", 1).reduceByKey(operator.add, 1)
    first = sorted(shuffled.collect())
    launched = ex.program_launches - launches0
    second = sorted(shuffled.map(_resident).collect())
    assert first == want and second == want
    # the second job ran the result stage alone, over the same store:
    # one narrow tail, no exchange and no reduce program
    record = tctx.scheduler.history[-1]
    assert [st["shuffle"] for st in record["stage_info"]] == [False]
    assert ex.program_launches - launches0 == launched + 1
    assert ex.stores_pre_reduced - marked0 == 1
    # the identity exchange's row accounting still reads the store's rows
    assert record["stage_info"][0]["ingest_pad_efficiency"] > 0


def test_a_cached_result_outlives_its_marked_store(masters):
    tctx = masters["tpu:1"]
    ex = tctx.scheduler.executor
    cached = _keyed(tctx, "int64", 1).reduceByKey(operator.add, 1).cache()
    first = sorted(cached.collect())
    (sid,) = _shuffle_ids(cached)
    assert ex.shuffle_store[sid]["pre_reduced"]
    assert cached.id in ex.result_cache
    # what the scheduler's drain does when the dependency dies
    tctx.scheduler._shuffle_unreachable(sid)
    assert sid not in ex.shuffle_store
    assert tctx.parallelize([(1, 2)] * 8, 1).reduceByKey(
        operator.add, 1).collect() == [(1, 16)]
    # the tail's result is its own buffers, not the store's
    assert sorted(cached.collect()) == first
    assert sorted(cached.mapValue(lambda v: v + 1).collect()) \
        == [(k, v + 1) for k, v in first]


def test_the_export_bridge_reads_a_marked_store(masters):
    tctx = masters["tpu:1"]
    ex = tctx.scheduler.executor
    shuffled = _keyed(tctx, "int64", 1).reduceByKey(operator.add, 1)
    want = sorted(shuffled.collect())
    (sid,) = _shuffle_ids(shuffled)
    assert ex.shuffle_store[sid]["pre_reduced"]
    assert sorted(ex._export_bucket(sid, 0, 0)) == want
    assert ex._export_bucket(sid, 1, 0) == []
    meta, mats = ex._export_bucket_cols(sid, 0, 0)
    assert meta == {"no_combine": False}
    assert sorted(zip(mats[0].tolist(), mats[1].tolist())) == want
    assert ex._export_bucket_cols(sid, 1, 0) == (meta, [])


def test_a_marked_store_spills_and_reads_back(masters):
    tctx = masters["tpu:1"]
    ex = tctx.scheduler.executor
    held = _keyed(tctx, "int64", 1).reduceByKey(operator.add, 1)
    want = sorted(held.collect())
    (sid,) = _shuffle_ids(held)
    assert ex.shuffle_store[sid]["pre_reduced"]
    old = conf.SHUFFLE_HBM_BUDGET
    conf.SHUFFLE_HBM_BUDGET = 1
    try:
        assert tctx.parallelize([(i % 3, 2) for i in range(90)], 1) \
            .reduceByKey(operator.add, 1).count() == 3
    finally:
        conf.SHUFFLE_HBM_BUDGET = old
    assert sid not in ex.shuffle_store
    locs = env.map_output_tracker.get_outputs(sid)
    assert locs and not any(str(l).startswith("hbm://") for l in locs)
    assert sorted(held.collect()) == want
    record = tctx.scheduler.history[-1]
    assert record["stages"] == 1
    assert record.get("resubmits", 0) == 0 and record.get("recomputes", 0) == 0


def test_a_chained_write_on_one_device_is_marked_too(masters):
    """reduceByKey after reduceByKey: the second write is the first
    reduce stage's epilogue, over a marked store's batch."""
    def rekey(kv):
        return (kv[0] % 3, kv[1])

    want = sorted(_keyed(masters["local"], "int64", 1)
                  .reduceByKey(operator.add, 1).map(rekey)
                  .reduceByKey(max, 1).collect())
    tctx = masters["tpu:1"]
    chained = _keyed(tctx, "int64", 1).reduceByKey(operator.add, 1) \
        .map(rekey).reduceByKey(max, 1)
    assert sorted(chained.collect()) == want
    assert [bool(s.get("pre_reduced")) for s in _stores(tctx, chained)] \
        == [True, True]


def test_the_verdict_is_asked_once_a_program_not_once_a_job(masters):
    """Whether a write combines is remembered by program key: the first
    job's one probe serves its program's build and the mark; a fresh plan
    of the same chain traces nothing."""
    tctx = masters["tpu:1"]
    ex = tctx.scheduler.executor
    probes = []
    real = ex._epilogue_merge

    def spy(plan):
        probes.append(plan)
        return real(plan)

    def fresh(a, b):
        return a + b

    ex._epilogue_merge = spy
    try:
        for _ in range(3):
            shuffled = _keyed(tctx, "int64", 1).reduceByKey(fresh, 1)
            assert shuffled.count() == 53
            (store,) = _stores(tctx, shuffled)
            assert store["pre_reduced"]
    finally:
        del ex._epilogue_merge
    assert len(probes) == 1
    assert fuse.classify_merge(fresh) == "add"
    # the memo is bounded like the program cache, oldest entry out
    old = conf.PROGRAM_CACHE_MAX
    conf.PROGRAM_CACHE_MAX = len(ex._combines_memo)
    try:
        shuffled = _keyed(tctx, "int64", 1).reduceByKey(
            lambda a, b: a + b + 0, 1)
        assert shuffled.count() == 53
        (store,) = _stores(tctx, shuffled)
        assert store["pre_reduced"]
        assert len(ex._combines_memo) == conf.PROGRAM_CACHE_MAX
    finally:
        conf.PROGRAM_CACHE_MAX = old
