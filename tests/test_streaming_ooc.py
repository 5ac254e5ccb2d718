"""Out-of-core streaming beyond monoid reduceByKey (SURVEY.md 7.2 item
4): sortByKey (range exchange, spilled sorted runs), groupByKey
(spill-to-disk runs + lazy heap merge), and text-source wave ingest.
Waves are forced tiny so a few thousand rows exercise the full pipeline;
each test asserts parity with the local master and that the spilled
stores hold (almost) nothing in HBM."""

import numpy as np
import pytest

from dpark_tpu import Columns, conf

pytestmark = pytest.mark.mesh    # full-mesh collectives (see conftest)


@pytest.fixture()
def tctx():
    from dpark_tpu import DparkContext
    c = DparkContext("tpu")
    c.start()
    yield c
    c.stop()


@pytest.fixture()
def tiny_waves():
    old = (conf.STREAM_CHUNK_ROWS, conf.STREAM_TEXT_BYTES)
    conf.STREAM_CHUNK_ROWS = 500
    conf.STREAM_TEXT_BYTES = 4000
    yield
    conf.STREAM_CHUNK_ROWS, conf.STREAM_TEXT_BYTES = old


def _spilled(tctx):
    """Did a shuffle stage stream through spilled runs?  From the job
    records (kind `array+spill`): a store lives only as long as the
    RDD that shuffled it, which most tests here do not keep."""
    return any(st.get("kind") == "array+spill"
               for rec in tctx.scheduler.history
               for st in rec["stage_info"])


def test_streamed_sortbykey(tctx, tiny_waves):
    rng = np.random.RandomState(5)
    keys = rng.randint(-10**6, 10**6, 20000).astype(np.int64)
    vals = np.arange(20000, dtype=np.int64)
    got = tctx.parallelize(Columns(keys, vals), 8) \
              .sortByKey(numSplits=8).collect()
    assert _spilled(tctx)
    assert [k for k, _ in got] == sorted(keys.tolist())
    # full row multiset parity
    assert sorted(got) == sorted(zip(keys.tolist(), vals.tolist()))


def test_streamed_sortbykey_descending(tctx, tiny_waves):
    keys = (np.arange(6000, dtype=np.int64) * 7919) % 1000
    vals = np.ones(6000, dtype=np.int64)
    got = tctx.parallelize(Columns(keys, vals), 8) \
              .sortByKey(ascending=False, numSplits=4).collect()
    assert [k for k, _ in got] == sorted(keys.tolist(), reverse=True)


def test_streamed_groupbykey(tctx, tiny_waves):
    n = 15000
    keys = (np.arange(n, dtype=np.int64) * 31) % 97
    vals = np.arange(n, dtype=np.int64) % 11
    got = {k: sorted(v) for k, v in
           tctx.parallelize(Columns(keys, vals), 8)
           .groupByKey(8).collect()}
    assert _spilled(tctx)
    expect = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        expect.setdefault(k, []).append(v)
    assert got == {k: sorted(v) for k, v in expect.items()}


def test_streamed_partitionby_then_reduce(tctx, tiny_waves):
    n = 8000
    keys = np.arange(n, dtype=np.int64) % 53
    vals = np.ones(n, dtype=np.int64)
    r = tctx.parallelize(Columns(keys, vals), 8).partitionBy(8)
    got = {}
    for k, v in r.collect():
        got[k] = got.get(k, 0) + v
    assert got == {k: n // 53 + (1 if k < n % 53 else 0)
                   for k in range(53)}


def test_streamed_text_wordcount(tctx, tiny_waves, tmp_path):
    import random
    rng = random.Random(9)
    words = ["aa", "bb", "cc", "dd", "ee"]
    p = str(tmp_path / "big.txt")
    with open(p, "w") as f:
        for _ in range(3000):
            f.write(" ".join(rng.choices(words, k=6)) + "\n")

    held = []                   # the chains: a store lives with its

    def run(ctx):
        held.append(ctx.textFile(p, splitSize=2000)
                    .flatMap(lambda line: line.split())
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda a, b: a + b, 8))
        return dict(held[-1].collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    # the monoid stream leaves a pre-reduced store, not a full dataset
    ex = tctx.scheduler.executor
    assert any(s.get("pre_reduced") for s in ex.shuffle_store.values())


def test_streamed_text_groupbykey(tctx, tiny_waves, tmp_path):
    p = str(tmp_path / "g.txt")
    with open(p, "w") as f:
        for i in range(2000):
            f.write("w%d x\n" % (i % 7))

    def run(ctx):
        return {k: sorted(v) for k, v in
                ctx.textFile(p, splitSize=1500)
                .flatMap(lambda line: line.split())
                .map(lambda w: (w, len(w)))
                .groupByKey(4).collect()}

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    assert _spilled(tctx)


def test_streamed_text_sortbykey(tctx, tiny_waves, tmp_path):
    """File-sourced numeric sort: text plan with a RANGE partitioner,
    streamed through spilled runs."""
    p = str(tmp_path / "nums.txt")
    rng = np.random.RandomState(3)
    nums = rng.randint(0, 10**6, 5000)
    with open(p, "w") as f:
        for x in nums.tolist():
            f.write("%d\n" % x)

    def run(ctx):
        return ctx.textFile(p, splitSize=3000) \
                  .map(lambda l: (int(l), 1)).sortByKey(numSplits=4) \
                  .collect()

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert [k for k, _ in got] == [k for k, _ in expect]
    assert sorted(got) == sorted(expect)


def test_spool_cleanup_on_drop(tctx, tiny_waves):
    import os
    keys = np.arange(5000, dtype=np.int64) % 17
    vals = np.ones(5000, dtype=np.int64)
    r = tctx.parallelize(Columns(keys, vals), 8).groupByKey(8)
    r.collect()
    ex = tctx.scheduler.executor
    spools = [s["spool_dir"] for s in ex.shuffle_store.values()
              if s.get("spool_dir")]
    assert spools and all(os.path.isdir(d) for d in spools)
    for sid in list(ex.shuffle_store):
        ex.drop_shuffle(sid)
    assert not any(os.path.isdir(d) for d in spools)


def test_streamed_generic_combiner(tctx, tiny_waves):
    """A traceable NON-monoid merge (tuple-wise sums) streams too, via
    the segmented associative scan."""
    n = 12000
    keys = (np.arange(n, dtype=np.int64) * 13) % 37
    vals = np.arange(n, dtype=np.int64) % 9
    rdd = tctx.parallelize(Columns(keys, vals), 8) \
        .mapValue(lambda v: (v, 1)) \
        .reduceByKey(lambda a, b: (a[0] + b[0], a[1] + b[1]), 8)
    got = dict(rdd.collect())             # rdd held: so is its store
    ex = tctx.scheduler.executor
    assert any(s.get("pre_reduced")
               for s in ex.shuffle_store.values()), "did not stream"
    expect = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        s, c = expect.get(k, (0, 0))
        expect[k] = (s + v, c + 1)
    assert got == expect


def test_logical_partitions_beyond_mesh(tctx, tiny_waves):
    """r > ndev: the spilled-run stream carries the LOGICAL partition id
    through the exchange, so big sorts/groups can use many small reduce
    partitions (bounded reduce memory) instead of mesh-sized ones."""
    rng = np.random.RandomState(11)
    keys = rng.randint(0, 10**6, 20000).astype(np.int64)
    vals = np.arange(20000, dtype=np.int64)
    rdd = tctx.parallelize(Columns(keys, vals), 8) \
        .sortByKey(numSplits=32)              # held: so is its store
    got = rdd.collect()
    assert _spilled(tctx)
    store = [s for s in tctx.scheduler.executor.shuffle_store.values()
             if "host_runs" in s][0]
    assert len(store["host_runs"]) == 32
    assert [k for k, _ in got] == sorted(keys.tolist())
    assert sorted(got) == sorted(zip(keys.tolist(), vals.tolist()))

    g = {k: sorted(v) for k, v in
         tctx.parallelize(Columns(keys % 101, vals), 8)
         .groupByKey(64).collect()}
    expect = {}
    for k, v in zip((keys % 101).tolist(), vals.tolist()):
        expect.setdefault(k, []).append(v)
    assert g == {k: sorted(v) for k, v in expect.items()}


def _spilled_rows(tctx):
    """Total rows across all spilled run files (column lengths)."""
    from dpark_tpu.backend.tpu.executor import JAXExecutor
    total = 0
    for s in tctx.scheduler.executor.shuffle_store.values():
        for paths in s.get("host_runs", []):
            for p in paths:
                cols = JAXExecutor._read_run(p)
                total += len(cols[0])
    return total


def test_traceable_monoid_beyond_mesh(tctx, tiny_waves):
    """r > ndev with a classified monoid merge rides the spilled-run
    stream; each wave pre-reduces per (rid, key) ON DEVICE before
    spilling, so runs hold one combiner per distinct key per wave, not
    every row (previously this fell to the object path)."""
    n = 20000
    i = np.arange(n, dtype=np.int64)
    keys = (i * 13) % 37
    vals = i % 7
    rdd = tctx.parallelize(Columns(keys, vals), 8) \
        .reduceByKey(lambda a, b: a + b, 24)  # held: so is its store
    got = dict(rdd.collect())
    assert _spilled(tctx)
    store = [s for s in tctx.scheduler.executor.shuffle_store.values()
             if "host_runs" in s][0]
    assert store["host_combine"]
    # 5 waves x <=37 distinct keys: far fewer spilled rows than input
    assert 0 < _spilled_rows(tctx) <= 37 * 8, _spilled_rows(tctx)
    expect = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        expect[k] = expect.get(k, 0) + v
    assert got == expect


def test_traceable_generic_merge_beyond_mesh(tctx, tiny_waves):
    """A traceable NON-monoid merge (tuple-wise sums) with r > ndev:
    pre-reduce runs through the segmented associative scan."""
    n = 16000
    i = np.arange(n, dtype=np.int64)
    keys = (i * 31) % 101
    vals = i % 9
    rdd = tctx.parallelize(Columns(keys, vals), 8) \
        .mapValue(lambda v: (v, 1)) \
        .reduceByKey(lambda a, b: (a[0] + b[0], a[1] + b[1]), 32)
    got = dict(rdd.collect())             # rdd held: so is its store
    assert _spilled(tctx)
    assert 0 < _spilled_rows(tctx) <= 101 * 8, _spilled_rows(tctx)
    expect = {}
    for k, v in zip(keys.tolist(), vals.tolist()):
        s, c = expect.get(k, (0, 0))
        expect[k] = (s + v, c + 1)
    assert got == expect


def test_traceable_merge_beyond_mesh_text(tctx, tiny_waves, tmp_path):
    """Text wordcount with r > ndev streams through the spilled runs
    with device pre-reduce, with exact parity vs the local master."""
    import random
    rng = random.Random(21)
    words = ["w%d" % d for d in range(23)]
    p = str(tmp_path / "wide.txt")
    with open(p, "w") as f:
        for _ in range(2500):
            f.write(" ".join(rng.choices(words, k=6)) + "\n")

    def run(ctx):
        return dict(ctx.textFile(p, splitSize=1800)
                    .flatMap(lambda line: line.split())
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda a, b: a + b, 20).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    assert _spilled(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect


def test_spilled_rerun_keeps_new_spool(tctx, tiny_waves):
    """Re-running a spilled map stage while the OLD store is still
    registered must not delete the new run files (per-run spool dirs)."""
    from dpark_tpu.env import env
    keys = np.arange(4000, dtype=np.int64) % 13
    vals = np.arange(4000, dtype=np.int64) % 7
    r = tctx.parallelize(Columns(keys, vals), 8).groupByKey(8)
    first = {k: sorted(v) for k, v in r.collect()}
    # force a full map-stage re-run with the old store still present
    for stage in tctx.scheduler.shuffle_to_stage.values():
        stage.output_locs = [None] * len(stage.output_locs)
    env.map_output_tracker.locs.clear()
    second = {k: sorted(v) for k, v in r.collect()}
    assert second == first


def test_streamed_store_recovery_after_drop(tctx, tiny_waves):
    """Dropping the spilled store recomputes through lineage."""
    keys = np.arange(6000, dtype=np.int64) % 29
    vals = np.arange(6000, dtype=np.int64) % 5
    r = tctx.parallelize(Columns(keys, vals), 8).sortByKey(numSplits=4)
    first = r.collect()
    ex = tctx.scheduler.executor
    for sid in list(ex.shuffle_store):
        ex.drop_shuffle(sid)
    second = r.collect()
    # key order is the contract; equal-key value order may differ
    # between the streamed and the recovered path
    assert [k for k, _ in second] == [k for k, _ in first]
    assert sorted(second) == sorted(first)
