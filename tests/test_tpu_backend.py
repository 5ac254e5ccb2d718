"""TPU master parity: every test asserts `-m tpu` output == local-master
semantics on the same program (SURVEY.md section 4 implication), running on
the 8-virtual-CPU-device mesh from conftest."""

import pytest

pytestmark = pytest.mark.mesh    # full-mesh collectives (see conftest)


@pytest.fixture()
def tctx():
    from dpark_tpu import DparkContext
    c = DparkContext("tpu")
    c.start()
    yield c
    c.stop()


def _used_array_path(tctx):
    from tests.conftest import shuffled_on_device
    return shuffled_on_device(tctx)


def test_parallelize_collect_roundtrip(tctx):
    data = list(range(100))
    assert tctx.parallelize(data, 8).collect() == data


def test_map_filter_fused(tctx):
    r = tctx.parallelize(list(range(64)), 8)
    got = r.map(lambda x: x * 3).filter(lambda x: x % 2 == 0).collect()
    assert got == [x * 3 for x in range(64) if (x * 3) % 2 == 0]


def test_reduce_by_key_device_shuffle(tctx):
    pairs = [(i % 13, i) for i in range(1000)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    expect = {}
    for k, v in pairs:
        expect[k] = expect.get(k, 0) + v
    assert got == expect
    assert _used_array_path(tctx)


def test_reduce_by_key_matches_local(tctx):
    from dpark_tpu import DparkContext
    pairs = [((i * 7919) % 101, i % 17) for i in range(5000)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    lctx = DparkContext("local")
    expect = dict(lctx.parallelize(pairs, 8)
                  .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == expect


def test_negative_and_large_keys(tctx):
    pairs = [(k, 1) for k in
             [-1, -2, 0, 2**30, -(2**30), 7, -7] * 10]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == {-1: 10, -2: 10, 0: 10, 2**30: 10,
                   -(2**30): 10, 7: 10, -7: 10}


def test_skewed_keys_multi_round(tctx):
    # one dominant key forces slot overflow -> multi-round exchange
    pairs = [(0, 1)] * 3000 + [(i, 1) for i in range(1, 50)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got[0] == 3000
    assert all(got[i] == 1 for i in range(1, 50))


def test_map_after_shuffle(tctx):
    pairs = [(i % 5, 1) for i in range(100)]
    got = sorted(tctx.parallelize(pairs, 8)
                 .reduceByKey(lambda a, b: a + b, 8)
                 .map(lambda kv: (kv[0], kv[1] * 10)).collect())
    assert got == [(k, 200) for k in range(5)]


def test_chained_shuffles(tctx):
    pairs = [(i % 10, 1) for i in range(400)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8)
               .map(lambda kv: (kv[0] % 2, kv[1]))
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == {0: 200, 1: 200}


def test_float_values(tctx):
    pairs = [(i % 4, float(i) * 0.5) for i in range(100)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    expect = {}
    for k, v in pairs:
        expect[k] = expect.get(k, 0.0) + v
    for k in expect:
        assert abs(got[k] - expect[k]) < 1e-3


def test_tuple_values_combine(tctx):
    # average via (sum, count) combiners — tuple-valued records
    pairs = [(i % 3, (i, 1)) for i in range(90)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: (a[0] + b[0], a[1] + b[1]), 8)
               .collect())
    assert got[0][1] == 30 and got[1][1] == 30 and got[2][1] == 30
    assert sum(v[0] for v in got.values()) == sum(range(90))


def test_untraceable_falls_back(tctx):
    # string records cannot ride the array path; result must still be right
    r = tctx.parallelize([("a", 1), ("b", 2), ("a", 3)], 2)
    got = dict(r.reduceByKey(lambda a, b: a + b).collect())
    assert got == {"a": 4, "b": 2}


def test_side_effect_lambda_falls_back(tctx):
    seen = []
    r = tctx.parallelize([(1, 1), (2, 2)], 2)
    got = dict(r.reduceByKey(lambda a, b: (seen.append(1), a + b)[1])
               .collect())
    assert got == {1: 1, 2: 2}


def test_hbm_to_host_bridge(tctx):
    """Device-written shuffle consumed by an untraceable downstream stage
    (mapPartitions is object-path-only) — must read via the HBM bridge."""
    pairs = [(i % 6, 1) for i in range(600)]
    r = (tctx.parallelize(pairs, 8)
         .reduceByKey(lambda a, b: a + b, 8)
         .mapPartitions(lambda it: [sorted(it)]))
    parts = r.collect()
    flat = [kv for part in parts for kv in part]
    assert dict(flat) == {k: 100 for k in range(6)}


def test_count_and_take_on_device_pipeline(tctx):
    r = tctx.parallelize([(i % 11, 1) for i in range(800)], 8) \
            .reduceByKey(lambda a, b: a + b, 8)
    assert r.count() == 11
    assert len(r.take(5)) == 5


def test_non_divisible_partitions_fall_back(tctx):
    # 5 partitions on an 8-device mesh -> object path, same answer
    pairs = [(i % 3, 1) for i in range(50)]
    got = dict(tctx.parallelize(pairs, 5)
               .reduceByKey(lambda a, b: a + b, 5).collect())
    assert got == {0: 17, 1: 17, 2: 16}


def test_large_sum_no_overflow(tctx):
    """Values summing past 2**31 must not wrap (int64 device path)."""
    pairs = [(1, 2_000_000_000)] * 8
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == {1: 16_000_000_000}


def test_int32_max_key_not_dropped(tctx):
    """INT32_MAX is a legitimate key, not padding."""
    pairs = [(2**31 - 1, 1)] * 8 + [(5, 2)] * 8
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == {2**31 - 1: 8, 5: 16}


def test_int64_sentinel_key_falls_back(tctx):
    """The one reserved key value (2**63-1) takes the host path."""
    pairs = [(2**63 - 1, 1)] * 4 + [(3, 1)] * 4
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == {2**63 - 1: 4, 3: 4}


def test_hbm_eviction_triggers_lineage_recovery(tctx):
    from dpark_tpu import conf
    old = conf.SHUFFLE_HBM_BUDGET
    conf.SHUFFLE_HBM_BUDGET = 1          # evict everything beyond newest
    try:
        r1 = tctx.parallelize([(i % 4, 1) for i in range(200)], 8) \
                 .reduceByKey(lambda a, b: a + b, 8)
        assert dict(r1.collect()) == {k: 50 for k in range(4)}
        r2 = tctx.parallelize([(i % 3, 1) for i in range(90)], 8) \
                 .reduceByKey(lambda a, b: a + b, 8)
        assert dict(r2.collect()) == {k: 30 for k in range(3)}
        # r1's HBM store may be gone; a new action must still succeed
        # (FetchFailed -> parent stage recompute)
        assert dict(r1.collect()) == {k: 50 for k in range(4)}
    finally:
        conf.SHUFFLE_HBM_BUDGET = old


def test_columnar_parallelize_device(tctx):
    import numpy as np
    n = 100_000
    keys = (np.arange(n, dtype=np.int64) * 2654435761) % 1000
    vals = np.ones(n, dtype=np.int64)
    from dpark_tpu import Columns
    got = dict(tctx.parallelize(Columns(keys, vals), 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert len(got) == 1000
    assert sum(got.values()) == n
    assert _used_array_path(tctx)


def test_columnar_parallelize_object_path_parity(ctx):
    import numpy as np
    keys = np.array([1, 2, 1, 3], dtype=np.int64)
    vals = np.array([10, 20, 30, 40], dtype=np.int64)
    from dpark_tpu import Columns
    got = dict(ctx.parallelize(Columns(keys, vals), 2)
               .reduceByKey(lambda a, b: a + b).collect())
    assert got == {1: 40, 2: 20, 3: 40}
    single = ctx.parallelize(np.arange(5), 2).map(lambda x: x * 2).collect()
    assert single == [0, 2, 4, 6, 8]


def test_sortbykey_on_device(tctx):
    import random
    from dpark_tpu import DparkContext
    rng = random.Random(9)
    pairs = [(rng.randint(-10000, 10000), i) for i in range(4000)]
    r = tctx.parallelize(pairs, 8)
    got = r.sortByKey(numSplits=8).collect()
    assert [k for k, _ in got] == sorted(k for k, _ in pairs)
    assert _used_array_path(tctx)
    got_desc = r.sortByKey(ascending=False, numSplits=8).collect()
    assert [k for k, _ in got_desc] == sorted(
        (k for k, _ in pairs), reverse=True)


def test_sortbykey_float_keys_device(tctx):
    import random
    rng = random.Random(4)
    pairs = [(rng.random() * 100 - 50, i) for i in range(2000)]
    got = tctx.parallelize(pairs, 8).sortByKey(numSplits=8).collect()
    ks = [k for k, _ in got]
    assert all(abs(a - b) < 1e-4 for a, b in
               zip(ks, sorted(k for k, _ in pairs)))


def test_groupbykey_on_device(tctx):
    pairs = [(i % 7, i) for i in range(700)]
    got = dict(tctx.parallelize(pairs, 8).groupByKey(8).collect())
    assert set(got) == set(range(7))
    for k in range(7):
        assert sorted(got[k]) == [i for i in range(700) if i % 7 == k]
    assert _used_array_path(tctx)


def test_partition_by_device_then_host_op(tctx):
    """partitionBy on device, then an untraceable op via the HBM bridge."""
    pairs = [(i, i * 2) for i in range(400)]
    r = tctx.parallelize(pairs, 8).partitionBy(8) \
            .mapPartitions(lambda it: [len(list(it))])
    counts = r.collect()
    assert sum(counts) == 400


def test_distinct_on_device(tctx):
    data = [i % 50 for i in range(2000)]
    got = sorted(tctx.parallelize(data, 8).distinct(8).collect())
    assert got == list(range(50))


def test_sentinel_key_in_range_sort_falls_back(tctx):
    """INT64_MAX key must not be silently dropped by device sortByKey."""
    pairs = [(i, i) for i in range(100, 1000)] + [(2**63 - 1, 111)]
    got = tctx.parallelize(pairs, 8).sortByKey(numSplits=8).collect()
    assert got[-1] == (2**63 - 1, 111)
    assert len(got) == len(pairs)


def test_inf_float_key_falls_back(tctx):
    pairs = [(float(i), i) for i in range(50)] + [(float("inf"), -1)]
    got = tctx.parallelize(pairs, 8).sortByKey(numSplits=8).collect()
    assert got[-1] == (float("inf"), -1)


def test_cogroup_device_exchange(tctx):
    a = tctx.parallelize([(i % 20, i) for i in range(400)], 8)
    b = tctx.parallelize([(i % 20, i * 3) for i in range(200)], 8)
    got = dict(a.cogroup(b, numSplits=8).collect())
    assert set(got) == set(range(20))
    for k in range(20):
        assert sorted(got[k][0]) == [i for i in range(400) if i % 20 == k]
        assert sorted(got[k][1]) == [i * 3 for i in range(200)
                                     if i % 20 == k]


def test_join_device_exchange_matches_local(tctx):
    from dpark_tpu import DparkContext
    a_pairs = [(i % 30, i) for i in range(300)]
    b_pairs = [(i % 30, -i) for i in range(150)]
    a = tctx.parallelize(a_pairs, 8)
    b = tctx.parallelize(b_pairs, 8)
    got = sorted(a.join(b, 8).collect())
    lctx = DparkContext("local")
    expect = sorted(lctx.parallelize(a_pairs, 8)
                    .join(lctx.parallelize(b_pairs, 8), 8).collect())
    assert got == expect


def test_hbm_result_cache(tctx):
    """A cached device result is reused: the second action consumes the
    HBM batch instead of re-ingesting, and downstream stages chain off
    it."""
    pairs = [(i % 9, 1) for i in range(900)]
    r = tctx.parallelize(pairs, 8).reduceByKey(lambda a, b: a + b, 8) \
            .cache()
    assert dict(r.collect()) == {k: 100 for k in range(9)}
    ex = tctx.scheduler.executor
    assert r.id in set(ex.result_cache_ids())
    # downstream of the cached batch
    doubled = dict(r.map(lambda kv: (kv[0], kv[1] * 2)).collect())
    assert doubled == {k: 200 for k in range(9)}
    assert r.count() == 9
    r.unpersist()
    assert r.id not in set(ex.result_cache_ids())
    # still correct after unpersist (recompute)
    assert r.count() == 9


def test_fewer_reduce_partitions_than_devices(tctx):
    """R < ndev rides the mesh (extra devices idle), exact results."""
    pairs = [(i % 6, 1) for i in range(600)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 3).collect())
    assert got == {k: 100 for k in range(6)}
    assert _used_array_path(tctx)
    srt = tctx.parallelize([(9 - i, i) for i in range(10)] * 10, 8) \
              .sortByKey(numSplits=4).collect()
    assert [k for k, _ in srt] == sorted(k for k in
                                         [9 - i for i in range(10)] * 10)


def test_cached_sentinel_key_falls_back(tctx):
    """A cached RDD containing the sentinel key still shuffles correctly
    (host path), not silently dropping the row."""
    pairs = [(2**63 - 1, 1), (5, 1)] * 4
    r = tctx.parallelize(pairs, 8).cache()
    assert sorted(r.collect()) == sorted(pairs)
    got = dict(r.reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == {2**63 - 1: 4, 5: 4}


def test_hbm_budget_shared_across_tiers(tctx):
    from dpark_tpu import conf
    ex = tctx.scheduler.executor
    old = conf.SHUFFLE_HBM_BUDGET
    conf.SHUFFLE_HBM_BUDGET = 1
    try:
        r1 = tctx.parallelize([(i % 4, 1) for i in range(400)], 8) \
                 .reduceByKey(lambda a, b: a + b, 8).cache()
        assert dict(r1.collect()) == {k: 100 for k in range(4)}
        r2 = tctx.parallelize([(i % 2, 1) for i in range(100)], 8) \
                 .reduceByKey(lambda a, b: a + b, 8).cache()
        assert dict(r2.collect()) == {0: 50, 1: 50}
        total = ex._store_bytes + ex._result_bytes
        # over-budget entries were evicted down to the newest survivors
        assert len(ex.shuffle_store) + len(ex.result_cache) <= 2
        # double-collect must not double-count bytes
        before = ex._result_bytes
        assert dict(r2.collect()) == {0: 50, 1: 50}
        assert ex._result_bytes == before
    finally:
        conf.SHUFFLE_HBM_BUDGET = old


def test_dstream_batches_reuse_compiled_programs(tctx):
    """Per-batch jobs hit the structural jit cache: after batch 1, later
    batches compile nothing new (SURVEY.md 7.2 item 5)."""
    import operator
    from dpark_tpu.dstream import StreamingContext
    ssc = StreamingContext(tctx, 1.0)
    out = []
    batches = [[(i % 5, 1) for i in range(64)] for _ in range(4)]
    q = ssc.queueStream(batches)
    q.reduceByKey(operator.add, 8).collect_batches(out)
    tctx.start()
    ssc.zero_time = 0.0
    ssc.run_batch(1.0)
    compiled_after_first = len(tctx.scheduler.executor._compiled)
    for k in (2, 3, 4):
        ssc.run_batch(float(k))
    assert len(out) == 4
    expect = {j: 13 if j < 4 else 12 for j in range(5)}
    assert all(dict(v) == expect for _, v in out)
    assert len(tctx.scheduler.executor._compiled) == compiled_after_first


def test_streamed_shuffle_out_of_core(tctx):
    """Columnar input above the chunk threshold reduces in waves; result
    identical to the in-core path."""
    import numpy as np
    from dpark_tpu import Columns, conf
    old = conf.STREAM_CHUNK_ROWS
    conf.STREAM_CHUNK_ROWS = 1000          # force ~4 waves
    try:
        n = 60_000
        i = np.arange(n, dtype=np.int64)
        keys = (i * 2654435761) % 37
        vals = np.ones(n, dtype=np.int64)
        rdd = tctx.parallelize(Columns(keys, vals), 8) \
            .reduceByKey(lambda a, b: a + b, 8)   # held: so is its store
        got = dict(rdd.collect())
        expect = {}
        for k in np.unique(keys):
            expect[int(k)] = int((keys == k).sum())
        assert got == expect
        sid = list(tctx.scheduler.executor.shuffle_store)[-1]
        assert tctx.scheduler.executor.shuffle_store[sid].get(
            "pre_reduced")
    finally:
        conf.STREAM_CHUNK_ROWS = old


def test_streamed_shuffle_bridge_to_host(tctx):
    """A host-path stage downstream of a streamed shuffle reads the
    pre-reduced state through the export bridge."""
    import numpy as np
    from dpark_tpu import Columns, conf
    old = conf.STREAM_CHUNK_ROWS
    conf.STREAM_CHUNK_ROWS = 500
    try:
        n = 4_000
        i = np.arange(n, dtype=np.int64)
        keys = i % 11
        vals = np.ones(n, dtype=np.int64)
        r = tctx.parallelize(Columns(keys, vals), 8) \
                .reduceByKey(lambda a, b: a + b, 8) \
                .mapPartitions(lambda it: [sorted(it)])
        flat = [kv for part in r.collect() for kv in part]
        assert dict(flat) == {k: n // 11 + (1 if k < n % 11 else 0)
                              for k in range(11)}
    finally:
        conf.STREAM_CHUNK_ROWS = old


def test_device_join_expansion(tctx):
    """a.join(b) expands pairs entirely on device, matching local."""
    from dpark_tpu import DparkContext
    a_pairs = [(i % 12, i) for i in range(240)]
    b_pairs = [(i % 12, -i) for i in range(120)]
    got = sorted(tctx.parallelize(a_pairs, 8)
                 .join(tctx.parallelize(b_pairs, 8), 8).collect())
    lctx = DparkContext("local")
    expect = sorted(lctx.parallelize(a_pairs, 8)
                    .join(lctx.parallelize(b_pairs, 8), 8).collect())
    assert got == expect
    assert len(got) == 240 * 120 // 12


def test_device_join_disjoint_and_skew(tctx):
    a = tctx.parallelize([(1, "no")] * 0 + [(k, k) for k in range(10)], 8)
    b = tctx.parallelize([(k + 100, k) for k in range(10)], 8)
    assert a.join(b, 8).collect() == []          # disjoint keys
    # heavy skew: one key with many matches on both sides
    aa = tctx.parallelize([(7, i) for i in range(50)] + [(1, 0)], 8)
    bb = tctx.parallelize([(7, -i) for i in range(40)] + [(2, 0)], 8)
    got = aa.join(bb, 8).collect()
    assert len(got) == 50 * 40
    assert all(k == 7 for k, _ in got)


def test_device_join_tuple_values(tctx):
    a = tctx.parallelize([(i % 5, (i, i * 2)) for i in range(50)], 8)
    b = tctx.parallelize([(i % 5, float(i)) for i in range(25)], 8)
    got = sorted(a.join(b, 8).collect())
    from dpark_tpu import DparkContext
    lctx = DparkContext("local")
    expect = sorted(
        lctx.parallelize([(i % 5, (i, i * 2)) for i in range(50)], 8)
        .join(lctx.parallelize([(i % 5, float(i)) for i in range(25)], 8),
              8).collect())
    assert got == expect


def test_tuple_key_join_rides_device(tctx):
    """Composite (tuple) keys now ride the device join end to end (the
    lexicographic key-match kernels): exact results vs the local golden
    model, with the join-source stage all-array."""
    a = tctx.parallelize([((i % 3, i % 2), i) for i in range(24)], 8)
    b = tctx.parallelize([((i % 3, i % 2), -i) for i in range(12)], 8)
    got = sorted(a.join(b, 8).collect())
    from dpark_tpu import DparkContext
    lctx = DparkContext("local")
    expect = sorted(
        lctx.parallelize([((i % 3, i % 2), i) for i in range(24)], 8)
        .join(lctx.parallelize([((i % 3, i % 2), -i) for i in range(12)],
                               8), 8).collect())
    assert got == expect
    kinds = _stage_kinds(tctx)
    assert kinds.get("FlatMappedValuesRDD") == "array", kinds


def test_tuple_key_all_array_stage_kinds(tctx):
    """The ISSUE 3 acceptance shape: reduceByKey / groupByKey /
    sortByKey over 2-int-tuple keys run with ALL-ARRAY stage kinds (no
    object fallback — tuple keys were the widest silent host-fallback
    trigger), with exact parity vs the local golden model."""
    import random
    from dpark_tpu import DparkContext
    rng = random.Random(21)
    data = [((rng.randint(0, 40), rng.randint(-7, 7)),
             rng.randint(-1000, 1000)) for _ in range(4000)]
    lctx = DparkContext("local")

    rt = sorted(tctx.parallelize(data, 8)
                .reduceByKey(lambda a, b: a + b, 8).collect())
    rl = sorted(lctx.parallelize(data, 8)
                .reduceByKey(lambda a, b: a + b, 8).collect())
    assert rt == rl
    kinds = _stage_kinds(tctx)
    assert set(kinds.values()) == {"array"}, kinds

    gt = sorted((k, sorted(v)) for k, v in
                tctx.parallelize(data, 8).groupByKey(8).collect())
    gl = sorted((k, sorted(v)) for k, v in
                lctx.parallelize(data, 8).groupByKey(8).collect())
    assert gt == gl
    kinds = _stage_kinds(tctx)
    assert set(kinds.values()) == {"array"}, kinds

    st = tctx.parallelize(data, 8).sortByKey(numSplits=8).collect()
    sl = lctx.parallelize(data, 8).sortByKey(numSplits=8).collect()
    assert [k for k, _ in st] == [k for k, _ in sl]
    kinds = _stage_kinds(tctx)
    assert set(kinds.values()) == {"array"}, kinds
    # descending too (the reversal keeps the lexicographic order)
    sd = tctx.parallelize(data, 8).sortByKey(
        ascending=False, numSplits=8).collect()
    ld = lctx.parallelize(data, 8).sortByKey(
        ascending=False, numSplits=8).collect()
    assert [k for k, _ in sd] == [k for k, _ in ld]


def test_tuple_key_partition_matches_host_partitioner(tctx):
    """Device-routed tuple keys land in the partition the HOST
    HashPartitioner computes (the pair-extended phash contract) —
    lookup() trusts get_partition to find device-shuffled rows."""
    from dpark_tpu.dependency import HashPartitioner
    data = [((i % 11, i % 3), i) for i in range(600)]
    r = tctx.parallelize(data, 8).reduceByKey(lambda a, b: a + b, 8)
    expect = {}
    for k, v in data:
        expect[k] = expect.get(k, 0) + v
    for key in list(expect)[:8]:
        assert r.lookup(key) == [expect[key]], key


def test_tuple_key_sentinel_column_falls_back(tctx):
    """A tuple key whose FIRST column carries the reserved sentinel
    value still produces exact results (host path, like scalar keys)."""
    pairs = [((2**63 - 1, 1), 1)] * 4 + [((3, 1), 1)] * 4
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    assert got == {(2**63 - 1, 1): 4, (3, 1): 4}


def test_nested_tuple_key_stays_on_host(tctx):
    """Only FLAT numeric tuples ride the device: a nested key keeps the
    object path and exact results."""
    pairs = [(((i % 3, i % 2), i % 2), 1) for i in range(48)]
    got = dict(tctx.parallelize(pairs, 8)
               .reduceByKey(lambda a, b: a + b, 8).collect())
    expect = {}
    for k, v in pairs:
        expect[k] = expect.get(k, 0) + v
    assert got == expect
    kinds = _stage_kinds(tctx)
    assert kinds.get("ShuffledRDD") != "array", kinds


def test_single_device_mesh_fast_path():
    """ndev == 1 (a real single-chip config): the exchange fast path
    returns the bucketized prefix directly — no collective program, no
    narrowing probe, zero wire bytes — with full parity on the in-core
    reduce, the spilled sort stream, and the r > mesh pre-reduce
    stream.  Runs in a subprocess: the suite's mesh is pinned to 8
    virtual devices at import time."""
    import os
    import subprocess
    import sys
    script = r'''
import os
os.environ["DPARK_TPU_PLATFORM"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import numpy as np
from dpark_tpu import DparkContext, Columns, conf

ctx = DparkContext("tpu"); ctx.start()
ex = ctx.scheduler.executor
assert ex.ndev == 1, ex.ndev
n = 60000
i = np.arange(n, dtype=np.int64)
got = dict(ctx.parallelize(Columns((i*7) % 1000, i % 5), 1)
           .reduceByKey(lambda a, b: a + b, 1).collect())
expect = {}
for k, v in zip(((i*7) % 1000).tolist(), (i % 5).tolist()):
    expect[k] = expect.get(k, 0) + v
assert got == expect
conf.STREAM_CHUNK_ROWS = 8000
keys = np.random.RandomState(3).randint(0, 10**6, n).astype(np.int64)
got2 = ctx.parallelize(Columns(keys, i), 1).sortByKey(numSplits=6).collect()
assert [k for k, _ in got2] == sorted(keys.tolist())
got3 = dict(ctx.parallelize(Columns((i*13) % 37, i % 7), 1)
            .reduceByKey(lambda a, b: a + b, 6).collect())
expect3 = {}
for k, v in zip(((i*13) % 37).tolist(), (i % 7).tolist()):
    expect3[k] = expect3.get(k, 0) + v
assert got3 == expect3
assert ex.exchange_wire_bytes == 0, ex.exchange_wire_bytes
ctx.stop()
print("OK_SINGLE_DEV")
'''
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=280)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK_SINGLE_DEV" in out.stdout


def test_egest_narrowed_wire_parity(tctx):
    """Large int64 results whose values fit int32 ride D2H narrowed
    — results identical."""
    from dpark_tpu import conf
    old = conf.EGEST_NARROW_MIN_BYTES
    conf.EGEST_NARROW_MIN_BYTES = 1           # force the probe at toy size
    try:
        pairs = [(i % 50, i) for i in range(4000)]
        got = dict(tctx.parallelize(pairs, 8)
                   .reduceByKey(lambda a, b: a + b, 8).collect())
        exp = {}
        for k, v in pairs:
            exp[k] = exp.get(k, 0) + v
        assert got == exp
    finally:
        conf.EGEST_NARROW_MIN_BYTES = old


def test_egest_narrow_skipped_for_big_values(tctx):
    """Values beyond int32 range must NOT be narrowed."""
    from dpark_tpu import conf
    old = conf.EGEST_NARROW_MIN_BYTES
    conf.EGEST_NARROW_MIN_BYTES = 1
    try:
        big = 1 << 40
        pairs = [(i % 10, big + i) for i in range(100)]
        got = dict(tctx.parallelize(pairs, 8)
                   .reduceByKey(lambda a, b: max(a, b), 8).collect())
        exp = {}
        for k, v in pairs:
            exp[k] = max(exp.get(k, 0), v)
        assert got == exp
    finally:
        conf.EGEST_NARROW_MIN_BYTES = old


def test_egest_oversize_warning(tctx, caplog):
    """collect() beyond EGEST_WARN_BYTES logs the reduce-before-collect
    hint (the reference's executor result-size flag analog)."""
    import logging
    from dpark_tpu import conf
    old = conf.EGEST_WARN_BYTES
    conf.EGEST_WARN_BYTES = 64                # trip at toy size
    try:
        with caplog.at_level(logging.WARNING):
            out = dict(tctx.parallelize([(i % 5, i) for i in range(100)],
                                        8)
                       .reduceByKey(lambda a, b: a + b, 8).collect())
        assert len(out) == 5
        assert any("reduce" in r.message and "collect" in r.message
                   for r in caplog.records)
    finally:
        conf.EGEST_WARN_BYTES = old


def _stage_kinds(tctx):
    """{rdd_name: kind} for the LAST job's stages."""
    rec = tctx.scheduler.history[-1]
    return {s["rdd"]: s.get("kind") for s in rec["stage_info"]}


def test_union_of_shuffles_rides_device(tctx):
    """A union of reduceByKey outputs feeding another reduceByKey (the
    windowed-stream shape, BASELINE config #4) runs the UNION stage on
    the array path: branches materialize as device batches, concatenate
    on device, and the shuffle write rides the mesh."""
    import operator
    rows = [(i % 50, i % 7) for i in range(5000)]
    b1 = tctx.parallelize(rows, 8).reduceByKey(operator.add, 8)
    b2 = tctx.parallelize(rows, 8).reduceByKey(operator.add, 8)
    got = dict(b1.union(b2).reduceByKey(operator.add, 8).collect())
    exp = {}
    for k, v in rows + rows:
        exp[k] = exp.get(k, 0) + v
    assert got == exp
    kinds = _stage_kinds(tctx)
    assert kinds.get("UnionRDD") == "array", kinds


def test_union_mixed_ingest_and_shuffle_branches(tctx):
    """Union branches may mix raw parallelize input with reduced HBM
    shuffles (cold-start window shape); narrow ops on a branch apply
    before the concat."""
    import operator
    rows = [(i % 50, 1) for i in range(4000)]
    reduced = tctx.parallelize(rows, 8).reduceByKey(operator.add, 8) \
        .mapValue(lambda v: v * 10)
    raw = tctx.parallelize(rows, 8)
    got = dict(raw.union(reduced).reduceByKey(operator.add, 8)
               .collect())
    exp = {}
    for k, v in rows:
        exp[k] = exp.get(k, 0) + v
    exp = {k: v + v * 10 for k, v in exp.items()}
    assert got == exp
    kinds = _stage_kinds(tctx)
    assert kinds.get("UnionRDD") == "array", kinds


def test_union_result_stage_stays_host(tctx):
    """collect() directly over a union (result stage) keeps the object
    path — result tasks index the union's own partition layout."""
    import operator
    rows = [(i % 20, 1) for i in range(800)]
    b1 = tctx.parallelize(rows, 8).reduceByKey(operator.add, 8)
    b2 = tctx.parallelize(rows, 8).reduceByKey(operator.add, 8)
    got = sorted(b1.union(b2).collect())
    exp = {}
    for k, v in rows:
        exp[k] = exp.get(k, 0) + v
    assert got == sorted(list(exp.items()) * 2)
    kinds = _stage_kinds(tctx)
    assert kinds.get("UnionRDD") != "array", kinds


def test_reslice_wrong_slice_count_rides_device(tctx):
    """parallelize with numSlices != mesh width feeding a shuffle write
    re-slices host-side onto the mesh instead of declining the array
    path (the DStream queue-batch shape)."""
    import operator
    rows = [(i % 64, i % 5) for i in range(6000)]
    for nsl in (2, 3, 16):
        r = tctx.parallelize(rows, nsl).reduceByKey(operator.add, 8)
        got = dict(r.collect())
        exp = {}
        for k, v in rows:
            exp[k] = exp.get(k, 0) + v
        assert got == exp, nsl
        kinds = _stage_kinds(tctx)
        assert kinds.get("ParallelCollection") == "array", (nsl, kinds)


def test_union_shuffle_feeds_object_consumer(tctx):
    """An OBJECT-path stage consuming a union-written shuffle fetches
    through the single_map export (device rows don't correspond to the
    union's 2x map partitions; without the flag every fetch failed and
    the scheduler resubmitted the parent forever)."""
    import operator
    rows = [(i % 30, 1) for i in range(3000)]
    b1 = tctx.parallelize(rows, 8).reduceByKey(operator.add, 8)
    b2 = tctx.parallelize(rows, 8).reduceByKey(operator.add, 8)
    u = b1.union(b2).reduceByKey(operator.add, 8)
    # str() is untraceable -> this stage runs object tasks that FETCH
    # the union's map outputs through the host bridge
    got = dict(u.map(lambda kv: (kv[0], str(kv[1]))).collect())
    exp = {}
    for k, v in rows:
        exp[k] = exp.get(k, 0) + v
    assert got == {k: str(v * 2) for k, v in exp.items()}


def test_resliced_shuffle_feeds_object_consumer(tctx):
    """Same single_map guarantee for resliced ingest: 2 logical map
    partitions redistributed over 8 devices, consumed by object tasks."""
    import operator
    rows = [(i % 40, i % 3) for i in range(4000)]
    r = tctx.parallelize(rows, 2).reduceByKey(operator.add, 8)
    got = dict(r.map(lambda kv: (kv[0], str(kv[1]))).collect())
    exp = {}
    for k, v in rows:
        exp[k] = exp.get(k, 0) + v
    assert got == {k: str(v) for k, v in exp.items()}


def test_join_source_pipeline_rides_device(tctx):
    """a.join(b) feeding further ops + a shuffle write runs the join
    as an array-path SOURCE (device expansion, no host rows): the
    TPC-H-shaped join->map->reduce pipeline is all-array."""
    import operator
    fact = [(i % 50, i % 7) for i in range(20000)]
    dim = [(i, i * 3) for i in range(50)]
    a = tctx.parallelize(fact, 8)
    b = tctx.parallelize(dim, 8)
    got = dict(a.join(b, 8)
               .map(lambda kv: (kv[0], kv[1][0] * kv[1][1]))
               .reduceByKey(operator.add, 8).collect())
    exp = {}
    for k, v in fact:
        exp[k] = exp.get(k, 0) + v * (k * 3)
    assert got == exp
    kinds = _stage_kinds(tctx)
    assert set(kinds.values()) == {"array"}, kinds
    assert "MappedRDD" in kinds, kinds    # the join-source stage's top


def test_count_answers_from_device_counts(tctx):
    """count() over an array result stage reads only counts (no row
    egest — note kind 'array+counts') and still matches the object
    path exactly; groupByKey counts KEYS via the on-device distinct
    scan over its key-sorted rows."""
    import operator
    rows = [(i % 100, i % 7) for i in range(30000)]
    assert tctx.parallelize(rows, 8).filter(
        lambda kv: kv[0] < 10).count() == 3000
    assert _stage_kinds(tctx).get("FilteredRDD") == "array+counts"
    assert tctx.parallelize(rows, 8).reduceByKey(
        operator.add, 8).count() == 100
    assert _stage_kinds(tctx).get("ShuffledRDD") == "array+counts"
    assert tctx.parallelize(rows, 8).groupByKey(8).count() == 100
    kinds = _stage_kinds(tctx)
    assert "array+counts" in kinds.values(), kinds
    # distinct-scan edge: every key unique, and a single-key skew
    assert tctx.parallelize(
        [(i, 1) for i in range(5000)], 8).groupByKey(8).count() == 5000
    assert tctx.parallelize(
        [(7, i) for i in range(5000)], 8).groupByKey(8).count() == 1


def test_reduce_monoid_answers_on_device(tctx):
    """reduce() with a provable monoid egests ndev scalars (note kind
    'array+reduced'), matching the object path exactly for ints; an
    unprovable reduce keeps the egest + host fold."""
    import operator
    vals = [((i * 7919) % 1000) - 500 for i in range(10000)]
    r = tctx.parallelize(vals, 8).map(lambda x: x * 3)
    assert r.reduce(operator.add) == sum(v * 3 for v in vals)
    assert _stage_kinds(tctx).get("MappedRDD") == "array+reduced"
    assert r.reduce(lambda a, b: a if a < b else b) \
        == min(v * 3 for v in vals)
    assert r.reduce(lambda a, b: a if a > b else b) \
        == max(v * 3 for v in vals)
    # subtraction is not a monoid: must NOT take the reduced path,
    # and must still fold in partition order like the object path
    got = tctx.parallelize([10, 1, 2, 3], 2).reduce(operator.sub)
    assert got == (10 - 1) - (2 - 3)
    assert _stage_kinds(tctx).get("ParallelCollection") \
        != "array+reduced"


def test_reduce_monoid_edge_semantics(tctx):
    """Integer-overflow, bool, and int-mul reduces keep the exact host
    fold (Python big ints) instead of wrapping on device (r4 review)."""
    import operator
    # sum would exceed int64: exact big-int answer required
    big = [2 ** 62, 2 ** 62, 2 ** 62]
    assert tctx.parallelize(big, 8).reduce(operator.add) == 3 * 2 ** 62
    # integer product overflows int64 almost immediately
    assert tctx.parallelize(list(range(1, 30)), 8) \
        .reduce(operator.mul) == __import__("math").factorial(29)
    # bool min/max must not crash the stage
    assert tctx.parallelize([True, False, True], 8).reduce(min) is False
    # float add stays on device (documented ordering divergence)
    vals = [0.5 * i for i in range(1000)]
    got = tctx.parallelize(vals, 8).map(lambda x: x + 0.25) \
        .reduce(operator.add)
    assert abs(got - sum(v + 0.25 for v in vals)) < 1e-6
    # empty devices (identity min/max) must not poison the overflow
    # bound into a needless fallback
    few = tctx.parallelize(list(range(16)), 8).filter(lambda x: x < 2)
    assert few.reduce(operator.add) == 1
    assert _stage_kinds(tctx).get("FilteredRDD") == "array+reduced"
