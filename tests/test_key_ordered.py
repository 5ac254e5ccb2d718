"""On ONE device a write that combines nothing orders its store by key.

A hash write sorts a device's rows by destination, and the reader of a
join or cogroup side sorts them again by key.  With one device every valid
row's destination is 0, so the first sort only packs.  The write sorts by
the key columns instead (collectives.bucketize_by_key), the executor
registers the store `key_ordered`, and the join's gather takes the store as
it stands.

The contracts under test:

* THE STORE — bucketize_by_key leaves, bit for bit, what bucketize followed
  by the reader's sentinel and sort_by_key left: one and two key columns,
  records the sort carries (8 words or fewer) and wider ones, rows a filter
  dropped from the write's mask, and a valid key equal to the sentinel,
  which takes the lax.cond's other branch.
* THE JOIN — over such sides on one device it equals numpy's and the local
  master; the stores are marked, `stores_key_ordered` counts them, their
  valid prefixes are the valid rows stably sorted by key, no gather
  program is launched, and the narrow program's `compile` event says
  `pack="key"`.  A cogroup's host merge reads such stores too.
* THE BYPASSES — a byte-string key, a range write (sortByKey), a combining
  write and every write past one device keep the destination sort, say
  `pack="dst"` where they combine nothing, and leave the counter alone.
"""

import operator

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dpark_tpu import Columns, DparkContext, trace
from dpark_tpu.backend.tpu import collectives

SENT = np.iinfo(np.int64).max
N = 700


@pytest.fixture(scope="module")
def masters():
    ctxs = {}
    for name in ("local", "tpu:1", "tpu:4"):
        ctxs[name] = DparkContext(name)
        ctxs[name].start()
    yield ctxs
    for c in ctxs.values():
        c.stop()


@pytest.fixture()
def _x64():
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def _old_write_and_gather(leaves, valid, nk):
    """The store a one-device write left before (bucketize by dst) and
    what the gather made of it (the sentinel past the count, then
    sort_by_key over the whole capacity)."""
    dst = jnp.where(valid, 0, 1).astype(jnp.int32)
    packed, counts, offsets = collectives.bucketize(
        leaves[0], leaves, valid, 1, dst=dst)
    packed[0] = jnp.where(jnp.arange(valid.shape[0]) < counts[0],
                          packed[0], SENT)
    return collectives.sort_by_key(packed, nk), counts, offsets


def _record(rng, cap, nk, words):
    """nk int64 key columns of few distinct values, then int64 and int32
    values up to `words` 32-bit words in all."""
    keys = [rng.integers(-3, 4, cap).astype(np.int64) for _ in range(nk)]
    vals, left = [], words - 2 * nk
    while left >= 2:
        vals.append(rng.integers(-2 ** 40, 2 ** 40, cap).astype(np.int64))
        left -= 2
    if left:
        vals.append(rng.integers(-2 ** 20, 2 ** 20, cap).astype(np.int32))
    return keys + vals


@pytest.mark.parametrize("sentinel", [False, True],
                         ids=["keys", "a-valid-sentinel"])
@pytest.mark.parametrize("mask", ["prefix", "filtered"])
@pytest.mark.parametrize("words", [6, 8, 13], ids=lambda w: "%dw" % w)
@pytest.mark.parametrize("nk", [1, 2])
def test_the_store_is_the_old_write_and_its_gather(_x64, nk, words, mask,
                                                   sentinel):
    rng = np.random.default_rng(nk * 100 + words)
    cap = 256
    leaves = _record(rng, cap, nk, words)
    valid = np.arange(cap) < 200
    if mask == "filtered":
        valid &= rng.random(cap) < 0.6
    if sentinel:
        # after a row the mask drops, so a sort by the key alone would
        # interleave it with the padding
        leaves[0][[5, 9, 150]] = SENT
        valid[[4, 5, 9, 150]] = [False, True, True, True]
    got, counts, offsets = jax.jit(collectives.bucketize_by_key,
                                   static_argnums=2)(
        [jnp.asarray(c) for c in leaves], jnp.asarray(valid), nk)
    want, want_counts, want_offsets = jax.jit(
        _old_write_and_gather, static_argnums=2)(
        [jnp.asarray(c) for c in leaves], jnp.asarray(valid), nk)
    n = int(valid.sum())
    assert int(counts[0]) == int(want_counts[0]) == n
    assert int(offsets[0]) == int(want_offsets[0]) == 0
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if sentinel and nk > 1:
        # padding of key (sentinel, k1) sorts among valid rows of the
        # same column 0 by k1: so it did, and so it still does
        return
    # the valid prefix: the valid rows in input order, stably by key
    order = np.lexsort([c[valid] for c in leaves[:nk]][::-1])
    for g, c in zip(got, leaves):
        np.testing.assert_array_equal(np.asarray(g)[:n], c[valid][order])


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


def _write_compiles(spans):
    return [s["args"]["pack"] for s in spans if s["name"] == "compile"
            and (s.get("args") or {}).get("program") == "narrow"
            and "pack" in s["args"]]


I = np.arange(N, dtype=np.int64)
KEYS = (I * 37 % 61) * (1 << 33) - (1 << 36)       # past 32 bits, signed
VALS = [(I * m % 1009).astype(np.int64) for m in (31, 17, 13, 7)]


def _keep(kv):
    return kv[1][0] % 5 != 0


def _tuple_key(kv):
    return ((kv[0] % 3, kv[0]), kv[1])


def _side(ctx, nk, nv, cut, ndev=1):
    """(key, (v0, ...)) records of one partition: a 1- or 2-column key,
    nv int64 values, the `cut` rows dropped by a filter that folds into
    the write's mask."""
    vals = VALS[:nv]
    rows = ctx.parallelize(Columns(KEYS[:cut], *[v[:cut] for v in vals]),
                           ndev).map(lambda r: (r[0], tuple(r[1:])))
    rows = rows.filter(_keep)
    return rows.map(_tuple_key) if nk == 2 else rows


def _numpy_join(nk, nv, cut_a, cut_b):
    """The inner join by numpy: every pair of rows whose keys match."""
    def side(cut):
        keep = VALS[0][:cut] % 5 != 0
        k = KEYS[:cut][keep]
        keys = np.stack([k % 3, k] if nk == 2 else [k], axis=1)
        vals = np.stack([v[:cut][keep] for v in VALS[:nv]], axis=1)
        return keys, vals

    (ka, va), (kb, vb) = side(cut_a), side(cut_b)
    ia, ib = np.nonzero((ka[:, None, :] == kb[None, :, :]).all(axis=2))
    key = (lambda k: tuple(k)) if nk == 2 else (lambda k: k[0])
    return sorted((key(ka[i].tolist()), (tuple(va[i].tolist()),
                                         tuple(vb[j].tolist())))
                  for i, j in zip(ia, ib))


@pytest.mark.parametrize("nv", [1, 3, 4], ids=lambda n: "%d-values" % n)
@pytest.mark.parametrize("nk", [1, 2])
def test_a_join_over_key_ordered_sides(masters, nk, nv):
    cut_a, cut_b = N, 300
    want = _numpy_join(nk, nv, cut_a, cut_b)
    local = masters["local"]
    assert sorted(_side(local, nk, nv, cut_a).join(
        _side(local, nk, nv, cut_b), 1).collect()) == want
    ctx = masters["tpu:1"]
    ex = ctx.scheduler.executor
    marked0 = ex.stores_key_ordered
    trace.configure("ring")
    try:
        joined = _side(ctx, nk, nv, cut_a).join(
            _side(ctx, nk, nv, cut_b), 1)
        launches0 = ex.program_launches
        got = sorted(joined.collect())
        launched = ex.program_launches - launches0
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert got == want
    assert ex.stores_key_ordered - marked0 == 2
    # two writes, the join's count and expansion, the result's tail:
    # no gather program
    assert launched == 5
    assert not [s for s in spans if s["name"] == "launch"
                and s["args"].get("program") == "reduce"]
    assert _write_compiles(spans).count("dst") == 0
    # no `dst` word rides the sort: a record of 8 words or fewer (one
    # key column and three values: 8) is carried whole
    assert {s["args"]["sort"] for s in spans if s["name"] == "compile"
            and s["args"].get("pack") == "key"} \
        == {"carried" if 2 * (nk + nv) <= 8 else "keys+rows"}
    for store, cut in zip(_stores(ctx, joined), (cut_a, cut_b)):
        assert store["key_ordered"] == nk
        n = int(np.asarray(store["counts"]).sum())
        cols = [np.asarray(l)[0, :n] for l in store["leaves"]]
        keep = np.asarray(VALS[0][:cut]) % 5 != 0
        keys = [KEYS[:cut][keep] % 3, KEYS[:cut][keep]] if nk == 2 \
            else [KEYS[:cut][keep]]
        order = np.lexsort(keys[::-1])
        for col, k in zip(cols, keys):
            np.testing.assert_array_equal(col, k[order])
        np.testing.assert_array_equal(cols[nk], VALS[0][:cut][keep][order])


def test_a_key_ordered_side_is_read_by_two_joins(masters):
    ctx = masters["tpu:1"]
    ex = ctx.scheduler.executor
    a, b = _side(ctx, 1, 1, N), _side(ctx, 1, 1, 300)
    want = _numpy_join(1, 1, N, 300)
    marked0 = ex.stores_key_ordered
    joined = a.join(b, 1)
    assert sorted(joined.collect()) == want
    # the same stores again: no write, no gather
    launches0 = ex.program_launches
    assert sorted(joined.map(lambda r: r).collect()) == want
    assert ex.program_launches - launches0 == 3
    assert ex.stores_key_ordered - marked0 == 2


def _cogroup(ctx):
    a = ctx.parallelize(Columns(KEYS, VALS[0]), 1)
    b = ctx.parallelize(Columns(KEYS[:300] * 3, VALS[1][:300]), 1)
    return a.cogroup(b, numSplits=1)


def test_a_cogroup_reads_key_ordered_stores(masters):
    """The host merge of a cogroup takes its sides' rows through the
    same gather (gather_rows): key-sorted, with no program launched."""
    def groups(rdd):
        return sorted((k, (sorted(x), sorted(y)))
                      for k, (x, y) in rdd.collect())

    want = groups(_cogroup(masters["local"]))
    ctx = masters["tpu:1"]
    ex = ctx.scheduler.executor
    marked0 = ex.stores_key_ordered
    trace.configure("ring")
    try:
        got = groups(_cogroup(ctx))
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert got == want
    assert any(x and y for _, (x, y) in got)    # keys on both sides
    assert ex.stores_key_ordered - marked0 == 2
    assert not [s for s in spans if s["name"] == "launch"
                and s["args"].get("program") == "reduce"]


def _bytes_join(ctx, ndev):
    ips = np.array([b"10.0.%d.%d" % (i % 7, i % 3) for i in range(N)],
                   dtype="S12")
    a = ctx.parallelize(Columns(ips, VALS[0]), ndev)
    return a.join(a.mapValue(lambda v: v + 1), ndev)


def _int_join(ctx, ndev):
    a = ctx.parallelize(Columns(KEYS, VALS[0]), ndev)
    return a.join(a.mapValue(lambda v: v + 1), ndev)


def _sort(ctx, ndev):
    # one partition in would sort where it is, with no write
    return ctx.parallelize(Columns(KEYS, VALS[0]), 2).sortByKey(
        numSplits=ndev)


def _reduce(ctx, ndev):
    return ctx.parallelize(Columns(KEYS, VALS[0]), ndev).reduceByKey(
        operator.add, ndev)


# name -> (chain, ndev, the form its write's compile event says)
BYPASSES = {
    "bytes-key": (_bytes_join, 1, "dst"),
    "range-write": (_sort, 1, "dst"),
    "combining-write": (_reduce, 1, "full"),
    "four-devices": (_int_join, 4, "dst"),
}


@pytest.mark.parametrize("case", list(BYPASSES))
def test_other_writes_keep_the_destination_sort(masters, case):
    build, ndev, pack = BYPASSES[case]
    want = build(masters["local"], ndev).collect()
    ctx = masters["tpu:%d" % ndev]
    ex = ctx.scheduler.executor
    marked0 = ex.stores_key_ordered
    trace.configure("ring")
    try:
        rdd = build(ctx, ndev)
        got = rdd.collect()
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    if build is _sort:
        assert got == want
    else:
        assert sorted(got) == sorted(want)
    assert ex.stores_key_ordered == marked0
    assert not any(s.get("key_ordered") for s in _stores(ctx, rdd))
    assert pack in _write_compiles(spans)
    assert "key" not in _write_compiles(spans)
