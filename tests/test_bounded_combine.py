"""A group-by over a table resident on the device packs its combined rows
only up to the key count the planner proved from the table's load-time
ranges.

The planner multiplies, over the key words, the values each word's range
allows (a number hi - lo + 1; word i of an S<w> column (hi - lo) >> 8 (8 - b)
+ 1, b = min(8, w - 8i) its bytes) and sets the product on the
ShuffleDependency of the reduceByKey it builds.  Where the bound's capacity
class is below the map program's input capacity, the combine sorts only its
keep flags and gathers the first slots of that order: the store leaves the
program at the bound's class, with the un-cut counts.

The contracts under test:

* PARITY — Q1-shaped queries over CHAR(1) keys, a two-word S12 key, a bare
  and a derived int key, and bounds at the class edge (256 and 257) equal
  the local master on one device and on four; the compile event says
  `pack=<slots>`, the store has that many slots, `combines_bounded` counts
  the launch.
* THE EDGES — a bound whose class is not below the input capacity packs in
  full (`pack=full`), with the program an unbounded plan compiles; a source
  the planner proves no bound for still goes through `_trim_combined` over
  the budget.
* THE CUT — `_bucketize_combine_cols` with `out_cap` equals the un-cut form's
  first `out_cap` rows, counts and offsets.
* LOUD, NEVER LOSSY — a forged key bound smaller than the data's key count
  fails the job with `CombineOverflow` and never returns fewer groups.
"""

import operator

import jax
import numpy as np
import pytest

from dpark_tpu import Columns, DparkContext, conf, trace
from dpark_tpu.backend.tpu import collectives
from dpark_tpu.backend.tpu.executor import CombineOverflow

N = 8192
FIELDS = "qty price tax flag status city k256 k257 kwide"


@pytest.fixture(scope="module")
def masters():
    ctxs = {}
    for name in ("local", "tpu:1", "tpu:4"):
        ctxs[name] = DparkContext(name)
        ctxs[name].start()
    yield ctxs
    for c in ctxs.values():
        c.stop()


def _ident(r):
    return r


def columns():
    rng = np.random.default_rng(41)
    i = np.arange(N, dtype=np.int64)
    qty = rng.integers(1, 51, N).astype(np.int64) * 100
    price = rng.integers(90000, 10495001, N).astype(np.int64)
    tax = rng.integers(0, 9, N).astype(np.int64)
    flag = rng.choice(np.array([b"A", b"N", b"R"], "S1"), N)
    status = rng.choice(np.array([b"F", b"O"], "S1"), N)
    # one 8-byte prefix, ten values in the last 4 bytes: two key words
    city = np.array([b"station-%04d" % (k % 10) for k in i], "S12")
    return [qty, price, tax, flag, status, city, i % 256, i % 257, i]


def resident(ctx, cols, parts):
    rdd = ctx.parallelize(Columns(*cols), parts).map(_ident).cache()
    assert rdd.count() == N
    return ctx.table(rdd, FIELDS)


Q1_SHAPED = ("select flag, status, sum(qty) as q, sum(price*(100-tax)) as p, "
             "avg(price) as a, min(qty) as lo, max(price) as hi, "
             "count(*) as c from t group by flag, status")


def _sql(text):
    return lambda t, ctx: ctx.sql(text, t=t)


# name -> (query over table t, the slots its write packs to on one device
# (input capacity 8,192) and on four (2,048 a device))
CASES = {
    "char1_keys": (_sql(Q1_SHAPED), 256, 256),          # 18 x 10
    "two_word_bytes_key": (_sql(
        "select city, sum(qty) as q, count(*) as c from t group by city"),
        16, 16),                                          # 1 x 10
    "bare_int_key": (_sql(
        "select tax, sum(price) as p, max(qty) as m from t group by tax"),
        16, 16),                                          # 9
    "derived_int_key": (lambda t, ctx: t.groupBy(
        "tax * 2 + 1", "sum(qty) as q", "count(*) as c"), 32, 32),  # 17
    "bound_256": (_sql(
        "select k256, sum(qty) as q from t group by k256"), 256, 256),
    "bound_257": (_sql(
        "select k257, sum(qty) as q from t group by k257"), 512, 512),
    "class_not_below_cap": (_sql(
        "select kwide, sum(qty) as q from t group by kwide"), "full",
        "full"),
}


def _compiles(spans):
    return [s["args"] for s in spans if s["name"] == "compile"
            and (s.get("args") or {}).get("program") == "narrow"
            and "pack" in s["args"]]


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("ndev", [1, 4])
def test_a_bounded_group_by_equals_the_local_master(masters, ndev, case):
    build, pack1, pack4 = CASES[case]
    pack = pack1 if ndev == 1 else pack4
    cols = columns()
    want = sorted(tuple(r) for r in build(
        resident(masters["local"], cols, ndev), masters["local"]).collect())
    ctx = masters["tpu:%d" % ndev]
    ex = ctx.scheduler.executor
    table = resident(ctx, cols, ndev)
    bounded = ex.combines_bounded
    trace.configure("ring")
    try:
        t = build(table, ctx)
        got = sorted(tuple(r) for r in t.collect())
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert got == want
    assert all(type(a) is type(b) for r, e in zip(got, want)
               for a, b in zip(r, e))
    assert [c["pack"] for c in _compiles(spans)] == [pack]
    assert ex.combines_bounded - bounded == (pack != "full")
    stage_kinds = [str(st.get("kind")) for rec in ctx.scheduler.history[-1:]
                   for st in rec["stage_info"]]
    assert all(k.startswith("array") for k in stage_kinds), stage_kinds
    # the one store the job wrote (alive while `t` holds its chain)
    store = max(ex.shuffle_store.values(), key=lambda s: s["seq"])
    cap = N // ndev
    assert store["leaves"][0].shape[1] == (cap if pack == "full" else pack)


def test_explain_shows_the_bound_and_how_it_was_proved(masters):
    ctx = masters["tpu:1"]
    table = resident(ctx, columns(), 1)
    t = ctx.sql(Q1_SHAPED, t=table)
    text = t.explain()
    assert ("[key-bound] group-agg -> device: at most 180 distinct group "
            "keys a partition (18 x 10 values") in text
    # a two-word byte key: its prefix word allows one value
    assert "(1 x 10 values" in ctx.sql(
        "select city, count(*) as c from t group by city", t=table).explain()
    assert "key-bound" not in resident(masters["local"], columns(), 1) \
        .groupBy("tax", "count(*) as c").explain()


def _spy_launches(ex, monkeypatch):
    seen = []
    real = ex._launch

    def spy(program, fn, *args):
        seen.append((fn, args))
        return real(program, fn, *args)
    monkeypatch.setattr(ex, "_launch", spy)
    return seen


def test_a_bound_not_below_the_capacity_compiles_the_unbounded_program(
        masters, monkeypatch):
    """The bound's class (8,192) is the input's: the map program that
    plan compiles lowers to the text of the one a plan with no bound
    compiles."""
    from dpark_tpu.query import planner
    ctx = masters["tpu:1"]
    ex = ctx.scheduler.executor
    table = resident(ctx, columns(), 1)
    sql = "select kwide, count(*) as c from t group by kwide"
    texts, rows = [], []
    for prove in (True, False):
        if not prove:
            monkeypatch.setattr(planner, "_key_bound", lambda *a: None)
        seen = _spy_launches(ex, monkeypatch)
        t = ctx.sql(sql, t=table)
        assert ("key-bound" in t.explain()) is prove
        rows.append(sorted(tuple(r) for r in t.collect()))
        fn, args = seen[0]              # the map program
        texts.append(fn.lower(*args).as_text())
    assert rows[0] == rows[1] and len(rows[0]) == N
    assert texts[0] == texts[1]


def test_an_unbounded_source_is_still_cut_by_its_count_over_the_budget(
        masters, monkeypatch):
    """A table that is not resident on the device gives the planner no
    load-time ranges to prove a bound from: its write packs every row,
    and over the HBM budget _trim_combined reads the count and cuts."""
    ctx = masters["tpu:1"]
    ex = ctx.scheduler.executor
    cols = columns()
    rows_in = list(zip(*(c.tolist() for c in cols)))
    sql = "select tax, sum(qty) as q, count(*) as c from t group by tax"
    want = sorted(tuple(r) for r in masters["local"].sql(
        sql, t=masters["local"].parallelize(rows_in, 1).asTable(
            FIELDS.split(), "t")).collect())
    source = ctx.parallelize(rows_in, 1).asTable(FIELDS.split(), "t")
    monkeypatch.setattr(conf, "SHUFFLE_HBM_BUDGET", 1)
    trace.configure("ring")
    try:
        t = ctx.sql(sql, t=source)
        assert "key-bound" not in t.explain()
        got = sorted(tuple(r) for r in t.collect())
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert got == want and len(got) == 9
    assert [c["pack"] for c in _compiles(spans)] == ["full"]
    assert [s for s in spans if s["name"] == "readback"
            and s["args"].get("site") == "store.counts"]
    store = max(ex.shuffle_store.values(), key=lambda s: s["seq"])
    assert store["leaves"][0].shape[1] == 16


def _pack(ks, vals, dst, n_dst, out_cap):
    fn = jax.jit(lambda d, k0, k1, v0, v1: collectives._bucketize_combine_cols(
        d, [k0, k1], [v0, v1], n_dst, None, "add", out_cap=out_cap))
    ko, vo, counts, offsets = fn(dst, *ks, *vals)
    return [np.asarray(x) for x in ko + vo], np.asarray(counts), \
        np.asarray(offsets)


@pytest.mark.parametrize("groups", [5, 300])     # under and past out_cap
@pytest.mark.parametrize("n_dst", [1, 3, 20])    # 20: counts past the loop
def test_the_cut_pack_is_the_whole_packs_first_rows(n_dst, groups):
    rng = np.random.default_rng(n_dst * 1000 + groups)
    n, out_cap = 1024, 64
    with jax.enable_x64(True):
        key = rng.integers(0, groups, n).astype(np.int64)
        valid = rng.random(n) < 0.8
        sentinel = np.iinfo(np.int64).max
        ks = [np.where(valid, key // 7, sentinel), key % 7]
        vals = [rng.integers(-50, 50, n).astype(np.int64),
                rng.integers(0, 9, n).astype(np.int64)]
        dst = np.where(valid, key % n_dst, n_dst).astype(np.int32)
        whole = _pack(ks, vals, dst, n_dst, None)
        cut = _pack(ks, vals, dst, n_dst, out_cap)
    for a, b in zip(cut[0], whole[0]):
        assert a.shape == (out_cap,) and np.array_equal(a, b[:out_cap])
    assert np.array_equal(cut[1], whole[1])
    assert np.array_equal(cut[2], whole[2])
    kept = len(set(zip(key[valid] // 7, key[valid] % 7)))
    assert int(cut[1].sum()) == kept        # every kept row, cut or not


@pytest.mark.parametrize("ndev", [1, 4])
def test_a_forged_bound_below_the_key_count_fails_loudly(masters, ndev):
    """300 keys under a bound of 4 (8 slots): the store is short, and the
    job fails at the first read of its counts instead of answering with
    eight groups."""
    ctx = masters["tpu:%d" % ndev]
    i = np.arange(N, dtype=np.int64)
    rows = ctx.parallelize(Columns(i % 300, i), ndev).map(_ident).cache()
    assert rows.count() == N
    right = rows.reduceByKey(operator.add)
    assert len(right.collect()) == 300
    forged = rows.reduceByKey(operator.add)
    forged.dep.key_bound = 4
    with pytest.raises(CombineOverflow, match="capacity 8"):
        forged.collect()
