"""A filter ahead of a shuffle write folds into the write's mask (ISSUE 40).

A filter in the trailing run of filters and maps of a plan that ends in a
shuffle write does not compact: fuse.run_ops ANDs its predicate into the
validity mask the write takes, and the write's own sort moves the failed
rows behind the kept ones, as it moves the padding.  Every other filter
packs as before: before a SortOp, a SegAggOp or a SegMapOp, and in a plan
with no write (a result stage the egest reads as a prefix).

The contracts under test:

* PARITY — filter -> map -> reduceByKey | groupByKey | join | sortByKey
  equals the local master at pass rates 0%, 1.5%, 50% and 100%, on one
  device and on four.
* THE PACK STAYS WHERE A PREFIX IS READ — a filter after sortByKey or after
  groupByKey().mapValues(sum), in a result stage, still packs and is right.
* NO PACK — the lowered map program of filter -> map -> reduceByKey on one
  device holds as many sorts as the chain without the filter (two: the
  combine's order and its pack).
* STREAMED WAVES — the same chains over an ingest too big for one wave fold
  the filter in the wave's map program: the combining stream, and the
  spilled-run stream (a combine per (rid, key), a group, a range sort).
* THE COUNTER — `filters_masked` counts 1 a job there, 0 for a result-
  stage filter; the `compile` event says `filter="mask"` or `"pack"`.
"""

import operator

import jax
import numpy as np
import pytest

from dpark_tpu import Columns, DparkContext, conf, trace
from dpark_tpu.backend.tpu import fuse
from dpark_tpu.backend.tpu.executor import JAXExecutor

N = 1200
I = np.arange(N, dtype=np.int64)
KEYS = (I * 7919 % 613) - 300            # signed, about two rows a key
VALS = I * 31 % 1009

# pass rates of `_keep(t)`: rows with v % 200 < t
RATES = {"none": 0, "1.5pct": 3, "half": 100, "all": 200}


@pytest.fixture(scope="module")
def masters():
    ctxs = {}
    for name in ("local", "tpu:1", "tpu:4"):
        ctxs[name] = DparkContext(name)
        ctxs[name].start()
    yield ctxs
    for c in ctxs.values():
        c.stop()


def _resident(r):
    return r


def _rows(ctx, ndev):
    return ctx.parallelize(Columns(KEYS, VALS), ndev).map(_resident)


def _keep(t):
    return lambda r: r[1] % 200 < t


def _project(r):
    return (r[0] % 97, r[1] * 3 + 1)


def _reduce(rdd, n):
    return rdd.reduceByKey(operator.add, n)


def _group(rdd, n):
    return rdd.groupByKey(n)


def _join(rdd, n):
    other = rdd.ctx.parallelize(
        Columns(np.arange(0, 97, 2, dtype=np.int64),
                np.arange(49, dtype=np.int64) * 5), n).map(_resident)
    return rdd.join(other, n)


def _sort(rdd, n):
    return rdd.sortByKey(numSplits=n)


CHAINS = {"reduceByKey": _reduce, "groupByKey": _group, "join": _join,
          "sortByKey": _sort}


def _canon(chain, rows):
    if chain == "groupByKey":
        return sorted((k, sorted(v)) for k, v in rows)
    if chain == "sortByKey":            # keys in order, equal keys any
        return [r[0] for r in rows], sorted(rows)
    return sorted(rows)


@pytest.mark.parametrize("rate", list(RATES))
@pytest.mark.parametrize("chain", list(CHAINS))
@pytest.mark.parametrize("ndev", [1, 4])
def test_filter_map_write_equals_the_local_master(masters, ndev, chain,
                                                  rate):
    build, t = CHAINS[chain], RATES[rate]
    want = build(_rows(masters["local"], ndev).filter(_keep(t))
                 .map(_project), ndev).collect()
    tctx = masters["tpu:%d" % ndev]
    ex = tctx.scheduler.executor
    masked0 = ex.filters_masked
    got = build(_rows(tctx, ndev).filter(_keep(t)).map(_project),
                ndev).collect()
    assert _canon(chain, got) == _canon(chain, want)
    if t == 0:
        assert got == []
    # the map side's filter folded into its write wherever there is one
    # (one device sorts by key in one narrow stage: no write)
    folded = 0 if (chain == "sortByKey" and ndev == 1) else 1
    assert ex.filters_masked - masked0 >= folded
    if chain == "reduceByKey":
        assert ex.filters_masked - masked0 == 1


@pytest.fixture()
def tiny_waves():
    old = conf.STREAM_CHUNK_ROWS
    conf.STREAM_CHUNK_ROWS = 100
    yield
    conf.STREAM_CHUNK_ROWS = old


# stream -> (chain, partitions it writes for `ndev` devices): more than
# the mesh rides the spilled-run stream.  One device sorts by key in one
# narrow stage, with no write to stream.
STREAMS = {"combine": ("reduceByKey", lambda ndev: ndev),
           "spilled_combine": ("reduceByKey", lambda ndev: 2 * ndev + 1),
           "spilled_group": ("groupByKey", lambda ndev: 2 * ndev + 1),
           "range": ("sortByKey", lambda ndev: ndev),
           "spilled_range": ("sortByKey", lambda ndev: 2 * ndev)}


@pytest.mark.parametrize("ndev,stream", [
    (ndev, stream) for stream in STREAMS for ndev in (1, 4)
    if ndev == 4 or STREAMS[stream][0] != "sortByKey"])
def test_streamed_waves_fold_the_filter(masters, tiny_waves, ndev, stream):
    chain, parts = STREAMS[stream]

    def job(ctx):
        rows = ctx.parallelize(Columns(KEYS, VALS), ndev)
        return CHAINS[chain](rows.filter(_keep(100)).map(_project),
                             parts(ndev)).collect()
    tctx = masters["tpu:%d" % ndev]
    ex = tctx.scheduler.executor
    masked0 = ex.filters_masked
    got = job(tctx)
    assert _canon(chain, got) == _canon(chain, job(masters["local"]))
    assert any("pipeline" in st
               for st in tctx.scheduler.history[-1]["stage_info"])
    assert ex.filters_masked - masked0 >= 1


def _sum(vs):
    return sum(vs)


# a filter whose consumer reads a prefix: a result stage after the shuffle
TAILS = {
    "sortByKey": lambda rdd, n: rdd.sortByKey(numSplits=n)
    .filter(lambda r: r[0] % 3 == 1),
    "groupByKey_sum": lambda rdd, n: rdd.groupByKey(n).mapValues(_sum)
    .filter(lambda r: r[1] % 2 == 0),
}


@pytest.mark.parametrize("tail", list(TAILS))
@pytest.mark.parametrize("ndev", [1, 4])
def test_a_filter_before_a_read_prefix_still_packs(masters, ndev, tail):
    build = TAILS[tail]
    want = build(_rows(masters["local"], ndev).map(_project),
                 ndev).collect()
    tctx = masters["tpu:%d" % ndev]
    ex = tctx.scheduler.executor
    masked0 = ex.filters_masked
    got = build(_rows(tctx, ndev).map(_project), ndev).collect()
    if tail == "sortByKey":
        assert [r[0] for r in got] == [r[0] for r in want]
    assert sorted(got) == sorted(want) and got
    assert ex.filters_masked == masked0


def _odd_keys(r):
    return r[0] % 2 == 1


@pytest.mark.parametrize("ops,writes,folded", [
    (("filter", "map"), True, 1),
    (("map", "filter", "filter", "map"), True, 2),
    (("filter", "map"), False, 0),              # a result stage
    (("filter", "sort", "map"), True, 0),       # a prefix for the sort
    (("filter", "sort", "filter"), True, 1),
    (("segagg", "filter"), True, 1),
    (("filter", "segagg"), True, 0),
    (("map", "map"), True, 0),
], ids=lambda v: "-".join(v) if isinstance(v, tuple) else str(v))
def test_which_filters_fold(ops, writes, folded):
    make = {"filter": lambda: fuse.FilterOp(_odd_keys),
            "map": lambda: fuse.MapOp(_project),
            "sort": lambda: fuse.SortOp(True),
            "segagg": lambda: fuse.SegAggOp.__new__(fuse.SegAggOp)}
    assert fuse.masked_filters([make[o]() for o in ops], writes) == folded


def _run_ops(ops, lv, n, writes):
    return fuse.run_ops(ops, [np.asarray(x) for x in lv], np.int32(n),
                        writes)


def test_run_ops_masks_where_it_folds_and_packs_where_it_does_not():
    ops = [fuse.FilterOp(_odd_keys), fuse.MapOp(_project)]
    treedef = jax.tree_util.tree_structure((0, 0))
    specs = [(np.dtype(np.int64), ())] * 2
    for op in ops:
        treedef, specs = op.probe(treedef, specs)
    keys, vals = np.arange(10, dtype=np.int64), np.arange(10) * 10
    lv, n, valid = _run_ops(ops, [keys, vals], 8, True)
    assert int(n) == 8
    assert np.asarray(valid).tolist() == [
        i % 2 == 1 and i < 8 for i in range(10)]
    assert np.asarray(lv[0]).tolist() == (keys % 97).tolist()  # in place
    lv, n, valid = _run_ops(ops, [keys, vals], 8, False)
    assert valid is None and int(n) == 4
    assert np.asarray(lv[0])[:4].tolist() == [1, 3, 5, 7]


@pytest.fixture()
def map_program_sorts(monkeypatch):
    """stablehlo.sort ops in the lowered text of every narrow program a
    job launches, the map program first."""
    seen = []
    launch = JAXExecutor._launch

    def spy(self, program, fn, *args):
        if program == "narrow":
            seen.append(fn.lower(*args).as_text().count("stablehlo.sort"))
        return launch(self, program, fn, *args)
    monkeypatch.setattr(JAXExecutor, "_launch", spy)
    return seen


def test_the_map_program_sorts_as_if_there_were_no_filter(
        masters, map_program_sorts):
    tctx = masters["tpu:1"]
    plain = _rows(tctx, 1).map(_project)
    assert sorted(_reduce(plain, 1).collect())
    without = map_program_sorts[0]
    del map_program_sorts[:]
    filtered = _rows(tctx, 1).filter(_keep(100)).map(_project)
    assert sorted(_reduce(filtered, 1).collect())
    assert without == 2
    assert map_program_sorts[0] == without


def test_the_compile_event_says_mask_or_pack():
    c = DparkContext("tpu:1")
    c.start()
    trace.configure("ring")
    try:
        _reduce(_rows(c, 1).filter(lambda r: r[1] % 5 < 2)
                .map(_project), 1).collect()
        _rows(c, 1).map(_project).filter(lambda r: r[1] % 7 < 2).collect()
        events = [r["args"] for r in trace.snapshot()
                  if r["name"] == "compile" and "filter" in r["args"]]
    finally:
        trace.configure("off")
        c.stop()
    assert {(a["program"], a["filter"]) for a in events} == {
        ("narrow", "mask"), ("narrow", "none"), ("narrow", "pack")}
