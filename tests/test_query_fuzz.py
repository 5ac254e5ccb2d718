"""Query parity fuzzer (ISSUE 13 satellite): random select / filter /
group-by / join trees over seeded int/float/string columns must be
BIT-IDENTICAL between the device query plan (DPARK_QUERY on) and the
host object path (DPARK_QUERY off, the pre-plan row path) — on the
local master and on a 2-device tpu mesh, over in-memory and
tabular-file sources and over a table RESIDENT ON THE DEVICE (ISSUE 39:
a cached columnar RDD, its string column a fixed-width byte string; the
scan, filter and projection then run inside the stage program).  Float columns are seeded integer-valued so
device f64 folds are exact (the documented GROUP_AGG_REWRITE-style
float caveat is about reassociation, not correctness).

Plus one chaos cell: a grouped query over a coded shuffle under
injected fetch faults completes with resubmits == recomputes == 0 —
erasure decode, not lineage replay, absorbs the failures."""

import os
import random

import pytest


def make_rows(rng, n):
    return [(rng.randint(0, 12),
             rng.randint(-40, 40),
             float(rng.randint(-30, 30)),
             "w%d" % rng.randint(0, 6))
            for _ in range(n)]


FIELDS = "k a f s"

WHERES = [
    "a > {c}", "a <= {c}", "a % {m} == {r}", "f >= {c}",
    "s == 'w{j}'", "a > {c} and s == 'w{j}'",
    "not (a % {m} == {r})", "k + a < {c}",
]

AGG_POOL = ["sum(a) as sa", "count(*) as c", "avg(f) as af",
            "min(a) as mn", "max(f) as mx", "avg(a) as aa",
            "sum(a * 2 + f) as sx", "max(a) as ma"]


def build_query(rng, resident=False):
    """A random DSL program as a list of (op, params), applied
    identically on both sides.  `resident`: only what the planner
    lowers over a table resident on the device (no join, no float
    comparison: float32 there), so that every such cell is a planned
    query and never the host chain."""
    prog = []
    if rng.random() < 0.7:
        w = rng.choice([w for w in WHERES
                        if not (resident and w.startswith("f "))]).format(
            c=rng.randint(-20, 20), m=rng.randint(2, 5),
            r=rng.randint(0, 1), j=rng.randint(0, 6))
        prog.append(("where", w))
    if rng.random() < 0.3:
        prog.append(("select",
                     ["k", "a * %d + 1 as a" % rng.randint(1, 3),
                      "f", "s"]))
    shape = rng.choice(["group", "group", "scan"] if resident else
                       ["group", "group", "join", "join_group", "scan"])
    if shape in ("join", "join_group"):
        on = rng.choice(["k", "s"])
        prog.append(("join", on, rng.randint(0, 2 ** 30)))
    if shape in ("group", "join_group"):
        keys = rng.choice([["k"], ["s"], ["k", "s"], ["k % 3"]])
        if shape == "join_group":
            keys = rng.choice([["k"], ["s"], ["dv"]])
        aggs = rng.sample(AGG_POOL, rng.randint(1, 3))
        if shape == "join_group":
            # joined-group keys/args must be plain joined columns
            aggs = rng.sample(["sum(a) as sa", "count(*) as c",
                               "min(a) as mn", "avg(f) as af"],
                              rng.randint(1, 2))
        prog.append(("group", keys, aggs))
    if rng.random() < 0.4:
        prog.append(("sort",))
    return prog


def apply_query(ctx, table, prog, as_bytes=False):
    """`as_bytes`: the string column holds `bytes` (an S<w> column of a
    resident table), so its literals and the join's dimension do too."""
    t = table
    for step in prog:
        op = step[0]
        if op == "where":
            t = t.where(step[1].replace("'w", "b'w") if as_bytes
                        else step[1])
        elif op == "select":
            t = t.select(*step[1])
        elif op == "join":
            _, on, seed2 = step
            r2 = random.Random(seed2)
            if on == "k":
                dim = [(i, r2.randint(0, 99)) for i in range(13)]
            else:
                dim = [(b"w%d" % i if as_bytes else "w%d" % i,
                        r2.randint(0, 99)) for i in range(7)]
            dt = ctx.parallelize(dim, 2).asTable([on, "dv"], "dim")
            t = t.join(dt, on=on)
        elif op == "group":
            t = t.groupBy(step[1], *step[2])
        elif op == "sort":
            t = t.sort(t.fields[0])
    return t


def canonical(rows):
    return sorted(tuple(r) for r in rows)


def _run_cell(master, seed, source):
    from dpark_tpu import DparkContext, conf
    rng = random.Random(seed)
    rows = make_rows(rng, rng.choice([200, 1500]))
    as_bytes = source == "resident"
    prog = build_query(rng, resident=as_bytes)
    if as_bytes:
        rows = [r[:3] + (r[3].encode(),) for r in rows]
    ctx = DparkContext(master)
    lctx = DparkContext("local")
    tmpdir = None
    try:
        ctx.start()
        lctx.start()

        def table_for(c):
            if source == "tabular":
                return c.tabular(tmpdir).asTable("t")
            if source == "resident" and c is ctx:
                return _resident_table(c, rows)
            return c.parallelize(rows, 4).asTable(FIELDS, "t")

        if source == "tabular":
            import tempfile
            from dpark_tpu.tabular import write_tabular
            tmpdir = tempfile.mkdtemp()
            write_tabular(os.path.join(tmpdir, "part-00000.tab"),
                          FIELDS.split(), rows, chunk_rows=256)
        conf.QUERY_PLAN = True
        dev = apply_query(ctx, table_for(ctx), prog, as_bytes)
        if as_bytes:
            assert dev._planned() is not None, dev.explain()
        got = canonical(dev.collect())
        got_n = dev.count()
        conf.QUERY_PLAN = False
        try:
            host = apply_query(lctx, table_for(lctx), prog, as_bytes)
            expect = canonical(host.collect())
            expect_n = host.count()
        finally:
            conf.QUERY_PLAN = True
        assert got == expect, \
            "parity violation for %r (seed %d): %r vs %r" \
            % (prog, seed, got[:3], expect[:3])
        assert got_n == expect_n == len(expect), (prog, seed)
    finally:
        ctx.stop()
        lctx.stop()


def _identity(r):
    return r


def _resident_table(ctx, rows):
    """The rows as a cached columnar RDD resident on the device: int64,
    int64, float32 (integer-valued: exact) and S2 columns."""
    import numpy as np
    from dpark_tpu import Columns
    k, a, f, s = zip(*rows)
    rdd = ctx.parallelize(
        Columns(np.array(k, np.int64), np.array(a, np.int64),
                np.array(f, np.float32), np.array(s, "S2")),
        ctx.default_parallelism).map(_identity).cache()
    assert rdd.count() == len(rows)
    t = ctx.table(rdd, FIELDS)
    assert t.plan is not None and t.plan.device is not None
    return t


@pytest.mark.parametrize("seed", range(10))
def test_query_parity_resident(seed):
    _run_cell("tpu:1" if seed % 2 else "tpu:2", 200 + seed, "resident")


@pytest.mark.parametrize("seed", range(12))
def test_query_parity_local(seed):
    _run_cell("local", seed,
              "tabular" if seed % 3 == 0 else "memory")


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.mesh
def test_query_parity_tpu2(seed):
    _run_cell("tpu:2", 100 + seed,
              "tabular" if seed % 2 == 0 else "memory")


def test_query_chaos_coded_shuffle():
    """Chaos cell: a grouped query over a coded shuffle under
    shuffle.fetch:p=0.2 — bit-identical to the clean run with ZERO
    resubmits/recomputes (decode absorbs every injected failure)."""
    from dpark_tpu import DparkContext, coding, conf, faults
    rows = make_rows(random.Random(77), 3000)
    ctx = DparkContext("local")
    ctx.start()
    try:
        def q():
            t = ctx.parallelize(rows, 4).asTable(FIELDS, "t")
            return canonical(
                t.where("a > -10")
                 .groupBy("k", "sum(a) as sa", "count(*) as c",
                          "avg(f) as af").collect())
        conf.QUERY_PLAN = True
        clean = q()
        coding.configure("rs(4,2)")
        faults.configure("shuffle.fetch:p=0.2,seed=7")
        try:
            chaotic = q()
            fired = faults.stats()["shuffle.fetch"]["fired"]
            rec = ctx.scheduler.history[-1]
        finally:
            faults.configure(None)
            coding.configure(None)
        assert chaotic == clean
        assert fired > 0, "injection never fired"
        assert rec.get("resubmits", 0) == 0, rec
        assert rec.get("recomputes", 0) == 0, rec
        assert rec["decodes"]["repair"] > 0, rec.get("decodes")
        assert rec["decodes"]["decode_failures"] == 0
    finally:
        ctx.stop()
