"""Joins of the query plane over tables resident on the device: a
left-deep chain of equi-joins, keys named differently on each side, is
lowered whole onto the device join (each side's scan, filter and
projection inside its own stage program), single-table predicates are
pushed to the scan they read at any depth, and every int64 sum over a
join is proved from a bound on the join's rows: the other side's rows
where a join key is shown unique (read when the table was made, or by
one np.unique over a driver-resident key), else left x right.  TPC-H Q3
at toy size is the running example; the benchmark's cell `tpch.q3` runs
it at scale (perf/jobs/tpch_q3.py)."""

import numpy as np
import pytest

from test_query_resident import Q1, CUTOFF, lineitem, resident_table

DATE = 9204             # 1995-03-15 as days since 1970-01-01
SEGMENTS = np.array([b"AUTOMOBILE", b"BUILDING", b"FURNITURE",
                     b"HOUSEHOLD", b"MACHINERY"], "S10")
FIELDS = {"customer": "c_custkey c_mktsegment",
          "orders": "o_orderkey o_custkey o_orderdate o_shippriority",
          "lineitem": "l_orderkey l_extendedprice l_discount l_shipdate"}
Q3 = ("select l_orderkey, sum(l_extendedprice*(100-l_discount)) as "
      "revenue, o_orderdate, o_shippriority from customer "
      "join orders on c_custkey = o_custkey "
      "join lineitem on l_orderkey = o_orderkey "
      "where c_mktsegment == b'BUILDING' and o_orderdate < %d "
      "and l_shipdate > %d "
      "group by l_orderkey, o_orderdate, o_shippriority "
      "order by revenue desc, o_orderdate" % (DATE, DATE))


def tpch(seed, customers):
    """customer, orders and lineitem after TPC-H's population rules, in
    key order: sparse order keys (8 of every 32), no order by a
    customer whose key is divisible by 3, 1..7 lines an order shipped
    1..121 days after it."""
    rng = np.random.default_rng(seed)
    nc, no = customers, customers * 10
    customer = [np.arange(1, nc + 1, dtype=np.int64),
                SEGMENTS[rng.integers(0, 5, nc)]]
    i = np.arange(no, dtype=np.int64)
    pick = rng.integers(0, nc - nc // 3, no)
    date = rng.integers(DATE - 200, DATE + 100, no).astype(np.int32)
    orders = [32 * (i // 8) + i % 8 + 1, 3 * (pick // 2) + pick % 2 + 1,
              date, rng.integers(0, 2, no).astype(np.int32)]
    lines = rng.integers(1, 8, no)
    n = int(lines.sum())
    lineitem_ = [np.repeat(orders[0], lines),
                 rng.integers(90000, 10495001, n).astype(np.int64),
                 rng.integers(0, 11, n).astype(np.int64),
                 (np.repeat(date, lines)
                  + rng.integers(1, 122, n)).astype(np.int32)]
    return {"customer": customer, "orders": orders, "lineitem": lineitem_}


def q3_reference(t):
    """Q3 in numpy over the host columns, no dpark_tpu code: every group
    (l_orderkey, revenue, o_orderdate, o_shippriority), revenue down,
    date up, key up."""
    ckey, cseg = t["customer"]
    okey, ocust, odate, oprio = t["orders"]
    lkey, price, disc, ship = t["lineitem"]
    keep = (odate < DATE) & np.isin(ocust, ckey[cseg == b"BUILDING"])
    lkeep = (ship > DATE) & np.isin(lkey, okey[keep])
    groups, inv = np.unique(lkey[lkeep], return_inverse=True)
    sums = np.zeros(len(groups), np.int64)
    np.add.at(sums, inv, (price * (100 - disc))[lkeep])
    at = np.searchsorted(okey, groups)
    rows = zip(groups.tolist(), sums.tolist(), odate[at].tolist(),
               oprio[at].tolist())
    return sorted(rows, key=lambda r: (-r[1], r[2], r[0]))


def driver_table(ctx, cols, fields):
    from dpark_tpu import Columns
    return ctx.parallelize(Columns(*cols), 2).asTable(fields)


def tables(ctx, data, sources="rrr"):
    """The three tables, each (r)esident on the device or a (d)river
    scan, in the order customer, orders, lineitem."""
    out = {}
    for (name, cols), src in zip(data.items(), sources):
        out[name] = resident_table(ctx, cols, FIELDS[name]) if src == "r" \
            else driver_table(ctx, cols, FIELDS[name])
    return out


@pytest.fixture(scope="module")
def masters():
    from dpark_tpu import DparkContext
    dev, host = DparkContext("tpu:1"), DparkContext("local")
    dev.start()
    host.start()
    yield dev, host
    dev.stop()
    host.stop()


def run_on_device(ctx, build):
    ex = ctx.scheduler.executor
    before = (len(ctx.scheduler.history), ex.scan_rows_device,
              ex.scan_rows_host, ex.query_joins_device)
    table = build()
    rows = [tuple(r) for r in table.collect()]
    return rows, table, {
        "records": ctx.scheduler.history[before[0]:],
        "device_rows": ex.scan_rows_device - before[1],
        "host_rows": ex.scan_rows_host - before[2],
        "joins": ex.query_joins_device - before[3]}


def kinds_and_reasons(left):
    stages = [st for rec in left["records"] for st in rec["stage_info"]]
    return ([str(st.get("kind")) for st in stages],
            [st.get("fallback_reason") or st.get("degrade_reason")
             for st in stages])


def reasons_of(table):
    return [f["reason"] for f in getattr(table.rdd, "_query_fallbacks",
                                         None) or ()]


# -- the front ---------------------------------------------------------------

FRONT = {
    "two_joins_different_names": (
        "select c_custkey, o_orderkey, l_shipdate from customer "
        "join orders on c_custkey = o_custkey "
        "join lineitem on l_orderkey = o_orderkey",
        [("o_orderkey", "l_orderkey"), ("c_custkey", "o_custkey")]),
    "sides_written_the_other_way": (
        "select c_custkey, o_orderkey, l_shipdate from customer "
        "join orders on o_custkey = c_custkey "
        "join lineitem on o_orderkey = l_orderkey",
        [("o_orderkey", "l_orderkey"), ("c_custkey", "o_custkey")]),
    "qualified_by_table": (
        "select c_custkey, o_orderkey, l_shipdate from customer "
        "join orders on orders.o_custkey = customer.c_custkey "
        "join lineitem on lineitem.l_orderkey = orders.o_orderkey",
        [("o_orderkey", "l_orderkey"), ("c_custkey", "o_custkey")]),
}


@pytest.mark.parametrize("case", sorted(FRONT))
def test_the_front_parses_a_chain_of_joins(masters, case):
    from dpark_tpu.query.logical import Join
    _, host = masters
    sql, keys = FRONT[case]
    data = tpch(3, 30)
    t = host.sql(sql, **tables(host, data, "ddd"))
    node, found = t.plan.children[0], []
    while isinstance(node, Join):
        found.append((node.on, node.right_on))
        node = node.children[0]
    assert found == keys
    okeys = dict(zip(data["orders"][0].tolist(),
                     data["orders"][1].tolist()))
    rows = [tuple(r) for r in t.collect()]
    assert len(rows) == len(data["lineitem"][0])
    assert all(okeys[o] == c for c, o, _ in rows)


@pytest.mark.parametrize("order,expect", [
    ("revenue desc, o_orderdate", lambda r: (-r[1], r[2])),
    ("o_orderdate desc, revenue", lambda r: (-r[2], r[1])),
    ("o_orderdate asc, revenue desc", lambda r: (r[2], -r[1])),
    ("o_shippriority, o_orderdate desc, l_orderkey",
     lambda r: (r[3], -r[2], r[0])),
])
def test_order_by_keeps_a_direction_a_key(masters, order, expect):
    """Per-key directions on the planned egest sort and on the host row
    chain's sort alike."""
    dev, host = masters
    data = tpch(5, 60)
    sql = Q3.split(" order by ")[0] + " order by " + order
    ref = sorted(q3_reference(data), key=lambda r: (expect(r), r[0]))
    for ctx in (dev, host):
        t = ctx.sql(sql, **tables(ctx, data,
                                  "rrr" if ctx is dev else "ddd"))
        rows = [tuple(r) for r in t.collect()]
        assert [expect(r) for r in rows] == [expect(r) for r in ref]
        assert sorted(rows) == sorted(ref)


# -- Q3 on the device --------------------------------------------------------

@pytest.mark.parametrize("seed,customers,sources", [
    (43, 150, "rrr"), (44, 60, "rrr"), (45, 150, "drr"), (46, 90, "ddd")])
def test_q3_equals_the_host_and_a_numpy_reference(masters, seed, customers,
                                                  sources):
    dev, host = masters
    data = tpch(seed, customers)
    loaded = tables(dev, data, sources)
    rows, t, left = run_on_device(dev, lambda: dev.sql(Q3, **loaded))
    pq = t._planned()
    assert pq is not None and pq.mode == "join_group", reasons_of(t)
    ref = q3_reference(data)
    assert sorted(rows) == sorted(ref) and len(ref) > 5
    assert [(-r[1], r[2]) for r in rows] == [(-r[1], r[2]) for r in ref]
    expect = [tuple(r) for r in host.sql(
        Q3, **tables(host, data, "ddd")).collect()]
    assert rows == expect                       # integer for integer
    assert all(type(v) is int for r in rows for v in r)
    kinds, reasons = kinds_and_reasons(left)
    assert all(k.startswith("array") for k in kinds) and not any(reasons)
    assert len(left["records"]) == 1            # ONE job
    resident = [n for n, s in zip(data, sources) if s == "r"]
    assert left["device_rows"] == sum(len(data[n][0]) for n in resident)
    assert left["host_rows"] == sum(len(data[n][0]) for n, s in zip(
        data, sources) if s == "d")
    assert left["joins"] == (2 if sources == "rrr" else 0)
    text = pq.explain()
    assert "[unique-key]" in text and "no key proved unique" not in text
    # each predicate went to the scan it reads, the derived revenue too
    assert [len([k for k, _ in seg.steps if k == "filter"])
            for seg in pq.segs] == [1, 1, 1]
    assert any(n.startswith("__j") for n in pq.segs[2].out)


def test_query_joins_device_and_the_plan_span_read_two(masters):
    from dpark_tpu import trace
    dev, _ = masters
    data = tpch(47, 120)
    loaded = tables(dev, data)
    trace.configure("ring")
    try:
        rows, _, left = run_on_device(dev, lambda: dev.sql(Q3, **loaded))
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert sorted(rows) == sorted(q3_reference(data))
    assert left["joins"] == 2
    job = left["records"][0]["id"]
    plans = [s for s in spans if s["name"] == "query.plan"
             and s.get("job") == job]
    assert [(s["args"]["joins"], s["args"]["mode"], s["args"]["source"])
            for s in plans] == [(2, "join_group", "device")]
    assert len([s for s in spans if s["name"] == "join"
                and s.get("job") == job]) == 2


# -- unique keys and the int64 proof ----------------------------------------

def test_a_table_shows_its_unique_columns(masters):
    from dpark_tpu import DparkContext
    dev, _ = masters
    data = tpch(48, 40)
    seen = {}
    for master in ("tpu:1", "tpu:2"):
        ctx = dev if master == "tpu:1" else DparkContext(master)
        ctx.start()
        try:
            t = {n: resident_table(ctx, c, FIELDS[n],
                                   parts=ctx.default_parallelism)
                 for n, c in data.items()}
            seen[master] = {n: x.plan.device["unique"]
                            for n, x in t.items()}
        finally:
            if ctx is not dev:
                ctx.stop()
    assert seen["tpu:1"] == seen["tpu:2"] == {
        "customer": [True, False], "orders": [True, False, False, False],
        "lineitem": [False, False, False, False]}


def _big_price(data):
    """One price so large that only a join bounded by unique keys keeps
    the revenue sum provably inside int64."""
    data["lineitem"][1][0] = 10 ** 12
    return data


@pytest.mark.parametrize("dup,sources", [
    ("customer", "rrr"), ("orders", "rrr"), ("customer", "drr"),
    ("orders", "ddd")])
def test_a_repeated_key_is_not_unique_and_the_sum_declines(masters, dup,
                                                           sources):
    dev, host = masters
    data = _big_price(tpch(49, 150))
    loaded = tables(dev, data, sources)
    rows, t, _ = run_on_device(dev, lambda: dev.sql(Q3, **loaded))
    assert t._planned() is not None            # unique keys: admitted
    assert sorted(rows) == sorted(q3_reference(data))
    key = data[dup][0]
    key[1] = key[0]                             # one key twice
    if sources[0 if dup == "customer" else 1] == "r":
        loaded = resident_table(dev, data[dup], FIELDS[dup])
        assert loaded.plan.device["unique"][0] is False
    loaded = tables(dev, data, sources)
    rows, t, left = run_on_device(dev, lambda: dev.sql(Q3, **loaded))
    assert t._planned() is None
    reasons = reasons_of(t)
    assert any("may overflow int64" in r and "no key proved unique" in r
               for r in reasons), reasons
    expect = [tuple(r) for r in host.sql(
        Q3, **tables(host, data, "ddd")).collect()]
    assert rows == expect
    # the row chain's combine folded Python ints on the host
    assert any(r and "Python ints" in r
               for r in kinds_and_reasons(left)[1])


@pytest.mark.parametrize("sources", ["dd", "rr", "dr"])
def test_a_many_to_many_sum_declines_and_the_host_is_exact(masters,
                                                           sources):
    """3 x 3 rows on one key, each value 2**61: the old bound (the larger
    side's rows) admitted 2**61 x 3 and the device wrapped to 2**61;
    the bound of a join with no unique key is 3 x 3 rows."""
    dev, host = masters
    a = [np.ones(3, np.int64), np.full(3, 2 ** 61, np.int64)]
    b = [np.ones(3, np.int64), np.arange(3, dtype=np.int64)]
    sql = "select k, sum(v) as s from a join b on k group by k"

    def make(ctx, cols, fields, src):
        return resident_table(ctx, cols, fields) if src == "r" \
            else driver_table(ctx, cols, fields)

    loaded = {"a": make(dev, a, "k v", sources[0]),
              "b": make(dev, b, "k w", sources[1])}
    rows, t, left = run_on_device(dev, lambda: dev.sql(sql, **loaded))
    assert t._planned() is None
    assert any("over 9 rows" in r and "no key proved unique" in r
               for r in reasons_of(t)), reasons_of(t)
    assert rows == [(1, 9 * 2 ** 61)]
    assert rows == [tuple(r) for r in host.sql(
        sql, a=driver_table(host, a, "k v"),
        b=driver_table(host, b, "k w")).collect()]


@pytest.mark.parametrize("sources", ["dd", "rr"])
def test_a_one_to_many_join_stays_admitted(masters, sources):
    """The dimension's key is unique: the join yields at most the fact's
    rows, and the sum is proved over those."""
    dev, _ = masters
    fact = [np.arange(6, dtype=np.int64) % 2,
            np.full(6, 2 ** 60, np.int64)]
    dim = [np.arange(2, dtype=np.int64), np.array([7, 8], np.int64)]
    sql = "select w, sum(v) as s from f join d on k group by w order by w"

    def make(cols, fields, src):
        return resident_table(dev, cols, fields) if src == "r" \
            else driver_table(dev, cols, fields)

    loaded = {"f": make(fact, "k v", sources[0]),
              "d": make(dim, "k w", sources[1])}
    rows, t, left = run_on_device(dev, lambda: dev.sql(sql, **loaded))
    assert t._planned() is not None, reasons_of(t)
    assert "k unique on the right" in t._planned().explain()
    assert rows == [(7, 3 * 2 ** 60), (8, 3 * 2 ** 60)]


# -- what still declines -----------------------------------------------------

def test_a_bushy_tree_and_a_cross_side_predicate_decline(masters):
    dev, host = masters
    data = tpch(50, 60)
    t = tables(dev, data)
    bushy = t["customer"].join(
        t["orders"].join(t["lineitem"], ("o_orderkey", "l_orderkey")),
        ("c_custkey", "o_custkey"))
    cross_sql = ("select o_orderdate, count(*) as c from orders join "
                 "lineitem on o_orderkey = l_orderkey "
                 "where l_shipdate < o_orderdate + 30 group by o_orderdate")
    cross = dev.sql(cross_sql, **t)
    for table, why in ((bushy, "bushy join tree"),
                       (cross, "cross-side post-join predicate")):
        assert table._planned() is None
        assert any(why in r for r in reasons_of(table)), reasons_of(table)
    hcross = host.sql(cross_sql, **tables(host, data, "ddd"))
    assert sorted(tuple(r) for r in cross.collect()) == sorted(
        tuple(r) for r in hcross.collect())
    assert len(bushy.collect()) == len(data["lineitem"][0])


# -- Q1 is untouched ---------------------------------------------------------

Q1_EXPLAIN = """plan (group):
    Project(l_returnflag, l_linestatus, sum_qty, sum_base_price, \
sum_disc_price, sum_charge, avg_qty, avg_price, avg_disc, count_order)
      Sort(l_returnflag, l_linestatus)
        GroupAgg(keys=['l_returnflag', 'l_linestatus'] aggs=['agg0:sum', \
'agg1:sum', 'agg2:sum', 'agg3:sum', 'agg4:avg', 'agg5:avg', 'agg6:avg', \
'agg7:count'])
          Filter(l_shipdate <= 10471)
            Scan(table cols=*)
decisions:
  [scan-resident] scan -> device: table 'table' is resident on the device \
(3000 rows): its filters and projections are the narrow stage's
  [prune-columns] scan -> device: scan reads ['l_quantity', \
'l_extendedprice', 'l_discount', 'l_tax', 'l_returnflag', 'l_linestatus', \
'l_shipdate'] of 7 columns
  [pushdown-predicate] scan -> device: 1 predicate(s) evaluate as \
vectorized array programs before any row materializes
  [key-bound] group-agg -> device: at most 180 distinct group keys a \
partition (18 x 10 values by the key words' load-time ranges): the \
combine packs to that bound's capacity class where it is below the input's
  [key-words] group-agg -> device: byte-string group key(s) \
['l_returnflag', 'l_linestatus'] ride as int64 key words (2 in all), \
rebuilt as bytes at egest
  [lower-group-agg] group-agg -> device: lowered as reduce over the \
tuple-key exchange (aggs: sum,sum,sum,sum,avg,avg,avg,count; 6 \
accumulator leaves)
  [egest] result -> columns: the groups finish over numpy columns: keys, \
aggregates and 2 result-finishing op(s) as picks, admitted programs and one \
stable lexsort"""


def test_q1_plans_as_before(masters):
    dev, _ = masters
    t = dev.sql(Q1 % CUTOFF, lineitem=resident_table(dev, lineitem(39,
                                                                   3000)))
    assert t.explain() == Q1_EXPLAIN


def test_a_string_key_joined_twice_shares_one_dictionary(masters):
    """Driver-resident string keys ride dictionary-encoded: the key of
    the second join is the first one's, so every side that holds it
    shares one TokenDict and the ids agree across both joins."""
    dev, host = masters
    sql = ("select k, sum(v) as s, sum(w) as sw, count(*) as n from a "
           "join b on k join c on k = kc group by k order by k")
    got = {}
    for ctx in (dev, host):
        a = ctx.parallelize([("k%d" % (i % 5), i) for i in range(40)],
                            2).asTable(["k", "v"], "a")
        b = ctx.parallelize([("k%d" % i, 10 * i) for i in range(5)],
                            2).asTable(["k", "w"], "b")
        c = ctx.parallelize([("k%d" % i, 100 * i) for i in range(4)],
                            2).asTable(["kc", "x"], "c")
        t = ctx.sql(sql, a=a, b=b, c=c)
        got[ctx] = ([tuple(r) for r in t.collect()], t._planned())
    assert got[dev][0] == got[host][0] == [
        ("k%d" % k, sum(i for i in range(40) if i % 5 == k), 80 * k, 8)
        for k in range(4)]
    assert got[dev][1].mode == "join_group"


# -- the finish over columns -------------------------------------------------

def _plant_q3_ties(pq, raw):
    """Groups that tie on (revenue, o_orderdate), on revenue alone and on
    the date alone, spread through the collected order: only that order
    decides between them."""
    out = []
    for i, (key, acc) in enumerate(raw):
        if i % 3 == 0:
            key, acc = (key[0], DATE - 7, key[2]), (10 ** 9,)
        elif i % 5 == 0:
            key, acc = (key[0], DATE - 9 - i % 2, key[2]), (10 ** 9 + 1,)
        elif i % 7 == 0:
            key = (key[0], DATE - 7, key[2])
        out.append((key, acc))
    return out


def _q1_sums_past_2_53(pq, raw):
    """Q1's sums near 2**59 over counts of 3, 7 and 11: an average is the
    correctly rounded quotient of two ints that float64 cannot hold."""
    leaves = pq._group["leaves"]
    out = []
    for i, (key, acc) in enumerate(raw):
        count = (3, 7, 11)[i % 3]
        out.append((key, tuple(
            count if op == "count" else 2 ** 59 + 12345 * i + 2 * j + 1
            for j, (op, _) in enumerate(leaves))))
    return out


def _q3_table(ctx):
    return ctx.sql(Q3, **tables(ctx, tpch(51, 150)))


def _q1_table(ctx, sql=Q1 % CUTOFF, seed=52):
    return ctx.sql(sql, lineitem=resident_table(ctx, lineitem(seed, 3000)))


def _words_table(ctx):
    rows = [("w%d" % (i % 9), i % 4, i, float(i % 13) / 4)
            for i in range(400)]
    return ctx.parallelize(rows, 2).asTable("s k a f", "t")


def _names_table(ctx, order):
    """A two-word S12 key whose bytes run high (negative signed words),
    hold a NUL inside and share prefixes across the word boundary."""
    names = np.array([b"\xffzz", b"A", b"A\x00B", b"ABCDEFGHIJKL",
                      b"ABCDEFGHIJK", b"\x80", b"zz\xff", b"ABCDEFGH\xff",
                      b"ABCDEFGH"], "S12")
    rng = np.random.default_rng(54)
    pick = rng.integers(0, len(names), 400)
    cols = [names[pick], rng.integers(0, 100, 400).astype(np.int64)]
    return ctx.sql("select name, sum(v) as s, count(*) as c from t "
                   "group by name order by " + order,
                   t=resident_table(ctx, cols, "name v"))


def _q1_text(select, tail):
    return ("select l_returnflag, l_linestatus, %s from lineitem "
            "where l_shipdate <= %d group by l_returnflag, l_linestatus %s"
            % (select, CUTOFF, tail))


# name -> (the table, what the case does to the collected records, the
# path the plan chooses, a part of that decision's reason)
FINISH = {
    "q3_ties_mixed_directions": (_q3_table, _plant_q3_ties, "columns",
                                 "one stable lexsort"),
    "q1_s1_keys_averages_past_2_53": (_q1_table, _q1_sums_past_2_53,
                                      "columns", "2 result-finishing"),
    "having_over_an_aggregate": (lambda ctx: _q1_table(ctx, _q1_text(
        "sum(l_quantity) as q, count(*) as c",
        "having count(*) > 600 and sum(l_tax) < 5000 order by q desc")),
        None, "columns", "3 result-finishing"),
    "projection_reorders": (lambda ctx: ctx.sql(
        "select o_shippriority, o_orderdate, sum(l_extendedprice*"
        "(100-l_discount)) as revenue, l_orderkey from customer "
        "join orders on c_custkey = o_custkey "
        "join lineitem on l_orderkey = o_orderkey "
        "where o_orderdate < %d group by l_orderkey, o_orderdate, "
        "o_shippriority order by o_orderdate desc, o_shippriority" % DATE,
        **tables(ctx, tpch(53, 90))), None, "columns", "picks"),
    "projection_computes": (lambda ctx: _q1_table(ctx, _q1_text(
        "sum(l_quantity) % 7 as r, float(sum(l_tax)) * 0.5 as h, "
        "count(*) // 3 as c3, -(sum(l_tax) % 5) as m",
        "order by r desc, l_linestatus")), None, "columns",
        "admitted programs"),
    "desc_bytes_key": (lambda ctx: _q1_table(
        ctx, Q1.replace("order by l_returnflag", "order by l_returnflag "
                        "desc") % CUTOFF), _q1_sums_past_2_53, "columns",
        "lexsort"),
    "desc_decoded_string_key": (lambda ctx: ctx.sql(
        "select s, k, sum(a) as sa, count(*) as c from t group by s, k "
        "order by s desc, sa", t=_words_table(ctx)), None, "columns",
        "lexsort"),
    "two_word_bytes_key_desc": (lambda ctx: _names_table(
        ctx, "name desc"), None, "columns", "lexsort"),
    "two_word_bytes_key_after_a_count": (lambda ctx: _names_table(
        ctx, "c desc, name"), None, "columns", "lexsort"),
    "average_sort_key_keeps_rows": (lambda ctx: _q1_table(
        ctx, (Q1 % CUTOFF).replace("order by l_returnflag, l_linestatus",
                                   "order by avg_qty")), None, "rows",
        "ORDER BY avg_qty: an average, None over a zero count"),
    "float_sort_key_keeps_rows": (lambda ctx: ctx.sql(
        "select k, max(f) as mf, count(*) as c from t group by k "
        "order by mf", t=_words_table(ctx)), None, "rows",
        "ORDER BY mf: a float key"),
    "int_beside_float_keeps_rows": (lambda ctx: _q1_table(ctx, _q1_text(
        "sum(l_quantity) as q", "having sum(l_quantity) > 1.5")), None,
        "rows", "past 2**53"),
}


def finish_both_ways(pq, raw):
    """One planned query's finish over the same collected records on
    each path: (over columns, row by row), each with its fields."""
    plan = pq._columnar
    got = pq._finish(list(raw)), pq._out_fields
    pq._columnar = None
    try:
        want = pq._finish(list(raw)), pq._out_fields
    finally:
        pq._columnar = plan
    return got, want


@pytest.mark.parametrize("case", sorted(FINISH))
def test_the_finish_over_columns_is_the_rows_finish(masters, case):
    dev, _ = masters
    build, edit, path, why = FINISH[case]
    pq = build(dev)._planned()
    assert pq is not None and pq.mode in ("group", "join_group")
    (decision,) = [d for d in pq.decisions if d["rule"] == "egest"]
    assert decision["choice"] == path and why in decision["reason"], \
        decision
    assert (pq._columnar is not None) == (path == "columns")
    raw, _ = pq._job(lambda rdd: rdd.collect())
    if edit is not None:
        raw = edit(pq, raw)
    if path == "rows":
        assert pq._finish(raw) == pq._egest(*pq._shape_rows(raw))
        return
    (got, got_fields), (want, want_fields) = finish_both_ways(pq, raw)
    assert len(want) > 1 and got_fields == want_fields
    assert got == want
    assert [tuple(map(type, r)) for r in got] \
        == [tuple(map(type, r)) for r in want]


def test_ties_keep_the_collected_order(masters):
    """Q3's planted ties on (revenue, o_orderdate) come out in the order
    the job collected them, on both paths."""
    dev, _ = masters
    pq = _q3_table(dev)._planned()
    raw = _plant_q3_ties(pq, pq._job(lambda rdd: rdd.collect())[0])
    (got, _), (want, _) = finish_both_ways(pq, raw)
    tied = [k for (k, d, _), (v,) in raw if (v, d) == (10 ** 9, DATE - 7)]
    assert len(tied) > 3
    assert [r[0] for r in got if (r[1], r[2]) == (10 ** 9, DATE - 7)] \
        == tied == [r[0] for r in want if (r[1], r[2]) == (10 ** 9,
                                                          DATE - 7)]


@pytest.mark.parametrize("case,path,counted", [
    ("q3_ties_mixed_directions", "columns", 1),
    ("average_sort_key_keeps_rows", "rows", 0)])
def test_a_columnar_finish_is_counted_and_its_span_says_the_path(
        masters, case, path, counted):
    from dpark_tpu import trace
    dev, _ = masters
    ex = dev.scheduler.executor
    table = FINISH[case][0](dev)
    trace.configure("ring")
    try:
        before = ex.query_finishes_columnar
        rows = [tuple(r) for r in table.collect()]
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert rows and ex.query_finishes_columnar - before == counted
    # the scheduler's history keeps the last 100 jobs: the ring, opened
    # for this one query, is where its job is read
    (finish,) = [s for s in spans if s["name"] == "query.finish"]
    assert finish.get("job") is not None
    assert (finish["args"]["path"], finish["args"]["rows"]) \
        == (path, len(rows))
