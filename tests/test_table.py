"""Table DSL + sketches tests (reference: tests/test_table.py style,
SURVEY.md section 4)."""

import pytest


@pytest.fixture()
def sales(ctx):
    rows = [("north", "apple", 3, 1.5),
            ("north", "pear", 2, 2.0),
            ("south", "apple", 5, 1.4),
            ("south", "pear", 1, 2.2),
            ("south", "apple", 2, 1.6)]
    return ctx.parallelize(rows, 2).asTable(
        "region item qty price", name="sales")


def test_select_exprs(sales):
    t = sales.select("item", "qty * price as total")
    got = t.collect()
    assert t.fields == ["item", "total"]
    assert got[0].item == "apple" and abs(got[0].total - 4.5) < 1e-9


def test_where(sales):
    t = sales.where("qty > 2", "region == 'south'")
    assert [r.item for r in t.collect()] == ["apple"]


def test_group_by(sales):
    t = sales.groupBy("region", "sum(qty) as total_qty",
                      "count(*) as n", "avg(price) as avg_price")
    got = {r.region: r for r in t.collect()}
    assert got["north"].total_qty == 5
    assert got["north"].n == 2
    assert got["south"].n == 3
    assert abs(got["south"].avg_price - (1.4 + 2.2 + 1.6) / 3) < 1e-9


def test_group_by_min_max(sales):
    t = sales.groupBy("item", "min(price) as lo", "max(price) as hi")
    got = {r.item: r for r in t.collect()}
    assert got["apple"].lo == 1.4 and got["apple"].hi == 1.6
    assert got["pear"].lo == 2.0 and got["pear"].hi == 2.2


def test_global_aggregate(sales):
    t = sales.select("sum(qty) as total", "count(*) as n")
    (row,) = t.collect()
    assert row.total == 13 and row.n == 5


def test_sort_top(sales):
    t = sales.sort("qty", reverse=True)
    rows = t.collect()
    assert [r.qty for r in rows] == [5, 3, 2, 2, 1]
    top2 = sales.top(2, key="qty")
    assert [r.qty for r in top2] == [5, 3]


def test_join(ctx, sales):
    prices = ctx.parallelize(
        [("apple", "fruit"), ("pear", "fruit")], 2).asTable(
        "item category", name="cat")
    j = sales.select("item", "qty").join(prices, on="item")
    got = j.collect()
    assert len(got) == 5
    assert all(r.category == "fruit" for r in got)


def test_adcount(ctx):
    t = ctx.parallelize([(i % 100, i) for i in range(10000)], 4) \
           .asTable("k v")
    (row,) = t.select("adcount(k) as distinct_keys").collect()
    assert 90 <= row.distinct_keys <= 110


def test_rdd_adcount_accuracy(ctx):
    n = ctx.parallelize(list(range(5000)), 4).adcount()
    assert 4500 <= n <= 5500


def test_hotcounter():
    from dpark_tpu.hotcounter import HotCounter
    hc = HotCounter(capacity=50)
    for i in range(10000):
        hc.add(i % 200)             # uniform noise
    for _ in range(500):
        hc.add("hot1")
    for _ in range(300):
        hc.add("hot2")
    top = [v for v, _ in hc.top(2)]
    assert "hot1" in top and "hot2" in top


def test_hyperloglog_merge():
    from dpark_tpu.hyperloglog import HyperLogLog
    a, b = HyperLogLog(), HyperLogLog()
    for i in range(3000):
        a.add(i)
    for i in range(2000, 6000):
        b.add(i)
    a.update(b)
    assert 5400 <= len(a) <= 6600


def test_ctx_table_roundtrip(ctx, tmp_path):
    rows = [(i, i * i) for i in range(100)]
    ctx.parallelize(rows, 3).saveAsTableFile(str(tmp_path / "t"))
    t = ctx.table(str(tmp_path / "t"), fields="a b")
    assert t.count() == 100
    got = t.where("a == 7").collect()
    assert got[0].b == 49


def test_sql_execute(ctx, sales):
    got = ctx.sql(
        "select region, sum(qty) as total from sales group by region",
        sales=sales).collect()
    assert sorted((r.region, r.total) for r in got) == [
        ("north", 5), ("south", 8)]

    rows = ctx.sql(
        "select item, qty from sales where region == 'south' "
        "order by qty desc limit 2", sales=sales)
    assert [r.qty for r in rows] == [5, 2]

    allrows = ctx.sql("select * from sales", sales=sales).collect()
    assert len(allrows) == 5

    with pytest.raises(ValueError):
        ctx.sql("delete from sales", sales=sales)
    with pytest.raises(ValueError):
        ctx.sql("select * from nope", sales=sales)


def test_sql_edge_cases(ctx, sales):
    # ORDER BY a column the projection drops
    rows = ctx.sql("select item from sales order by qty desc limit 2",
                   sales=sales)
    assert [r.item for r in rows] == ["apple", "apple"]
    # SELECT order respected in GROUP BY output
    got = ctx.sql(
        "select sum(qty) as q, region from sales group by region",
        sales=sales).collect()
    assert got[0]._fields == ("q", "region")
    # clause keyword inside a string literal
    none = ctx.sql(
        "select * from sales where item == 'a group by b'",
        sales=sales).collect()
    assert none == []
    # table named like the positional parameter
    assert ctx.sql("select * from query", query=sales).count() == 5
    # non-aggregate select column that is not a group key
    with pytest.raises(ValueError):
        ctx.sql("select price, sum(qty) from sales group by region",
                sales=sales)


def test_sql_order_by_variants(ctx, sales):
    rows = ctx.sql("select qty * 2 as d from sales order by qty asc "
                   "limit 2", sales=sales)
    assert [r.d for r in rows] == [2, 4]
    rows = ctx.sql("select qty * 2 from sales order by qty * 2 desc "
                   "limit 1", sales=sales)
    assert rows[0][0] == 10
    got = ctx.sql(r"select * from sales where item == 'don\'t group by'",
                  sales=sales).collect()
    assert got == []


@pytest.mark.mesh
def test_sql_group_by_rides_device_shuffle():
    """VERDICT r3 #8: ctx.sql GROUP BY sum/count/avg/min/max compiles
    onto the monoid device shuffle (a shuffle stage of kind array, wire bytes
    moved) — the Table DSL inherits the core's speed, with results
    matching the host-computed expectation exactly."""
    from dpark_tpu import DparkContext
    tctx = DparkContext("tpu")
    tctx.start()
    try:
        rows = [(i % 7, i, i * 2) for i in range(2000)]
        t = tctx.table(tctx.parallelize(rows, 8), ["g", "x", "y"])
        res = tctx.sql(
            "select g, sum(x) as sx, count(*) as c, avg(y) as ay, "
            "min(x) as mn, max(y) as mx from t group by g order by g",
            t=t).collect()
        from tests.conftest import shuffled_on_device
        ex = tctx.scheduler.executor
        assert shuffled_on_device(tctx), \
            "SQL group-by did not ride the device"
        assert ex.exchange_wire_bytes > 0, "no device exchange ran"
        exp = {}
        for g, x, y in rows:
            s, c, sy, mn, mx = exp.get(g, (0, 0, 0, x, y))
            exp[g] = (s + x, c + 1, sy + y, min(mn, x), max(mx, y))
        assert len(res) == 7
        for r in res:
            s, c, sy, mn, mx = exp[r.g]
            assert (r.sx, r.c, r.mn, r.mx) == (s, c, mn, mx)
            assert abs(r.ay - sy / c) < 1e-9
    finally:
        tctx.stop()


@pytest.mark.mesh
def test_table_join_rides_device():
    """Numeric table equi-joins inherit the array-path join source:
    every stage of select-over-join runs on the device (VERDICT r3 #8
    sibling — the Table DSL inherits the core's speed)."""
    from dpark_tpu import DparkContext
    tctx = DparkContext("tpu")
    tctx.start()
    li = tctx.parallelize(
        [(i % 500, i % 7, (i % 11) * 10) for i in range(20000)], 8) \
        .asTable(["okey", "qty", "price"], "lineitem")
    od = tctx.parallelize([(i, i % 3) for i in range(500)], 8) \
        .asTable(["okey", "prio"], "orders")
    out = li.join(od, on="okey").select("okey", "qty", "prio").collect()
    assert len(out) == 20000
    kinds = set()
    for rec in tctx.scheduler.history:
        for s in rec.get("stage_info", []):
            kinds.add(s.get("kind"))
    assert kinds == {"array"}, kinds
    tctx.stop()


def test_sql_join_having_agg_exprs(ctx, sales):
    """r5 SQL front: JOIN ... ON, HAVING, and aggregate expressions in
    SELECT/HAVING (VERDICT r4 #6)."""
    dim = ctx.table(ctx.parallelize(
        [("apple", 1), ("pear", 2), ("plum", 3)], 2), ["item", "code"])
    rows = ctx.sql("select item, qty, code from sales join dim on item "
                   "order by qty limit 10", sales=sales, dim=dim)
    assert all(hasattr(r, "code") for r in rows)
    got = {(r.item, r.code) for r in rows}
    assert got <= {("apple", 1), ("pear", 2), ("plum", 3)}

    t = ctx.sql("select item, sum(qty) as s from sales group by item "
                "having sum(qty) > 3 order by s desc", sales=sales)
    res = t.collect()
    assert all(r.s > 3 for r in res)
    assert [r.s for r in res] == sorted((r.s for r in res),
                                        reverse=True)

    t = ctx.sql("select item, sum(qty) * 2 + count(*) as score from "
                "sales group by item", sales=sales)
    base = {}
    for r in sales.collect():
        s, c = base.get(r.item, (0, 0))
        base[r.item] = (s + r.qty, c + 1)
    exp = {k: s * 2 + c for k, (s, c) in base.items()}
    assert {r.item: r.score for r in t.collect()} == exp

    # a.col = b.col spelling; a column neither table has refuses
    rows = ctx.sql("select item, code from sales join dim on "
                   "sales.item = dim.item limit 3",
                   sales=sales, dim=dim)
    assert rows
    import pytest as _pytest
    with _pytest.raises(ValueError):
        ctx.sql("select * from sales join dim on sales.item = dim.nosuch",
                sales=sales, dim=dim)
    with _pytest.raises(ValueError):
        ctx.sql("select item from sales having sum(qty) > 1",
                sales=sales)


@pytest.mark.mesh
def test_sql_join_group_rides_device():
    """SQL JOIN -> GROUP BY -> HAVING runs its join and aggregation on
    the array path (the join lowers to the device join source)."""
    from dpark_tpu import DparkContext
    tctx = DparkContext("tpu")
    tctx.start()
    try:
        li = tctx.parallelize(
            [(i % 200, (i % 7) + 1) for i in range(8000)], 8) \
            .asTable(["okey", "qty"], "li")
        od = tctx.parallelize([(i, i % 3) for i in range(200)], 8) \
            .asTable(["okey", "prio"], "od")
        t = tctx.sql(
            "select prio, sum(qty) as s, sum(qty) * 1.0 / count(*) "
            "as aq from li join od on okey group by prio "
            "having count(*) > 10 order by prio", li=li, od=od)
        res = t.collect()
        exp = {}
        od_map = {i: i % 3 for i in range(200)}
        for i in range(8000):
            p = od_map[i % 200]
            s, c = exp.get(p, (0, 0))
            exp[p] = (s + (i % 7) + 1, c + 1)
        assert [(r.prio, r.s) for r in res] \
            == sorted((p, s) for p, (s, c) in exp.items() if c > 10)
        for r in res:
            s, c = exp[r.prio]
            assert abs(r.aq - s / c) < 1e-9
        kinds = set()
        for rec in tctx.scheduler.history:
            for s_ in rec.get("stage_info", []):
                if rec.get("parts") == 1:
                    continue
                kinds.add((s_["rdd"], s_.get("kind")))
        assert ("CoGroupedRDD", "array") not in kinds
        from tests.conftest import shuffled_on_device
        assert shuffled_on_device(tctx), \
            "SQL join+group did not ride the device"
        arr = {v for _, v in kinds}
        assert "array" in arr, kinds
    finally:
        tctx.stop()
