"""The query plane over a table resident on the device (ISSUE 39): a
planned query over a cached, device-resident columnar RDD runs whole on
the array path — scan, filter and projection traced into the narrow
stage that aggregates, byte-string columns as key words, int64 sums
admitted by the interval proofs from load-time ranges — and equals both
a numpy reference and the `local` master's host row path, integer for
integer.  TPC-H Q1 at toy size is the running example; the benchmark's
cell `tpch.q1` runs it at 8M rows a job (perf/jobs/tpch_q1.py)."""

import numpy as np
import pytest

FIELDS = ("l_quantity l_extendedprice l_discount l_tax l_returnflag "
          "l_linestatus l_shipdate")
CUTOFF = 10471          # 1998-09-02 as days since 1970-01-01

Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
      "sum(l_extendedprice) as sum_base_price, "
      "sum(l_extendedprice*(100-l_discount)) as sum_disc_price, "
      "sum(l_extendedprice*(100-l_discount)*(100+l_tax)) as sum_charge, "
      "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
      "avg(l_discount) as avg_disc, count(*) as count_order "
      "from lineitem where l_shipdate <= %d "
      "group by l_returnflag, l_linestatus "
      "order by l_returnflag, l_linestatus")


def _ident(r):
    return r


def lineitem(seed, n):
    """Seven columns in TPC-H's widths: four DECIMAL(15,2) as int64
    hundredths, two CHAR(1) as S1, a DATE as int32 days."""
    rng = np.random.default_rng(seed)
    qty = rng.integers(1, 51, n).astype(np.int64) * 100
    price = rng.integers(90000, 10495001, n).astype(np.int64)
    disc = rng.integers(0, 11, n).astype(np.int64)
    tax = rng.integers(0, 9, n).astype(np.int64)
    ship = rng.integers(8036, 10562, n).astype(np.int32)
    flag = np.where(ship + 15 <= 9298,
                    rng.choice(np.array([b"R", b"A"], "S1"), n),
                    np.array(b"N", "S1")).astype("S1")
    status = np.where(ship > 9298, b"O", b"F").astype("S1")
    return [qty, price, disc, tax, flag, status, ship]


def q1_reference(cols, cutoff):
    """Q1 in numpy int64 over the host columns, no dpark_tpu code: rows
    of (flag, status, four sums, three averages, count), ordered."""
    qty, price, disc, tax, flag, status, ship = cols
    keep = ship <= cutoff
    key = flag.view(np.uint8).astype(np.int64) * 256 \
        + status.view(np.uint8)
    out = []
    for k in np.unique(key[keep]):
        m = keep & (key == k)
        p, d, t = price[m], disc[m], tax[m]
        c = int(m.sum())
        sums = [int(qty[m].sum()), int(p.sum()),
                int((p * (100 - d)).sum()),
                int((p * (100 - d) * (100 + t)).sum())]
        out.append((bytes([k // 256]), bytes([k % 256])) + tuple(sums)
                   + (sums[0] / c, sums[1] / c, int(d.sum()) / c, c))
    return out


def resident_table(ctx, cols, fields=FIELDS, parts=1):
    from dpark_tpu import Columns
    rdd = ctx.parallelize(Columns(*cols), parts).map(_ident).cache()
    assert rdd.count() == len(cols[0])
    return ctx.table(rdd, fields)


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
    """Rows of `build()`'s table on the tpu master, and what the run
    left: its job records and the two scan counters' movement."""
    ex = ctx.scheduler.executor
    before = (len(ctx.scheduler.history), ex.scan_rows_device,
              ex.scan_rows_host)
    table = build()
    rows = [tuple(r) for r in table.collect()]
    return rows, table, {
        "records": ctx.scheduler.history[before[0]:],
        "device_rows": ex.scan_rows_device - before[1],
        "host_rows": ex.scan_rows_host - before[2]}


def assert_array_path(left):
    kinds = [str(st.get("kind")) for rec in left["records"]
             for st in rec["stage_info"]]
    reasons = [st.get("fallback_reason") or st.get("degrade_reason")
               for rec in left["records"] for st in rec["stage_info"]]
    assert kinds and all(k.startswith("array") for k in kinds), kinds
    assert not any(reasons), reasons
    assert left["host_rows"] == 0


# name -> (sql over table `lineitem`, rows of the toy table, what the
# case edits in the columns before loading)
def _one_row_group(cols):
    cols[4][7] = b"Z"
    cols[6][7] = 9000


CASES = {
    "one_s1_key": (
        "select l_returnflag, sum(l_quantity) as q, count(*) as c "
        "from lineitem group by l_returnflag order by l_returnflag",
        None),
    "two_s1_keys": (Q1 % CUTOFF, None),
    "int_key_beside_bytes_key": (
        "select l_tax, l_linestatus, sum(l_extendedprice) as p, "
        "avg(l_discount) as d, min(l_quantity) as lo, "
        "max(l_shipdate) as hi from lineitem where l_discount > 2 "
        "group by l_tax, l_linestatus order by l_tax, l_linestatus",
        None),
    "keeps_nothing": (Q1 % 0, None),
    "keeps_everything": (Q1 % 99999, None),
    "group_of_one_row": (Q1 % CUTOFF, _one_row_group),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_resident_query_equals_reference_and_local(masters, case):
    dev, host = masters
    sql, edit = CASES[case]
    cols = lineitem(39, 3000)
    if edit is not None:
        edit(cols)
    loaded = resident_table(dev, cols)
    rows, table, left = run_on_device(
        dev, lambda: dev.sql(sql, lineitem=loaded))
    expect = [tuple(r) for r in host.sql(
        sql, lineitem=resident_table(host, cols, parts=2)).collect()]
    assert rows == expect                   # integer for integer
    assert all(type(a) is type(b) for r, e in zip(rows, expect)
               for a, b in zip(r, e))
    if sql.startswith(Q1[:40]):
        cutoff = int(sql.split("<= ")[1].split()[0])
        assert rows == q1_reference(cols, cutoff)
    if case == "group_of_one_row":
        assert [r for r in rows if r[0] == b"Z"][0][-1] == 1
    assert_array_path(left)
    assert len(left["records"]) == 1        # ONE job
    assert left["device_rows"] == 3000
    assert table._planned().mode == "group"


def test_q1_across_two_devices_takes_the_exchange():
    """Past one device the map-side combine's store is not the answer:
    the key words cross the exchange and the reduce merges them.  (The
    DSL and no ORDER BY: past one partition `TableRDD.sort` builds its
    host chain through sortByKey, whose bounds sample is a job.)"""
    from dpark_tpu import DparkContext
    ctx = DparkContext("tpu:2")
    ctx.start()
    try:
        cols = lineitem(7, 4000)
        loaded = resident_table(ctx, cols, parts=2)
        rows, _, left = run_on_device(ctx, lambda: loaded.where(
            "l_shipdate <= %d" % CUTOFF).groupBy(
                ["l_returnflag", "l_linestatus"],
                "sum(l_quantity)", "sum(l_extendedprice)",
                "sum(l_extendedprice*(100-l_discount))",
                "sum(l_extendedprice*(100-l_discount)*(100+l_tax))",
                "avg(l_quantity)", "avg(l_extendedprice)",
                "avg(l_discount)", "count(*)"))
        assert sorted(rows) == q1_reference(cols, CUTOFF)
        assert_array_path(left)
        assert len(left["records"]) == 1
    finally:
        ctx.stop()


def test_a_product_past_int64_declines_and_the_host_answers(masters):
    """The interval proof is from the columns' ranges: two columns that
    are never large in one row still bound their product past int64,
    so the query declines with the reason and the row chain answers
    what the local master answers."""
    dev, host = masters
    sql = ("select l_linestatus, sum(l_extendedprice * l_quantity) as v "
           "from lineitem group by l_linestatus order by l_linestatus")
    cols = lineitem(3, 500)
    cols[0][:] = 1
    cols[1][:] = 1
    cols[0][::2] = 4_000_000_000
    cols[1][1::2] = 4_000_000_000       # 1.6e19 by the ranges alone
    loaded = resident_table(dev, cols)
    rows, table, left = run_on_device(
        dev, lambda: dev.sql(sql, lineitem=loaded))
    assert table._planned() is None
    reasons = [f["reason"] for f in table.rdd._query_fallbacks]
    assert any("may leave int64" in r for r in reasons), reasons
    expect = [tuple(r) for r in host.sql(
        sql, lineitem=resident_table(host, cols)).collect()]
    assert rows == expect
    assert sum(r[1] for r in rows) == 500 * 4_000_000_000
    # the row chain that answers is itself a stage program over the
    # resident batch (fuse.py traces it): the executor counts the rows
    # where a program is launched over them, whoever built the chain
    assert left["device_rows"] == 500 and left["host_rows"] == 0


def test_a_sum_past_int64_over_the_rows_declines(masters):
    """Each product fits; the table's row count times the largest does
    not: the proof is from the load-time ranges and the rows."""
    dev, _ = masters
    cols = lineitem(5, 200)
    cols[1][:] = 7
    cols[1][0] = 3_000_000_000      # its square x 200 rows > 2**63
    t = resident_table(dev, cols).groupBy(
        "l_linestatus", "sum(l_extendedprice * l_extendedprice) as s")
    assert t._planned() is None
    reasons = [f["reason"] for f in t.rdd._query_fallbacks]
    assert any("may overflow int64" in r for r in reasons), reasons
    assert sum(r[1] for r in t.collect()) == 9 * 10 ** 18 + 199 * 49


def test_a_float32_argument_is_summed_as_on_the_driver_path(masters):
    """A float column is float32 on the device (layout.record_spec):
    its sums are admitted as today and held to float32's tolerance, one
    rounding an addition; counts and keys stay exact."""
    dev, _ = masters
    cols = lineitem(11, 2000)
    weight = np.random.default_rng(11).random(2000, dtype=np.float32)
    fields = FIELDS + " l_weight"
    loaded = resident_table(dev, cols + [weight], fields)
    rows, _, left = run_on_device(dev, lambda: loaded.groupBy(
        "l_returnflag", "sum(l_weight) as w", "avg(l_weight) as a",
        "count(*) as c"))
    assert_array_path(left)
    for flag, w, a, c in rows:
        v = weight[cols[4] == flag].astype(np.float64)
        assert c == len(v)
        assert abs(w - v.sum()) <= c * 2.0 ** -23 * np.abs(v).sum()
        assert abs(a - v.mean()) <= 2.0 ** -23 * np.abs(v).sum()
    assert sorted(r[0] for r in rows) == sorted(
        bytes(k) for k in np.unique(cols[4]))


def test_a_float_comparison_over_a_resident_table_declines(masters):
    """float32 there, float64 here: not provably the host's answer."""
    dev, _ = masters
    cols = lineitem(11, 300)
    weight = np.random.default_rng(1).random(300, dtype=np.float32)
    t = resident_table(dev, cols + [weight], FIELDS + " l_weight") \
        .where("l_weight > 0.1").groupBy("l_tax", "count(*) as c")
    assert t._planned() is None
    assert any("float comparison" in f["reason"]
               for f in t.rdd._query_fallbacks)
    assert sum(r[1] for r in t.collect()) == int(
        (weight.astype(np.float64) > 0.1).sum())


def test_the_same_table_queried_twice_loads_and_reads_nothing_again(
        masters):
    from dpark_tpu import trace
    dev, _ = masters
    cols = lineitem(21, 2500)
    trace.configure("ring")
    try:
        table = resident_table(dev, cols)
        loaded = set(dev.scheduler.executor.result_cache_ids())
        runs = [run_on_device(dev, lambda: dev.sql(Q1 % CUTOFF,
                                                   lineitem=table))
                for _ in range(2)]
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert runs[0][0] == runs[1][0] == q1_reference(cols, CUTOFF)
    ids = set()
    for _, _, left in runs:
        assert_array_path(left)
        assert left["device_rows"] == 2500
        ids |= {rec["id"] for rec in left["records"]}
    assert set(dev.scheduler.executor.result_cache_ids()) == loaded
    in_jobs = [s for s in spans if s.get("job") in ids]
    assert not [s for s in in_jobs if s["name"] == "ingest"]
    # the load-time ranges were read once, before either query
    stats = [s for s in spans if s["name"] == "readback"
             and (s.get("args") or {}).get("site") == "table.stats"]
    assert len(stats) == 1 and stats[0].get("job") not in ids
    plans = [s for s in in_jobs if s["name"] == "query.plan"]
    assert [s["args"]["source"] for s in plans] == ["device"] * 2
    assert [s["args"]["mode"] for s in plans] == ["group"] * 2
    finishes = [s for s in in_jobs if s["name"] == "query.finish"]
    assert [s["args"]["rows"] for s in finishes] == [
        len(runs[0][0])] * 2
    # planned anew, the same stage programs: nothing compiled twice
    compiles = [s for s in spans if s["name"] == "compile"
                and s.get("job") in ids]
    assert {s.get("job") for s in compiles} <= {min(ids)}


def test_a_part_file_source_still_scans_on_the_driver(masters, tmp_path):
    from dpark_tpu.tabular import write_tabular
    dev, host = masters
    cols = lineitem(2, 1200)
    rows_in = list(zip(*(c.tolist() for c in cols[:4] + [cols[6]])))
    fields = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
              "l_shipdate"]
    (tmp_path / "t").mkdir()
    write_tabular(str(tmp_path / "t" / "part-00000.tab"), fields,
                  rows_in, chunk_rows=500)
    sql = ("select l_tax, sum(l_extendedprice*(100-l_discount)) as s, "
           "count(*) as c from lineitem where l_shipdate <= %d "
           "group by l_tax order by l_tax" % CUTOFF)
    rows, table, left = run_on_device(dev, lambda: dev.sql(
        sql, lineitem=dev.tabular(str(tmp_path / "t")).asTable(
            "lineitem")))
    assert table._planned() is not None
    assert table._planned().segs[0].device is None
    assert left["host_rows"] == 1200 and left["device_rows"] == 0
    keep = cols[6] <= CUTOFF
    assert rows == [
        (int(t), int((cols[1] * (100 - cols[2]))[keep & (cols[3] == t)]
                     .sum()), int((keep & (cols[3] == t)).sum()))
        for t in np.unique(cols[3][keep])]
    assert rows == [tuple(r) for r in host.sql(
        sql, lineitem=host.parallelize(rows_in, 2).asTable(
            fields, "lineitem")).collect()]


def test_a_plain_scan_over_a_resident_table_filters_on_the_device(masters):
    dev, host = masters
    cols = lineitem(8, 1500)
    sql = ("select l_returnflag, l_quantity * 2 + l_tax as v "
           "from lineitem where l_tax == 3 and l_returnflag != b'N'")
    loaded = resident_table(dev, cols)
    rows, table, left = run_on_device(
        dev, lambda: dev.sql(sql, lineitem=loaded))
    assert table._planned().mode == "scan"
    assert_array_path(left)
    keep = (cols[3] == 3) & (cols[4] != b"N")
    assert sorted(rows) == sorted(zip(
        cols[4][keep].tolist(), (cols[0][keep] * 2 + 3).tolist()))
    assert sorted(rows) == sorted(tuple(r) for r in host.sql(
        sql, lineitem=resident_table(host, cols)).collect())
    assert table.count() == int(keep.sum())


def test_an_evicted_table_declines_with_a_reason(masters):
    dev, _ = masters
    cols = lineitem(4, 400)
    table = resident_table(dev, cols)
    dev.scheduler.executor.drop_result(table.rdd.id)
    t = table.groupBy("l_linestatus", "count(*) as c")
    assert t._planned() is None
    assert any("no longer resident" in f["reason"]
               for f in t.rdd._query_fallbacks)
    assert sorted(tuple(r) for r in t.collect()) == sorted(
        (bytes(k), int((cols[5] == k).sum())) for k in np.unique(cols[5]))


def test_a_key_of_too_many_words_declines(masters):
    dev, _ = masters
    cols = lineitem(4, 300)
    wide = np.array([b"%040d" % i for i in range(300)], "S40")
    t = resident_table(dev, cols + [wide], FIELDS + " l_comment") \
        .groupBy("l_comment", "count(*) as c")
    assert t._planned() is None
    assert any("MAX_KEY_LEAVES" in f["reason"]
               for f in t.rdd._query_fallbacks)


def test_a_planner_failure_leaves_a_fallback_reason(masters, monkeypatch):
    """TableRDD._planned no longer swallows plan_query's exception: the
    reason rides the lineage, and the host chain answers."""
    from dpark_tpu.query import planner
    dev, _ = masters

    def boom(*a, **kw):
        raise RuntimeError("boom\nsecond line")
    monkeypatch.setattr(planner, "plan_query", boom)
    cols = lineitem(4, 300)
    t = resident_table(dev, cols).groupBy("l_linestatus", "count(*) as c")
    assert t._planned() is None
    assert {"op": "plan", "reason":
            "query planning failed: RuntimeError: boom"} \
        in t.rdd._query_fallbacks
    assert sum(r[1] for r in t.collect()) == 300


def test_one_admission_two_emitters():
    """The same admitted program over numpy batches and over traced
    records, and a plan made anew is the same program."""
    import jax
    import jax.numpy as jnp
    from dpark_tpu.query import exprs as E
    dtypes = {"p": np.int64, "d": np.int64, "s": np.dtype("S1")}
    ranges = {"p": (0, 10 ** 7), "d": (0, 10)}
    text = "p * (100 - d) + abs(d - 5) // 2 + min(p, 7) % 3"
    ve, why = E.vectorize(E.compile_expr(text, list(dtypes)), dtypes,
                          ranges, device=True)
    assert why is None and ve.kind == "i"
    again, _ = E.vectorize(E.compile_expr(text, list(dtypes)), dtypes,
                           {"p": (5, 6), "d": (1, 2)}, device=True)
    assert again.prog == ve.prog and hash(again.prog) == hash(ve.prog)
    p = np.arange(0, 10 ** 7, 99991, dtype=np.int64)
    d = (p % 11).astype(np.int64)
    host = ve.fn({"p": p, "d": d})
    traced = jax.vmap(lambda a, b: E.evaluate(ve.prog, {"p": a, "d": b}))(
        jnp.asarray(p), jnp.asarray(d))
    assert isinstance(host, np.ndarray)
    assert np.array_equal(host, np.asarray(traced))
    assert host.tolist() == [int(a) * (100 - int(b)) + abs(int(b) - 5)
                             // 2 + min(int(a), 7) % 3
                             for a, b in zip(p, d)]
    # the device's own declines: ordering byte strings, comparing floats
    for bad, dts in (("s < b'B'", dtypes), ("f > 0.5", {"f": np.float32})):
        got, why = E.vectorize(E.compile_expr(bad, list(dts)), dts, {},
                               boolean=True, device=True)
        assert got is None and "resident" in why
        got, why = E.vectorize(E.compile_expr(bad, list(dts)), dts, {},
                               boolean=True)
        assert got is not None


def test_key_words_are_the_words_the_host_packs():
    """A byte-string key column rides as its own int64 words; a host
    row's `bytes` and a traced ByteStr give the same words, high bytes
    and NUL padding included, and the words give the bytes back."""
    import jax
    import jax.numpy as jnp
    from dpark_tpu.backend.tpu import layout
    from dpark_tpu.query import planner
    assert planner._key_nwords((1, 1)) == 2
    assert planner._key_nwords((2, 0, 9)) == 4
    rows = [(b"\xff", 7, b"ab", b"", b"0123456789"),
            (b"", -3, b"\x80", b"xyz", b"9")]
    widths = (1, 0, 2, 3, 10)
    for row in rows:
        host = planner._key_of(row, widths)
        assert len(host) == planner._key_nwords(widths) == 6
        packed = [layout.pack_bytes(np.array([v], "S%d" % w))[0]
                  if w else v for v, w in zip(row, widths)]

        def traced(*leaves):
            it = iter(leaves)
            rec = [layout.ByteStr(w, [next(it) for _ in range(-(-w // 8))])
                   if w else next(it) for w in widths]
            return planner._key_of(rec, widths)
        with jax.enable_x64(True):      # as the executor does
            flat = [jnp.asarray(x, jnp.int64)
                    for v, w in zip(packed, widths)
                    for x in (v if w else [v])]
            dev = jax.jit(traced)(*flat)
        assert tuple(int(x) for x in dev) == host
        assert planner._key_columns(host, widths, lambda i, v: v) == row


def test_high_byte_keys_group_exactly(masters):
    dev, host = masters
    cols = lineitem(13, 600)
    cols[4] = np.where(cols[3] % 2 == 0, b"\xfe", b"\x01").astype("S1")
    cols[5] = np.where(cols[2] % 3 == 0, b"\x80", b"~").astype("S1")
    sql = ("select l_returnflag, l_linestatus, sum(l_extendedprice) as p, "
           "count(*) as c from lineitem "
           "group by l_returnflag, l_linestatus")
    loaded = resident_table(dev, cols)
    rows, table, left = run_on_device(
        dev, lambda: dev.sql(sql, lineitem=loaded))
    assert_array_path(left)
    assert sorted(rows) == sorted(tuple(r) for r in host.sql(
        sql, lineitem=resident_table(host, cols)).collect())
    assert len(rows) == 4 and sum(r[3] for r in rows) == 600


def test_a_plan_that_opens_no_job_leaves_no_span(masters):
    """query.plan rides its PlannedQuery to the job the action opens:
    a plan that was only explained, or that declined, stamps nothing on
    the query that follows, and each job carries its own plan alone."""
    from dpark_tpu import trace
    dev, _ = masters
    cols = lineitem(17, 800)
    trace.configure("ring")
    try:
        table = resident_table(dev, cols)
        idle = dev.sql(Q1 % CUTOFF, lineitem=table)
        assert "scan-resident" in idle.explain()    # planned, never run
        declined = table.groupBy("l_linestatus",
                                 "sum(l_extendedprice * 10 ** 17) as v")
        assert declined._planned() is None
        rows, ran, left = run_on_device(dev, lambda: table.where(
            "l_tax == 2").groupBy("l_linestatus", "count(*) as c"))
        again = [tuple(r) for r in ran.collect()]   # cached rows: no job
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert again == rows and len(left["records"]) == 1
    job = left["records"][0]["id"]
    plans = [s for s in spans if s["name"] == "query.plan"]
    assert [(s.get("job"), s["args"]["mode"], s["args"]["source"])
            for s in plans] == [(job, "group", "device")]
    finishes = [s for s in spans if s["name"] == "query.finish"]
    assert [s.get("job") for s in finishes] == [job]
    assert idle._planned()._plan_reading is not None    # still its own


def test_a_combined_store_is_cut_to_its_count_under_hbm_pressure(
        masters, monkeypatch):
    """Four groups of a table's rows: the store's leaves keep the
    input's capacity unless registering them whole would pass the HBM
    budget; then the count is read, the leaves are cut to its capacity
    class and the table stays resident."""
    from dpark_tpu import conf, trace
    dev, _ = masters
    ex = dev.scheduler.executor
    cols = lineitem(23, 4096)
    table = resident_table(dev, cols)
    sql = Q1 % CUTOFF

    def run():
        t = dev.sql(sql, lineitem=table)
        rows = [tuple(r) for r in t.collect()]
        (store,) = [st for st in ex.shuffle_store.values()
                    if st.get("pre_reduced")
                    and st["seq"] == max(x["seq"] for x in
                                         ex.shuffle_store.values())]
        return rows, store["leaves"][0].shape[1], store["nbytes"], t

    rows, cap, nbytes, keep = run()
    assert rows == q1_reference(cols, CUTOFF) and cap == 4096
    # a budget the table and the whole store do not fit in together
    monkeypatch.setattr(conf, "SHUFFLE_HBM_BUDGET",
                        ex._result_bytes + ex._store_bytes + nbytes // 2)
    trace.configure("ring")
    try:
        reads = ex.host_reads
        rows2, cap2, nbytes2, keep2 = run()
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    assert rows2 == rows and cap2 == 8 and nbytes2 == nbytes * 8 // 4096
    assert table.rdd.id in ex.result_cache_ids()
    assert [s for s in spans if s["name"] == "readback"
            and s["args"].get("site") == "store.counts"]
    assert not [s for s in spans if s["name"] == "hbm.spill"]
