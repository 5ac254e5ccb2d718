"""tpch-q3: TPC-H Query 3, the shipping priority query (clause 2.4.3), at
its validation parameters SEGMENT = 'BUILDING', DATE = 1995-03-15, over
`customer`, `orders` and `lineitem` resident in HBM:

    SELECT l_orderkey, SUM(l_extendedprice*(1-l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer, orders, lineitem
    WHERE c_mktsegment = 'BUILDING' AND c_custkey = o_custkey
      AND l_orderkey = o_orderkey AND o_orderdate < DATE '1995-03-15'
      AND l_shipdate > DATE '1995-03-15'
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate
    LIMIT 10

DECIMAL(15,2) is an int64 count of hundredths, CHAR(10) an S10 byte
string, DATE an int32 count of days: the text the job sends (`SQL`) is
written over hundredths and days, with the comma list as two explicit
JOINs, and revenue is an exact integer of hundredths squared.  A job is
`ctx.sql(SQL, customer=..., orders=..., lineitem=...)`: the text parsed
and planned anew inside every job's wall, a left-deep chain of two
device joins, the ten rows collected.

Everything of this configuration: the three tables seeded after the
specification's population rules (clause 4.2.3; lineitem's prices and
dates as perf/jobs/tpch_q1.py draws them), the load to HBM with its
probe, the one dpark call, the numpy reference (joins by sorted keys and
searchsorted, groups by np.unique, the order by a lexsort; checked once
against Python ints; no code shared with dpark_tpu) and the verdict.
"""

import numpy as np

from perf.jobs import tpch_q1

TABLES = {
    "customer": ("c_custkey", "c_mktsegment"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"),
}
# on the device every column is 8-byte words: an S10 is two
DEVICE_ROW_BYTES = {"customer": 24, "orders": 32, "lineitem": 32}
SEGMENTS = np.array([b"AUTOMOBILE", b"BUILDING", b"FURNITURE",
                     b"HOUSEHOLD", b"MACHINERY"], "S10")
SEGMENT = b"BUILDING"
DATE = tpch_q1.days(1995, 3, 15)
CUSTOMERS_PER_SF = 150000
ORDERS_PER_SF = 1500000
PROBE_SF = 0.001
SAMPLE_STEP = 1 << 10       # the Python-int cross-check's 1-in-1,024 orders
TOP = 10

_TEXT = ("select l_orderkey, sum(l_extendedprice*(100-l_discount)) as "
         "revenue, o_orderdate, o_shippriority from customer "
         "join orders on c_custkey = o_custkey "
         "join lineitem on l_orderkey = o_orderkey "
         "where c_mktsegment == b'%s' and o_orderdate < %d "
         "and l_shipdate > %d "
         "group by l_orderkey, o_orderdate, o_shippriority "
         "order by revenue desc, o_orderdate"
         % (SEGMENT.decode(), DATE, DATE))
SQL = _TEXT + " limit %d" % TOP
SQL_ALL = _TEXT             # every group: the set-up action `all_groups`

QUERIES = {"q3": SQL}
ACTIONS = ("collect", "all_groups")

# what make_data implements; the configuration's file has to say the same
DISTRIBUTIONS = {
    "c_custkey": "1 .. SF x 150,000, in order",
    "c_mktsegment": {"kind": "uniform_choice",
                     "values": [s.decode() for s in SEGMENTS]},
    "o_orderkey": "the first 8 of every 32 keys, in order: "
                  "32 (i // 8) + i % 8 + 1 for order i",
    "o_custkey": {"kind": "uniform_over", "set": "customers whose key is "
                  "not divisible by 3"},
    "o_orderdate": tpch_q1.DISTRIBUTIONS["o_orderdate"],
    "o_shippriority": 0,
    "lines_per_order": tpch_q1.DISTRIBUTIONS["lines_per_order"],
    "l_quantity": tpch_q1.DISTRIBUTIONS["l_quantity"],
    "l_partkey": tpch_q1.DISTRIBUTIONS["l_partkey"],
    "p_retailprice_hundredths":
        tpch_q1.DISTRIBUTIONS["p_retailprice_hundredths"],
    "l_extendedprice": tpch_q1.DISTRIBUTIONS["l_extendedprice"],
    "l_discount_hundredths": tpch_q1.DISTRIBUTIONS["l_discount_hundredths"],
    "l_shipdate": tpch_q1.DISTRIBUTIONS["l_shipdate"],
}


def resident(r):
    return r


def order_keys(n):
    """TPC-H's sparse order keys: the first 8 of every 32."""
    i = np.arange(n, dtype=np.int64)
    return 32 * (i // 8) + i % 8 + 1


def make_tables(seed, sf):
    """customer, orders and lineitem at scale factor `sf`, each in key
    order, every column drawn as clause 4.2.3 says from numpy's
    default_rng([seed, table]).  Returns {table: tuple of columns}."""
    nc = max(1, int(round(CUSTOMERS_PER_SF * sf)))
    no = max(1, int(round(ORDERS_PER_SF * sf)))
    rng = np.random.default_rng([seed, 1])
    customer = (np.arange(1, nc + 1, dtype=np.int64),
                SEGMENTS[rng.integers(0, len(SEGMENTS), nc)])
    rng = np.random.default_rng([seed, 2])
    # the customers whose key is not divisible by 3, by their index
    ordering = rng.integers(0, nc - nc // 3, no)
    orderdate = rng.integers(tpch_q1.START_DATE, tpch_q1.LAST_ORDER + 1,
                             no).astype(np.int32)
    orders = (order_keys(no), 3 * (ordering // 2) + ordering % 2 + 1,
              orderdate, np.zeros(no, np.int32))
    rng = np.random.default_rng([seed, 3])
    lines = rng.integers(1, 8, no)
    nl = int(lines.sum())
    partkey = rng.integers(1, 2000001, nl)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    lineitem = (np.repeat(orders[0], lines),
                (rng.integers(1, 51, nl) * retail).astype(np.int64),
                rng.integers(0, 11, nl).astype(np.int64),
                (np.repeat(orderdate, lines)
                 + rng.integers(1, 122, nl)).astype(np.int32))
    return {"customer": customer, "orders": orders, "lineitem": lineitem}


def _sample(tables, step):
    """Every `step`-th order with its lines, and every customer."""
    o = tables["orders"]
    keep = np.zeros(len(o[0]), bool)
    keep[::step] = True
    li = tables["lineitem"]
    lkeep = np.isin(li[0], o[0][keep])
    return {"customer": tables["customer"],
            "orders": tuple(c[keep] for c in o),
            "lineitem": tuple(c[lkeep] for c in li)}


def make_data(config, traffic, seed, scale):
    """The tables at the traffic's scale factor over `scale` (never below
    the probe's); the reference's arithmetic is checked here, once,
    against Python ints."""
    if config["distributions"] != DISTRIBUTIONS:
        raise ValueError("the configuration's distributions %r are not "
                         "what this module implements (%r)"
                         % (config["distributions"], DISTRIBUTIONS))
    sf = max(PROBE_SF, float(traffic["scale_factor"]) / scale)
    tables = make_tables(seed, sf)
    sample = _sample(tables, SAMPLE_STEP)
    if q3_rows(sample) != q3_rows_python(sample):
        raise RuntimeError("the numpy reference disagrees with Python's "
                           "integers on the 1-in-%d sample" % SAMPLE_STEP)
    rows = {t: len(cols[0]) for t, cols in tables.items()}
    return {"tables": tables, "sf": sf, "table_rows": rows,
            "rows": sum(rows.values())}


def input_rows(data):
    return data["rows"]


def n_partitions(data):
    return 1                # one set of the three tables


def resident_bytes(data):
    return sum(n * DEVICE_ROW_BYTES[t]
               for t, n in data["table_rows"].items())


def _member(keys, values):
    """Which of `values` are in the sorted array `keys`."""
    if not len(keys):
        return np.zeros(len(values), bool)
    at = np.minimum(np.searchsorted(keys, values), len(keys) - 1)
    return keys[at] == values


def q3_rows(tables, date=DATE, segment=SEGMENT):
    """Q3 over host columns in numpy int64, every group: (l_orderkey,
    revenue in hundredths squared, o_orderdate, o_shippriority) in the
    ORDER BY's order (revenue down, date up, then the key: the reference
    orders its ties, the verdict accepts any order of them)."""
    ckey, cseg = tables["customer"]
    okey, ocust, odate, oprio = tables["orders"]
    lkey, price, disc, ship = tables["lineitem"]
    okeep = (odate < date) & _member(np.sort(ckey[cseg == segment]), ocust)
    order = np.argsort(okey[okeep], kind="stable")
    keys, dates, prios = (c[okeep][order] for c in (okey, odate, oprio))
    lkeep = ship > date
    lk = lkey[lkeep]
    hit = _member(keys, lk)
    rev = (price[lkeep] * (100 - disc[lkeep]))[hit]
    groups, inv = np.unique(lk[hit], return_inverse=True)
    sums = np.zeros(len(groups), np.int64)
    np.add.at(sums, inv, rev)
    at = np.searchsorted(keys, groups)
    gdate, gprio = dates[at].astype(np.int64), prios[at].astype(np.int64)
    by = np.lexsort((groups, gdate, -sums))
    return list(zip(groups[by].tolist(), sums[by].tolist(),
                    gdate[by].tolist(), gprio[by].tolist()))


def q3_rows_python(tables, date=DATE, segment=SEGMENT):
    """The same a row at a time over Python's integers."""
    building = {k for k, s in zip(*(c.tolist() for c in
                                    tables["customer"])) if s == segment}
    orders = {k: (d, p) for k, c, d, p in zip(*(
        c.tolist() for c in tables["orders"])) if d < date and c in building}
    groups = {}
    for k, p, d, s in zip(*(c.tolist() for c in tables["lineitem"])):
        if s > date and k in orders:
            groups[k] = groups.get(k, 0) + p * (100 - d)
    rows = [(k, v) + orders[k] for k, v in groups.items()]
    return sorted(rows, key=lambda r: (-r[1], r[2], r[0]))


def reference(data, part, query, action):
    if query not in QUERIES or action not in ACTIONS:
        raise ValueError("unknown query or action %r %r" % (query, action))
    rows = q3_rows(data["tables"])
    return {"rows": rows, "set": frozenset(rows)}


def _load_table(ctx, name, cols, ndev):
    from dpark_tpu import Columns
    from dpark_tpu.table import TableRDD
    rdd = ctx.parallelize(Columns(*cols), ndev).map(resident).cache()
    if rdd.count() != len(cols[0]):
        raise RuntimeError("loading %s lost rows" % name)
    return TableRDD(rdd, list(TABLES[name]), name)


def probe(ctx, ndev):
    """Q3 over a resident set of scale factor 0.001, before anything of
    size is loaded: a program that cannot plan it whole on the device
    ends here.  The executor has to show the counter
    `query_joins_device`; the query has to plan with no decline, be one
    job whose stages are all on the array path with no fallback reason,
    raise the counter by 2 and scan no row on the host; and every group
    has to be the reference's."""
    ex = ctx.scheduler.executor
    for counter in ("query_joins_device", "scan_rows_host"):
        if not isinstance(getattr(ex, counter, None), int):
            raise RuntimeError(
                "the executor has no counter %s: this program's query "
                "plane does not lower a join over tables resident on the "
                "device and cannot run the configuration" % counter)
    tables = make_tables(0, PROBE_SF)
    loaded = {t: _load_table(ctx, t, cols, ndev)
              for t, cols in tables.items()}
    t = ctx.sql(SQL_ALL, **loaded)
    if t._planned() is None:
        raise RuntimeError("Q3 over resident tables declined (%s): this "
                           "program cannot run the configuration"
                           % getattr(t.rdd, "_query_fallbacks", None))
    since = len(ctx.scheduler.history)
    joins0, host0 = ex.query_joins_device, ex.scan_rows_host
    rows = [tuple(r) for r in t.collect()]
    records = ctx.scheduler.history[since:]
    kinds = [str(st.get("kind")) for rec in records
             for st in rec["stage_info"]]
    if len(records) != 1 or not kinds \
            or not all(k.startswith("array") for k in kinds) \
            or ctx.scheduler.fallback_reasons() \
            or ex.query_joins_device - joins0 != 2 \
            or ex.scan_rows_host != host0:
        raise RuntimeError(
            "Q3 over resident tables was not one job on the array path "
            "of two device joins (jobs %d, stage kinds %s, fallback %s, "
            "device joins %d, rows scanned on the host %d): this "
            "program cannot run the configuration"
            % (len(records), kinds, ctx.scheduler.fallback_reasons(),
               ex.query_joins_device - joins0, ex.scan_rows_host - host0))
    ref = q3_rows(tables)
    if not verdict(rows, {"rows": ref, "set": frozenset(ref)},
                   "all_groups"):
        raise RuntimeError("the probe's groups are not the reference's: "
                           "%d rows against %d" % (len(rows), len(ref)))
    for tab in loaded.values():
        ex.drop_result(tab.rdd.id)


def load(ctx, data, ndev):
    """The probe, then the three tables, each a table over a cached RDD
    resident in HBM (the identity map makes it a device stage); their
    ranges and unique keys are read here, once, and no query reads
    them again."""
    probe(ctx, ndev)
    tables = {t: _load_table(ctx, t, cols, ndev)
              for t, cols in data["tables"].items()}
    return {"tables": tables,
            "resident_ids": [t.rdd.id for t in tables.values()]}


def run(ctx, tables, part, query, action, ndev):
    """One job: the text parsed, planned and run; its ten rows (or, in
    set-up, every group) collected."""
    if action == "collect":
        return [tuple(r) for r in ctx.sql(QUERIES[query],
                                          **tables["tables"])]
    if action == "all_groups":
        return [tuple(r) for r in ctx.sql(SQL_ALL,
                                          **tables["tables"]).collect()]
    raise ValueError("unknown action %r" % action)


def _ordered(rows):
    keys = [(-r[1], r[2]) for r in rows]
    return keys == sorted(keys)


def verdict(result, expected, action):
    """Every row an exact group of the reference (keys, dates and revenue
    as Python ints), no row twice, in the ORDER BY's order; `collect`
    the first ten of it, where rows that tie on (revenue, o_orderdate)
    across the tenth place may be any of the tied groups; `all_groups`
    every group."""
    rows = expected["rows"]
    want = rows if action == "all_groups" else rows[:TOP]
    if len(result) != len(want) or len(set(result)) != len(result):
        return False
    if not all(len(r) == 4 and all(type(v) is int for v in r)
               for r in result):
        return False
    if not set(result) <= expected["set"] or not _ordered(result):
        return False
    if [(-r[1], r[2]) for r in result] != [(-r[1], r[2]) for r in want]:
        return False
    if result != want:
        print("[verdict] rows that tie on (revenue, o_orderdate) came in "
              "another order than the reference's", flush=True)
    return True


def least(config, traffic, data, ndev, query):
    """HBM: every read column of the three tables once at its device
    width, and the ten result rows written.  One chip: nothing
    crosses."""
    hbm = sum(n * DEVICE_ROW_BYTES[t] for t, n in data["table_rows"].items())
    return {"hbm_bytes": float(hbm + TOP * 32), "ici_bytes": 0.0}
