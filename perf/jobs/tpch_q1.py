"""tpch-q1: TPC-H Query 1, the pricing summary report (clause 2.4.1), at
its validation parameter DELTA = 90, over partitions of `lineitem` that
are resident in HBM as seven columns:

    SELECT l_returnflag, l_linestatus, SUM(l_quantity),
           SUM(l_extendedprice), SUM(l_extendedprice*(1-l_discount)),
           SUM(l_extendedprice*(1-l_discount)*(1+l_tax)),
           AVG(l_quantity), AVG(l_extendedprice), AVG(l_discount), COUNT(*)
    FROM lineitem WHERE l_shipdate <= DATE '1998-12-01' - INTERVAL '90' DAY
    GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus

DECIMAL(15,2) is an int64 count of hundredths, CHAR(1) an S1 byte string,
DATE an int32 count of days, so the text the job sends (`SQL`) is written
over hundredths and days: every product and every sum is an exact integer.
A job is `ctx.sql(SQL, lineitem=<the partition's table>).collect()`: the
text is parsed and planned anew inside every job's wall.

Everything of this configuration: seeded data after the specification's
population rules (clause 4.2.3), the load to HBM with its probe, the one
dpark call, the numpy reference (int64 over the host columns, checked
once against Python ints; no code shared with dpark_tpu) and the verdict.
"""

import numpy as np

from perf.lib import least_bytes

FIELDS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
          "l_returnflag", "l_linestatus", "l_shipdate")
SCHEMA_ROW_BYTES = 38   # 4 x int64 + 2 x S1 + int32: the schema's widths
DEVICE_ROW_BYTES = 56   # on the device every column is 8-byte words
PROBE_ROWS = 1 << 10
SAMPLE_STEP = 1 << 10   # the Python-int cross-check's 1-in-1,024 rows


def days(year, month, day):
    return int((np.datetime64("%04d-%02d-%02d" % (year, month, day))
                - np.datetime64("1970-01-01")) / np.timedelta64(1, "D"))


START_DATE = days(1992, 1, 1)       # the specification's STARTDATE
LAST_ORDER = days(1998, 8, 2)       # ENDDATE - 151 days
CURRENT_DATE = days(1995, 6, 17)
CUTOFF = days(1998, 12, 1) - 90     # 1998-09-02

SQL = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
       "sum(l_extendedprice) as sum_base_price, "
       "sum(l_extendedprice*(100-l_discount)) as sum_disc_price, "
       "sum(l_extendedprice*(100-l_discount)*(100+l_tax)) as sum_charge, "
       "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
       "avg(l_discount) as avg_disc, count(*) as count_order "
       "from lineitem where l_shipdate <= %d "
       "group by l_returnflag, l_linestatus "
       "order by l_returnflag, l_linestatus" % CUTOFF)

QUERIES = {"q1": SQL}

# what make_data implements; the configuration's file has to say the same
DISTRIBUTIONS = {
    "lines_per_order": {"kind": "uniform_int", "low": 1, "high": 7},
    "o_orderdate": {"kind": "uniform_days", "low": "1992-01-01",
                    "high": "1998-08-02"},
    "l_quantity": {"kind": "uniform_int", "low": 1, "high": 50},
    "l_partkey": {"kind": "uniform_int", "low": 1, "high": 2000000},
    "p_retailprice_hundredths":
        "90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)",
    "l_extendedprice": "l_quantity * p_retailprice",
    "l_discount_hundredths": {"kind": "uniform_int", "low": 0, "high": 10},
    "l_tax_hundredths": {"kind": "uniform_int", "low": 0, "high": 8},
    "l_shipdate": {"kind": "o_orderdate_plus_uniform_days", "low": 1,
                   "high": 121},
    "l_receiptdate": {"kind": "l_shipdate_plus_uniform_days", "low": 1,
                      "high": 30},
    "l_linestatus": "O if l_shipdate > 1995-06-17 else F",
    "l_returnflag": "R or A evenly if l_receiptdate <= 1995-06-17 else N",
}


def resident(r):
    return r


def lineitem_partition(seed, part, rows):
    """`rows` consecutive lineitem rows in generation order: whole orders
    of 1..7 lines (the last one cut at the partition's end), every column
    drawn as clause 4.2.3 says, from numpy's default_rng([seed, part])."""
    rng = np.random.default_rng([seed, part])
    lines = rng.integers(1, 8, rows // 4 + 64)
    while int(lines.sum()) < rows:
        lines = np.concatenate([lines, rng.integers(1, 8, 64 + rows // 64)])
    order_date = rng.integers(START_DATE, LAST_ORDER + 1, len(lines))
    ordered = np.repeat(order_date, lines)[:rows]
    quantity = rng.integers(1, 51, rows)
    partkey = rng.integers(1, 2000001, rows)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    discount = rng.integers(0, 11, rows)
    tax = rng.integers(0, 9, rows)
    ship = ordered + rng.integers(1, 122, rows)
    receipt = ship + rng.integers(1, 31, rows)
    returned = np.where(rng.integers(0, 2, rows) == 0, b"R", b"A")
    flag = np.where(receipt <= CURRENT_DATE, returned, b"N").astype("S1")
    status = np.where(ship > CURRENT_DATE, b"O", b"F").astype("S1")
    return ((quantity * 100).astype(np.int64),
            (quantity * retail).astype(np.int64),
            discount.astype(np.int64), tax.astype(np.int64),
            flag, status, ship.astype(np.int32))


def make_data(config, traffic, seed, scale):
    """The table: `resident_partitions` partitions of rows_per_job //
    scale consecutive lineitem rows each.  The reference's arithmetic is
    checked here, once, against Python ints."""
    if config["distributions"] != DISTRIBUTIONS:
        raise ValueError("the configuration's distributions %r are not "
                         "what this module implements (%r)"
                         % (config["distributions"], DISTRIBUTIONS))
    rows = max(PROBE_ROWS, int(traffic["rows_per_job"]) // scale)
    parts = [lineitem_partition(seed, p, rows)
             for p in range(int(traffic["resident_partitions"]))]
    sample = tuple(c[::SAMPLE_STEP] for c in parts[0])
    if q1_rows(sample) != q1_rows_python(sample):
        raise RuntimeError("the numpy reference disagrees with Python's "
                           "integers on the 1-in-%d sample" % SAMPLE_STEP)
    return {"parts": parts, "rows": rows}


def input_rows(data):
    return data["rows"]


def n_partitions(data):
    return len(data["parts"])


def resident_bytes(data):
    return len(data["parts"]) * data["rows"] * DEVICE_ROW_BYTES


def q1_rows(cols, cutoff=CUTOFF):
    """Q1 over host columns in numpy int64: (flag, status, the four sums
    in hundredths / 10^-4 / 10^-6, the three averages in hundredths as
    sum / count in float64, count) a group, in the ORDER BY's order."""
    quantity, price, discount, tax, flag, status, ship = cols
    keep = ship <= cutoff
    key = flag.view(np.uint8).astype(np.int64) * 256 + status.view(np.uint8)
    out = []
    for k in np.unique(key[keep]).tolist():
        m = keep & (key == k)
        p, d = price[m], discount[m]
        disc_price = p * (100 - d)
        sums = [int(quantity[m].sum()), int(p.sum()),
                int(disc_price.sum()),
                int((disc_price * (100 + tax[m])).sum())]
        count = int(m.sum())
        out.append((bytes([k // 256]), bytes([k % 256])) + tuple(sums)
                   + (sums[0] / count, sums[1] / count,
                      int(d.sum()) / count, count))
    return out


def q1_rows_python(cols, cutoff=CUTOFF):
    """The same a row at a time over Python's integers."""
    groups = {}
    for q, p, d, t, f, s, ship in zip(*(c.tolist() for c in cols)):
        if ship > cutoff:
            continue
        g = groups.setdefault((f, s), [0, 0, 0, 0, 0, 0])
        g[0] += q
        g[1] += p
        g[2] += p * (100 - d)
        g[3] += p * (100 - d) * (100 + t)
        g[4] += d
        g[5] += 1
    return [k + tuple(g[:4]) + (g[0] / g[5], g[1] / g[5], g[4] / g[5],
                                g[5]) for k, g in sorted(groups.items())]


def reference(data, part, query, action):
    if query not in QUERIES or action != "collect":
        raise ValueError("unknown query or action %r %r" % (query, action))
    return q1_rows(data["parts"][part])


def _load_table(ctx, cols, ndev):
    from dpark_tpu import Columns
    rdd = ctx.parallelize(Columns(*cols), ndev).map(resident).cache()
    if rdd.count() != len(cols[0]):
        raise RuntimeError("loading a partition lost rows")
    return ctx.table(rdd, list(FIELDS))


def probe(ctx, ndev):
    """Q1 over a resident table of PROBE_ROWS rows, before anything of
    size is loaded: a program that does not run it whole on the device
    ends here.  The executor has to show the counters `scan_rows_host`
    and `scan_rows_device`; the query has to be one job whose stages are
    all on the array path, with no fallback reason, that hands the
    stage program every row of the table and scans none on the host;
    and its four rows have to be the reference's."""
    ex = ctx.scheduler.executor
    for counter in ("scan_rows_host", "scan_rows_device"):
        if not isinstance(getattr(ex, counter, None), int):
            raise RuntimeError(
                "the executor has no counter %s: this program's query "
                "plane does not scan a table resident on the device and "
                "cannot run the configuration" % counter)
    cols = lineitem_partition(0, 0, PROBE_ROWS)
    table = _load_table(ctx, cols, ndev)
    since = len(ctx.scheduler.history)
    host0, dev0 = ex.scan_rows_host, ex.scan_rows_device
    rows = [tuple(r) for r in ctx.sql(SQL, lineitem=table).collect()]
    records = ctx.scheduler.history[since:]
    kinds = [str(st.get("kind")) for rec in records
             for st in rec["stage_info"]]
    if len(records) != 1 or not kinds \
            or not all(k.startswith("array") for k in kinds) \
            or ctx.scheduler.fallback_reasons() \
            or ex.scan_rows_host != host0 \
            or ex.scan_rows_device - dev0 != PROBE_ROWS:
        raise RuntimeError(
            "Q1 over a resident table was not one job on the array path "
            "that scans on the device (jobs %d, stage kinds %s, fallback "
            "%s, rows scanned on the host %d, on the device %d of %d): "
            "this program cannot run the configuration"
            % (len(records), kinds, ctx.scheduler.fallback_reasons(),
               ex.scan_rows_host - host0, ex.scan_rows_device - dev0,
               PROBE_ROWS))
    if not verdict(rows, q1_rows(cols), "collect"):
        raise RuntimeError("the probe's answer is not the reference's: "
                           "%r" % (rows,))
    ex.drop_result(table.rdd.id)


def load(ctx, data, ndev):
    """The probe, then each partition as a table over a cached RDD that
    is resident in HBM (the identity map makes it a device stage); the
    table's ranges are read here, once, and no query reads them again."""
    probe(ctx, ndev)
    tables = [_load_table(ctx, cols, ndev) for cols in data["parts"]]
    return {"parts": tables, "resident_ids": [t.rdd.id for t in tables]}


def run(ctx, tables, part, query, action, ndev):
    """One job: the text parsed, planned and run, its rows collected."""
    if action != "collect":
        raise ValueError("unknown action %r" % action)
    return [tuple(r) for r in ctx.sql(
        QUERIES[query], lineitem=tables["parts"][part]).collect()]


def verdict(result, expected, action):
    """Every group once and in order, keys exact as bytes, COUNT and the
    four SUMs exact as integers, each AVG within 1 ulp of sum / count in
    float64."""
    if len(result) != len(expected):
        return False
    for got, ref in zip(result, expected):
        if len(got) != 10 or tuple(got[:6]) != ref[:6] or got[9] != ref[9]:
            return False
        if not all(type(v) is int for v in got[2:6] + (got[9],)):
            return False
        for a, b in zip(got[6:9], ref[6:9]):
            if not abs(a - b) <= np.spacing(b):
                return False
    return True


def least(config, traffic, data, ndev, query):
    """HBM: every row's seven columns read once at the schema's widths,
    the four groups' rows written.  One chip: nothing crosses."""
    return least_bytes.keyed_aggregate(data["rows"] // ndev,
                                       SCHEMA_ROW_BYTES, 4, ndev)
