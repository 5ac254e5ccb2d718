"""uservisits-q2: AMPLab Big Data Benchmark query 2C,

    SELECT SUBSTR(sourceIP, 1, 12), SUM(adRevenue) FROM uservisits GROUP BY 1

over partitions of (sourceIP S16, adRevenue float32) resident in HBM: the
dpark chain is map(lambda r: (r[0][:12], r[1])).reduceByKey(add), the key
a fixed-width byte string the device holds as two 64-bit words.

Everything of this configuration: seeded data, the load to HBM (which
proves that it ran on the array path), the dpark calls, and the numpy
reference (over the bytes; no code shared with dpark_tpu).  Queries and
predicates are module-level functions with distinct code: fuse.fn_key
ignores __defaults__ (PERF.md, Findings).
"""

import numpy as np

from perf.lib import least_bytes

ROW_BYTES = 20          # sourceIP S16 + adRevenue float32
IP_BYTES = 16
SAMPLE_PREFIX = b"173."  # collect_sample keeps the groups of first octet 173
SUM_ULP = 2.0 ** -23    # one float32 rounding an addition (the guarantee)

IP_DISTRIBUTION = {"kind": "dotted_quad",
                   "octets": {"kind": "uniform", "low": 0, "high": 256}}
REVENUE_DISTRIBUTION = {"kind": "uniform", "low": 0.0, "high": 1.0,
                        "dtype": "float32"}


def add(a, b):
    return a + b


def resident(r):
    return r


def q2c(r):
    return (r[0][:12], r[1])


def in_sample(kv):
    # the key begins b"173." ('1' '7' '3' '.'); & and not `and`: the
    # same expression has to trace on the device
    return ((kv[0][0] == 49) & (kv[0][1] == 55) & (kv[0][2] == 51)
            & (kv[0][3] == 46))


# query -> (the map function, bytes of sourceIP the group keeps)
QUERIES = {"q2c": (q2c, 12)}


def _half_tables():
    """For every pair of octets (a, b): the bytes of "a.b." and of "a.b"
    as left-aligned big-endian uint64, and the length of "a.b."."""
    head = np.zeros(65536, np.uint64)
    tail = np.zeros(65536, np.uint64)
    head_len = np.zeros(65536, np.uint64)
    for a in range(256):
        for b in range(256):
            text = b"%d.%d" % (a, b)
            i = a * 256 + b
            tail[i] = int.from_bytes(text.ljust(8, b"\0"), "big")
            head[i] = int.from_bytes((text + b".").ljust(8, b"\0"), "big")
            head_len[i] = len(text) + 1
    return head, tail, head_len


def dotted_quads(octets, tables):
    """(n, 4) uint8 octets -> (n,) S16 dotted quads, NUL-padded, by word
    arithmetic: "a.b." (4-8 bytes) and "c.d" (3-7 bytes) come from the
    tables, and the second is shifted in behind the first."""
    head, tail, head_len = tables
    first = octets[:, 0].astype(np.int64) * 256 + octets[:, 1]
    second = octets[:, 2].astype(np.int64) * 256 + octets[:, 3]
    h, n, t = head[first], head_len[first], tail[second]
    full = n == 8           # "a.b." fills word 0: a shift by 64 is undefined
    bits = np.where(full, np.uint64(0), n * np.uint64(8))
    words = np.empty((len(octets), 2), ">u8")
    words[:, 0] = np.where(full, h, h | (t >> bits))
    words[:, 1] = np.where(full, t, t << (np.uint64(64) - bits))
    return words.view("S%d" % IP_BYTES).reshape(len(octets))


def make_data(config, traffic, seed, scale):
    """The table: `resident_partitions` partitions ("days") of
    rows_per_job // scale rows, each (sourceIP S16, adRevenue float32),
    drawn as the configuration's file says."""
    if config["source_ip_distribution"] != IP_DISTRIBUTION:
        raise ValueError("sourceIP distribution %r is not implemented "
                         "(known: dotted quads of uniform octets)"
                         % (config["source_ip_distribution"],))
    if config["ad_revenue_distribution"] != REVENUE_DISTRIBUTION:
        raise ValueError("adRevenue distribution %r is not implemented "
                         "(known: uniform float32 in [0, 1))"
                         % (config["ad_revenue_distribution"],))
    rows = max(1024, int(traffic["rows_per_job"]) // scale)
    tables = _half_tables()
    parts = []
    for p in range(int(traffic["resident_partitions"])):
        rng = np.random.default_rng([seed, p])
        octets = rng.integers(0, 256, (rows, 4), dtype=np.uint8)
        revenue = rng.random(rows, dtype=np.float32)
        parts.append((dotted_quads(octets, tables), revenue))
    return {"parts": parts, "rows": rows}


def input_rows(data):
    return data["rows"]


def n_partitions(data):
    return len(data["parts"])


def resident_bytes(data):
    return len(data["parts"]) * data["rows"] * ROW_BYTES


def _prefix_words(ip, width):
    """The first `width` (8 < width <= 16) bytes of each S16 string as
    (first 8 bytes, the rest) unsigned big-endian integers."""
    raw = ip.view(np.uint8).reshape(len(ip), IP_BYTES)
    rest = np.zeros((len(ip), 8), np.uint8)
    rest[:, :width - 8] = raw[:, 8:width]
    return (np.ascontiguousarray(raw[:, :8]).view(">u8")[:, 0],
            rest.view(">u8")[:, 0])


def distinct_prefixes(ip, width):
    a, b = _prefix_words(ip, width)
    order = np.lexsort((b, a))
    a, b = a[order], b[order]
    return int(1 + np.count_nonzero((a[1:] != a[:-1]) | (b[1:] != b[:-1]))) \
        if len(ip) else 0


def reference(data, part, query, action):
    """What the job must return for this partition: for `count` the
    number of distinct prefixes; for `collect_sample` (keys as bytes
    ascending, float64 sums, rows a group, sum of |v| a group) of the
    groups that begin SAMPLE_PREFIX."""
    ip, revenue = data["parts"][part]
    width = QUERIES[query][1]
    if action == "count":
        return distinct_prefixes(ip, width)
    if action == "collect_sample":
        raw = ip.view(np.uint8).reshape(len(ip), IP_BYTES)
        keep = (raw[:, :len(SAMPLE_PREFIX)]
                == np.frombuffer(SAMPLE_PREFIX, np.uint8)).all(axis=1)
        keys = np.ascontiguousarray(raw[keep, :width]) \
            .view("S%d" % width)[:, 0]
        uniq, inv = np.unique(keys, return_inverse=True)
        v = revenue[keep].astype(np.float64)
        return (uniq.tolist(),
                np.bincount(inv, weights=v, minlength=len(uniq)),
                np.bincount(inv, minlength=len(uniq)),
                np.bincount(inv, weights=np.abs(v), minlength=len(uniq)))
    raise ValueError("unknown action %r" % action)


def load(ctx, data, ndev):
    """Each partition as a cached RDD resident in HBM (the identity map
    makes it a device stage: PERF.md, Findings).  The first partition
    PROVES it: a program that keeps byte strings on the host would walk
    every row of 96 partitions through Python, so it is stopped here."""
    from dpark_tpu import Columns
    ex = ctx.scheduler.executor
    tables = []
    for ip, revenue in data["parts"]:
        rdd = ctx.parallelize(Columns(ip, revenue), ndev) \
            .map(resident).cache()
        if rdd.count() != len(ip):
            raise RuntimeError("loading a partition lost rows")
        if not tables:
            kinds = [str(st.get("kind")) for st in
                     ctx.scheduler.history[-1]["stage_info"]]
            if rdd.id not in ex.result_cache_ids() or not kinds \
                    or not all(k.startswith("array") for k in kinds) \
                    or ctx.scheduler.fallback_reasons():
                raise RuntimeError(
                    "the S16 column left the array path (stage kinds %s, "
                    "resident %s, fallback %s): this program cannot run "
                    "the configuration"
                    % (kinds, rdd.id in ex.result_cache_ids(),
                       ctx.scheduler.fallback_reasons()))
        tables.append(rdd)
    return {"parts": tables, "resident_ids": [r.id for r in tables]}


def run(ctx, tables, part, query, action, ndev):
    """One job: the chain is built and its action returns inside the
    caller's clock."""
    reduced = tables["parts"][part].map(QUERIES[query][0]) \
        .reduceByKey(add, ndev)
    if action == "count":
        return reduced.count()
    if action == "collect_sample":
        return reduced.filter(in_sample).collect()
    raise ValueError("unknown action %r" % action)


def sums_within(rows, expected):
    """Are the (key bytes, sum) rows the expected groups, every key once
    and exact, each sum within rows-of-the-group * 2**-23 * sum|v| of
    the float64 reference?"""
    keys, sums, counts, magnitudes = expected
    rows = sorted(rows)
    if [k for k, _ in rows] != keys:
        return False
    got = np.array([v for _, v in rows], np.float64)
    return bool(np.all(np.abs(got - sums) <= counts * SUM_ULP * magnitudes))


def verdict(result, expected, action):
    if action == "count":
        return result == expected
    return sums_within(result, expected)


def least(config, traffic, data, ndev, query):
    rows = data["rows"] // ndev
    groups = distinct_prefixes(data["parts"][0][0],
                               QUERIES[query][1]) // ndev
    return least_bytes.keyed_aggregate(rows, ROW_BYTES, groups, ndev)
