"""intpairs-reducebykey: map(shift of the key).reduceByKey(add) over
partitions of (int64 key, int64 value) pairs resident in HBM
(BASELINE.json's north-star job, as chip_smoke.py step_reduce runs it).

Everything of this configuration: seeded data, the load to HBM, the dpark
calls of each query, and the numpy references (which share no code with
dpark_tpu).  The queries are module-level functions with distinct code on
purpose: fuse.fn_key ignores __defaults__, so two lambdas that differ only
in a default argument would share one compiled program (PERF.md,
Findings).
"""

import numpy as np

from perf.lib import checks, draws, least_bytes

ROW_BYTES = 16          # two int64 columns
SAMPLE_MASK = 1023      # collect_sample keeps keys with key & 1023 == 7
SAMPLE_RESIDUE = 7


def add(a, b):
    return a + b


def resident(kv):
    return kv


def prefix_16(kv):
    return (kv[0] >> 16, kv[1])


def prefix_24(kv):
    return (kv[0] >> 24, kv[1])


def prefix_32(kv):
    return (kv[0] >> 0, kv[1])


def in_sample(kv):
    return (kv[0] & 1023) == 7


# query -> (the map function, bits of the key the group keeps)
QUERIES = {"prefix_16": (prefix_16, 16), "prefix_24": (prefix_24, 8),
           "prefix_32": (prefix_32, 32)}


def make_data(config, traffic, seed, scale):
    """The table: `resident_partitions` partitions of rows_per_job //
    scale rows, each (key, value) as int64 columns drawn as the
    configuration's file says."""
    if config["key_distribution"] != {"kind": "uniform", "bits": 32}:
        raise ValueError("key distribution %r is not implemented (known: "
                         "uniform over 32 bits, whose shifts the queries "
                         "are)" % (config["key_distribution"],))
    key_bits = 32
    rows = max(1024, int(traffic["rows_per_job"]) // scale)
    parts = []
    for p in range(int(traffic["resident_partitions"])):
        rng = np.random.default_rng([seed, p])
        key = draws.integers(rng, config["key_distribution"], rows)
        val = draws.integers(rng, config["value_distribution"], rows)
        parts.append((key, val))
    return {"parts": parts, "rows": rows, "key_bits": key_bits}


def input_rows(data):
    return data["rows"]


def n_partitions(data):
    return len(data["parts"])


def resident_bytes(data):
    return len(data["parts"]) * data["rows"] * ROW_BYTES


def reference(data, part, query, action):
    """What the job must return for this partition."""
    key, rev = data["parts"][part]
    bits = QUERIES[query][1]
    keys = key >> (data["key_bits"] - bits)
    if action == "collect":
        return checks.keyed_sums(keys, rev, 1 << bits)
    if action == "count":
        s = np.sort(keys)
        return int(1 + np.count_nonzero(s[1:] != s[:-1])) if len(s) else 0
    if action == "collect_sample":
        keep = (keys & SAMPLE_MASK) == SAMPLE_RESIDUE
        uniq, inv = np.unique(keys[keep], return_inverse=True)
        sums = np.bincount(inv, weights=rev[keep], minlength=len(uniq))
        return uniq.astype(np.int64), sums.astype(np.int64)
    raise ValueError("unknown action %r" % action)


def load(ctx, data, ndev):
    """Each partition as a cached RDD resident in HBM.  A bare
    parallelize(...).cache() is NOT device-resident (PERF.md, Findings):
    the identity map makes it a device stage whose output the executor
    keeps in its result_cache."""
    from dpark_tpu import Columns
    tables = []
    for key, val in data["parts"]:
        rdd = ctx.parallelize(Columns(key, val), ndev).map(resident).cache()
        if rdd.count() != len(key):
            raise RuntimeError("loading a partition lost rows")
        tables.append(rdd)
    return {"parts": tables, "resident_ids": [r.id for r in tables]}


def run(ctx, tables, part, query, action, ndev):
    """One job: the chain is built and its action returns inside the
    caller's clock."""
    reduced = tables["parts"][part].map(QUERIES[query][0]) \
        .reduceByKey(add, ndev)
    if action == "collect":
        return reduced.collect()
    if action == "count":
        return reduced.count()
    if action == "collect_sample":
        return reduced.filter(in_sample).collect()
    raise ValueError("unknown action %r" % action)


def verdict(result, expected, action):
    if action == "count":
        return result == expected
    return checks.same_keyed_sums(result, expected)


def least(config, traffic, data, ndev, query):
    rows = data["rows"] // ndev
    groups = min(rows, (1 << QUERIES[query][1]) // ndev)
    return least_bytes.keyed_aggregate(rows, ROW_BYTES, groups, ndev)
