"""intpairs-join: fact.join(dim).map(to_group).reduceByKey(add) over fact
partitions and a dimension resident in HBM (BASELINE.json config 3, as
chip_smoke.py step_join runs it).

Everything of this configuration: seeded data, the load to HBM, the dpark
calls, and the numpy reference (no code shared with dpark_tpu).
"""

import numpy as np

from perf.lib import checks, draws, least_bytes

ROW_BYTES = 16


def add(a, b):
    return a + b


def resident(kv):
    return kv


def to_group_1024(kv):
    key, (fact_value, dim_value) = kv
    return (key % 1024, fact_value * dim_value)


QUERIES = {"join_group_1024": (to_group_1024, 1024)}


def make_data(config, traffic, seed, scale):
    """As chip_smoke.make_join: the dimension holds dim_rows distinct keys
    out of [0, domain) with domain = key_domain_over_dimension x dim_rows;
    fact keys are drawn over the whole domain, so 1 / that factor of the
    fact rows find a dimension row."""
    if config["key_distribution"] != {"kind": "uniform"}:
        raise ValueError("key distribution %r is not implemented (known: "
                         "uniform)" % (config["key_distribution"],))
    fact_rows = max(1024, int(traffic["rows_per_job"]) // scale)
    dim_rows = max(64, fact_rows // int(config["fact_to_dimension"]))
    domain = int(config["key_domain_over_dimension"]) * dim_rows
    rng = np.random.default_rng([seed, 1 << 20])
    dim_key = rng.permutation(domain)[:dim_rows].astype(np.int64)
    dim_val = draws.integers(rng, config["dimension_value_distribution"],
                             dim_rows)
    if dim_val.min() < 1:
        raise ValueError("the reference marks a missing key by 0")
    parts = []
    for p in range(int(traffic["resident_partitions"])):
        prng = np.random.default_rng([seed, p])
        key = prng.integers(0, domain, fact_rows, dtype=np.int64)
        val = draws.integers(prng, config["fact_value_distribution"],
                             fact_rows)
        parts.append((key, val))
    # the reference's lookup: the dimension's value at each key of the
    # dense domain, 0 where the dimension has no such key
    lookup = np.zeros(domain, np.int64)
    lookup[dim_key] = dim_val
    return {"parts": parts, "dim": (dim_key, dim_val), "rows": fact_rows,
            "dim_rows": dim_rows, "lookup": lookup}


def input_rows(data):
    return data["rows"] + data["dim_rows"]


def n_partitions(data):
    return len(data["parts"])


def resident_bytes(data):
    return (len(data["parts"]) * data["rows"] + data["dim_rows"]) * ROW_BYTES


def reference(data, part, query, action):
    """Look each fact key up in the dimension, then exact sums of
    fact_value * dim_value by key % groups over the rows that matched."""
    if action != "collect":
        raise ValueError("unknown action %r" % action)
    groups = QUERIES[query][1]
    key, val = data["parts"][part]
    dim_val = data["lookup"][key]
    hit = dim_val > 0
    return checks.keyed_sums(key[hit] % groups, val[hit] * dim_val[hit],
                             groups)


def load(ctx, data, ndev):
    from dpark_tpu import Columns

    def cached(k, v):
        rdd = ctx.parallelize(Columns(k, v), ndev).map(resident).cache()
        if rdd.count() != len(k):
            raise RuntimeError("loading a table lost rows")
        return rdd

    dim = cached(*data["dim"])
    parts = [cached(k, v) for k, v in data["parts"]]
    return {"parts": parts, "dim": dim,
            "resident_ids": [dim.id] + [r.id for r in parts]}


def run(ctx, tables, part, query, action, ndev):
    if action != "collect":
        raise ValueError("unknown action %r" % action)
    return tables["parts"][part].join(tables["dim"], ndev) \
        .map(QUERIES[query][0]).reduceByKey(add, ndev).collect()


def verdict(result, expected, action):
    return checks.same_keyed_sums(result, expected)


def least(config, traffic, data, ndev, query):
    return least_bytes.join_aggregate(
        data["rows"] // ndev, data["dim_rows"] // ndev, ROW_BYTES,
        QUERIES[query][1] // ndev, ndev)
