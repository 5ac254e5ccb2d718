"""gensort-terasort: the Sort Benchmark's job (GraySort / MinuteSort;
Hadoop TeraGen + TeraSort + TeraValidate), Indy rules: records of 100
bytes as `gensort` makes them, a 10-byte uniformly random binary key and
90 bytes of payload; the output is every input record in ascending order
of memcmp over the key.

The dpark chain over one partition of (key S10, payload S90) resident in
HBM is `sortByKey(numSplits=ndev)`: a sample of the keys cuts the key
space into one range a chip, every record goes to its range's owner, and
each chip sorts what it received.  The sorted RDD stays in HBM (the
source writes files); the timed job brings a 1-in-4,096 sample of the
sorted records to the host, and that sample is compared, record for
record, with numpy's sort of the same partition.

Everything of this configuration: seeded records, the load to HBM (which
stops a program that cannot sort byte strings on the device), the dpark
calls, and the numpy reference (no code shared with dpark_tpu).
"""

import numpy as np

KEY_BYTES = 10
PAYLOAD_BYTES = 90
HOST_ROW_BYTES = KEY_BYTES + PAYLOAD_BYTES      # the source's record
DEVICE_ROW_BYTES = 8 * (2 + 12)     # S10 and S90 as 64-bit words in HBM
PROBE_ROWS = 4096

KEY_DISTRIBUTION = {"kind": "uniform_bytes", "low": 0, "high": 256}
RECORD_LAYOUT = "gensort_binary"


def resident(kv):
    return kv


def in_sample(kv):
    # 1 key in 4,096: its last byte is 7 and the one before under 16
    # (& and not `and`: the same expression traces on the device)
    return (kv[0][9] == 7) & (kv[0][8] < 16)


def in_dense_sample(kv):
    # 1 key in 64
    return kv[0][9] % 64 == 7


# query -> {action: the predicate over the sorted records, or None}
QUERIES = {"terasort": {"sample": in_sample,
                        "dense_sample": in_dense_sample,
                        "count": None}}


def _keep(keys, action):
    """The predicates above over (n, 10) uint8 keys, for the reference."""
    if action == "sample":
        return (keys[:, 9] == 7) & (keys[:, 8] < 16)
    if action == "dense_sample":
        return keys[:, 9] % 64 == 7
    raise ValueError("unknown action %r" % action)


def _hex(nibbles):
    """uint8 values 0-15 as their ASCII hex digits, upper case."""
    return nibbles + np.uint8(48) + (nibbles > 9) * np.uint8(7)


def _nibbles(octets):
    """(n, w) uint8 -> (n, 2w): each byte's high and low four bits."""
    out = np.empty((len(octets), 2 * octets.shape[1]), np.uint8)
    out[:, 0::2] = octets >> 4
    out[:, 1::2] = octets & 15
    return out


def gensort_records(rng, first, rows):
    """`rows` records numbered from `first`, as (key S10, payload S90).
    The layout is gensort's binary record as the configuration's file
    describes it under `assumed`; keys and filler come from `rng`."""
    drawn = rng.integers(0, 256, (rows, 16), dtype=np.uint8)
    key = np.ascontiguousarray(drawn[:, :KEY_BYTES])
    number = np.arange(first, first + rows, dtype=np.uint64)
    pay = np.empty((rows, PAYLOAD_BYTES), np.uint8)
    pay[:, 0:2] = (0x00, 0x11)
    pay[:, 2:18] = 48               # the number's high 64 bits: "0" x 16
    pay[:, 18:34] = _hex(_nibbles(
        number.astype(">u8").view(np.uint8).reshape(rows, 8)))
    pay[:, 34:38] = (0x88, 0x99, 0xAA, 0xBB)
    pay[:, 38:86] = np.repeat(_hex(_nibbles(drawn[:, KEY_BYTES:])), 4,
                              axis=1)
    pay[:, 86:90] = (0xCC, 0xDD, 0xEE, 0xFF)
    return (key.view("S%d" % KEY_BYTES)[:, 0],
            pay.view("S%d" % PAYLOAD_BYTES)[:, 0])


def make_data(config, traffic, seed, scale):
    """`resident_partitions` partitions of rows_per_job // scale records
    in generation order, drawn as the configuration's file says."""
    if config["key_distribution"] != KEY_DISTRIBUTION:
        raise ValueError("key distribution %r is not implemented (known: "
                         "10 uniform bytes, gensort without -s)"
                         % (config["key_distribution"],))
    if config["record_layout"] != RECORD_LAYOUT:
        raise ValueError("record layout %r is not implemented (known: %s)"
                         % (config["record_layout"], RECORD_LAYOUT))
    rows = max(PROBE_ROWS, int(traffic["rows_per_job"]) // scale)
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=4) as pool:
        parts = list(pool.map(
            lambda p: gensort_records(np.random.default_rng([seed, p]),
                                      p * rows, rows),
            range(int(traffic["resident_partitions"]))))
    return {"parts": parts, "rows": rows}


def input_rows(data):
    return data["rows"]


def n_partitions(data):
    return len(data["parts"])


def resident_bytes(data):
    return len(data["parts"]) * data["rows"] * DEVICE_ROW_BYTES


def key_bytes(keys):
    """An S10 column as its (n, 10) bytes, NUL-padded."""
    keys = np.ascontiguousarray(keys, "S%d" % KEY_BYTES)
    return keys.view(np.uint8).reshape(len(keys), KEY_BYTES)


def key_words(keys):
    """The keys as (first 8 bytes, last 2) unsigned big-endian integers:
    their lexicographic order is memcmp's."""
    raw = key_bytes(keys)
    return (np.ascontiguousarray(raw[:, :8]).view(">u8")[:, 0],
            np.ascontiguousarray(raw[:, 8:]).view(">u2")[:, 0])


def reference(data, part, query, action):
    """What the job must return for this partition: for `count` its rows;
    else the (keys S10, payloads S90) of the records that the action's
    predicate keeps of the partition sorted by key (np.lexsort over the
    key's unsigned big-endian words)."""
    if action not in QUERIES[query]:
        raise ValueError("unknown action %r" % action)
    keys, payload = data["parts"][part]
    if action == "count":
        return len(keys)
    hi, lo = key_words(keys)
    order = np.lexsort((lo, hi))
    keep = order[_keep(key_bytes(keys)[order], action)]
    return keys[keep], payload[keep]


def _probe(ctx, data, ndev):
    """The chain over PROBE_ROWS rows: every stage of both jobs has to be
    on the array path, and the answer right, before the tables are
    loaded.  A program with no device form for the sort would walk them
    through Python row by row."""
    from dpark_tpu import Columns
    keys, payload = (c[:PROBE_ROWS] for c in data["parts"][0])
    since = len(ctx.scheduler.history)
    table = ctx.parallelize(Columns(keys, payload), ndev) \
        .map(resident).cache()
    table.count()
    got = table.sortByKey(numSplits=ndev).collect()
    kinds = [str(st.get("kind")) for rec in ctx.scheduler.history[since:]
             for st in rec["stage_info"]]
    reasons = ctx.scheduler.fallback_reasons()
    if not kinds or not all(k.startswith("array") for k in kinds) \
            or reasons or ctx.scheduler.degrade_reasons():
        raise RuntimeError(
            "sortByKey over (S10, S90) left the array path (stage kinds "
            "%s, fallback %s, degrade %s): this program cannot run the "
            "configuration"
            % (kinds, reasons, ctx.scheduler.degrade_reasons()))
    hi, lo = key_words(keys)
    order = np.lexsort((lo, hi))
    if not same_records(got, (keys[order], payload[order])):
        raise RuntimeError("the probe's %d rows came back in another "
                           "order than numpy sorts them" % PROBE_ROWS)


def load(ctx, data, ndev):
    """Each partition as a cached RDD resident in HBM (the identity map
    makes it a device stage: PERF.md, Findings), after the probe; then
    the timed chain once over every partition.  A partition's bounds
    are its own sample's, so its largest range, and with it the size
    class of the exchange's slots and of the programs behind them, is
    its own too: two or three classes over a table, and a window that
    met one for the first time would compile inside a job."""
    from dpark_tpu import Columns
    _probe(ctx, data, ndev)
    tables = []
    for keys, payload in data["parts"]:
        rdd = ctx.parallelize(Columns(keys, payload), ndev) \
            .map(resident).cache()
        if rdd.count() != len(keys):
            raise RuntimeError("loading a partition lost rows")
        tables.append(rdd)
    loaded = {"parts": tables, "resident_ids": [r.id for r in tables]}
    for part in range(len(tables)):
        run(ctx, loaded, part, "terasort", "sample", ndev)
    return loaded


def run(ctx, tables, part, query, action, ndev):
    """One job: the chain is built (sortByKey samples its bounds while it
    is) and its action returns inside the caller's clock."""
    keep = QUERIES[query][action]
    ordered = tables["parts"][part].sortByKey(numSplits=ndev)
    if action == "count":
        return ordered.count()
    return ordered.filter(keep).collect()


def same_records(rows, expected):
    """Are the (key, payload) rows the expected records: as many, their
    keys non-decreasing under memcmp, and row for row the reference's,
    key and all 90 bytes (records of equal keys in any order)?  A key
    that ends in NUL comes back shorter from the egest, so everything is
    compared at the column's width."""
    exp_keys, exp_payload = expected
    if len(rows) != len(exp_keys):
        return False
    keys = np.array([r[0] for r in rows], "S%d" % KEY_BYTES)
    payload = np.array([r[1] for r in rows], "S%d" % PAYLOAD_BYTES)
    hi, lo = key_words(keys)
    if len(rows) > 1 and not np.all(
            (hi[1:] > hi[:-1]) | ((hi[1:] == hi[:-1]) & (lo[1:] >= lo[:-1]))):
        return False
    if not np.array_equal(keys, np.asarray(exp_keys, keys.dtype)):
        return False
    exp_payload = np.asarray(exp_payload, payload.dtype)
    if np.array_equal(payload, exp_payload):
        return True
    # runs of equal keys as multisets: order both sides by (key, payload)
    return bool(np.array_equal(
        payload[np.lexsort((payload, lo, hi))],
        exp_payload[np.lexsort((exp_payload, lo, hi))]))


def verdict(result, expected, action):
    if action == "count":
        return result == expected
    return same_records(result, expected)


def least(config, traffic, data, ndev, query):
    """HBM: every record is read once and written once.  ICI: with the
    key space cut evenly, (ndev - 1) / ndev of a chip's records belong
    to another chip and cross once.  At the source's 100 bytes a record."""
    rows = data["rows"] // ndev
    ici = rows * HOST_ROW_BYTES * (ndev - 1) / ndev if ndev > 1 else 0
    return {"hbm_bytes": float(2 * rows * HOST_ROW_BYTES),
            "ici_bytes": float(ici)}
