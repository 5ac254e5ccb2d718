"""Fixed-width byte strings on the array path (ISSUE 26).

A numpy ``S<w>`` column of ``Columns`` is a device leaf held as
ceil(w / 8) big-endian int64 words (layout.ByteStr); a static slice of it
traces, and in key position it is a hash-shuffle key through the
composite-key code.  The `local` master and numpy are the references:
every case runs the same chain on both and compares rows.

The contracts under test:

* PARITY — reduceByKey, groupByKey().mapValues(len), distinct, count and
  collect over S8, S12, S16 keys and over [:X] of an S16 column, on one
  and four virtual devices, every stage `array` with no reason recorded.
* BYTES — strings of mixed length (NUL padding), keys that agree in word
  0 and differ in word 1, and only in the last byte, come back as the
  `bytes` the local master computes.
* PLACEMENT — a row lands in the partition the host HashPartitioner
  names for its `bytes` key.
* SUMS — float32 sums stay within n_g * 2**-23 * sum|v| of float64, and a
  sum forced through bfloat16 does not.
* DECLINES — what is not covered keeps the host path with its reason
  recorded, and the right answer.
"""

import operator
import os
import sys

import numpy as np
import pytest

from dpark_tpu import Columns, DparkContext, conf
from dpark_tpu.backend.tpu import layout
from dpark_tpu.dependency import HashPartitioner
from dpark_tpu.utils.phash import portable_hash

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _ips(n, seed=7):
    """Dotted quads of mixed length (7-15 bytes) that repeat, then the
    edge rows: equal in word 0 and different in word 1, different in the
    last of 16 bytes only, and the empty string."""
    rng = np.random.default_rng(seed)
    octets = rng.integers(0, 256, (n, 4)) % np.array([256, 3, 2, 256])
    rows = [b"%d.%d.%d.%d" % tuple(o) for o in octets]
    rows += [b"10.0.0.1", b"10.0.0.10", b"10.0.0.11", b"10.0.0.1",
             b"abcdefghijklmnop", b"abcdefghijklmnoq", b"abcdefghijklmnop",
             b"255.255.255.254", b"255.255.255.255", b"", b""]
    return rows


N = 600
VALS = np.random.default_rng(11).random(N + 11)


def _column(width):
    return np.array(_ips(N), dtype="S%d" % width)   # numpy truncates


# (id, column width, the key function): whole S8/S12/S16 columns, and
# [:X] of an S16 column on both sides of the word boundary
def _whole(r):
    return (r[0], r[1])


def _p7(r):
    return (r[0][:7], r[1])


def _p8(r):
    return (r[0][:8], r[1])


def _p9(r):
    return (r[0][:9], r[1])


def _p12(r):
    return (r[0][:12], r[1])


def _p16(r):
    return (r[0][:16], r[1])


KEYS = [("S8", 8, _whole), ("S12", 12, _whole), ("S16", 16, _whole),
        ("S16[:7]", 16, _p7), ("S16[:8]", 16, _p8), ("S16[:9]", 16, _p9),
        ("S16[:12]", 16, _p12), ("S16[:16]", 16, _p16)]
# four devices for the forms that differ in kind: one word, two words
# sliced inside the second, and the whole two-word column
KEYS_4DEV = [k for k in KEYS if k[0] in ("S8", "S16", "S16[:12]")]


def _resident(r):
    return r


def _fst(r):
    return r[0]


@pytest.fixture(scope="module")
def masters():
    ctxs = {}
    for name in ("local", "tpu:1", "tpu:4"):
        ctxs[name] = DparkContext(name)
        ctxs[name].start()
    yield ctxs
    for c in ctxs.values():
        c.stop()


def _stage_reasons(ctx, since):
    return [(str(st.get("kind")), st.get("fallback_reason"),
             st.get("degrade_reason"))
            for rec in ctx.scheduler.history[since:]
            for st in rec["stage_info"]]


def _on_array_path(ctx, since, but_last_of=()):
    """Every stage `array`, no reason recorded.  `but_last_of` names the
    jobs (by position since `since`) whose LAST stage may run on the host:
    distinct's final map(_fst) over (k, None) rows does, for int keys
    too."""
    for j, rec in enumerate(ctx.scheduler.history[since:]):
        stages = rec["stage_info"]
        for i, st in enumerate(stages):
            assert not st.get("fallback_reason"), st
            assert not st.get("degrade_reason"), st
            if j in but_last_of and i == len(stages) - 1:
                continue
            assert str(st.get("kind")).startswith("array"), stages


def _chains(ctx, col, keyfn, ndev):
    table = ctx.parallelize(Columns(col, VALS), ndev).map(_resident)
    keyed = table.map(keyfn)
    reduced = keyed.reduceByKey(operator.add, ndev)
    return {
        "reduce_collect": lambda: sorted(reduced.collect()),
        "reduce_count": reduced.count,
        "group_len": lambda: sorted(
            keyed.groupByKey(ndev).mapValues(len).collect()),
        "distinct": lambda: sorted(
            keyed.map(_fst).distinct(ndev).collect()),
    }


def _same_rows(got, want):
    assert [r[0] if isinstance(r, tuple) else r for r in got] \
        == [r[0] if isinstance(r, tuple) else r for r in want]
    if got and isinstance(got[0], tuple):
        np.testing.assert_allclose([r[1] for r in got],
                                   [r[1] for r in want], rtol=1e-5)


@pytest.mark.parametrize("op", ["reduce_collect", "reduce_count",
                                "group_len", "distinct"])
@pytest.mark.parametrize("ndev,name,width,keyfn",
                         [(1,) + k for k in KEYS]
                         + [(4,) + k for k in KEYS_4DEV],
                         ids=lambda v: str(getattr(v, "__name__", v)))
def test_byte_keys_equal_the_local_master(masters, ndev, name, width,
                                          keyfn, op):
    col = _column(width)
    want = _chains(masters["local"], col, keyfn, ndev)[op]()
    tctx = masters["tpu:%d" % ndev]
    since = len(tctx.scheduler.history)
    got = _chains(tctx, col, keyfn, ndev)[op]()
    if op == "reduce_count":
        assert got == want
    else:
        _same_rows(got, want)
        keys = [r[0] if isinstance(r, tuple) else r for r in got]
        assert all(type(k) is bytes for k in keys)
    _on_array_path(tctx, since, but_last_of=(0,) if op == "distinct"
                   else ())


def test_edge_keys_keep_their_bytes(masters):
    """The rows the generator appends, by name: keys equal in word 0 that
    differ in word 1, keys that differ in the last byte only, the empty
    string; and a slice shorter than some strings, longer than others."""
    col = _column(16)
    got = dict(_chains(masters["tpu:1"], col, _whole, 1)["group_len"]())
    assert got[b"10.0.0.1"] == 2 and got[b"10.0.0.10"] == 1 \
        and got[b"10.0.0.11"] == 1
    assert got[b"abcdefghijklmnop"] == 2 and got[b"abcdefghijklmnoq"] == 1
    assert got[b"255.255.255.254"] == 1 and got[b"255.255.255.255"] == 1
    assert got[b""] == 2
    cut = dict(_chains(masters["tpu:1"], col, _p9, 1)["group_len"]())
    assert cut[b"10.0.0.1"] == 2          # shorter than 9: whole string
    assert cut[b"10.0.0.10"] == 1 and cut[b"10.0.0.11"] == 1
    assert cut[b"abcdefghi"] == 3         # the three 16-byte rows agree
    assert cut[b"255.255.2"] >= 2


def test_a_row_lands_where_the_host_partitioner_says(masters):
    """Destinations come from the host's own hash of the bytes: each
    device's partition holds exactly the keys HashPartitioner names for
    it, so lookup() and co-partitioned consumers find them."""
    tctx = masters["tpu:4"]
    col = _column(16)
    parted = tctx.parallelize(Columns(col, VALS), 4).map(_resident) \
        .map(_p12).reduceByKey(operator.add, 4)
    part = HashPartitioner(4)
    seen = 0
    for i, rows in enumerate(parted.glom().collect()):
        for k, _ in rows:
            assert part.get_partition(k) == i, (k, i)
            seen += 1
    assert seen == len({r[:12] for r in _ips(N)})
    assert portable_hash(b"10.0.0.1") == portable_hash(
        np.bytes_(b"10.0.0.1"))
    found = parted.lookup(b"10.0.0.1")
    assert len(found) == 1
    np.testing.assert_allclose(
        found, masters["local"].parallelize(Columns(col, VALS), 4)
        .map(_p12).reduceByKey(operator.add, 4).lookup(b"10.0.0.1"),
        rtol=1e-5)


def _first_is_one(kv):
    # b"1..." : an element read compared with an int
    return kv[0][0] == 49


def _begins_10dot(kv):
    return kv[0][:3] == b"10."


def _not_10dot(kv):
    return kv[0][:3] != b"10."


@pytest.mark.parametrize("pred", [_first_is_one, _begins_10dot,
                                  _not_10dot],
                         ids=lambda f: f.__name__)
def test_element_reads_and_comparisons_trace(masters, pred):
    col = _column(16)[:N]        # b""[0] raises on the host
    want = sorted(_reduced(masters["local"], col).filter(pred).collect())
    tctx = masters["tpu:1"]
    since = len(tctx.scheduler.history)
    got = sorted(_reduced(tctx, col).filter(pred).collect())
    assert want and len(want) < len(set(_ips(N)))
    _same_rows(got, want)
    _on_array_path(tctx, since)


def _reduced(ctx, col, ndev=1):
    return ctx.parallelize(Columns(col, VALS[:len(col)]), ndev) \
        .map(_resident).map(_p12).reduceByKey(operator.add, ndev)


def test_a_cached_table_serves_later_jobs_from_hbm(masters):
    """The chain of the benchmark's cell: the table is cached on the
    device once, and a later job neither packs nor unpacks a string."""
    tctx = masters["tpu:1"]
    col = _column(16)
    table = tctx.parallelize(Columns(col, VALS), 1).map(_resident).cache()
    assert table.count() == len(col)
    ex = tctx.scheduler.executor
    assert table.id in ex.result_cache_ids()
    packed, unpacked = ex.bytes_rows_packed, ex.bytes_rows_unpacked
    since = len(tctx.scheduler.history)
    n = table.map(_p12).reduceByKey(operator.add, 1).count()
    assert n == len({r[:12] for r in _ips(N)})
    assert (ex.bytes_rows_packed, ex.bytes_rows_unpacked) \
        == (packed, unpacked)
    _on_array_path(tctx, since)
    rows = table.map(_p12).reduceByKey(operator.add, 1).collect()
    assert ex.bytes_rows_unpacked == unpacked + len(rows)


@pytest.mark.parametrize("ndev", [1, 4])
def test_byte_keys_ride_the_wave_stream(masters, ndev):
    """An input over one wave streams: in-core combine, the no-combine
    stream, and (more reduce partitions than devices) the spilled-run
    stream whose rows leave through the host bridge."""
    col = _column(16)
    old = conf.STREAM_CHUNK_ROWS
    conf.STREAM_CHUNK_ROWS = 64
    try:
        tctx = masters["tpu:%d" % ndev]
        since = len(tctx.scheduler.history)
        def jobs(c, parts):
            keyed = c.parallelize(Columns(col, VALS), ndev).map(_p12)
            return [sorted(keyed.reduceByKey(operator.add,
                                             parts).collect()),
                    sorted(keyed.groupByKey(parts).mapValues(len)
                           .collect())]

        for parts in (ndev, 3 * ndev):
            for got, want in zip(jobs(tctx, parts),
                                 jobs(masters["local"], parts)):
                _same_rows(got, want)
        kinds = [k for k, f, d in _stage_reasons(tctx, since)
                 if not (f or d)]
    finally:
        conf.STREAM_CHUNK_ROWS = old
    assert kinds.count("array+spill") == 2 and len(kinds) == 8, kinds


def test_pack_and_unpack_show_on_counters_and_enclosing_spans(masters):
    """What an ingest packs and an egest unpacks reads on the counters'
    deltas and on the `rows` of the enclosing `ingest` / `egest` spans
    (no span of their own: they sit in those loops); the width and the
    word count are the ingested batch's own."""
    from dpark_tpu import trace
    from dpark_tpu.backend.tpu import layout
    tctx = masters["tpu:1"]
    ex = tctx.scheduler.executor
    col = _column(12)
    packed, unpacked = ex.bytes_rows_packed, ex.bytes_rows_unpacked
    trace.configure("ring")
    try:
        table = tctx.parallelize(Columns(col, VALS), 1) \
            .map(_resident).cache()
        assert table.count() == len(col)
        rows = table.map(_whole).reduceByKey(operator.add, 1).collect()
        snap = trace.snapshot()
    finally:
        trace.configure("off")
    spans = {s["name"]: s for s in snap if s["name"] in ("ingest", "egest")}
    assert not [s for s in snap if s["name"].startswith("bytes.")]
    assert ex.bytes_rows_packed - packed == len(col) \
        == spans["ingest"]["args"]["rows"]
    assert ex.bytes_rows_unpacked - unpacked == len(rows) \
        == spans["egest"]["args"]["rows"]
    meta = ex.result_cache[table.id]
    _, groups = layout.column_groups(meta["treedef"], len(meta["leaves"]))
    (key,) = [g for g in groups if isinstance(g, layout.ByteStr)]
    assert (key.width, len(key.words)) == (12, 2)


# -- float32 sums --------------------------------------------------------

def _load_job_module():
    from perf.lib import manifest
    return manifest.load_module(manifest.job_module_path("uservisits_q2"))


def _q2_data(job, rows, seed=5, parts=1):
    config = {"source_ip_distribution": job.IP_DISTRIBUTION,
              "ad_revenue_distribution": job.REVENUE_DISTRIBUTION}
    return job.make_data(config, {"rows_per_job": rows,
                                  "resident_partitions": parts}, seed, 1)


def _few_groups(job, rows=4096):
    """A partition of the benchmark's data with its octets folded so that
    groups hold ~16 rows: sums that really add."""
    rng = np.random.default_rng(3)
    octets = rng.integers(0, 256, (rows, 4), dtype=np.uint8) \
        % np.array([4, 2, 2, 16], np.uint8)
    ip = job.dotted_quads(octets, job._half_tables())
    return ip, rng.random(rows, dtype=np.float32)


def _f64_reference(ip, revenue, width=12):
    keys = np.array([k[:width] for k in ip.tolist()], "S%d" % width)
    uniq, inv = np.unique(keys, return_inverse=True)
    v = revenue.astype(np.float64)
    return (uniq.tolist(), np.bincount(inv, weights=v),
            np.bincount(inv), np.bincount(inv, weights=np.abs(v)))


@pytest.mark.parametrize("ndev", [1, 4])
def test_float32_sums_within_the_stated_tolerance(masters, ndev):
    job = _load_job_module()
    ip, revenue = _few_groups(job)
    expected = _f64_reference(ip, revenue)
    assert expected[2].max() >= 8
    tctx = masters["tpu:%d" % ndev]
    rows = tctx.parallelize(Columns(ip, revenue), ndev).map(_resident) \
        .map(_p12).reduceByKey(operator.add, ndev).collect()
    assert job.sums_within(rows, expected)
    # the same groups summed through bfloat16 fail it: the limit tells
    # the stated precision from the next one down
    import jax.numpy as jnp
    keys, sums, counts, mags = expected
    index = {k: i for i, k in enumerate(keys)}
    inv = np.array([index[k[:12]] for k in ip.tolist()])
    low = np.zeros(len(keys), np.float64)
    for g in range(len(keys)):
        acc = jnp.bfloat16(0)
        for v in revenue[inv == g][:64]:
            acc = acc + jnp.bfloat16(v)
        low[g] = float(acc)
    assert not job.sums_within(list(zip(keys, low.tolist())), expected)
    # and one row through bfloat16 alone
    one = (keys[:1], sums[:1], np.array([1]), mags[:1])
    assert not job.sums_within(
        [(keys[0], float(jnp.bfloat16(np.float32(0.7001))))],
        (keys[:1], np.array([np.float64(np.float32(0.7001))]),
         np.array([1]), np.array([0.7001])))
    assert job.sums_within([(keys[0], float(sums[0]))], one)


def test_the_benchmark_reference_equals_the_local_master(masters):
    """perf/jobs/uservisits_q2.py: its numpy reference (over the bytes)
    against the dpark chain on the `local` master, 4,096 rows."""
    job = _load_job_module()
    data = _q2_data(job, 4096)
    ip, revenue = data["parts"][0]
    assert ip.dtype == np.dtype("S16") and revenue.dtype == np.float32
    assert all(7 <= len(k) <= 15 and k.count(b".") == 3
               for k in ip.tolist())
    local = masters["local"]
    tables = {"parts": [local.parallelize(Columns(ip, revenue), 1)]}
    assert job.run(local, tables, 0, "q2c", "count", 1) \
        == job.reference(data, 0, "q2c", "count") \
        == len({k[:12] for k in ip.tolist()})
    sample = job.run(local, tables, 0, "q2c", "collect_sample", 1)
    expected = job.reference(data, 0, "q2c", "collect_sample")
    assert sample and all(k.startswith(b"173.") for k, _ in sample)
    assert job.verdict(sample, expected, "collect_sample")
    assert not job.verdict(sample[1:], expected, "collect_sample")


# -- what is not covered keeps the host path -------------------------------

def _sentinel_rows():
    rows = _ips(40)
    rows[3] = b"\x7f\xff\xff\xff\xff\xff\xff\xffab"
    return np.array(rows, "S16")


def _declined(case):
    vals = VALS[:51]
    if case == "too_wide":
        wide = layout.BYTES_WIDTH_MAX + 8
        col = np.array([r + b"/" + b"x" * (wide - 16) for r in _ips(40)],
                       "S%d" % wide)
        return (lambda c: sorted(c.parallelize(Columns(col, vals), 1)
                                 .map(_whole)
                                 .reduceByKey(operator.add, 1).collect()),
                "over the device limit")
    if case == "unicode":
        col = np.array([r.decode() for r in _ips(40)])
        return (lambda c: sorted(c.parallelize(Columns(col, vals), 1)
                                 .map(_whole)
                                 .reduceByKey(operator.add, 1).collect()),
                "string leaf (dtype <U")
    if case == "object":
        col = np.array(_ips(40), dtype=object)
        return (lambda c: sorted(c.parallelize(Columns(col, vals), 1)
                                 .map(_whole)
                                 .reduceByKey(operator.add, 1).collect()),
                "string leaf (dtype")
    if case == "sentinel_word":
        col = _sentinel_rows()
        return (lambda c: sorted(c.parallelize(Columns(col, vals), 1)
                                 .map(_p12)
                                 .reduceByKey(operator.add, 1).collect()),
                "key sentinel (bytes 7f ff")
    raise AssertionError(case)


@pytest.mark.parametrize("case", ["too_wide", "unicode",
                                  "object", "sentinel_word"])
def test_what_is_not_covered_keeps_the_host_path(masters, case):
    job, reason = _declined(case)
    want = job(masters["local"])
    tctx = masters["tpu:1"]
    since = len(tctx.scheduler.history)
    got = job(tctx)
    assert [r[0] for r in got] == [r[0] for r in want]
    np.testing.assert_allclose([r[1] for r in got], [r[1] for r in want],
                               rtol=1e-5)
    reasons = [r for _, r, _ in _stage_reasons(tctx, since) if r]
    assert any(reason in r for r in reasons), reasons


@pytest.mark.parametrize("bad", ["len", "iterate", "order", "negative"])
def test_what_needs_the_true_length_does_not_trace(bad):
    s = layout.ByteStr(12, (np.int64(1), np.int64(2)))
    with pytest.raises(TypeError):
        {"len": lambda: len(s), "iterate": lambda: list(s),
         "order": lambda: s < s, "negative": lambda: s[-1]}[bad]()


def test_a_slice_is_the_bytes_slice():
    """ByteStr windows against Python's bytes slices, every start and
    stop of a 16-byte string."""
    text = b"192.168.100.7"
    words = layout.pack_bytes(np.array([text], "S16"))[0]
    s = layout.ByteStr(16, [np.int64(w) for w in words])
    for lo in range(0, 17):
        for hi in range(lo, 20):
            got = s[lo:hi]
            back = layout.unpack_bytes(
                [np.array([w]) for w in got.words], got.width) \
                if got.width else np.array([b""])
            assert back.tolist()[0] == text[lo:hi], (lo, hi)
    assert [int(s[i]) for i in range(len(text))] == list(text)
    assert bool(s[:3] == b"192") and not bool(s[:3] == b"193")
    assert not bool(s == b"192.168.100.7\0") and bool(s == text)
    assert (s == 5) is False


def test_a_second_key_column_with_the_sentinel_is_guarded(masters):
    """_check_cached_keys guards every key column of a cached batch: a
    composite key whose SECOND column holds the padding sentinel takes
    the host path, and the answer is the local master's."""
    k1 = np.arange(64, dtype=np.int64) % 4
    k2 = np.arange(64, dtype=np.int64) % 3
    k2[5] = layout.KEY_SENTINEL
    vals = np.arange(64, dtype=np.int64)

    def keyed(r):
        return ((r[0], r[1]), r[2])

    def job(c):
        table = c.parallelize(Columns(k1, k2, vals), 1).map(keyed).cache()
        assert table.count() == 64
        return sorted(table.reduceByKey(operator.add, 1).collect())

    want = job(masters["local"])
    tctx = masters["tpu:1"]
    since = len(tctx.scheduler.history)
    assert job(tctx) == want
    assert any("sentinel" in (d or "")
               for _, _, d in _stage_reasons(tctx, since))


def test_the_lint_rule_agrees_with_admission():
    from dpark_tpu.analysis.plan_rules import _key_fallback_reason
    assert _key_fallback_reason(np.bytes_(b"1.2.3.4"), fixed_width=16) \
        is None
    assert _key_fallback_reason(       # sortByKey: a device path too
        np.bytes_(b"1.2.3.4"), hash_keys=False, fixed_width=16) is None
    assert "limit" in _key_fallback_reason(
        np.bytes_(b"1.2.3.4"), fixed_width=layout.BYTES_WIDTH_MAX + 8)
    assert "string key" in _key_fallback_reason(b"1.2.3.4")
    assert "string key" in _key_fallback_reason("1.2.3.4")
