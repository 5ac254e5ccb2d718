"""sortByKey and range shuffles over fixed-width byte strings on the
array path (ISSUE 33).

A byte string is held as big-endian SIGNED int64 words (layout.ByteStr),
so wherever it is ORDERED on the device (a range destination, the
no-combine reduce, SortOp) the words are compared through an ordering
view (collectives.bytes_order): as `bytes` compare in Python and `S<w>`
in numpy, unsigned, NUL-padded.  The `local` master is the golden model:
every case runs the same chain on both and compares the lists exactly.

The contracts under test:

* ORDER — sortByKey over S<w> keys with S90 values, ascending and
  descending, one and four virtual devices, widths 1, 8, 9, 10, 16 and
  BYTES_WIDTH_MAX, keys over all 256 byte values with the forced cases
  (first byte 0x80-0xff beside 0x00-0x7f, a key ending in NUL, equal
  keys, keys differing only in the last byte): the local master's list,
  every stage `array`, no reason recorded.
* PLACEMENT — a row's partition is RangePartitioner.get_partition of its
  key, bound-equal keys included; the bounds are the local master's.
* SAMPLE — the bounds sample brings sampleSize keys to the host, not the
  table.
* DECLINES — the sentinel key, U strings, a bound the key column cannot
  hold and more splits than devices keep the host path with a reason.
* The benchmark's job module: its numpy reference against a full
  collect(), and its verdict refusing a swapped pair, a lost row and a
  changed payload byte.
"""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from dpark_tpu import Columns, DparkContext
from dpark_tpu.backend.tpu import collectives, layout
from dpark_tpu.dependency import RangePartitioner
from dpark_tpu.rdd import ShuffledRDD

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

N = 1200
PAYLOAD = 90


def _resident(r):
    return r


def _table(width, n=N, seed=3):
    """(S<width> keys over all 256 byte values, distinct S90 payloads):
    the forced cases first, in generation order (not sorted)."""
    rng = np.random.default_rng([seed, width])
    raw = rng.integers(0, 256, (n, width), dtype=np.uint8)
    raw[0:40, 0] = 0x80
    raw[40:80, 0] = 0xFF
    raw[80:120, 0] = 0x00
    raw[120:160, 0] = 0x7F
    raw[160:200, -1] = 0                    # ends in NUL: shorter bytes
    raw[200:230] = raw[200]                 # equal keys, distinct payloads
    raw[230:260] = raw[230]
    raw[230:260, -1] = np.arange(30) * 8    # differ in the last byte only
    raw[260] = 0                            # the empty string
    raw[261] = 0xFF
    keys = np.ascontiguousarray(raw).view("S%d" % width)[:, 0]
    pay = rng.integers(1, 256, (n, PAYLOAD), dtype=np.uint8)
    pay[:, :4] = np.arange(n, dtype=">u4").view(np.uint8).reshape(n, 4)
    pay[:, -1] = 0xFF
    payload = np.ascontiguousarray(pay).view("S%d" % PAYLOAD)[:, 0]
    order = rng.permutation(n)
    return keys[order], payload[order]


@pytest.fixture(scope="module")
def masters():
    ctxs = {}
    for name in ("local", "tpu:1", "tpu:4"):
        ctxs[name] = DparkContext(name)
        ctxs[name].start()
    yield ctxs
    for c in ctxs.values():
        c.stop()


def _stages(ctx, since):
    return [(str(st.get("kind")), st.get("fallback_reason"),
             st.get("degrade_reason"))
            for rec in ctx.scheduler.history[since:]
            for st in rec["stage_info"]]


def _assert_array_path(ctx, since):
    stages = _stages(ctx, since)
    assert stages
    for kind, fallback, degrade in stages:
        assert kind.startswith("array") and not fallback and not degrade, \
            stages
    assert not ctx.scheduler.fallback_reasons()
    assert not ctx.scheduler.degrade_reasons()


def _sorted_rdd(ctx, cols, ndev, ascending=True):
    return ctx.parallelize(Columns(*cols), ndev).map(_resident).cache() \
        .sortByKey(ascending=ascending, numSplits=ndev)


# -- ORDER -----------------------------------------------------------------

WIDTHS = [1, 8, 9, 10, 16, layout.BYTES_WIDTH_MAX]
ORDER_CASES = [(w, ndev, True) for w in WIDTHS for ndev in (1, 4)] \
    + [(w, ndev, False) for w in (10, 16) for ndev in (1, 4)]


@pytest.mark.parametrize("width,ndev,ascending", ORDER_CASES)
def test_sortByKey_over_byte_keys_equals_the_local_master(
        masters, width, ndev, ascending):
    cols = _table(width)
    want = _sorted_rdd(masters["local"], cols, ndev, ascending).collect()
    tctx = masters["tpu:%d" % ndev]
    since = len(tctx.scheduler.history)
    got = _sorted_rdd(tctx, cols, ndev, ascending).collect()
    assert got == want
    _assert_array_path(tctx, since)
    # and the local master's order is bytes' own: unsigned, NUL-padded
    keys = [k for k, _ in got]
    assert keys == sorted(cols[0].tolist(), reverse=not ascending)
    if width > 1:
        assert keys[0 if ascending else -1] == b""
        assert keys[-1 if ascending else 0][:1] == b"\xff"


@pytest.mark.parametrize("values", [
    [0, 1, -1, 2 ** 63 - 2, -2 ** 63, -2 ** 63 + 1, 2 ** 62, -2],
    "random"])
def test_the_ordering_view_orders_words_as_bytes(values):
    """collectives.bytes_order: the signed order of the view is the
    unsigned order of the words, and the sentinel stays the largest
    word 0."""
    if values == "random":
        rng = np.random.default_rng(9)
        values = rng.integers(-2 ** 63, 2 ** 63 - 1, 500).tolist()
    sent = int(layout.KEY_SENTINEL)
    w0 = np.array([v for v in values if v != sent] + [sent], np.int64)
    w1 = np.array(values[::-1][:len(w0) - 1] + [5], np.int64)[:len(w0)]
    w1 = np.resize(w1, len(w0))
    view = [np.asarray(v) for v in
            collectives.bytes_order([jnp.asarray(w0), jnp.asarray(w1)])]
    assert view[0][-1] == sent and (view[0][:-1] < sent).all()
    by_view = sorted(range(len(w0) - 1),
                     key=lambda i: (int(view[0][i]), int(view[1][i])))
    by_bytes = sorted(range(len(w0) - 1), key=lambda i: (
        int(w0[i]).to_bytes(8, "big", signed=True),
        int(w1[i]).to_bytes(8, "big", signed=True)))
    assert by_view == by_bytes


# -- PLACEMENT -------------------------------------------------------------

def _range_partitioner(rdd):
    while not isinstance(rdd, ShuffledRDD):
        rdd = rdd.prev
    assert isinstance(rdd.partitioner, RangePartitioner)
    return rdd.partitioner


@pytest.mark.parametrize("ascending", [True, False])
def test_a_row_lands_where_get_partition_says_and_bounds_are_locals(
        masters, ascending):
    cols = _table(10, n=3000, seed=4)
    tctx = masters["tpu:4"]
    since = len(tctx.scheduler.history)
    ordered = _sorted_rdd(tctx, cols, 4, ascending)
    parts = ordered.glom().collect()
    part = _range_partitioner(ordered)
    local = _range_partitioner(
        _sorted_rdd(masters["local"], cols, 4, ascending))
    assert part.bounds == local.bounds and len(part.bounds) == 3
    assert all(isinstance(b, bytes) for b in part.bounds)
    assert sum(len(p) for p in parts) == 3000
    for p, rows in enumerate(parts):
        assert rows, parts
        assert {part.get_partition(k) for k, _ in rows} == {p}
    # a key equal to a bound is in the table (the bounds are sampled
    # keys) and went left of it: bisect_left
    placed = {k: p for p, rows in enumerate(parts) for k, _ in rows}
    for i, b in enumerate(part.bounds):
        assert placed[b] == (i if ascending else 3 - i)
    # the sample and the range shuffle's write ran on the device (glom
    # reads the store through the export bridge, on the host)
    stages = _stages(tctx, since)
    assert all(k.startswith("array") and not f and not d
               for k, f, d in stages[:2]), stages


@pytest.mark.parametrize("bounds", [
    [b"\x00", b"\x7f\xff", b"\x80"],
    [b"\x7f\xff\xff\xff\xff\xff\xff\xff", b"\xff\xff\xff\xff\xff\xff\xff\xff\xff"],
    [b"\x80\x00\x00\x00\x00\x00\x00\x00\x01"],
    []])
def test_partitionBy_handmade_bytes_bounds_equals_local(masters, bounds):
    cols = _table(10, n=800, seed=6)

    def job(ctx):
        return ctx.parallelize(Columns(*cols), 4).map(_resident) \
            .partitionBy(RangePartitioner(bounds)).glom().collect()

    want = job(masters["local"])
    tctx = masters["tpu:4"]
    since = len(tctx.scheduler.history)
    got = job(tctx)
    assert [sorted(p) for p in got] == [sorted(p) for p in want]
    assert _stages(tctx, since)[0][:2] == ("array", None)


# -- SAMPLE ----------------------------------------------------------------

@pytest.mark.parametrize("sample_size,expect", [(2000, 2000), (400, 400),
                                                (40, 80)])
def test_the_bounds_sample_reads_the_keys_it_keeps(masters, sample_size,
                                                   expect):
    cols = _table(10, n=4000, seed=5)
    tctx = masters["tpu:4"]
    ex = tctx.scheduler.executor
    table = tctx.parallelize(Columns(*cols), 4).map(_resident).cache()
    assert table.count() == 4000
    rows0, unpacked0 = ex.sort_sample_rows, ex.bytes_rows_unpacked
    since = len(tctx.scheduler.history)
    ordered = table.sortByKey(numSplits=4, sampleSize=sample_size)
    # the sample job ran while the chain was built: on the array path,
    # and only the kept keys became host bytes
    assert ex.sort_sample_rows - rows0 == expect
    assert ex.bytes_rows_unpacked - unpacked0 == expect
    assert [k for k, _, _ in _stages(tctx, since)] == ["array"]
    local = masters["local"].parallelize(Columns(*cols), 4) \
        .map(_resident).sortByKey(numSplits=4, sampleSize=sample_size)
    assert _range_partitioner(ordered).bounds \
        == _range_partitioner(local).bounds
    assert ordered.count() == 4000


def test_the_sample_of_int_and_tuple_keys_is_sliced_too(masters):
    tctx = masters["tpu:4"]
    ex = tctx.scheduler.executor
    k = np.random.default_rng(2).integers(-10 ** 6, 10 ** 6, 4000)
    for cols, key in ((Columns(k, k * 2), None),
                      (Columns(k % 7, k, k * 2),
                       lambda r: ((r[0], r[1]), r[2]))):
        for name in ("local", "tpu:4"):
            rdd = masters[name].parallelize(cols, 4)
            rdd = (rdd.map(key) if key else rdd.map(_resident)).cache()
            rdd.count()
            rows0 = ex.sort_sample_rows
            got = rdd.sortByKey(numSplits=4).collect()
            if name == "local":
                want = got
        assert got == want
        assert ex.sort_sample_rows - rows0 == 2000


# -- DECLINES --------------------------------------------------------------

def _declined(case):
    keys, payload = _table(10, n=300, seed=8)
    if case == "sentinel":
        keys = keys.copy()
        keys[7] = b"\x7f\xff\xff\xff\xff\xff\xff\xffab"
        return (lambda c: c.parallelize(Columns(keys, payload), 4)
                .map(_resident).sortByKey(numSplits=4).collect(),
                "key sentinel (bytes 7f ff")
    if case == "unicode":
        col = np.array(["k%05d" % i for i in range(299, -1, -1)])
        return (lambda c: c.parallelize(Columns(col, payload), 4)
                .map(_resident).sortByKey(numSplits=4).collect(),
                "string leaf (dtype <U")
    if case == "wider_bound":
        return (lambda c: c.parallelize(Columns(keys, payload), 4)
                .map(_resident)
                .partitionBy(RangePartitioner([b"a", b"b" * 11]))
                .glom().map(sorted).collect(),
                "range bounds of another width than the S10 key column")
    if case == "too_wide":
        wide = layout.BYTES_WIDTH_MAX + 8
        col = np.array([b"%05d" % i + b"x" * (wide - 5)
                        for i in range(299, -1, -1)], "S%d" % wide)
        return (lambda c: c.parallelize(Columns(col, payload), 4)
                .map(_resident).sortByKey(numSplits=4).collect(),
                "over the device limit")
    if case == "more_splits":
        return (lambda c: c.parallelize(Columns(keys, payload), 4)
                .map(_resident).sortByKey(numSplits=8).collect(),
                "more splits than devices")
    raise AssertionError(case)


@pytest.mark.parametrize("case", ["sentinel", "unicode", "wider_bound",
                                  "too_wide", "more_splits"])
def test_what_is_not_covered_keeps_the_host_path(masters, case):
    job, reason = _declined(case)
    want = job(masters["local"])
    tctx = masters["tpu:4"]
    since = len(tctx.scheduler.history)
    got = job(tctx)
    assert got == want
    reasons = [r for _, r, _ in _stages(tctx, since) if r]
    assert any(reason in r for r in reasons), _stages(tctx, since)


def test_more_splits_than_devices_records_a_reason_and_lints(masters):
    """Before ISSUE 33 this ran `object` with no reason recorded."""
    from dpark_tpu.analysis.plan_rules import lint_plan
    tctx = masters["tpu:1"]
    k = np.arange(1000, dtype=np.int64)[::-1].copy()
    since = len(tctx.scheduler.history)
    rdd = tctx.parallelize(Columns(k, k), 4).sortByKey(numSplits=4)
    assert [r[0] for r in rdd.collect()] == list(range(1000))
    stages = _stages(tctx, since)
    assert {kind for kind, _, _ in stages} == {"object"}
    assert any("shuffle into 4 partitions on 1 device(s): more splits "
               "than devices" in (r or "") for _, r, _ in stages)
    found = [f for f in lint_plan(rdd) if f.rule == "host-fallback-splits"]
    assert len(found) == 1 and "4 partitions on 1 device" in found[0].message
    # quiet where the splits fit, and on a master without devices
    assert not [f for f in lint_plan(tctx.parallelize(Columns(k, k), 1)
                                     .reduceByKey(max, 1))
                if f.rule == "host-fallback-splits"]
    assert not [f for f in lint_plan(
        masters["local"].parallelize(Columns(k, k), 4)
        .sortByKey(numSplits=4)) if f.rule == "host-fallback-splits"]


def test_the_lint_rule_agrees_on_bytes_bounds():
    from dpark_tpu.analysis.plan_rules import _bytes_bounds_reason
    assert _bytes_bounds_reason(RangePartitioner([b"a", b"bc"]), 10) is None
    assert _bytes_bounds_reason(RangePartitioner([]), 10) is None
    assert "another width" in _bytes_bounds_reason(
        RangePartitioner([b"x" * 11]), 10)
    assert _bytes_bounds_reason(RangePartitioner([b"ab\0"]), 10) is None
    assert "no device form" in _bytes_bounds_reason(
        RangePartitioner(["ab"]), 10)


@pytest.mark.parametrize("name", ["RANGE_STRING_REASON",
                                  "MORE_SPLITS_REASON"])
def test_the_lint_layer_repeats_the_programs_reasons(name):
    """analysis/plan_rules.py never imports jax, so it repeats the two
    reasons backend/tpu/fuse.py records: letter for letter."""
    from dpark_tpu.analysis import plan_rules
    from dpark_tpu.backend.tpu import fuse
    assert getattr(plan_rules, name) == getattr(fuse, name)


def test_only_sortByKeys_reduce_orders_as_bytes(monkeypatch):
    """The no-combine reduce sorts a byte key a word at a time, as
    bytes order, only where SortOp takes that order for its own; a
    partitionBy over the same bounds needs equal keys adjacent and
    keeps the one carried sort of the signed words."""
    cols = _table(10, n=600, seed=13)
    seen = []
    sort_by_key = collectives.sort_by_key
    monkeypatch.setattr(
        collectives, "sort_by_key", lambda rows, nk, unsigned=False: (
            seen.append(bool(unsigned)), sort_by_key(rows, nk, unsigned))[1])
    tctx = DparkContext("tpu:4")        # programs of its own: compiles
    tctx.start()
    try:
        t = tctx.parallelize(Columns(*cols), 4).map(_resident).cache()
        want = sorted(zip(cols[0].tolist(), cols[1].tolist()))
        got = t.sortByKey(numSplits=4).collect()
        assert [k for k, _ in got] == [k for k, _ in want]
        assert sorted(got) == want
        assert seen == [True]
        del seen[:]
        got = t.partitionBy(RangePartitioner(
            [b"\x40", b"\x80", b"\xc0"])).map(_resident).collect()
        assert sorted(got) == want
        assert seen == [False]
        assert not tctx.scheduler.fallback_reasons()
    finally:
        tctx.stop()


def test_the_programs_say_how_they_order(masters):
    """`compile` events (ring on): the range epilogue's and SortOp's
    programs name their destination, key kind and order."""
    from dpark_tpu import trace
    cols = _table(10, n=500, seed=12)
    trace.configure("ring")
    tctx = DparkContext("tpu:4")        # programs of its own: compiles
    tctx.start()
    try:
        _sorted_rdd(tctx, cols, 4).collect()
        k = np.arange(500)
        tctx.parallelize(Columns(k, k), 4).map(_resident) \
            .sortByKey(numSplits=4).collect()
        snap = trace.snapshot()
    finally:
        tctx.stop()
        trace.configure("off")
    said = {(e["args"]["program"], e["args"].get("dst"), e["args"]["key"],
             e["args"]["order"]) for e in snap
            if e["name"] == "compile" and "order" in e["args"]}
    assert said == {("narrow", "range", "bytes", "unsigned"),
                    ("reduce", None, "bytes", "unsigned"),
                    ("narrow", "range", "int", "signed"),
                    ("reduce", None, "int", "signed")}
    samples = [s for s in snap if s["name"] == "sort.sample"]
    assert len(samples) == 2
    assert {(s["args"]["splits"], s["args"]["rows"]) for s in samples} \
        == {(4, 500)}
    assert "sample" in {s["args"].get("program") for s in snap
                        if s["name"] == "launch"}


# -- the benchmark's job module --------------------------------------------

@pytest.fixture(scope="module")
def job():
    from perf.lib import manifest
    return manifest.load_module(manifest.job_module_path("gensort_terasort"))


def _job_data(job, rows=4096):
    config = {"key_distribution": job.KEY_DISTRIBUTION,
              "record_layout": job.RECORD_LAYOUT}
    traffic = {"rows_per_job": rows, "resident_partitions": 2}
    return job.make_data(config, traffic, 2 ** 31 + 11, 1)


def test_the_records_are_gensort_shaped(job):
    data = _job_data(job)
    keys, payload = data["parts"][1]
    assert keys.dtype == "S10" and payload.dtype == "S90"
    raw = payload.view(np.uint8).reshape(len(payload), 90)
    assert (raw[:, :2] == (0x00, 0x11)).all()
    assert (raw[:, -4:] == (0xCC, 0xDD, 0xEE, 0xFF)).all()
    numbers = [int(bytes(r[2:34]), 16) for r in raw[:50]]
    assert numbers == list(range(4096, 4146))     # partition 1's
    firsts = job.key_bytes(keys)[:, 0]
    assert firsts.min() < 8 and firsts.max() > 247  # all byte values
    with pytest.raises(ValueError):
        job.make_data({"key_distribution": {"kind": "skewed"},
                       "record_layout": job.RECORD_LAYOUT},
                      {"rows_per_job": 1, "resident_partitions": 1}, 1, 1)


@pytest.mark.parametrize("ndev", [1, 4])
def test_the_job_modules_reference_is_the_full_collect(masters, job, ndev):
    data = _job_data(job)
    keys, payload = data["parts"][0]
    tctx = masters["tpu:%d" % ndev]
    table = tctx.parallelize(Columns(keys, payload), ndev) \
        .map(job.resident).cache()
    full = table.sortByKey(numSplits=ndev).collect()
    assert len(full) == 4096
    assert job.same_records(full, (np.sort(keys), payload[np.argsort(
        keys, kind="stable")]))
    for action in ("sample", "dense_sample"):
        want = job.reference(data, 0, "terasort", action)
        keep = job._keep(job.key_bytes(np.array([r[0] for r in full],
                                                "S10")), action)
        picked = [r for r, k in zip(full, keep) if k]
        assert len(picked) == len(want[0])
        assert job.verdict(picked, want, action)
    assert job.reference(data, 0, "terasort", "count") == 4096
    assert job.least({}, {}, data, 4, "terasort") == {
        "hbm_bytes": 2 * 1024 * 100.0, "ici_bytes": 1024 * 100 * 0.75}


@pytest.mark.parametrize("fault", ["none", "swapped_pair", "lost_row",
                                   "payload_byte", "doubled_row",
                                   "equal_keys_reordered"])
def test_the_verdict_refuses_what_valsort_refuses(job, fault):
    data = _job_data(job)
    keys, payload = data["parts"][0]
    keys = keys.copy()
    keys[10:14] = keys[10]                  # a run of equal keys
    order = np.lexsort(job.key_words(keys)[::-1])
    expected = (keys[order], payload[order])
    rows = list(zip(expected[0].tolist(), expected[1].tolist()))
    run = [i for i in range(len(rows)) if rows[i][0] == keys[10]]
    assert len(run) == 4
    if fault == "swapped_pair":
        rows[100], rows[101] = rows[101], rows[100]
    elif fault == "lost_row":
        del rows[2000]
    elif fault == "payload_byte":
        k, p = rows[3000]
        rows[3000] = (k, p[:50] + bytes([p[50] ^ 1]) + p[51:])
    elif fault == "doubled_row":
        rows[5] = rows[4]
    elif fault == "equal_keys_reordered":
        rows[run[0]], rows[run[3]] = rows[run[3]], rows[run[0]]
    assert job.verdict(rows, expected, "sample") \
        == (fault in ("none", "equal_keys_reordered"))


# -- the egest of a sparse result ------------------------------------------

@pytest.mark.parametrize("keep,sliced", [(3, True), (8, True), (9, False),
                                         (4000, False)])
def test_a_sparse_result_is_read_as_a_prefix_not_the_capacity(
        masters, monkeypatch, keep, sliced):
    """layout._egest_rows: a result of at most capacity >> 10 rows a
    partition crosses to the host as that prefix of every column."""
    k = np.arange(8192, dtype=np.int64)
    cols = Columns(k, k * 3)
    seen = []
    to_host = layout._to_host
    monkeypatch.setattr(layout, "_to_host", lambda x: (
        seen.append([a.shape for a in (x if isinstance(x, list) else [x])]),
        to_host(x))[1])
    tctx = masters["tpu:1"]
    got = tctx.parallelize(cols, 1).map(_resident) \
        .filter(lambda kv: kv[0] % (8192 // keep) == 0 if keep < 100
                else kv[0] < keep).collect()
    monkeypatch.undo()
    want = [(int(i), int(i) * 3) for i in k
            if (i % (8192 // keep) == 0 if keep < 100 else i < keep)]
    assert got == want and len(got) >= keep
    widths = {s[1] for shapes in seen for s in shapes if len(s) == 2}
    assert widths == ({8} if sliced else {8192})


def test_the_egest_span_counts_the_prefix_it_read(masters):
    """The `egest` span's `bytes` is what crossed to the host: the
    prefix's bytes when the result is sparse, not the capacity's."""
    from dpark_tpu import trace
    k = np.arange(8192, dtype=np.int64)
    tctx = masters["tpu:1"]
    trace.configure("ring")
    try:
        got = tctx.parallelize(Columns(k, k), 1).map(_resident) \
            .filter(lambda kv: kv[0] % 2048 == 0).collect()
        snap = trace.snapshot()
    finally:
        trace.configure("off")
    assert len(got) == 4
    spans = [s for s in snap if s["name"] == "egest"]
    assert [(s["args"]["rows"], s["args"]["bytes"]) for s in spans] \
        == [(4, 2 * 8 * 8)]
