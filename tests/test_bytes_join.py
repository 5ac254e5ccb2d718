"""The device join over fixed-width byte-string keys (ISSUE 31).

``a.join(b)`` over two HBM-resident no-combine shuffles keyed by an
``S<w>`` column of one width is expanded on the device: both sides are
ordered by ONE word (a 64-bit hash of the key's words), ranges are found on
it, and every emitted pair's key words are compared, so that the answer is
exact whatever the hash does.  Columns of up to layout.BYTES_WIDTH_MAX
bytes are device leaves in any position of a record.  The `local` master
and numpy are the references.

The contracts under test:

* PARITY - a bare join and AMPLab's query-3 chain (filter, map, join, map,
  reduceByKey, top) over S8, S16, S33 and S100 keys with S16 and int
  values, on one and four virtual devices, every stage `array` with no
  reason recorded; keys equal in word 0 that differ in the last word or
  the last byte only, mixed lengths, rows without a partner on either
  side, a key with 1,000 visits, a page listed twice.
* EXACT - with a hash that maps every key to one of two words the pairs
  whose bytes differ are dropped on the device (`join_pairs_dropped`) and
  the answer does not change.
* DECLINES - keys of two widths and a word equal to the padding sentinel
  keep the host path with their reasons, and the right answer.
* SUMS - the float32 revenue stays within the configuration's tolerance,
  and a sum through bfloat16 does not.
"""

import os
import sys
from collections import Counter

import numpy as np
import pytest

import jax.numpy as jnp

from dpark_tpu import Columns, DparkContext
from dpark_tpu.backend.tpu import collectives, layout

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WIDTHS = [8, 16, 33, 100]
HOT_VISITS = 1000


def _job():
    from perf.lib import manifest
    return manifest.load_module(manifest.job_module_path("uservisits_q3"))


def _resident(r):
    return r


def _pair(r):
    return (r[0], r[1])


@pytest.fixture(scope="module")
def masters():
    ctxs = {}
    for name in ("local", "tpu:1", "tpu:4"):
        ctxs[name] = DparkContext(name)
        ctxs[name].start()
    yield ctxs
    for c in ctxs.values():
        c.stop()


def _tables(width, seed=3):
    """(pages, visits): `pages` = (url S<width>, rank), distinct urls and
    one listed twice; `visits` = (url, ip S16, date, revenue) drawn over
    most pages and over urls no page has, one url HOT_VISITS times.  The
    edge rows: urls that agree in their first 8 bytes and differ in the
    last word, urls that differ in the last byte only, a one-byte url."""
    rng = np.random.default_rng([seed, width])
    tail = max(width - 7, 1)
    urls = {b"p%05d/" % i + bytes(rng.integers(97, 123, int(n)).tolist())
            for i, n in enumerate(rng.integers(0, tail, 220))}
    edge = [b"q" * (width - 1) + b"x", b"q" * (width - 1) + b"y",
            b"samehead" + b"r" * max(width - 9, 0) + b"1",
            b"samehead" + b"r" * max(width - 9, 0) + b"2", b"z"]
    urls = np.array(sorted(urls | set(edge)), "S%d" % width)
    urls = np.unique(urls)                  # S8 truncates: keep distinct
    npages = len(urls)
    pages = (np.concatenate([urls[:-20], urls[5:6]]),     # one page twice
             rng.integers(1, 10001, npages - 19).astype(np.int32))
    strangers = np.array([b"nopage%d" % i for i in range(30)],
                         "S%d" % width)
    visited = np.concatenate([urls[rng.integers(0, npages, 1500)],
                              strangers[rng.integers(0, 30, 100)],
                              np.repeat(urls[7:8], HOT_VISITS), urls[:8]])
    n = len(visited)
    ips = np.array([b"%d.%d.1.%d" % (a, b % 3, c % 2) for a, b, c
                    in rng.integers(0, 256, (n, 3))], "S16")
    visits = (visited, ips, rng.integers(0, 15930, n).astype(np.int32),
              rng.random(n, dtype=np.float32))
    return pages, visits


def _reasons(ctx, since):
    return [(str(st.get("kind")), st.get("fallback_reason"),
             st.get("degrade_reason"))
            for rec in ctx.scheduler.history[since:]
            for st in rec["stage_info"]]


def _on_array_path(ctx, since):
    found = _reasons(ctx, since)
    assert found and all(k.startswith("array") and not f and not d
                         for k, f, d in found), found


def _bare_join(ctx, pages, visits, values, ndev):
    vcol = visits[1] if values == "S16" else visits[2]
    left = ctx.parallelize(Columns(visits[0], vcol), ndev).map(_resident)
    right = ctx.parallelize(Columns(*pages), ndev).map(_resident)
    return left.map(_pair).join(right.map(_pair), ndev).collect()


@pytest.mark.parametrize("values", ["S16", "int"])
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("ndev", [1, 4])
def test_join_equals_the_local_master(masters, ndev, width, values):
    pages, visits = _tables(width)
    want = Counter(_bare_join(masters["local"], pages, visits, values,
                              ndev))
    tctx = masters["tpu:%d" % ndev]
    since = len(tctx.scheduler.history)
    got = _bare_join(tctx, pages, visits, values, ndev)
    assert Counter(got) == want
    assert all(type(k) is bytes for k, _ in got)
    _on_array_path(tctx, since)
    # what the data holds, by name
    hot = bytes(pages[0][7])
    assert sum(1 for k, _ in got if k == hot) >= HOT_VISITS
    twice = bytes(pages[0][5])
    assert sum(1 for k, _ in got if k == twice) \
        == 2 * int((visits[0] == pages[0][5]).sum())
    assert not any(k.startswith(b"nopage") for k, _ in got)
    for a, b in ((b"q" * (width - 1) + b"x", b"q" * (width - 1) + b"y"),):
        for k in (a, b):
            assert sum(1 for key, _ in got if key == k) \
                == int((visits[0] == np.bytes_(k)).sum()) \
                * int((pages[0] == np.bytes_(k)).sum())


def _query3(ctx, job, pages, visits, ndev):
    rk = ctx.parallelize(Columns(*pages), ndev).map(job.resident)
    uv = ctx.parallelize(Columns(*visits), ndev).map(job.resident)
    grouped = uv.filter(job.in_dates).map(job.by_url).join(rk) \
        .map(job.by_ip).reduceByKey(job.add3, ndev)
    return grouped.top(1, key=job.revenue), sorted(grouped.collect())


@pytest.mark.parametrize("ndev,width", [(1, w) for w in WIDTHS]
                         + [(4, 16), (4, 100)])
def test_query3_chain_equals_the_local_master(masters, ndev, width):
    job = _job()
    pages, visits = _tables(width, seed=9)
    want_top, want = _query3(masters["local"], job, pages, visits, ndev)
    tctx = masters["tpu:%d" % ndev]
    since = len(tctx.scheduler.history)
    got_top, got = _query3(tctx, job, pages, visits, ndev)
    _on_array_path(tctx, since)
    kinds = [k for k, _, _ in _reasons(tctx, since)]
    assert kinds[:4] == ["array", "array", "array", "array+top"]
    assert [k for k, _ in got] == [k for k, _ in want]
    for (_, v), (_, w) in zip(got, want):
        assert v[:2] == w[:2]                   # ranks and rows: exact
        assert abs(v[2] - w[2]) <= v[1] * 2.0 ** -23 * max(w[2], 1.0)
    assert got_top[0][0] == want_top[0][0]
    assert got_top[0][1][:2] == want_top[0][1][:2]


def test_byte_strings_ride_as_values_of_an_int_keyed_join(masters):
    """Byte strings anywhere in the values: an int64 key, S100 and S16
    values on the left, S33 on the right."""
    rng = np.random.default_rng(4)
    (urls, ranks), visits = _tables(100, seed=4)
    keys = rng.integers(0, 50, len(visits[0]))
    right_urls = np.array(urls[:60].tolist(), "S33")

    def job(ctx):
        left = ctx.parallelize(Columns(keys, visits[0], visits[1]), 1) \
            .map(_resident).map(_left_record)
        right = ctx.parallelize(
            Columns(np.arange(60), right_urls, ranks[:60]), 1) \
            .map(_resident).map(_left_record)
        return Counter(left.join(right, 1).collect())

    tctx = masters["tpu:1"]
    since = len(tctx.scheduler.history)
    assert job(tctx) == job(masters["local"])
    _on_array_path(tctx, since)


def _left_record(r):
    return (r[0], (r[1], r[2]))


def _two_words(key_cols, valid):
    """A hash of two values: every key collides with half of the rest."""
    h = (key_cols[0] >> 8) & 1
    for c in key_cols[1:]:
        h = h ^ ((c >> 8) & 1)
    return jnp.where(valid, h.astype("int64"),
                     collectives._sentinel("int64"))


@pytest.mark.parametrize("ndev", [1, 4])
def test_a_forced_hash_collision_drops_pairs_and_keeps_the_answer(
        masters, ndev, monkeypatch):
    pages, visits = _tables(33, seed=6)
    want = Counter(_bare_join(masters["local"], pages, visits, "S16",
                              ndev))
    monkeypatch.setattr(collectives, "key_hash64", _two_words)
    tctx = DparkContext("tpu:%d" % ndev)     # programs of its own
    tctx.start()
    try:
        ex = tctx.scheduler.executor
        got = _bare_join(tctx, pages, visits, "S16", ndev)
        _on_array_path(tctx, 0)
        assert Counter(got) == want
        emitted = ex.join_rows_out
        assert emitted > 10 * len(got)      # candidates, mostly false
        # the count of dropped pairs rides the next join's one read
        assert Counter(_bare_join(tctx, pages, visits, "S16", ndev)) \
            == want
        assert ex.join_pairs_dropped == emitted - len(got)
    finally:
        tctx.stop()


def test_an_honest_hash_drops_nothing(masters):
    pages, visits = _tables(100, seed=8)
    tctx = masters["tpu:1"]
    ex = tctx.scheduler.executor
    for _ in range(2):
        before = ex.join_rows_out
        got = _bare_join(tctx, pages, visits, "int", 1)
        assert ex.join_rows_out - before == len(got)
    assert ex.join_pairs_dropped == 0


def test_the_join_span_and_its_arguments(masters):
    from dpark_tpu import trace
    pages, visits = _tables(100, seed=8)
    tctx = masters["tpu:1"]
    trace.configure("ring")
    try:
        got = _bare_join(tctx, pages, visits, "int", 1)
        spans = trace.snapshot()
    finally:
        trace.configure("off")
    (join,) = [s for s in spans if s["name"] == "join"]
    assert join["args"]["rows_out"] == len(got)
    assert join["args"]["key_bytes"] == 100
    assert join["args"]["cap_a"] >= len(visits[0])
    inside = [s for s in spans if s["name"] in ("launch", "readback")
              and join["ts"] <= s["ts"] <= join["ts"] + join["dur"]]
    assert {"join_count", "join_expand"} <= {
        s["args"].get("program") for s in inside}
    assert "join.totals" in {s["args"].get("site") for s in inside}


def test_wide_rows_move_as_rows_and_the_hash_is_a_key():
    """The programs of an S100 join say how they order (`compile`
    events, ring on from the start): keys and an iota through the sort,
    the row behind them; and the hash that orders the join's sides is
    below the padding sentinel on valid rows, the sentinel on the rest,
    equal for equal keys."""
    from dpark_tpu import trace
    pages, visits = _tables(100, seed=8)
    trace.configure("ring")
    tctx = DparkContext("tpu:1")         # programs of its own: compiles
    tctx.start()
    try:
        _bare_join(tctx, pages, visits, "S16", 1)
        events = [s for s in trace.snapshot() if s["name"] == "compile"]
    finally:
        tctx.stop()
        trace.configure("off")
    assert {(e["args"]["program"], e["args"]["sort"]) for e in events
            if "sort" in e["args"]} \
        == {("narrow", "keys+rows"), ("reduce", "keys+rows"),
            ("narrow", "none")}     # the joined batch's own program
    # the join's two programs order nothing: they say how they match
    assert {(e["args"]["program"], e["args"]["match"]) for e in events
            if "sort" not in e["args"]} \
        == {("join_count", "merge"), ("join_expand", "merge")}
    words = [np.array([5, 5, 7, 2 ** 62], np.int64),
             np.array([1, 1, 1, -3], np.int64)]
    valid = np.array([True, True, True, False])
    h = np.asarray(collectives.key_hash64(
        [jnp.asarray(w) for w in words], jnp.asarray(valid)))
    assert h.dtype == np.int64 and h[0] == h[1] != h[2]
    assert (0 <= h[:3]).all() and (h[:3] < layout.KEY_SENTINEL).all()
    assert h[3] == layout.KEY_SENTINEL


@pytest.mark.parametrize("case", ["widths", "sentinel"])
def test_declines_keep_the_host_path_with_a_reason(masters, case):
    pages, visits = _tables(16, seed=2)
    if case == "widths":
        pages = (np.array(pages[0].tolist(), "S24"), pages[1])
        needle = "one width"
    else:
        bad = np.array([b"\x7f" + b"\xff" * 7 + b"tail"], "S16")
        visits = tuple(np.concatenate([c[:1], c]) for c in visits)
        visits[0][0] = bad[0]
        needle = "sentinel"
    want = Counter(_bare_join(masters["local"], pages, visits, "int", 1))
    tctx = masters["tpu:1"]
    since = len(tctx.scheduler.history)
    assert Counter(_bare_join(tctx, pages, visits, "int", 1)) == want
    assert any(needle in (f or "") for _, f, _ in _reasons(tctx, since))


def test_the_width_limit_is_the_strings_own(masters):
    from dpark_tpu import conf
    from dpark_tpu.analysis import plan_rules
    assert plan_rules.BYTES_WIDTH_MAX == layout.BYTES_WIDTH_MAX >= 104
    assert layout.BYTES_WIDTH_MAX > 8 * conf.MAX_KEY_LEAVES
    col = np.array([b"k%03d" % (i % 7) + b"w" * 100 for i in range(64)],
                   "S%d" % layout.BYTES_WIDTH_MAX)
    tctx = masters["tpu:1"]
    since = len(tctx.scheduler.history)
    got = tctx.parallelize(Columns(col, np.arange(64)), 1) \
        .map(_resident).map(_pair).groupByKey(1).mapValues(len).collect()
    assert sorted(got) == sorted(Counter(col.tolist()).items())
    _on_array_path(tctx, since)


def test_float32_revenue_within_the_tolerance_and_bfloat16_outside(masters):
    """The comparison that decides `correct` in uservisits.join tells
    float32 sums from the next precision down."""
    job = _job()
    rng = np.random.default_rng(12)
    pages, visits = _tables(100, seed=12)
    # few sourceIPs: groups that really add
    visits = (visits[0], np.array([b"10.0.0.%d" % i for i in
                                   rng.integers(0, 40, len(visits[0]))],
                                  "S16"), visits[2], visits[3])
    order = np.argsort(pages[0], kind="stable")
    keep = np.concatenate([[True], pages[0][order][1:]
                           != pages[0][order][:-1]])     # distinct pages
    pages = (pages[0][order][keep], pages[1][order][keep])
    data = {"parts": [visits], "rows": len(visits[0]), "pages": pages,
            "pages_sorted": pages}
    expected = job.reference(data, 0, "q3c", "top1")
    assert expected[2].max() >= 8
    tctx = masters["tpu:1"]
    tables = {"parts": [tctx.parallelize(Columns(*visits), 1)
                        .map(job.resident)],
              "pages": tctx.parallelize(Columns(*pages), 1)
              .map(job.resident)}
    top = job.run(tctx, tables, 0, "q3c", "top1", 1)
    assert job.verdict(top, expected, "top1")
    rows = tables["parts"][0].filter(job.in_dates).map(job.by_url) \
        .join(tables["pages"]).map(job.by_ip) \
        .reduceByKey(job.add3, 1).collect()
    assert job._rows_match(rows, expected) is not None
    # the same groups with their revenue summed through bfloat16
    inside = (visits[2] >= job.DATE_LOW) & (visits[2] <= job.DATE_HIGH) \
        & np.isin(visits[0], pages[0])
    low = []
    for k, (ranks, n, _) in rows:
        acc = jnp.bfloat16(0)
        for v in visits[3][inside & (visits[1] == np.bytes_(k))]:
            acc = acc + jnp.bfloat16(v)
        low.append((k, (ranks, n, float(acc))))
    assert job._rows_match(low, expected) is None
    best = max(low, key=lambda kv: expected[3][
        list(expected[0]).index(np.bytes_(kv[0]))])
    assert not job.verdict([best], expected, "top1")
