"""Device ingest for text sources (SURVEY.md 3.1 hot loop #1): the narrow
chain over ctx.textFile runs as a host prologue (user generators or the
verified C++ tokenizer), string keys dictionary-encode to int64 columns,
and the shuffle+combine ride the device.  Every test asserts parity with
the local master."""

import gzip
import os

import pytest

pytestmark = pytest.mark.mesh    # full-mesh collectives (see conftest)


@pytest.fixture()
def tctx():
    from dpark_tpu import DparkContext
    c = DparkContext("tpu")
    c.start()
    yield c
    c.stop()


@pytest.fixture()
def corpus(tmp_path):
    import random
    rng = random.Random(42)
    words = ["spark", "tpu", "mesh", "jit", "pallas", "ici", "hbm"]
    p = str(tmp_path / "corpus.txt")
    with open(p, "w") as f:
        for _ in range(4000):
            f.write(" ".join(rng.choices(words, k=5)) + "\n")
    return p


def _local_counts(path, **kw):
    from dpark_tpu import DparkContext
    lctx = DparkContext("local")
    got = dict(lctx.textFile(path, **kw)
               .flatMap(lambda line: line.split())
               .map(lambda w: (w, 1))
               .reduceByKey(lambda a, b: a + b, 4).collect())
    lctx.stop()
    return got


def _text_path_used(tctx):
    from tests.conftest import shuffled_on_device
    return shuffled_on_device(tctx) \
        and hasattr(tctx.scheduler.executor, "token_dict")


def test_canonical_wordcount_rides_device(tctx, corpus):
    got = dict(tctx.textFile(corpus, splitSize=30000)
               .flatMap(lambda line: line.split())
               .map(lambda w: (w, 1))
               .reduceByKey(lambda a, b: a + b, 4).collect())
    assert got == _local_counts(corpus, splitSize=30000)
    assert _text_path_used(tctx)


def test_str_split_method_ref(tctx, corpus):
    got = dict(tctx.textFile(corpus).flatMap(str.split)
               .map(lambda w: (w, 1))
               .reduceByKey(lambda a, b: a + b, 4).collect())
    assert got == _local_counts(corpus)


def test_non_canonical_chain_host_prologue(tctx, corpus):
    """Arbitrary string-keyed narrow chain: the user's own generators
    run per split, keys encode, the device combines."""
    def first_two(line):
        return [(w[:2], len(w)) for w in line.split()]

    def run(ctx):
        return dict(ctx.textFile(corpus)
                    .flatMap(first_two)
                    .reduceByKey(lambda a, b: a + b, 4).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    assert _text_path_used(tctx)


def test_int_key_text_chain_no_encoding(tctx, tmp_path):
    p = str(tmp_path / "nums.txt")
    with open(p, "w") as f:
        for i in range(2000):
            f.write("%d\n" % i)

    def run(ctx):
        return dict(ctx.textFile(p, splitSize=4000)
                    .map(lambda l: (int(l) % 13, 1))
                    .reduceByKey(lambda a, b: a + b, 4).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    from tests.conftest import shuffled_on_device
    assert shuffled_on_device(tctx)


def test_group_by_key_words(tctx, corpus):
    def run(ctx):
        return {k: sorted(v) for k, v in
                ctx.textFile(corpus)
                .flatMap(lambda line: line.split())
                .map(lambda w: (w, len(w)))
                .groupByKey(4).collect()}

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect


def test_downstream_map_after_reduce(tctx, corpus):
    """Further ops on the reduced words force the host path for the
    result stage; the export bridge must hand it DECODED rows."""
    def run(ctx):
        return sorted(ctx.textFile(corpus)
                      .flatMap(lambda line: line.split())
                      .map(lambda w: (w, 1))
                      .reduceByKey(lambda a, b: a + b, 4)
                      .map(lambda kv: (kv[0].upper(), kv[1] * 2))
                      .collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect


def test_word_join_device(tctx, corpus):
    """Str-keyed join: both sides encode through one dict, the device
    matches ids, the exit decodes."""
    def run(ctx):
        words = ctx.textFile(corpus).flatMap(lambda line: line.split())
        a = words.map(lambda w: (w, 1)).reduceByKey(
            lambda x, y: x + y, 4)
        b = words.map(lambda w: (w, len(w))).reduceByKey(
            lambda x, y: x, 4)
        return sorted(a.join(b, 4).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect


def test_unicode_whitespace_falls_back_correctly(tctx, tmp_path):
    """NBSP splits in Python but not in the byte tokenizer: the sample
    verification must catch the divergence and take the host prologue —
    results stay correct."""
    p = str(tmp_path / "nbsp.txt")
    with open(p, "w", encoding="utf-8") as f:
        for i in range(200):
            f.write("a\u00a0b c%d\n" % (i % 3))

    def run(ctx):
        return dict(ctx.textFile(p)
                    .flatMap(lambda line: line.split())
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda x, y: x + y, 4).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    assert "a" in got and "b" in got     # NBSP split like Python
    assert "a\u00a0b" not in got


def test_late_split_divergence_caught(tctx, tmp_path):
    """ADVICE r2: divergence appearing AFTER the first split's 4KB
    sample — NBSP and \\x1c (both str.split() whitespace, neither byte-
    tokenizer whitespace) only in later splits — must not silently
    corrupt counts: the per-split byte-safety scan routes exactly those
    splits to the host prologue."""
    p = str(tmp_path / "late.txt")
    with open(p, "w", encoding="utf-8", newline="") as f:
        for i in range(2000):
            f.write("clean ascii words %d\n" % (i % 5))  # ~40KB clean
        for i in range(200):
            f.write("a b\n")                # unicode whitespace
        for i in range(200):
            f.write("p\x1cq\n")                  # FS control char

    def run(ctx):
        return dict(ctx.textFile(p, splitSize=8000)
                    .flatMap(lambda line: line.split())
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda x, y: x + y, 4).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    assert got["a"] == 200 and got["b"] == 200   # NBSP split
    assert got["p"] == 200 and got["q"] == 200   # \x1c split
    assert "a b" not in got and "p\x1cq" not in got
    assert got["clean"] == 2000                  # clean splits rode C++


def test_long_first_line_not_trusted(tctx, tmp_path):
    """A >4KB first line leaves nothing to verify the byte tokenizer
    against; the canonical path must NOT run unverified."""
    p = str(tmp_path / "long.txt")
    with open(p, "w", encoding="utf-8") as f:
        f.write("x y " * 2000 + "\n")     # NBSP inside, one line

    def run(ctx):
        return dict(ctx.textFile(p)
                    .flatMap(lambda line: line.split())
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda x, y: x + y, 2).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    assert "x" in got and "y" in got     # NBSP split like Python
    assert "x\u00a0y" not in got


def test_separator_split_rides_device(tctx, tmp_path):
    """flatMap(lambda l: l.split('\\t')) + (w,1): the constant-
    separator C++ tokenizer (VERDICT r2 ask #9's 'one more native
    tokenizer shape').  Exact str.split(sep) semantics incl. EMPTY
    fields between consecutive separators."""
    p = str(tmp_path / "tsv.txt")
    with open(p, "w") as f:
        for i in range(3000):
            f.write("a\tb b\t\tc%d\n" % (i % 4))   # empty field + space
            if i % 7 == 0:
                f.write("\n")                       # empty line -> ['']

    def run(ctx):
        return dict(ctx.textFile(p, splitSize=9000)
                    .flatMap(lambda line: line.split("\t"))
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda x, y: x + y, 4).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
    assert got["b b"] == 3000          # space is NOT a separator here
    assert got[""] == 3000 + (3000 + 6) // 7   # empties counted
    assert _text_path_used(tctx)


def test_separator_split_comma(tctx, tmp_path):
    p = str(tmp_path / "c.txt")
    with open(p, "w") as f:
        for i in range(2000):
            f.write("x,y%d,,z\n" % (i % 3))

    def run(ctx):
        return dict(ctx.textFile(p, splitSize=7000)
                    .flatMap(lambda line: line.split(","))
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda x, y: x + y, 4).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect and got[""] == 2000


def test_parallel_ingest_matches_serial(tmp_path):
    """VERDICT r2 ask #2: splits tokenize concurrently into private
    dicts merged in split order — results AND the global id assignment
    must be identical to the serial walk."""
    import random
    import dpark_tpu.conf as conf
    from dpark_tpu import DparkContext
    rng = random.Random(3)
    words = ["w%d" % i for i in range(300)]
    p = str(tmp_path / "par.txt")
    with open(p, "w") as f:
        for _ in range(3000):
            f.write(" ".join(rng.choices(words, k=6)) + "\n")

    def run(threads):
        was = conf.INGEST_THREADS
        conf.INGEST_THREADS = threads
        try:
            c = DparkContext("tpu")
            c.start()
            got = dict(c.textFile(p, splitSize=9000)
                       .flatMap(lambda line: line.split())
                       .map(lambda w: (w, 1))
                       .reduceByKey(lambda a, b: a + b, 4).collect())
            td = c.scheduler.executor.token_dict
            vocab = [td.decode(i) for i in range(len(td))]
            c.stop()
            return got, vocab
        finally:
            conf.INGEST_THREADS = was

    serial, vocab_serial = run(1)
    parallel, vocab_parallel = run(4)
    assert parallel == serial
    assert vocab_parallel == vocab_serial    # id-for-id identical


def test_parallel_ingest_unsafe_first_split(tmp_path):
    """The sample verification may not resolve on split 0 (unsafe
    prefix): the parallel path must keep walking serially until it
    does — the C++ tokenizer never runs unverified, and parity holds
    with the divergent bytes in the FIRST split this time."""
    import dpark_tpu.conf as conf
    from dpark_tpu import DparkContext
    p = str(tmp_path / "front.txt")
    with open(p, "w", encoding="utf-8") as f:
        for i in range(500):
            f.write("x y%d\n" % (i % 7))  # NBSP up front
        for i in range(3000):
            f.write("clean words here %d\n" % (i % 5))

    def run(threads, master):
        was = conf.INGEST_THREADS
        conf.INGEST_THREADS = threads
        try:
            c = DparkContext(master)
            c.start()
            got = dict(c.textFile(p, splitSize=7000)
                       .flatMap(lambda line: line.split())
                       .map(lambda w: (w, 1))
                       .reduceByKey(lambda a, b: a + b, 4).collect())
            c.stop()
            return got
        finally:
            conf.INGEST_THREADS = was

    expect = run(1, "local")
    got = run(4, "tpu")
    assert got == expect
    assert got["x"] == 500 and "x y0" not in got


def test_gzip_source_host_prologue(tctx, tmp_path):
    p = str(tmp_path / "z.gz")
    with gzip.open(p, "wt") as f:
        for i in range(500):
            f.write("x y z w%d\n" % (i % 5))

    def run(ctx):
        return dict(ctx.textFile(p)
                    .flatMap(lambda line: line.split())
                    .map(lambda w: (w, 1))
                    .reduceByKey(lambda x, y: x + y, 2).collect())

    from dpark_tpu import DparkContext
    got = run(tctx)
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect


def test_cache_not_poisoned_by_encoded_results(tctx, corpus):
    """A cached reduced-words RDD must return strings on every access."""
    r = (tctx.textFile(corpus)
         .flatMap(lambda line: line.split())
         .map(lambda w: (w, 1))
         .reduceByKey(lambda a, b: a + b, 4).cache())
    first = dict(r.collect())
    second = dict(r.collect())
    assert first == second
    assert all(isinstance(k, str) for k in second)


def test_lineage_recovery_after_hbm_eviction(tctx, corpus):
    """Evicting the encoded shuffle recomputes the text stage through
    lineage; decoded results stay identical."""
    r = (tctx.textFile(corpus)
         .flatMap(lambda line: line.split())
         .map(lambda w: (w, 1))
         .reduceByKey(lambda a, b: a + b, 4))
    first = dict(r.collect())
    ex = tctx.scheduler.executor
    for sid in list(ex.shuffle_store):
        ex.drop_shuffle(sid)
    assert dict(r.collect()) == first


def test_tabular_source_rides_device(tctx, tmp_path):
    """Tabular chains reach the device shuffle via the host prologue."""
    from dpark_tpu import DparkContext
    from dpark_tpu.tabular import write_tabular
    p = str(tmp_path / "t.tab")
    rows = [(i % 23, i % 7, i) for i in range(4000)]
    write_tabular(p, ["k", "v", "x"], rows, chunk_rows=500)

    def run(ctx):
        return dict(ctx.tabular(p)
                    .map(lambda r: (r[0], r[1]))
                    .reduceByKey(lambda a, b: a + b, 4).collect())

    got = run(tctx)
    from tests.conftest import shuffled_on_device
    assert shuffled_on_device(tctx), "host fallback"
    lctx = DparkContext("local")
    expect = run(lctx)
    lctx.stop()
    assert got == expect
