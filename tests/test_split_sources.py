"""Intra-file splitting for compressed and CSV sources (SURVEY.md 2.2):
one .gz file with several members splits across tasks, one .bz2 with
several streams likewise, and CSV splits land only on record boundaries
even when quoted fields contain newlines."""

import bz2
import csv
import gzip
import io

import numpy as np
import pytest


def _write_multi_member_gz(path, nmembers, lines_per):
    with open(path, "wb") as out:
        n = 0
        for m in range(nmembers):
            buf = io.BytesIO()
            with gzip.GzipFile(fileobj=buf, mode="wb") as g:
                for _ in range(lines_per):
                    g.write(b"line-%06d\n" % n)
                    n += 1
            out.write(buf.getvalue())
    return ["line-%06d" % i for i in range(n)]


def test_gzip_one_file_multi_split(ctx, tmp_path):
    p = str(tmp_path / "multi.gz")
    expect = _write_multi_member_gz(p, 4, 500)
    r = ctx.textFile(p)
    r.split_size = 1               # force one split per member
    splits = r.splits
    assert len(splits) == 4, [s.__dict__ for s in splits]
    got = r.collect()
    assert got == expect


def test_gzip_single_member_one_split(ctx, tmp_path):
    p = str(tmp_path / "one.gz")
    with gzip.open(p, "wt") as f:
        for i in range(100):
            f.write("x%d\n" % i)
    r = ctx.textFile(p)
    assert len(r.splits) == 1
    assert r.collect() == ["x%d" % i for i in range(100)]


def test_gzip_false_positive_magic_rejected(ctx, tmp_path):
    """Random bytes that happen to contain the gzip magic inside the
    compressed payload must not become split boundaries."""
    rng = np.random.RandomState(0)
    payload = rng.bytes(1 << 20) + b"\x1f\x8b\x08\x00" * 50
    lines = [payload.hex()[i:i + 64]
             for i in range(0, 4096, 64)]
    p = str(tmp_path / "fp.gz")
    with gzip.open(p, "wt") as f:
        for ln in lines:
            f.write(ln + "\n")
    # append a REAL second member so the scan has work to do
    with open(p, "ab") as out:
        buf = io.BytesIO()
        with gzip.GzipFile(fileobj=buf, mode="wb") as g:
            g.write(b"tail\n")
        out.write(buf.getvalue())
    r = ctx.textFile(p)
    r.split_size = 1
    assert r.collect() == lines + ["tail"]


def test_bzip2_multi_stream_split(ctx, tmp_path):
    p = str(tmp_path / "multi.bz2")
    expect = []
    with open(p, "wb") as out:
        for s in range(3):
            block = "".join("s%d-%d\n" % (s, i) for i in range(200))
            expect.extend(block.splitlines())
            out.write(bz2.compress(block.encode()))
    r = ctx.textFile(p)
    r.split_size = 1
    assert len(r.splits) == 3
    assert r.collect() == expect


def test_bzip2_single_stream_block_split(ctx, tmp_path):
    """ONE bz2 stream with several 100KB blocks (compresslevel=1) must
    split at the bit-aligned block magics — the round-2 gap was
    splitting only at byte-aligned stream starts (VERDICT r2 ask #9)."""
    p = str(tmp_path / "one_stream.bz2")
    lines = ["line-%06d %s" % (i, "x" * (i % 37)) for i in range(14000)]
    text = "\n".join(lines) + "\n"
    assert len(text) > 350000                   # > 3 blocks at level 1
    with open(p, "wb") as f:
        f.write(bz2.compress(text.encode(), compresslevel=1))
    r = ctx.textFile(p, splitSize=6000)   # compressed bytes
    from dpark_tpu.rdd import Bz2BlockSplit
    assert len(r.splits) >= 3, len(r.splits)
    assert all(isinstance(s, Bz2BlockSplit) for s in r.splits)
    assert r.collect() == lines
    # parallelism is real: distinct splits own distinct line ranges
    per_split = [len(list(r.compute(s))) for s in r.splits]
    assert sum(per_split) == len(lines)
    assert max(per_split) < len(lines)


def test_bzip2_block_split_line_spans_blocks(ctx, tmp_path):
    """A single line larger than a whole compression block: exactly one
    split owns it, none lose or duplicate it."""
    p = str(tmp_path / "giant.bz2")
    import random
    rng = random.Random(5)
    giant = "".join(rng.choice("abcdefgh ") for _ in range(250000))
    lines = ["head-%d" % i for i in range(2000)] + [giant] + \
            ["tail-%d" % i for i in range(2000)]
    with open(p, "wb") as f:
        f.write(bz2.compress(("\n".join(lines) + "\n").encode(),
                             compresslevel=1))
    r = ctx.textFile(p, splitSize=15000)
    assert len(r.splits) >= 2
    assert r.collect() == lines


def test_bzip2_multi_stream_block_split(ctx, tmp_path):
    """Concatenated streams each with multiple blocks; also exercises
    per-stream levels and the tpu master's host prologue over bz2."""
    p = str(tmp_path / "ms.bz2")
    expect = []
    with open(p, "wb") as out:
        for s, level in ((0, 1), (1, 2)):
            block = "".join("s%d-%06d\n" % (s, i) for i in range(25000))
            expect.extend(block.splitlines())
            out.write(bz2.compress(block.encode(), compresslevel=level))
    r = ctx.textFile(p, splitSize=5000)
    assert len(r.splits) >= 4
    assert r.collect() == expect


def test_csv_quoted_newline_across_split(ctx, tmp_path):
    """A quoted field containing newlines straddles the naive split
    boundary; the quote-parity scan must keep the record whole."""
    p = str(tmp_path / "q.csv")
    rows = []
    for i in range(500):
        if i % 50 == 7:
            rows.append([str(i), "multi\nline\nfield %d" % i, "z"])
        else:
            rows.append([str(i), "plain %d" % i, "z"])
    with open(p, "w", newline="") as f:
        csv.writer(f).writerows(rows)
    r = ctx.csvFile(p, splitSize=900)      # many tiny splits
    assert len(r.splits) > 5
    got = r.collect()
    assert got == rows


def test_csv_doubled_quotes(ctx, tmp_path):
    p = str(tmp_path / "dq.csv")
    rows = [[str(i), 'say ""hi""\nthere %d' % i] for i in range(300)]
    with open(p, "w", newline="") as f:
        csv.writer(f).writerows(rows)
    r = ctx.csvFile(p, splitSize=700)
    got = r.collect()
    expect = list(csv.reader(open(p, newline="")))
    assert got == expect


def test_csv_numsplits_and_quotechar(ctx, tmp_path):
    class SQ(csv.Dialect):
        delimiter = ","
        quotechar = "'"
        quoting = csv.QUOTE_MINIMAL
        lineterminator = "\r\n"
        doublequote = True
    csv.register_dialect("squote", SQ)
    p = str(tmp_path / "sq.csv")
    rows = [[str(i), "nl\nin field %d" % i] for i in range(200)]
    with open(p, "w", newline="") as f:
        csv.writer(f, "squote").writerows(rows)
    r = ctx.csvFile(p, dialect="squote", numSplits=6)
    assert len(r.splits) >= 4          # numSplits drives split size
    assert r.collect() == rows


def test_compressed_sources_over_chunkserver(ctx, tmp_path):
    """gzip/csv sources route ALL IO through file_manager, so they work
    on a DFS scheme path too."""
    from dpark_tpu.file_manager.chunkserver import ChunkServer
    root = tmp_path / "dfs"
    root.mkdir()
    expect = _write_multi_member_gz(str(root / "m.gz"), 3, 50)
    with open(root / "r.csv", "w", newline="") as f:
        csv.writer(f).writerows([["a", "x\ny"], ["b", "z"]])
    srv = ChunkServer(str(root)).start()
    try:
        r = ctx.textFile("cfs://%s/m.gz" % srv.addr)
        r.split_size = 1
        assert len(r.splits) == 3
        assert r.collect() == expect
        got = ctx.csvFile("cfs://%s/r.csv" % srv.addr).collect()
        assert got == [["a", "x\ny"], ["b", "z"]]
    finally:
        srv.stop()


def test_csv_bare_quote_in_unquoted_field(ctx, tmp_path):
    """A stray quote in an unquoted field (legal to csv.reader) must not
    poison later split boundaries — the exact state machine ignores it
    where a quote-parity count would flip forever."""
    p = str(tmp_path / "bare.csv")
    with open(p, "w", newline="") as f:
        f.write('1,5" nail,plain\r\n')        # bare quote, unquoted
        for i in range(300):
            f.write('%d,"multi\nline %d",z\r\n' % (i, i))
    expect = list(csv.reader(open(p, newline="")))
    r = ctx.csvFile(p, splitSize=500)
    assert len(r.splits) > 3
    assert r.collect() == expect


@pytest.mark.mesh
def test_csvfile_rides_device_text_path(tmp_path):
    """csvFile chains reach the device text-ingest path on the tpu
    master."""
    from dpark_tpu import DparkContext
    p = str(tmp_path / "dev.csv")
    with open(p, "w", newline="") as f:
        csv.writer(f).writerows(
            [["k%d" % (i % 7), str(i % 3)] for i in range(500)])
    tctx = DparkContext("tpu")
    tctx.start()
    try:
        got = dict(tctx.csvFile(p)
                   .map(lambda row: (row[0], int(row[1])))
                   .reduceByKey(lambda a, b: a + b, 4).collect())
        from tests.conftest import shuffled_on_device
        assert shuffled_on_device(tctx), "host fallback"
        lctx = DparkContext("local")
        expect = dict(lctx.csvFile(p)
                      .map(lambda row: (row[0], int(row[1])))
                      .reduceByKey(lambda a, b: a + b, 4).collect())
        lctx.stop()
        assert got == expect
    finally:
        tctx.stop()


def test_gzip_splitsize_via_textfile(ctx, tmp_path):
    p = str(tmp_path / "s.gz")
    expect = _write_multi_member_gz(p, 4, 100)
    r = ctx.textFile(p, splitSize=1)       # forwarded to member grouping
    assert len(r.splits) == 4
    assert r.collect() == expect


def test_csv_roundtrip_save_load(ctx, tmp_path):
    data = [["a", "1"], ["b", "2"], ["c,d", "3"]]
    ctx.parallelize(data, 2).saveAsCSVFile(str(tmp_path / "csv"))
    back = ctx.csvFile(str(tmp_path / "csv")).collect()
    assert sorted(back) == sorted(data)
