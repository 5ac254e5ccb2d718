"""The combine reduces over the sorted order itself (ISSUE 27).

`bucketize_combine_keys` (a shuffle write's map-side combine) and
`segment_reduce_keys` (the reduce side) merge the values of equal keys
with a scan along rows they have just sorted, and count rows per
destination by compares: no scatter-add, no gather back.  numpy is the
reference.

The contracts under test:

* PARITY — every (monoid kind, value dtype, key width, row shape,
  destination count): integer results bit-equal, sums that wrap past
  2**63 included; float sums within n_g * eps * sum|v| of float64;
  counts and offsets equal.
* ORDER — an unclassified, non-commutative merge sees a segment's rows
  in input order.
* NO SCATTER — the lowered `narrow` and `reduce` programs of four
  reduceByKey shapes hold none, and their `compile` events say which
  form of the merge they were built with.
* NO GATHER (ISSUE 29) — the same programs pull no column through a
  permutation: their orderings carry the rows (`sort == "carried"`);
  a record with a rank-2 value gathers that leaf alone
  (`carried+gathered`).
* SLICES (ISSUE 34) — the `exchange` program between them gathers no
  slot row either: a destination's block is a `dynamic_slice` of the
  sorted leaf, of a rank-2 leaf too, and its `compile` event says
  `send == "slices"`.
"""

import functools
import operator

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dpark_tpu import Columns, DparkContext, trace
from dpark_tpu.backend.tpu import collectives
from dpark_tpu.backend.tpu.executor import JAXExecutor

CAP = 48
KINDS = {"add": np.add, "min": np.minimum, "max": np.maximum,
         "mul": np.multiply, "unclassified": np.add}
DTYPES = ("int64", "int32", "float32", "float64")
ROWS = ("one_segment", "all_distinct", "empty", "full", "prefix", "holes")


@pytest.fixture(autouse=True)
def _x64():
    """Device compute is 64-bit: the executor turns it on with its
    first context, these tests for the functions they call bare."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def _user_add(a, b):
    return [a[0] + b[0]]


@functools.lru_cache(maxsize=None)
def _programs(kind, nk, n_dst):
    """The two functions under test, jitted once per static shape (the
    row count and the rows are arguments)."""
    monoid = None if kind == "unclassified" else kind

    def combine(n, dst, *cols):
        return collectives.bucketize_combine_keys(
            cols[:nk], cols[nk:], n, n_dst, _user_add, monoid=monoid,
            dst=dst)

    def reduce(mask, *cols):
        return collectives.segment_reduce_keys(
            cols[:nk], cols[nk:], mask, _user_add, monoid=monoid)

    return jax.jit(combine), jax.jit(reduce)


def _rows(rows, kind, dtype, nk, seed):
    """(key columns, values, valid mask) of one case; invalid rows hold
    keys and values that would show in any result they leaked into."""
    rng = np.random.default_rng(seed)
    keys = {"one_segment": np.full(CAP, 5),
            "all_distinct": rng.permutation(CAP) - 7}.get(
                rows, rng.integers(-4, 5, CAP))
    cols = [keys.astype(np.int64)]
    if nk == 2:     # equal in word 0, told apart by word 1 (and back);
        # spread, so that no two keys share the 32-bit hash the map side
        # orders by (a collision leaves two partial combiners: legal,
        # but not what a reference counts)
        cols = [cols[0] // 3 * 1000003, cols[0] % 3 * 7919 + 5]
    mask = {"empty": np.zeros(CAP, bool),
            "prefix": np.arange(CAP) < CAP * 5 // 8,
            "holes": rng.random(CAP) < 0.6}.get(rows, np.ones(CAP, bool))
    if np.dtype(dtype).kind == "f":
        vals = rng.random(CAP) + 0.5
    elif kind == "mul":
        vals = rng.choice([-3, -1, 1, 2, 3, 7], CAP)
    elif dtype == "int64":      # a segment's sum wraps past 2**63
        vals = rng.integers(-2**62, 2**62, CAP)
    else:
        vals = rng.integers(-2**30, 2**30, CAP)
    return cols, vals.astype(dtype), mask


def _reference(kind, groups_of, cols, vals, mask):
    """{group: (reduced value, float64 bound term n_g * sum|v|)} over
    the valid rows, reduced in the value's own dtype (ints wrap as the
    device's do)."""
    out = {}
    op = KINDS[kind]
    for i in np.flatnonzero(mask):
        g = groups_of(i)
        out.setdefault(g, []).append(vals[i])
    with np.errstate(over="ignore"):
        return {g: (op.reduce(np.array(v, vals.dtype), dtype=vals.dtype),
                    op.reduce(np.array(v, np.float64)),
                    len(v) * np.abs(np.array(v, np.float64)).sum())
                for g, v in out.items()}


def _check_values(kind, dtype, got, ref):
    assert set(got) == set(ref)
    for g, v in got.items():
        same, f64, bound = ref[g]
        assert v.dtype == np.dtype(dtype)
        if v.dtype.kind == "i" or kind in ("min", "max"):
            assert v == same, (g, v, same)
        elif kind == "mul":
            assert abs(v - f64) <= bound * np.finfo(dtype).eps * abs(f64)
        else:
            assert abs(v - f64) <= bound * np.finfo(dtype).eps, (g, v, f64)


@pytest.mark.parametrize("n_dst", (1, 4))
@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("nk", (1, 2))
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_combine_and_reduce_match_numpy(kind, dtype, nk, rows, n_dst):
    cols, vals, mask = _rows(rows, kind, dtype, nk,
                             seed=len(kind) + 7 * nk + 31 * n_dst)
    combine, reduce = _programs(kind, nk, n_dst)
    sentinel = np.iinfo(np.int64).max

    # the map side takes a valid PREFIX: valid rows first, in order
    order = np.argsort(~mask, kind="stable")
    n = int(mask.sum())
    pcols = [c[order] for c in cols]
    pvals = vals[order]
    dst = np.where(np.arange(CAP) < n, (pcols[0] + 8) % n_dst, n_dst)
    ks, (vs,), counts, offsets = combine(
        np.int32(n), dst.astype(np.int32), *pcols, pvals)
    ks, vs = [np.asarray(k) for k in ks], np.asarray(vs)
    ref = _reference(kind, lambda i: (dst[i],) + tuple(c[i] for c in pcols),
                     pcols, pvals, np.arange(CAP) < n)
    want = [sum(1 for g in ref if g[0] == b) for b in range(n_dst)]
    assert counts.dtype == offsets.dtype == jnp.int32
    assert counts.tolist() == want
    assert offsets.tolist() == [sum(want[:b]) for b in range(n_dst)]
    got = {(b,) + tuple(k[i] for k in ks): vs[i]
           for b in range(n_dst)
           for i in range(offsets[b], offsets[b] + counts[b])}
    assert len(got) == sum(want)
    _check_values(kind, dtype, got, ref)

    # the reduce side takes a mask, and the sentinel in key column 0
    rcols = [np.where(mask, cols[0], sentinel)] + cols[1:]
    ks, (vs,), n_unique = reduce(mask, *rcols, vals)
    ks, vs = [np.asarray(k) for k in ks], np.asarray(vs)
    ref = _reference(kind, lambda i: tuple(c[i] for c in cols), cols, vals,
                     mask)
    assert int(n_unique) == len(ref)
    keys = [tuple(k[i] for k in ks) for i in range(len(ref))]
    assert keys == sorted(ref)          # uniques at the front, key-sorted
    _check_values(kind, dtype, dict(zip(keys, vs[:len(ref)])), ref)


def test_an_unclassified_merge_sees_a_segments_rows_in_input_order():
    """f(a, b) = 10 * a + b is associative over digit strings and not
    commutative: the result spells the segment's values in order."""
    def spell(a, b):
        return [a[0] * 10 ** jnp.ceil(jnp.log10(b[0] + 0.5)).astype(
            jnp.int64) + b[0]]
    keys = np.array([3, 1, 3, 2, 1, 3, 9, 9], np.int64)
    vals = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int64)
    ks, (vs,), counts, _ = collectives.bucketize_combine_keys(
        [keys], [vals], 6, 1, spell)
    assert counts.tolist() == [3]
    assert dict(zip(ks[0][:3].tolist(), vs[:3].tolist())) == {
        1: 25, 2: 4, 3: 136}
    mask = np.arange(8) < 6
    ks, (vs,), n = collectives.segment_reduce_keys(
        [np.where(mask, keys, np.iinfo(np.int64).max)], [vals], mask, spell)
    assert int(n) == 3
    assert (ks[0][:3].tolist(), vs[:3].tolist()) == ([1, 2, 3], [25, 4, 136])


@pytest.mark.parametrize("nb", (3, 17, 18, 40))
def test_bucket_counts_by_compares_and_by_boundaries(nb):
    d = np.sort(np.random.default_rng(nb).integers(0, nb, 500))
    d = d[d != 1].astype(np.int32)               # an empty bucket
    got = collectives._bucket_counts(jnp.asarray(d), nb)
    assert got.dtype == jnp.int32
    assert got.tolist() == np.bincount(d, minlength=nb).tolist()


@pytest.mark.parametrize("n_dst", (4, 20))
def test_bucketize_groups_rows_by_destination(n_dst):
    rng = np.random.default_rng(n_dst)
    key = rng.integers(0, 1000, 64)
    dst = np.where(np.arange(64) < 50, key % n_dst, n_dst).astype(np.int32)
    (k2, i2), counts, offsets = collectives.bucketize(
        jnp.asarray(key), [jnp.asarray(key), jnp.arange(64)], 50, n_dst,
        dst=jnp.asarray(dst))
    order = np.argsort(dst, kind="stable")
    assert np.asarray(i2).tolist() == order.tolist()
    assert np.asarray(k2).tolist() == key[order].tolist()
    want = np.bincount(dst, minlength=n_dst + 1)[:n_dst]
    assert counts.tolist() == want.tolist()
    assert offsets.tolist() == (np.cumsum(want) - want).tolist()


# ----------------------------------------------------------------------
# the stage programs, as the executor builds them
# ----------------------------------------------------------------------

def _int_pairs():
    rng = np.random.default_rng(3)
    return Columns(rng.integers(0, 50, 400), rng.integers(-9, 9, 400))


def _byte_pairs():
    rng = np.random.default_rng(5)
    ips = np.array([b"10.%d.%d.7" % (a, b) for a, b in
                    rng.integers(0, 12, (400, 2))], dtype="S12")
    return Columns(ips, rng.random(400).astype(np.float32))


def _pair(r):
    return (r[0], r[1])


def _add_through_a_name(a, b):      # an add no classifier proves
    total = a + b
    return total


SHAPES = {"int_add": (_int_pairs, operator.add, "seg_scan"),
          "bytes_float32_add": (_byte_pairs, operator.add, "seg_scan"),
          # the builtin min classifies too, but does not trace: its
          # reduce side folds on the host and compiles no program
          "int_min": (_int_pairs, jnp.minimum, "seg_scan"),
          "int_unclassified": (_int_pairs, _add_through_a_name, "user_scan")}


@pytest.fixture()
def launches(monkeypatch):
    """(program, lowered text) of every stage program a job launches."""
    seen = []
    launch = JAXExecutor._launch

    def spy(self, program, fn, *args):
        if program in ("narrow", "exchange", "reduce"):
            seen.append((program, fn.lower(*args).as_text()))
        return launch(self, program, fn, *args)
    monkeypatch.setattr(JAXExecutor, "_launch", spy)
    return seen


@pytest.fixture()
def compiles_of():
    """Run a job on two virtual chips with the ring on; returns what
    the job returned and the args of its `compile` events."""
    def run(job):
        c = DparkContext("tpu:2")
        c.start()
        trace.configure("ring")
        try:
            return job(c), [r["args"] for r in trace.snapshot()
                            if r["name"] == "compile"]
        finally:
            trace.configure("off")
            c.stop()
    return run


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_stage_programs_hold_no_scatter_and_name_their_combine(
        shape, launches, compiles_of):
    make, merge, form = SHAPES[shape]
    data = make()
    rows, compiles = compiles_of(
        lambda c: c.parallelize(data, 2).map(_pair)
        .reduceByKey(merge, 2).collect())
    got = dict(rows)
    ref = {}
    for k, v in zip(*data.arrays):
        k = k.item()
        ref[k] = merge(ref[k], v) if k in ref else v
    assert set(got) == set(ref)
    assert all(abs(got[k] - ref[k]) <= 1e-5 * abs(ref[k]) for k in ref)
    assert {p for p, _ in launches} == {"narrow", "exchange", "reduce"}
    for program, text in launches:
        assert _HOLDS[program] in text, program     # the text is its own
        assert "scatter" not in text, program
        assert not _column_gathers(text), program
    assert {(a["program"], a["combine"], a["sort"]) for a in compiles
            if "sort" in a} == {
        ("narrow", form, "carried"), ("reduce", form, "carried")}
    assert _exchange_sends(compiles) == {"slices"}


# what moves the rows in each program
_HOLDS = {"narrow": "sort", "exchange": "dynamic_slice", "reduce": "sort"}


def _column_gathers(text):
    """The lowered text's gathers of a whole column of rows (a result
    of 64 elements or more: every padded capacity of these jobs is
    larger, every per-destination lookup smaller)."""
    out = []
    for line in text.splitlines():
        if "stablehlo.gather" in line or "stablehlo.dynamic_gather" in line:
            dims = line.rsplit("tensor<", 1)[1].split(">")[0].split("x")[:-1]
            if int(np.prod([int(d) for d in dims] or [1])) >= 64:
                out.append(line.strip())
    return out


def _exchange_sends(compiles):
    """How the job's exchange programs say they build a send block."""
    sends = [a["send"] for a in compiles if a["program"] == "exchange"]
    assert sends, compiles
    return set(sends)


def _vector_pair(r):
    return (r[0], jnp.stack([r[1], r[1] * 2]))


def test_a_rank2_value_is_gathered_behind_the_carried_sort(
        launches, compiles_of):
    data = _int_pairs()
    rows, compiles = compiles_of(
        lambda c: c.parallelize(data, 2).map(_vector_pair)
        .reduceByKey(operator.add, 2).collect())
    ref = {}
    for k, v in zip(*data.arrays):
        ref[k.item()] = ref.get(k.item(), 0) + np.array([v, 2 * v])
    assert {k: list(v) for k, v in rows} == {
        k: v.tolist() for k, v in ref.items()}
    assert {p for p, _ in launches} == {"narrow", "exchange", "reduce"}
    for program, text in launches:
        gathers = _column_gathers(text)
        if program == "exchange":       # a rank-2 leaf is sliced too
            assert not gathers and "dynamic_slice" in text, gathers
            continue
        assert gathers, program
        assert all("x2xi64>" in g.rsplit("->", 1)[1] for g in gathers), (
            program, gathers)
    assert {(a["program"], a["sort"]) for a in compiles if "sort" in a} == {
        ("narrow", "carried+gathered"), ("reduce", "carried+gathered")}
    assert _exchange_sends(compiles) == {"slices"}


def test_a_program_that_combines_nothing_says_none(compiles_of):
    n, compiles = compiles_of(
        lambda c: c.parallelize(_int_pairs(), 2).map(_pair).count())
    assert n == 400
    assert [(a["program"], a["combine"], a["sort"]) for a in compiles] == [
        ("narrow", "none", "none")]
