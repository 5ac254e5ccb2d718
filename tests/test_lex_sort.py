"""`_lex_sort` carries its operands through the sort (ISSUE 29).

One stable multi-operand `lax.sort` per ordering: the keys and every
rank-1 operand ride it, a leaf of rank > 1 is gathered through an iota
that rides it too.  The reference kept here is the form it replaced:
one stable argsort per key column, last to first, the permutations
composed and every operand pulled through the result (numpy).

The contracts under test:

* ORDER — bool / int32 / uint32 / int64 / float32 keys (with `-0.0`
  and `inf`), 0 to 3 of them, ties broken by input order, a rank-2
  payload beside scalar ones: every output equal to the reference's bit
  for bit, dtypes kept.
* SENTINELS — rows whose first key is the dtype's sentinel sort last.
* BUCKETIZE — against `np.argsort(dst, kind="stable")`.
* ONE SORT — the lowered module holds one `sort`; no `gather` unless a
  leaf of rank > 1 rides, and then only that leaf's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dpark_tpu.backend.tpu import collectives

N = 96
KEY_DTYPES = ("bool", "int32", "uint32", "int64", "float32")


@pytest.fixture(autouse=True)
def _x64():
    """Device compute is 64-bit: the executor turns it on with its
    first context, these tests for the functions they call bare."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def _compose_and_gather(ops, num_keys):
    """The reference: today's answer by yesterday's route."""
    order = None
    for k in range(num_keys - 1, -1, -1):
        key = ops[k] if order is None else ops[k][order]
        perm = np.argsort(key, kind="stable")
        order = perm if order is None else order[perm]
    return list(ops) if order is None else [o[order] for o in ops]


def _key(dtype, rng, j):
    """A key column with ties: each value about N / 5 times; column j of
    several is drawn apart from the others."""
    if dtype == "bool":
        return rng.random(N) < 0.4 + 0.1 * j
    if dtype == "float32":
        return rng.choice(np.array([-np.inf, -1.5, -0.0, 0.0, 2.25, np.inf],
                                   np.float32), N)
    info = np.iinfo(dtype)
    return rng.choice(np.array([info.min, info.min + 1, 0, 7, info.max - 1],
                               dtype), N)


def _bits(a):
    """An array as the bits it holds: -0.0 is not 0.0 here."""
    a = np.asarray(a)
    return a.view("u%d" % a.dtype.itemsize) if a.dtype.kind == "f" else a


def _assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert (_bits(g) == _bits(w)).all()


@pytest.mark.parametrize("wide", (False, True), ids=("scalar", "rank2"))
@pytest.mark.parametrize("num_keys", (0, 1, 2, 3))
@pytest.mark.parametrize("dtype", KEY_DTYPES)
def test_lex_sort_matches_compose_and_gather(dtype, num_keys, wide):
    rng = np.random.default_rng(
        1000 * KEY_DTYPES.index(dtype) + 10 * num_keys + wide)
    ops = [_key(dtype, rng, j) for j in range(max(num_keys, 1))]
    ops.append(np.arange(N, dtype=np.int64))    # a tie keeps input order
    if wide:
        ops.append(rng.integers(-9, 9, (N, 3)).astype(np.int32))
    ops.append(rng.random(N).astype(np.float32))
    got = jax.jit(collectives._lex_sort, static_argnums=1)(
        tuple(ops), num_keys)
    assert isinstance(got, tuple)
    _assert_same(got, _compose_and_gather(ops, num_keys))


@pytest.mark.parametrize("dtype", ("int32", "uint32", "int64", "float32"))
def test_sentinel_rows_sort_last(dtype):
    rng = np.random.default_rng(len(dtype))
    sentinel = np.asarray(collectives._sentinel(dtype))
    key = _key(dtype, rng, 0)
    key = np.where(key == sentinel, np.zeros((), dtype), key)
    pad = rng.random(N) < 0.3
    key = np.where(pad, sentinel, key).astype(dtype)
    rows = np.arange(N, dtype=np.int32)
    k, r = collectives._lex_sort((jnp.asarray(key), jnp.asarray(rows)), 1)
    live = int((~pad).sum())
    assert (np.asarray(k)[live:] == sentinel).all()
    assert (np.asarray(k)[:live] != sentinel).all()
    assert np.asarray(r)[live:].tolist() == rows[pad].tolist()
    _assert_same((k, r), _compose_and_gather([key, rows], 1))


@pytest.mark.parametrize("wide", (False, True), ids=("scalar", "rank2"))
@pytest.mark.parametrize("n_dst", (1, 4, 32))
def test_bucketize_matches_a_stable_argsort(n_dst, wide):
    rng = np.random.default_rng(n_dst + wide)
    n = N - 20
    key = rng.integers(-50, 50, N)
    dst = np.where(np.arange(N) < n, key % n_dst, n_dst).astype(np.int32)
    leaves = [key, rng.random(N).astype(np.float32)]
    if wide:
        leaves.append(rng.integers(0, 9, (N, 2)))
    got, counts, offsets = jax.jit(
        lambda d, *lv: collectives.bucketize(lv[0], list(lv), n, n_dst,
                                             dst=d))(dst, *leaves)
    order = np.argsort(dst, kind="stable")
    _assert_same(got, [leaf[order] for leaf in leaves])
    want = np.bincount(dst, minlength=n_dst + 1)[:n_dst]
    assert counts.dtype == offsets.dtype == jnp.int32
    assert counts.tolist() == want.tolist()
    assert offsets.tolist() == (np.cumsum(want) - want).tolist()


@pytest.mark.parametrize("num_keys", (1, 2, 3, 4))
def test_scalar_operands_lower_to_one_sort_and_no_gather(num_keys):
    ops = (jnp.zeros(N, jnp.int32), jnp.zeros(N, jnp.int64),
           jnp.zeros(N, bool), jnp.zeros(N, jnp.float32),
           jnp.zeros(N, jnp.int64))
    text = jax.jit(collectives._lex_sort, static_argnums=1).lower(
        ops, num_keys).as_text()
    assert text.count("stablehlo.sort") == 1
    assert "gather" not in text


def test_a_rank2_leaf_is_the_only_operand_gathered():
    ops = (jnp.zeros(N, jnp.int64), jnp.zeros((N, 3), jnp.float32),
           jnp.zeros(N, jnp.int64))
    text = jax.jit(collectives._lex_sort, static_argnums=1).lower(
        ops, 1).as_text()
    assert text.count("stablehlo.sort") == 1
    gathers = [line for line in text.splitlines()
               if "stablehlo.gather" in line]
    assert len(gathers) == 1 and "x3xf32" in gathers[0]


# -- wide rows (ISSUE 31): past _CARRIED_WORDS the keys and an iota ride
# the sort and the rest of the row follows as whole rows --------------

def _wide_row(rng, with_rank2):
    """A row of 13 + 2 int64 words, a float32, a bool and an int32 (the
    query-3 visit and then some), and optionally a rank-2 leaf."""
    ops = [rng.integers(-2 ** 62, 2 ** 62, N) for _ in range(15)]
    ops.append(rng.random(N).astype(np.float32) - np.float32(0.5))
    ops.append(rng.random(N) < 0.5)
    ops.append(rng.integers(-9, 9, N).astype(np.int32))
    if with_rank2:
        ops.insert(3, rng.integers(0, 9, (N, 2)))
    return ops


@pytest.mark.parametrize("with_rank2", (False, True),
                         ids=("scalar", "rank2"))
@pytest.mark.parametrize("num_keys", (1, 2))
@pytest.mark.parametrize("dtype", KEY_DTYPES)
def test_a_wide_row_follows_its_keys(dtype, num_keys, with_rank2):
    rng = np.random.default_rng(
        77 + 10 * KEY_DTYPES.index(dtype) + num_keys)
    ops = [_key(dtype, rng, j) for j in range(num_keys)] \
        + _wide_row(rng, with_rank2)
    assert sum(collectives._words(jnp.asarray(o)) for o in ops
               if o.ndim == 1) > collectives._CARRIED_WORDS
    got = jax.jit(collectives._lex_sort, static_argnums=1)(
        tuple(ops), num_keys)
    _assert_same(got, _compose_and_gather(ops, num_keys))


def test_a_wide_row_lowers_to_a_keys_sort_and_row_gathers():
    ops = (jnp.zeros(N, jnp.int32),) + tuple(
        jnp.zeros(N, jnp.int64) for _ in range(15)) \
        + (jnp.zeros(N, jnp.float32),)
    text = jax.jit(collectives._lex_sort, static_argnums=1).lower(
        ops, 1).as_text()
    sorts = [line for line in text.splitlines() if "stablehlo.sort" in line]
    assert len(sorts) == 1 and sorts[0].count("tensor<") <= 6  # key, iota
    gathers = [line for line in text.splitlines()
               if "stablehlo.gather" in line]
    # 31 words: whole rows, collectives._ROW_WORDS words at a time
    assert len(gathers) == 2
    assert "x16xui32" in gathers[0] and "x15xui32" in gathers[1]


def test_the_cells_rows_stay_carried():
    """The widest sort of the accepted cells (uservisits.agg's map order
    sort: dst, hash, two key words, a float32) is 7 words: one sort, no
    gather, as PR 29 left it."""
    ops = (jnp.zeros(N, jnp.int32), jnp.zeros(N, jnp.uint32),
           jnp.zeros(N, jnp.int64), jnp.zeros(N, jnp.int64),
           jnp.zeros(N, jnp.float32))
    assert sum(collectives._words(o) for o in ops) \
        <= collectives._CARRIED_WORDS
    text = jax.jit(collectives._lex_sort, static_argnums=1).lower(
        ops, 2).as_text()
    assert text.count("stablehlo.sort") == 1 and "gather" not in text


@pytest.mark.parametrize("kind", ("few", "many", "not_words"))
def test_take_rows_by_an_index_that_repeats(kind):
    """Whole rows, collectives._ROW_WORDS words a gather (33: three); a
    gather a column for a few columns that are not whole words."""
    rng = np.random.default_rng(5 + len(kind))
    cols = {"few": [rng.integers(-9, 9, N),
                    rng.random(N).astype(np.float32)],
            "many": _wide_row(rng, False),
            "not_words": [rng.integers(-9, 9, N), rng.random(N) < 0.5,
                          rng.integers(-9, 9, N).astype(np.int16),
                          rng.integers(0, 9, (N, 2))]}[kind]
    idx = rng.integers(0, N, 2 * N).astype(np.int32)
    fn = jax.jit(collectives.take_rows)
    _assert_same(fn(cols, idx), [c[idx] for c in cols])
    gathers = [line for line in fn.lower(cols, idx).as_text().splitlines()
               if "stablehlo.gather" in line]
    assert len(gathers) == {"few": 1, "many": 3, "not_words": 4}[kind]
