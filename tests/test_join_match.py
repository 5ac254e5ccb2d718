"""The device join's matching (ISSUE 32): one merge sort finds, for every
row of side A, its range of equal keys in side B (collectives.join_ranges),
and a second gives every output slot its pair of rows (join_slots).  The
count program hands the ranges on to the expansion; no program of the join
searches.

The contracts under test:

* MATCHING - against numpy.searchsorted left / right and numpy.repeat, on
  each device of a 1-, 2- and 8-device mesh with rows of its own: duplicate
  keys on both sides, a hot key, disjoint sides, an empty side, all rows
  padding, keys at the ends of int64, capacities that differ, an output
  capacity a class above and below the matches, a two-column key.
* STRUCTURE - an int64 join's two programs lower without a `while` (the
  loop of a binary search), the expansion's first two inputs are the
  ranges, and both programs say so in their `compile` events.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dpark_tpu import Columns, DparkContext, trace
from dpark_tpu.backend.tpu import collectives, layout
from dpark_tpu.backend.tpu.executor import AXIS, _shard_map

I64_MIN = np.iinfo(np.int64).min
SENT = int(layout.KEY_SENTINEL)


@pytest.fixture(autouse=True)
def _x64():
    """int64 keys, as the executor runs them (it turns x64 on when it is
    built; these programs are built without one)."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def _sides(case, rng):
    """(A rows, B rows, cap_a, cap_b, cap_out or None) of one device:
    sorted int64 keys, (n, columns)."""
    def draw(n, lo, hi):
        return np.sort(rng.integers(lo, hi, n)).astype(np.int64)[:, None]

    if case == "duplicates":
        return draw(300, 0, 40), draw(200, 0, 40), 512, 256, None
    if case == "hot_key":
        a = np.sort(np.concatenate([np.full(60, 7), rng.integers(0, 30, 40)]))
        b = np.sort(np.concatenate([np.full(50, 7), rng.integers(0, 30, 30)]))
        return (a.astype(np.int64)[:, None], b.astype(np.int64)[:, None],
                128, 128, None)
    if case == "disjoint":
        return draw(100, 0, 50), draw(80, 50, 90), 128, 128, None
    if case == "empty_a":
        return draw(0, 0, 1), draw(90, 0, 20), 64, 128, None
    if case == "empty_b":
        return draw(90, 0, 20), draw(0, 0, 1), 128, 64, None
    if case == "all_padding":
        return draw(0, 0, 1), draw(0, 0, 1), 64, 32, None
    if case == "int64_ends":
        ends = np.array([I64_MIN, I64_MIN, I64_MIN + 1, -1, 0, SENT - 2,
                         SENT - 1, SENT - 1], np.int64)
        return (np.sort(np.concatenate([ends, ends[:3]]))[:, None],
                ends[:, None], 16, 8, None)
    if case == "caps_differ":
        return draw(1000, 0, 500), draw(30, 0, 500), 1024, 32, None
    if case == "cap_out_above":
        return draw(40, 0, 30), draw(40, 0, 30), 64, 64, 1024
    if case == "cap_out_below":
        return draw(200, 0, 10), draw(100, 0, 10), 256, 128, 64
    if case == "two_columns":
        def pairs(n):
            k = np.sort(rng.integers(0, 400, n))
            return np.stack([k // 20, k % 20], axis=1).astype(np.int64)
        return pairs(300), pairs(250), 512, 256, None
    raise AssertionError(case)


CASES = ["duplicates", "hot_key", "disjoint", "empty_a", "empty_b",
         "all_padding", "int64_ends", "caps_differ", "cap_out_above",
         "cap_out_below", "two_columns"]


def _padded(rows, cap, rng):
    """Rows past the valid ones hold anything: the sentinel on column 0
    is the matching's own to set."""
    junk = rng.integers(-5, 5, (cap - len(rows), rows.shape[1]))
    return np.concatenate([rows, junk.astype(np.int64)])


@pytest.mark.parametrize("ndev", [1, 2, 8])
@pytest.mark.parametrize("case", CASES)
def test_matching_against_numpy(case, ndev):
    rng = np.random.default_rng([CASES.index(case), ndev])
    sides = [_sides(case, rng) for _ in range(ndev)]
    _, _, cap_a, cap_b, cap_out = sides[0]
    want = []
    for A, B, *_ in sides:
        # one scalar a row: exact for the two-column case's small words
        ka = A[:, 0] if A.shape[1] == 1 else A[:, 0] * 1000 + A[:, 1]
        kb = B[:, 0] if B.shape[1] == 1 else B[:, 0] * 1000 + B[:, 1]
        lo = np.searchsorted(kb, ka, "left")
        want.append((lo, np.searchsorted(kb, ka, "right") - lo))
    if cap_out is None:
        cap_out = layout.round_capacity(max(int(p.sum()) for _, p in want))
    mk = sides[0][0].shape[1]
    na = np.array([len(s[0]) for s in sides], np.int32)
    nb = np.array([len(s[1]) for s in sides], np.int32)
    ga = np.stack([_padded(s[0], cap_a, rng) for s in sides])
    gb = np.stack([_padded(s[1], cap_b, rng) for s in sides])

    def per_device(ca, cb, *keys):
        lo, per = collectives.join_ranges(
            [k[0] for k in keys[:mk]], [k[0] for k in keys[mk:]],
            ca[0], cb[0])
        i, bi = collectives.join_slots(lo, per, cap_out, cap_b)
        return tuple(jnp.expand_dims(o, 0)
                     for o in (jnp.sum(per), lo, per, i, bi))

    mesh = Mesh(np.array(jax.devices()[:ndev]), (AXIS,))
    fn = jax.jit(_shard_map(per_device, mesh,
                            in_specs=(P(AXIS),) * (2 + 2 * mk),
                            out_specs=(P(AXIS),) * 5))
    total, lo, per, i, bi = [np.asarray(o) for o in fn(
        na, nb, *[ga[:, :, c] for c in range(mk)],
        *[gb[:, :, c] for c in range(mk)])]

    for d, (want_lo, want_per) in enumerate(want):
        a, n = int(na[d]), int(want_per.sum())
        assert total[d] == n
        assert np.array_equal(lo[d, :a], want_lo)
        assert np.array_equal(per[d, :a], want_per)
        assert not per[d, a:].any()
        rows = np.repeat(np.arange(a), want_per)
        offs = np.cumsum(want_per) - want_per
        n = min(n, cap_out)
        assert np.array_equal(i[d, :n], rows[:n])
        assert np.array_equal(
            bi[d, :n], (want_lo[rows] + np.arange(len(rows)) - offs[rows])[:n])
        # slots past the pairs still name rows that exist
        assert (0 <= i[d]).all() and (i[d] < cap_a).all()
        assert (0 <= bi[d]).all() and (bi[d] < cap_b).all()


def test_join_programs_lower_without_a_search(monkeypatch):
    """An int64 join on one device, ring on from the start: what its two
    programs are built from, read off the lowering and off the ring."""
    rng = np.random.default_rng(32)
    fact = (rng.integers(0, 400, 3000).astype(np.int64),
            rng.integers(0, 9, 3000).astype(np.int64))
    dim = (np.arange(0, 400, 2, dtype=np.int64),
           rng.integers(0, 9, 200).astype(np.int64))
    launched = {}
    trace.configure("ring")
    tctx = DparkContext("tpu:1")        # programs of its own: compiles
    tctx.start()
    try:
        ex = tctx.scheduler.executor
        launch = ex._launch

        def spy(program, fn, *args):
            launched.setdefault(program, (fn, args))
            return launch(program, fn, *args)
        monkeypatch.setattr(ex, "_launch", spy)
        got = tctx.parallelize(Columns(*fact), 1) \
            .join(tctx.parallelize(Columns(*dim), 1), 1).collect()
        events = {e["args"]["program"]: e["args"]
                  for e in trace.snapshot() if e["name"] == "compile"
                  and e["args"]["program"].startswith("join_")}
    finally:
        tctx.stop()
        trace.configure("off")
    even = fact[0] % 2 == 0
    assert len(got) == int(even.sum())
    assert sorted(k for k, _ in got) == sorted(fact[0][even].tolist())

    for program in ("join_count", "join_expand"):
        fn, args = launched[program]
        text = fn.lower(*args).as_text()
        assert "while" not in text, program
        assert "stablehlo.sort" in text, program
    cap_a, cap_b = (x.shape[1] for x in launched["join_count"][1][2:])
    # the expansion: (lo, per) of A's rows, then the two records
    _, args = launched["join_expand"]
    assert len(args) == 2 + 2 + 2
    assert [(x.shape, str(x.dtype)) for x in args[:2]] \
        == [((1, cap_a), "int32")] * 2
    cap_out = layout.round_capacity(len(got))
    assert events == {
        "join_count": {"program": "join_count", "cap_a": cap_a,
                       "cap_b": cap_b, "match": "merge",
                       "ranges": "handed"},
        "join_expand": {"program": "join_expand", "cap_a": cap_a,
                        "cap_b": cap_b, "cap_out": cap_out,
                        "match": "merge", "ranges": "handed"}}
