"""Parity fuzzer (SURVEY.md 7.1 step 11): random RDD programs must
produce identical results on the tpu master and the local master — the
local master is the golden model, whatever path (array or object) the
tpu master picks per stage."""

import operator
import random

import pytest


OPS = ["map_affine", "filter_mod", "map_swap", "reduce_sum", "reduce_min",
       "reduce_max", "group", "group_agg", "sort", "distinct_keys",
       "count_tail", "union_extra", "host_partitions", "join_dim",
       "cartesian_dim", "zip_index", "sample_det", "tuple_key",
       "seg_map"]


def _seg_fns():
    """Traceable per-group functions BEYOND the five provable
    aggregates (the ISSUE 4 SegMapOp shapes) — module-level singletons
    so classification/program caches key stably across contexts.
    Mixed zero-pad (sums) and repeat-pad (order statistics) forms."""
    import jax.numpy as jnp
    return {
        "sumsq": lambda vs: sum(v * v for v in vs),
        "amax": lambda vs: jnp.max(jnp.asarray(vs)),
        "amin": lambda vs: jnp.min(jnp.asarray(vs)),
        "span": lambda vs: jnp.max(jnp.asarray(vs))
        - jnp.min(jnp.asarray(vs)),
        "wsum": lambda vs: 3 * sum(vs) + sum(v * v for v in vs),
    }


_SEG_FN_CACHE = {}


def _seg_fn(kind):
    if not _SEG_FN_CACHE:
        _SEG_FN_CACHE.update(_seg_fns())
    return _SEG_FN_CACHE[kind]


def build_program(rng, depth=4):
    """A random pipeline as a list of (op, params); applied identically
    to both contexts."""
    prog = []
    shuffled = False
    for _ in range(depth):
        op = rng.choice(OPS)
        if op == "map_affine":
            prog.append(("map_affine", rng.randint(1, 5),
                         rng.randint(-10, 10)))
        elif op == "filter_mod":
            prog.append(("filter_mod", rng.randint(2, 5),
                         rng.randint(0, 1)))
        elif op == "map_swap":
            prog.append(("map_swap", rng.randint(1, 7)))
        elif op == "union_extra":
            prog.append(("union_extra", rng.randint(0, 2 ** 30)))
        elif op == "host_partitions":
            # an untraceable op: forces THIS stage onto the object path,
            # exercising the HBM export bridge mid-pipeline
            prog.append(("host_partitions",))
        elif op == "cartesian_dim":
            prog.append(("cartesian_dim", rng.randint(2, 4)))
        elif op == "zip_index":
            # order-sensitive: device shuffles return rows key-sorted
            # while the host object path keeps bucket insertion order —
            # both are valid RDD semantics, so index-dependent ops only
            # fuzz BEFORE the first shuffle
            if not shuffled:
                prog.append(("zip_index",))
        elif op == "sample_det":
            if not shuffled:             # per-row rng: order-sensitive
                prog.append(("sample_det", rng.choice([0.3, 0.6]),
                             rng.randint(1, 10_000)))
        elif op == "join_dim":
            # inner join with a small dim table, values flattened back
            # to ints — exercises the device join source + downstream.
            # A join is a shuffle: row order downstream is unspecified
            prog.append(("join_dim", rng.randint(2, 40),
                         rng.choice([2, 4, 8])))
            shuffled = True
        elif op == "group_agg":
            # groupByKey().mapValues(provable aggregate): rides the
            # device segment-scatter path ("mean" stays out of the fuzz
            # set — float sums reassociate; it has deterministic unit
            # tests in test_seg_groups.py)
            if shuffled and rng.random() < 0.5:
                continue
            prog.append(("group_agg", rng.choice([2, 4, 8]),
                         rng.choice(["sum", "len", "min", "max"])))
            shuffled = True
        elif op == "seg_map":
            # groupByKey().mapValues(traceable non-provable f): the
            # ISSUE 4 SegMapOp shape under random surroundings, over
            # whatever ragged group-size distribution the pipeline
            # produced
            if shuffled and rng.random() < 0.5:
                continue
            prog.append(("seg_map", rng.choice([2, 4, 8]),
                         rng.choice(["sumsq", "amax", "amin", "span",
                                     "wsum"])))
            shuffled = True
        elif op == "tuple_key":
            # composite ((k1, k2), v) keys through a device shuffle
            # (reduce/group/sort), keys flattened back to ints after —
            # the ISSUE 3 tentpole shape under random surroundings
            if shuffled and rng.random() < 0.5:
                continue
            prog.append(("tuple_key", rng.randint(2, 30),
                         rng.randint(2, 7),
                         rng.choice(["sum", "min", "group", "sort"]),
                         rng.choice([2, 4, 8])))
            shuffled = True
        elif op in ("reduce_sum", "reduce_min", "reduce_max", "group",
                    "sort", "distinct_keys"):
            if shuffled and rng.random() < 0.5:
                continue                 # limit chained shuffles a bit
            prog.append((op, rng.choice([2, 4, 8])))
            shuffled = True
    if not prog:
        prog = [("map_affine", 2, 1)]
    return prog


def apply_program(ctx, data, prog):
    r = ctx.parallelize(data, 8)
    for step in prog:
        op = step[0]
        if op == "map_affine":
            _, a, b = step
            r = r.map(lambda kv, a=a, b=b: (kv[0], kv[1] * a + b))
        elif op == "filter_mod":
            _, m, want = step
            r = r.filter(lambda kv, m=m, w=want: kv[0] % m == w)
        elif op == "map_swap":
            _, m = step
            r = r.map(lambda kv, m=m: (kv[1] % m, kv[0]))
        elif op == "reduce_sum":
            r = r.reduceByKey(operator.add, step[1])
        elif op == "reduce_min":
            r = r.reduceByKey(lambda a, b: a if a < b else b, step[1])
        elif op == "reduce_max":
            r = r.reduceByKey(lambda a, b: a if a > b else b, step[1])
        elif op == "group":
            r = r.groupByKey(step[1]) \
                 .mapValue(lambda vs: sum(vs) if isinstance(vs, list)
                           else vs)
        elif op == "group_agg":
            f = {"sum": sum, "len": len, "min": min, "max": max}[step[2]]
            r = r.groupByKey(step[1]).mapValues(f)
        elif op == "seg_map":
            r = r.groupByKey(step[1]).mapValues(_seg_fn(step[2]))
        elif op == "sort":
            r = r.sortByKey(numSplits=step[1])
        elif op == "distinct_keys":
            r = r.map(lambda kv: (kv[0], 0)).reduceByKey(
                lambda a, b: 0, step[1])
        elif op == "union_extra":
            seed2 = step[1]
            extra = [((seed2 + i) % 97, i % 13) for i in range(64)]
            r = r.union(ctx.parallelize(extra, 8))
        elif op == "host_partitions":
            r = r.mapPartitions(lambda it: list(it))
        elif op == "cartesian_dim":
            _, m = step
            dim = [(i, i + 1) for i in range(m)]
            r = (r.cartesian(ctx.parallelize(dim, 2))
                 .map(lambda ab: (ab[0][0] + ab[1][0],
                                  ab[0][1] + ab[1][1])))
        elif op == "zip_index":
            # zipWithIndex depends on partition layout, which the two
            # masters share for identical programs; fold the index in
            r = r.zipWithIndex().map(
                lambda kvi: (kvi[0][0], kvi[0][1] + kvi[1] % 13))
        elif op == "sample_det":
            _, frac, sseed = step
            r = r.sample(False, frac, sseed)
        elif op == "join_dim":
            _, ksp, nsp = step
            dim = [(i - ksp // 2, i * 3 + 1) for i in range(ksp)]
            r = (r.map(lambda kv, m=ksp: (kv[0] % m - m // 2, kv[1]))
                 .join(ctx.parallelize(dim, 8), nsp)
                 .map(lambda kv: (kv[0], kv[1][0] + kv[1][1])))
        elif op == "tuple_key":
            _, m, p, red, nsp = step
            r = r.map(lambda kv, m=m, p=p:
                      ((kv[0] % m - m // 2, kv[1] % p), kv[1]))
            if red == "sum":
                r = r.reduceByKey(operator.add, nsp)
            elif red == "min":
                r = r.reduceByKey(lambda a, b: a if a < b else b, nsp)
            elif red == "group":
                r = r.groupByKey(nsp).mapValues(len)
            else:
                r = r.sortByKey(numSplits=nsp)
            # flatten the tuple key back to a collision-free int so any
            # downstream op keeps its (int, int) record contract
            # (column 2 is bounded by p <= 7 < 37)
            r = r.map(lambda kv: (kv[0][0] * 37 + kv[0][1], kv[1]))
    return r


def canonical(rows):
    return sorted((int(k), int(v)) for k, v in rows)


@pytest.mark.parametrize("seed", range(20))
@pytest.mark.mesh
def test_random_program_parity(seed):
    from dpark_tpu import DparkContext
    rng = random.Random(seed)
    n = rng.choice([100, 1000, 4096])
    kspace = rng.choice([3, 17, 256, 10_000])
    data = [(rng.randint(-kspace, kspace), rng.randint(-1000, 1000))
            for _ in range(n)]
    prog = build_program(rng)

    tctx = DparkContext("tpu")
    lctx = DparkContext("local")
    try:
        rt = apply_program(tctx, data, prog)
        rl = apply_program(lctx, data, prog)
        got = canonical(rt.collect())
        expect = canonical(rl.collect())
        assert got == expect, "parity violation for program %r" % (prog,)
        # ACTIONS too: count (device counts leaf) and monoid reduce
        # (per-device reduction) must agree with the local master
        assert rt.count() == rl.count() == len(expect), prog
        if expect:
            va = rt.map(lambda kv: kv[1]).reduce(operator.add)
            vb = rl.map(lambda kv: kv[1]).reduce(operator.add)
            if isinstance(va, float) or isinstance(vb, float):
                # device reduce answers from per-device reductions;
                # float summation order differs from the host fold —
                # compare with a tolerance (ADVICE r4)
                import math
                assert math.isclose(va, vb, rel_tol=1e-9,
                                    abs_tol=1e-9), prog
            else:
                assert va == vb, prog
    finally:
        tctx.stop()
        lctx.stop()


def _text_chain(ctx, path, prog, splitSize):
    r = ctx.textFile(path, splitSize=splitSize)
    kind = prog[0]
    if kind == "canonical":
        r = r.flatMap(lambda line: line.split()).map(lambda w: (w, 1))
    elif kind == "lengths":
        r = r.flatMap(lambda line: [(w[:2], len(w))
                                    for w in line.split()])
    else:                       # int keys
        r = r.map(lambda l, m=prog[1]: (len(l) % m, 1))
    red = prog[-1]
    if red == "sum":
        return r.reduceByKey(lambda a, b: a + b, 4)
    if red == "max":
        return r.reduceByKey(lambda a, b: max(a, b), 4)
    return r.groupByKey(4).mapValue(
        lambda vs: sum(vs) if isinstance(vs, list) else vs)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.mesh
def test_text_chain_parity(seed, tmp_path):
    """Random text-source chains: host-prologue ingest + encode +
    device shuffle == local object path, across split layouts."""
    from dpark_tpu import DparkContext
    rng = random.Random(1000 + seed)
    words = ["w%d" % i for i in range(rng.choice([5, 40, 300]))]
    p = str(tmp_path / "fuzz.txt")
    with open(p, "w") as f:
        for _ in range(rng.randint(200, 2000)):
            f.write(" ".join(rng.choices(words,
                                         k=rng.randint(1, 9))) + "\n")
    prog = (rng.choice([("canonical",), ("lengths",),
                        ("intkey", rng.randint(2, 9))])
            + (rng.choice(["sum", "max", "group"]),))
    splitSize = rng.choice([1000, 7000, None])

    tctx = DparkContext("tpu")
    lctx = DparkContext("local")
    try:
        got = sorted(_text_chain(tctx, p, prog, splitSize).collect())
        expect = sorted(_text_chain(lctx, p, prog, splitSize).collect())
        assert got == expect, "parity violation for %r" % (prog,)
    finally:
        tctx.stop()
        lctx.stop()


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.mesh
def test_forced_ooc_columnar_parity(seed):
    """Tiny forced wave sizes push random columnar programs through the
    streamed OOC shuffle paths — in-core results and streamed results
    must be indistinguishable, and both must match the local master
    (VERDICT r4 #9: forced OOC chunk sizes in the fuzzer)."""
    import numpy as np
    from dpark_tpu import Columns, DparkContext
    from dpark_tpu import conf
    rng = random.Random(500 + seed)
    n = 30_000
    kspace = rng.choice([17, 301, 4096])
    keys = np.asarray([rng.randrange(kspace) for _ in range(n)],
                      np.int64)
    vals = np.asarray([rng.randint(-50, 50) for _ in range(n)],
                      np.int64)
    red = rng.choice(["sum", "max", "group", "sort", "segmap"])
    nsp = rng.choice([4, 8, 16])        # 16 > mesh: spilled-run stream
    old = conf.STREAM_CHUNK_ROWS
    conf.STREAM_CHUNK_ROWS = 2048       # force multi-wave streaming
    try:
        outs = []
        for master in ("tpu", "local"):
            c = DparkContext(master)
            c.start()
            try:
                r = c.parallelize(Columns(keys, vals), 8)
                if red == "sum":
                    r = r.reduceByKey(operator.add, nsp)
                elif red == "max":
                    r = r.reduceByKey(lambda a, b: max(a, b), nsp)
                elif red == "group":
                    r = r.groupByKey(nsp).mapValues(sum)
                elif red == "segmap":
                    # forced-OOC waves feeding the segmented apply:
                    # the spilled no-combine runs load back as a
                    # device batch (executor._seg_batch_from_runs)
                    r = r.groupByKey(nsp).mapValues(_seg_fn("sumsq"))
                else:
                    r = r.sortByKey(numSplits=nsp)
                got = r.collect()
                if red == "sort":
                    # equal-key value order is unspecified (stable on
                    # the host, exchange-order on device): assert the
                    # key order, compare the multiset
                    ks = [k for k, _ in got]
                    assert ks == sorted(ks), (master, seed)
                outs.append(sorted(got))
                if master == "tpu" and red != "sort":
                    from tests.conftest import shuffled_on_device
                    assert shuffled_on_device(c), \
                        "did not ride the device"
            finally:
                c.stop()
        assert outs[0] == outs[1], (seed, red, nsp)
    finally:
        conf.STREAM_CHUNK_ROWS = old


def test_tuple_value_reduce_minmax_parity():
    """Satellite regression (r5 advisor, high): a classified monoid
    (min/max) over MULTI-LEAF values must not ride the per-leaf device
    monoid path — the host merges whole records (tuples compare
    lexicographically) while per-leaf reduction mixes leaves from
    different records.  _epilogue_merge now degrades such plans to the
    raw-combiner exchange; results must match the local golden master
    exactly.  A 2-device mesh keeps the map-side bucketize-combine +
    exchange machinery engaged without needing the full virtual mesh."""
    from dpark_tpu import DparkContext

    rng = random.Random(99)
    data = [(rng.randint(0, 20),
             (rng.randint(0, 1000), rng.randint(0, 1000)))
            for _ in range(4000)]

    tctx = DparkContext("tpu:2")
    lctx = DparkContext("local")
    try:
        for fn in (lambda a, b: max(a, b),
                   lambda a, b: min(a, b)):
            rt = sorted(tctx.parallelize(data, 2)
                        .reduceByKey(fn, 2).collect())
            rl = sorted(lctx.parallelize(data, 2)
                        .reduceByKey(fn, 2).collect())
            assert rt == rl, (rt[:3], rl[:3])
    finally:
        tctx.stop()
        lctx.stop()


def test_tuple_key_parity_small_mesh():
    """Composite (tuple) keys on a 2-device mesh (runs on any box, no
    full-mesh marker): reduce/group/sort/join over ((k1, k2), v)
    records match the local golden model exactly, and the shuffle rode
    the device (ISSUE 3 tentpole, fuzzed deterministic shapes)."""
    from dpark_tpu import DparkContext

    rng = random.Random(42)
    data = [((rng.randint(0, 15), rng.randint(-4, 4)),
             rng.randint(-500, 500)) for _ in range(3000)]
    dim = [((rng.randint(0, 15), rng.randint(-4, 4)),
            rng.randint(0, 99)) for _ in range(400)]

    tctx = DparkContext("tpu:2")
    lctx = DparkContext("local")
    tctx.start()
    try:
        def both(make):
            return (sorted(make(tctx)), sorted(make(lctx)))

        got, exp = both(lambda c: c.parallelize(data, 2)
                        .reduceByKey(operator.add, 2).collect())
        assert got == exp
        from tests.conftest import shuffled_on_device
        assert shuffled_on_device(tctx), \
            "tuple-key reduce did not ride the device"
        got, exp = both(lambda c: [
            (k, sorted(v)) for k, v in
            c.parallelize(data, 2).groupByKey(2).collect()])
        assert got == exp
        st = tctx.parallelize(data, 2).sortByKey(numSplits=2).collect()
        sl = lctx.parallelize(data, 2).sortByKey(numSplits=2).collect()
        assert [k for k, _ in st] == [k for k, _ in sl]
        got, exp = both(lambda c: c.parallelize(data, 2)
                        .join(c.parallelize(dim, 2), 2).collect())
        assert got == exp
    finally:
        tctx.stop()
        lctx.stop()


def test_monoid_multileaf_lint_rule_matches_executor_guard():
    """The monoid-multileaf lint rule is the pre-flight twin of the
    _epilogue_merge guard: the exact plan shape the guard degrades is
    the shape the rule flags."""
    from dpark_tpu import DparkContext
    from dpark_tpu.analysis import lint_plan

    ctx = DparkContext("local")
    try:
        r = ctx.parallelize(
            [(1, (2, 3)), (1, (5, 1)), (2, (7, 8))], 2) \
            .reduceByKey(lambda a, b: max(a, b), 2)
        assert any(f.rule == "monoid-multileaf" for f in lint_plan(r))
    finally:
        ctx.stop()
