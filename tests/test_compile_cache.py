"""Compiled stage programs persist across processes through JAX's own
compilation cache, and nothing else.

Two fresh processes run the same chains, one after the other, over one
cache directory: one chain for the shape of each benchmark cell, at toy
size on `tpu:1`.  The second process must find every program the first
one compiled: at least one persistent-cache hit a chain, no miss, and
the same answers.  A miss means a program's cache key is not stable
across processes, and a fresh process on the chip pays that compile in
its set-up.  The two processes run under different PYTHONHASHSEEDs, so
a key that hangs on a string hash misses here too.

The cache is turned on for the CPU inside the child processes only
(thresholds at zero, so every program is written); the executor itself
places it on device backends alone (executor._place_compile_cache).

    python tests/test_compile_cache.py <cache dir> <out.json>

runs one child by hand."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# ----------------------------------------------------------------------
# the chains: each builds its data from a fixed seed and returns an
# answer that compares with ==
# ----------------------------------------------------------------------

def _ident(r):
    return r


def _prefix_16(kv):
    return (kv[0] >> 16, kv[1])


def _q2c(r):
    return (r[0][:12], r[1])


def _in_dates(r):
    return (r[2] >= 100) & (r[2] <= 300)


def _by_url(r):
    return (r[0], (r[1], r[3]))


def _by_ip(kv):
    return (kv[1][0][0], (kv[1][1], 1, kv[1][0][1]))


def _add3(a, b):
    return (a[0] + b[0], a[1] + b[1], a[2] + b[2])


def _revenue(kv):
    return kv[1][2]


def _in_sample(kv):
    return kv[0][9] % 16 == 7


def _resident(ctx, *cols):
    from dpark_tpu import Columns
    rdd = ctx.parallelize(Columns(*cols), 1).map(_ident).cache()
    assert rdd.count() == len(cols[0])
    return rdd


def _bytes(rng, n, width):
    return rng.integers(48, 58, (n, width), dtype=np.uint8) \
        .view("S%d" % width)[:, 0]


def chain_lowcard(ctx):
    import chip_smoke as cs
    keys, vals = cs.make_pairs(1, 20_000, 1 << 32)
    rdd = _resident(ctx, keys, vals)
    return sorted(rdd.map(_prefix_16).reduceByKey(cs.add, 1).collect())


def chain_highcard(ctx):
    import chip_smoke as cs
    keys, vals = cs.make_pairs(2, 50_000, 1 << 32)
    return _resident(ctx, keys, vals).reduceByKey(cs.add, 1).count()


def chain_join(ctx):
    import chip_smoke as cs
    fk, fv, dk, dv = cs.make_join(3, 12_000, 1500)
    dim, fact = _resident(ctx, dk, dv), _resident(ctx, fk, fv)
    return sorted(fact.join(dim, 1).map(cs._join_to_group)
                  .reduceByKey(cs.add, 1).collect())


def chain_uservisits_agg(ctx):
    import chip_smoke as cs
    rng = np.random.default_rng(4)
    ip = _bytes(rng, 8000, 16)
    revenue = rng.random(8000, np.float32)
    rdd = _resident(ctx, ip, revenue)
    return sorted(rdd.map(_q2c).reduceByKey(cs.add, 1).collect())


def chain_uservisits_join(ctx):
    rng = np.random.default_rng(5)
    urls = _bytes(rng, 600, 100)
    ranks = rng.integers(1, 1000, 600).astype(np.int32)
    n = 6000
    visits = _resident(
        ctx, urls[rng.integers(0, 600, n)], _bytes(rng, n, 16),
        rng.integers(0, 400, n).astype(np.int32),
        rng.random(n, np.float32))
    pages = _resident(ctx, urls, ranks)
    return visits.filter(_in_dates).map(_by_url).join(pages) \
        .map(_by_ip).reduceByKey(_add3, 1).top(1, key=_revenue)


def chain_sort(ctx):
    rng = np.random.default_rng(6)
    n = 5000
    rdd = _resident(ctx, rng.integers(0, 256, (n, 10), dtype=np.uint8)
                    .view("S10")[:, 0], _bytes(rng, n, 90))
    return rdd.sortByKey(numSplits=1).filter(_in_sample).collect()


def chain_pagerank(ctx):
    import chip_smoke as cs
    from dpark_tpu.bagel import PregelGraph
    ids, src, dst = cs.make_graph(7, 2048, cs.PR_DEGREE)
    n = len(ids)

    def compute(value, msg, has_msg, active, agg, superstep):
        is0 = superstep == 0
        new = is0 * value + (1 - is0) * ((1 - cs.PR_DAMPING) / n
                                         + cs.PR_DAMPING * msg)
        return new, superstep < cs.PR_STEPS

    def send(src_value, edge_value, src_degree):
        return src_value / src_degree

    ctx.scheduler._pregel_device_used = None
    out_ids, ranks, _ = PregelGraph(ctx, ids, (src, dst)).run(
        np.full(n, 1.0 / n, np.float32), compute, send, combine="add")
    assert ctx.scheduler._pregel_device_used is True
    return [out_ids.tolist(), np.asarray(ranks).tolist()]


def chain_q1(ctx):
    import test_query_resident as qr
    table = qr.resident_table(ctx, qr.lineitem(8, 3000))
    return [tuple(r) for r in
            ctx.sql(qr.Q1 % qr.CUTOFF, lineitem=table).collect()]


# benchmark cell -> the chain of its shape
CHAINS = {
    "agg.lowcard": chain_lowcard,
    "agg.highcard": chain_highcard,
    "join.resident": chain_join,
    "uservisits.agg": chain_uservisits_agg,
    "uservisits.join": chain_uservisits_join,
    "sort.4chip": chain_sort,
    "pagerank.graph500": chain_pagerank,
    "tpch.q1": chain_q1,
}


# ----------------------------------------------------------------------
# one child process
# ----------------------------------------------------------------------

def child(cache_dir, out_path):
    """Run every chain in CHAINS on `tpu:1` with the persistent cache at
    `cache_dir`, and write {chain: {answer, hits, misses, kinds,
    reasons}}: the persistent cache's hits and misses while the chain
    ran, and its stages' kinds and fallback or degrade reasons."""
    import jax
    from jax import monitoring
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counts = {"hits": 0, "misses": 0}

    def on_event(event, **kw):
        for name in counts:
            if str(event) == "/jax/compilation_cache/cache_" + name:
                counts[name] += 1

    monitoring.register_event_listener(on_event)
    sys.path[:0] = [ROOT, HERE]
    from dpark_tpu import DparkContext
    ctx = DparkContext("tpu:1")
    ctx.start()
    out = {}
    try:
        for name, chain in CHAINS.items():
            before = dict(counts)
            h0 = len(ctx.scheduler.history)
            answer = chain(ctx)
            stages = [st for rec in ctx.scheduler.history[h0:]
                      for st in rec["stage_info"]]
            out[name] = {
                "answer": repr(answer),
                "hits": counts["hits"] - before["hits"],
                "misses": counts["misses"] - before["misses"],
                "kinds": sorted({str(st.get("kind")) for st in stages}),
                "reasons": [r for st in stages
                            for r in (st.get("fallback_reason"),
                                      st.get("degrade_reason")) if r]}
    finally:
        ctx.stop()
    with open(out_path, "w") as f:
        json.dump(out, f)


# ----------------------------------------------------------------------
# the tests
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def two_runs(tmp_path_factory):
    """The first and the second fresh process's records, over one
    cache directory."""
    base = tmp_path_factory.mktemp("compile_cache")
    cache = str(base / "jax_cache")
    runs = []
    for i in (1, 2):
        out = str(base / ("run%d.json" % i))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=str(i),
                   DPARK_WORK_DIR=str(base / ("work%d" % i)))
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), cache, out],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=240)
        assert proc.returncode == 0, proc.stderr[-4000:]
        with open(out) as f:
            runs.append(json.load(f))
    return runs


@pytest.mark.parametrize("chain", list(CHAINS))
def test_a_fresh_process_compiles_nothing_the_last_one_did(two_runs,
                                                           chain):
    first, second = two_runs[0][chain], two_runs[1][chain]
    assert second["answer"] == first["answer"]
    assert second["hits"] >= 1, second
    assert second["misses"] == 0, (first, second)
    if chain != "pagerank.graph500":
        assert second["kinds"] and all(
            k.startswith("array") for k in second["kinds"]), second
    assert not second["reasons"], second


if __name__ == "__main__":
    child(sys.argv[1], sys.argv[2])
