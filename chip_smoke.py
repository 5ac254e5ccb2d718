#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that dpark_tpu still starts on the chip.

Drives the `tpu` master once through the entry points a user calls
(`DparkContext("tpu")`, `DparkContext("service:tpu")`, `bagel.run_pregel`)
at job sizes, on every local device, and compares each answer with a plain
numpy reference that shares no code with dpark_tpu.  One process; it exits
non-zero unless `jax.devices()[0].platform == "tpu"`, and on the first
failed check.  The last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Walls and compile counts printed on the way are a log, not metrics.

    python chip_smoke.py [--seed N] [--scale K]

`--scale K` divides every size by K (rehearsals, and a four-chip run
on a short budget); the run says so on its `[size]` lines.
"""

import argparse
import itertools
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)    # import dpark_tpu from the checkout

# Sizes per chip.  CUT from ISSUE 21's table to fit the 1200 s contract
# with a cold compile cache: on the v5e the TPU compiler takes 17-48 s
# over EVERY sort op at any size (~460 s of a 745 s run is compile), so the
# first job runs at the issue's floor and PageRank at half the table's
# edges.  At the table's sizes the same jobs passed in 1330 s + the
# service job (PR 21, smoke log).
N_KEYS = 65_536                 # reduceByKey key domain
PAIRS_PER_CHIP = 1 << 24        # 16M i64 pairs = 256 MiB (table: 64M)
WAVE_ROWS = 2 << 20             # pinned wave: 16M / 2M = 8 waves
SORT_PER_CHIP = 1 << 24         # 16M (table: 16M)
JOIN_FACT_PER_CHIP = 1 << 24    # 16M (table: 16M)
JOIN_DIM_PER_CHIP = 1 << 20     # 1M (table: 1M)
JOIN_GROUPS = 1024
PR_VERTICES = 1 << 20           # 1M (table: 1M)
PR_DEGREE = 8                   # 8M edges (table: 16M)
PR_STEPS = 10
PR_DAMPING = 0.85
# the resident server runs job 1's size, so its first submit finds
# job 1's programs in the persistent cache instead of recompiling them
SERVICE_PER_CHIP = PAIRS_PER_CHIP


class SmokeFailure(AssertionError):
    """A check failed; main() lets it end the process."""


def add(a, b):
    return a + b


def log(msg):
    print(msg, flush=True)


# ----------------------------------------------------------------------
# compile accounting: a listener of this script's own (the trace plane's
# listener records nothing while DPARK_TRACE=off)
# ----------------------------------------------------------------------

class CompileCounter:
    """Counts jax compile requests (the backend_compile_duration event
    fires for each, persistent-cache hit or not) and persistent-cache
    hits; requests - hits = programs the backend really compiled."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0

    def install(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **kw):
        if str(event).endswith("backend_compile_duration"):
            self.requests += 1
            self.seconds += float(duration)

    def _event(self, event, **kw):
        if str(event).endswith("/compilation_cache/cache_hits"):
            self.hits += 1

    def snapshot(self):
        return (self.requests, self.hits, self.seconds)

    def since(self, snap):
        req = self.requests - snap[0]
        hits = self.hits - snap[1]
        return {"requests": req, "cache_hits": hits,
                "compiled": req - hits,
                "seconds": round(self.seconds - snap[2], 1)}


# ----------------------------------------------------------------------
# data, from the seed, with numpy
# ----------------------------------------------------------------------

def make_pairs(seed, n, n_keys):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n, dtype=np.int64)
    vals = rng.integers(0, 1 << 16, n, dtype=np.int64)
    return keys, vals


def make_join(seed, n_fact, n_dim):
    """Fact keys from [0, 2*n_dim): about half find a dimension row."""
    rng = np.random.default_rng(seed)
    dim_k = rng.permutation(2 * n_dim)[:n_dim].astype(np.int64)
    dim_v = rng.integers(1, 100, n_dim, dtype=np.int64)
    fact_k = rng.integers(0, 2 * n_dim, n_fact, dtype=np.int64)
    fact_v = rng.integers(0, 1 << 16, n_fact, dtype=np.int64)
    return fact_k, fact_v, dim_k, dim_v


def make_graph(seed, n_vertices, degree):
    """Constant out-degree, uniform targets (examples/pagerank.py's ring
    with chords, at size)."""
    rng = np.random.default_rng(seed)
    ids = np.arange(n_vertices, dtype=np.int64)
    src = np.repeat(ids, degree)
    dst = rng.integers(0, n_vertices, n_vertices * degree, dtype=np.int64)
    return ids, src, dst


# ----------------------------------------------------------------------
# plain references (numpy / dict only)
# ----------------------------------------------------------------------

def ref_reduce(keys, vals, n_keys):
    """(present keys ascending, their sums)."""
    sums = np.zeros(n_keys, np.int64)
    np.add.at(sums, keys, vals)
    present = np.flatnonzero(np.bincount(keys, minlength=n_keys))
    return present, sums[present]


def ref_sort_packed(keys, vals):
    """The sorted multiset of pairs, each packed into one int64."""
    return np.sort((keys << 16) | vals)


def ref_join_reduce(fact_k, fact_v, dim_k, dim_v, groups):
    """dict join, then sum(fact_v * dim_v) by key % groups."""
    lookup = dict(zip(dim_k.tolist(), dim_v.tolist()))
    dv = np.fromiter(map(lookup.get, fact_k.tolist(),
                         itertools.repeat(-1)), np.int64, len(fact_k))
    hit = dv >= 0
    sums = np.zeros(groups, np.int64)
    np.add.at(sums, fact_k[hit] % groups, fact_v[hit] * dv[hit])
    present = np.flatnonzero(
        np.bincount(fact_k[hit] % groups, minlength=groups))
    return present, sums[present]


def ref_pagerank(n, src, dst, steps, damping):
    """Dense power iteration in float64."""
    deg = np.bincount(src, minlength=n).astype(np.float64)
    rank = np.full(n, 1.0 / n)
    for _ in range(steps):
        msg = np.bincount(dst, weights=rank[src] / deg[src], minlength=n)
        rank = (1 - damping) / n + damping * msg
    return rank


# ----------------------------------------------------------------------
# checks on how a job ran
# ----------------------------------------------------------------------

def assert_device_path(scheduler):
    """The job that just ended ran every stage on the array path, and no
    stage of any job so far recorded a fallback or a degrade."""
    record = scheduler.history[-1]
    kinds = [(st["id"], st.get("kind")) for st in record["stage_info"]]
    bad = [k for k in kinds if not str(k[1]).startswith("array")]
    fallbacks = scheduler.fallback_reasons()
    degrades = scheduler.degrade_reasons()
    if bad or fallbacks or degrades:
        raise SmokeFailure(
            "job %s left the device path: stage kinds %s; fallback "
            "reasons %s; degrade reasons %s"
            % (record.get("id"), kinds, fallbacks, degrades))
    return kinds


def _pairs_to_arrays(rows):
    k = np.fromiter((r[0] for r in rows), np.int64, len(rows))
    v = np.fromiter((r[1] for r in rows), np.int64, len(rows))
    return k, v


def check_keyed_sums(rows, ref):
    k, v = _pairs_to_arrays(rows)
    order = np.argsort(k, kind="stable")
    exp_k, exp_v = ref
    if not (np.array_equal(k[order], exp_k)
            and np.array_equal(v[order], exp_v)):
        raise SmokeFailure("keyed sums differ from the numpy reference "
                           "(%d rows vs %d expected)" % (len(rows),
                                                         len(exp_k)))


# ----------------------------------------------------------------------
# the jobs: dpark calls only; references are compared by the caller
# ----------------------------------------------------------------------

def job_reduce(ctx, keys, vals, n):
    """reduceByKey, then count() and collect().  Returns (count,
    per-partition row lists)."""
    from dpark_tpu import Columns
    rdd = ctx.parallelize(Columns(keys, vals), n).reduceByKey(add, n)
    count = rdd.count()
    assert_device_path(ctx.scheduler)
    parts = list(ctx.runJob(rdd, list))       # collect(), per partition
    assert_device_path(ctx.scheduler)
    return count, parts


def check_reduce(result, ref, n):
    count, parts = result
    if count != len(ref[0]):
        raise SmokeFailure("count() = %d, reference has %d keys"
                           % (count, len(ref[0])))
    check_keyed_sums([r for p in parts for r in p], ref)
    if n > 1 and not all(len(p) for p in parts):
        raise SmokeFailure("reduce output is not spread over the mesh: "
                           "per-device counts %s"
                           % [len(p) for p in parts])


def job_reduce_waves(ctx, keys, vals, n, wave_rows):
    """The same job with the wave size pinned (the documented way to
    force the streamed path).  Returns (job_reduce result, the map
    stage's pipeline snapshot)."""
    from dpark_tpu import conf
    old = conf.STREAM_CHUNK_ROWS
    conf.STREAM_CHUNK_ROWS = wave_rows
    try:
        h0 = len(ctx.scheduler.history)
        result = job_reduce(ctx, keys, vals, n)
    finally:
        conf.STREAM_CHUNK_ROWS = old
    pipelines = [st["pipeline"] for rec in ctx.scheduler.history[h0:]
                 for st in rec["stage_info"] if st.get("pipeline")]
    return result, (pipelines[0] if pipelines else None)


def check_waves(pipeline, min_waves):
    if pipeline is None:
        raise SmokeFailure("the pinned-wave job did not stream: no "
                           "stage recorded last_stream_stats")
    if pipeline["waves"] < min_waves:
        raise SmokeFailure("streamed %d waves, wanted >= %d"
                           % (pipeline["waves"], min_waves))


def job_sort(ctx, keys, vals, n):
    from dpark_tpu import Columns
    rows = ctx.parallelize(Columns(keys, vals), n) \
        .sortByKey(numSplits=n).collect()
    assert_device_path(ctx.scheduler)
    return rows


def check_sort(rows, keys, vals):
    k, v = _pairs_to_arrays(rows)
    if len(k) > 1 and not bool(np.all(k[1:] >= k[:-1])):
        raise SmokeFailure("sortByKey output is not globally sorted")
    if not np.array_equal(ref_sort_packed(k, v),
                          ref_sort_packed(keys, vals)):
        raise SmokeFailure("sortByKey output is not the input multiset")


def _join_to_group(kv):
    k, (fv, dv) = kv
    return (k % JOIN_GROUPS, fv * dv)


def job_join(ctx, fact_k, fact_v, dim_k, dim_v, n):
    from dpark_tpu import Columns
    a = ctx.parallelize(Columns(fact_k, fact_v), n)
    b = ctx.parallelize(Columns(dim_k, dim_v), n)
    rows = a.join(b, n).map(_join_to_group).reduceByKey(add, n).collect()
    assert_device_path(ctx.scheduler)
    return rows


def job_pagerank(ctx, ids, src, dst, steps, damping, dtype):
    """The examples/pagerank.py formulation."""
    from dpark_tpu.bagel import run_pregel
    n = len(ids)

    def compute(value, msg, has_msg, active, agg, superstep):
        is0 = superstep == 0
        new = is0 * value + (1 - is0) * ((1 - damping) / n
                                         + damping * msg)
        return new, superstep < steps

    def send(src_value, edge_value, src_degree):
        return src_value / src_degree

    ctx.start()
    ctx.scheduler._pregel_device_used = None
    values = np.full(n, 1.0 / n, dtype)
    out_ids, ranks, _ = run_pregel(ctx, ids, values, (src, dst), compute,
                                   send, combine="add")
    if ctx.scheduler._pregel_device_used is not True:
        raise SmokeFailure("device Pregel was not used (host numpy loop "
                           "served the run)")
    return out_ids, ranks


def check_pagerank(result, ids, ref):
    out_ids, ranks = result
    ranks = np.asarray(ranks, np.float64)
    if not np.array_equal(out_ids, ids):
        raise SmokeFailure("pagerank returned other vertex ids")
    if not np.all(np.isfinite(ranks)):
        raise SmokeFailure("pagerank ranks are not finite")
    err = float(np.max(np.abs(ranks - ref)))
    rel = float(np.max(np.abs(ranks - ref) / ref))
    if err > 1e-6 or rel > 1e-3:
        raise SmokeFailure("pagerank differs from the dense iteration: "
                           "max abs %.3g, max rel %.3g" % (err, rel))


def job_service(keys, vals, n, counter, master="service:tpu"):
    """One reduceByKey through the resident job server, twice.  Returns
    per submit (rows, compile delta, the job record's program-cache
    probes)."""
    from dpark_tpu import Columns, DparkContext
    ctx = DparkContext(master)
    ctx.start()
    out = []
    try:
        for _ in range(2):
            snap = counter.snapshot()
            rows = ctx.parallelize(Columns(keys, vals), n) \
                .reduceByKey(add, n).collect()
            assert_device_path(ctx.scheduler)
            out.append((rows, counter.since(snap),
                        dict(ctx.scheduler.history[-1]
                             .get("program_cache") or {})))
    finally:
        ctx.stop()
        from dpark_tpu import service
        service.shutdown()
    return out


def check_service(out, ref):
    for rows, _, _ in out:
        check_keyed_sums(rows, ref)
    _, compiles, cache = out[1]
    if compiles["requests"] or cache.get("misses"):
        raise SmokeFailure("the second submit compiled: %s, program "
                           "cache %s" % (compiles, cache))


# ----------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------

def _timed(name, counter, fn, check):
    """Run a job cold then warm; compare each answer outside the timed
    region; log wall and compile counts of each."""
    for phase in ("cold", "warm"):
        snap = counter.snapshot()
        t0 = time.perf_counter()
        result = fn()
        wall = time.perf_counter() - t0
        check(result)
        log("[job] %-12s %s wall %.2fs  compiles %s  PASS"
            % (name, phase, wall, json.dumps(counter.since(snap))))
        del result


def _size(x, scale):
    return max(1024, x // scale)


def _reduce_case(seed, pairs):
    """(keys, vals, reference) of the reduceByKey jobs."""
    keys, vals = make_pairs(seed, pairs, N_KEYS)
    return keys, vals, ref_reduce(keys, vals, N_KEYS)


def step_reduce(ctx, n, seed, scale, counter):
    """Job 1: reduceByKey in core."""
    pairs = _size(PAIRS_PER_CHIP, scale) * n
    keys, vals, ref = _reduce_case(seed, pairs)
    log("[size] reduce: %d pairs (%d per chip), %d keys"
        % (pairs, pairs // n, N_KEYS))
    _timed("reduce", counter,
           lambda: job_reduce(ctx, keys, vals, n),
           lambda r: check_reduce(r, ref, n))


def step_waves(ctx, n, seed, scale, counter):
    """Job 2: the same data, streamed in pinned waves."""
    import jax
    from dpark_tpu import conf
    pairs = _size(PAIRS_PER_CHIP, scale) * n
    wave = _size(WAVE_ROWS, scale)
    keys, vals, ref = _reduce_case(seed, pairs)
    auto = conf.stream_chunk_rows()
    limit = conf._hbm_bytes_limit()
    log("[size] waves: %d pairs in waves of %d rows/device; auto wave "
        "budget %d rows/device from bytes_limit %d"
        % (pairs, wave, auto, limit))
    if jax.devices()[0].platform != "cpu" and not limit:
        raise SmokeFailure("auto wave budget has no bytes_limit behind it")

    def check(res):
        check_reduce(res[0], ref, n)
        check_waves(res[1], min(8, pairs // n // wave))

    _timed("reduce-waves", counter,
           lambda: job_reduce_waves(ctx, keys, vals, n, wave), check)


def step_sort(ctx, n, seed, scale, counter):
    """Job 3: sortByKey over near-distinct 40-bit keys."""
    rows = _size(SORT_PER_CHIP, scale) * n
    rng = np.random.default_rng(seed + 1)
    keys = rng.integers(0, 1 << 40, rows, dtype=np.int64)
    vals = rng.integers(0, 1 << 16, rows, dtype=np.int64)
    log("[size] sort: %d pairs (%d per chip)" % (rows, rows // n))
    _timed("sort", counter,
           lambda: job_sort(ctx, keys, vals, n),
           lambda r: check_sort(r, keys, vals))


def step_join(ctx, n, seed, scale, counter):
    """Job 4: join -> map -> reduceByKey."""
    fk, fv, dk, dv = make_join(seed + 2,
                               _size(JOIN_FACT_PER_CHIP, scale) * n,
                               _size(JOIN_DIM_PER_CHIP, scale) * n)
    ref = ref_join_reduce(fk, fv, dk, dv, JOIN_GROUPS)
    log("[size] join: %d x %d pairs" % (len(fk), len(dk)))
    _timed("join", counter,
           lambda: job_join(ctx, fk, fv, dk, dv, n),
           lambda r: check_keyed_sums(r, ref))


def step_pagerank(ctx, n, seed, scale, counter, dtype=np.float32):
    """Job 5: device Pregel PageRank."""
    nv = _size(PR_VERTICES, scale)
    ids, src, dst = make_graph(seed + 3, nv, PR_DEGREE)
    ref = ref_pagerank(nv, src, dst, PR_STEPS, PR_DAMPING)
    log("[size] pagerank: %d vertices, %d edges, %d supersteps, %s"
        % (nv, len(src), PR_STEPS, np.dtype(dtype).name))
    _timed("pagerank", counter,
           lambda: job_pagerank(ctx, ids, src, dst, PR_STEPS, PR_DAMPING,
                                dtype),
           lambda r: check_pagerank(r, ids, ref))


def step_service(n, seed, scale, counter):
    """Job 6: the resident job server, after the tpu context stopped
    (one mesh owner at a time)."""
    keys, vals, ref = _reduce_case(
        seed + 4, _size(SERVICE_PER_CHIP, scale) * n)
    log("[size] service: %d pairs" % len(keys))
    snap = counter.snapshot()
    t0 = time.perf_counter()
    out = job_service(keys, vals, n, counter)
    wall = time.perf_counter() - t0
    check_service(out, ref)
    log("[job] %-12s two submits wall %.2fs  compiles %s; second "
        "submit %s, program cache %s  PASS"
        % ("service", wall, json.dumps(counter.since(snap)),
           json.dumps(out[1][1]), json.dumps(out[1][2])))


CONTEXT_STEPS = (step_reduce, step_waves, step_sort, step_join,
                 step_pagerank)


def run(seed, scale, counter):
    """Every job, on every local device.  Raises on the first failed
    check."""
    import jax
    from dpark_tpu import DparkContext, conf, native

    if native.get_lib() is None:
        raise SmokeFailure("the native library did not build/load "
                           "(see the WARNING above for g++'s stderr)")
    ctx = DparkContext("tpu")
    ctx.start()
    ex = ctx.scheduler.executor
    n = ex.ndev
    devices = list(ex.mesh.devices.flat)
    on_cpu = devices[0].platform == "cpu"
    log("[env] compile cache dir in force: %s"
        % jax.config.jax_compilation_cache_dir)
    log("[env] native library loaded: True; mesh of %d device(s); "
        "bytes_limit %d" % (n, conf._hbm_bytes_limit()))
    log("[size] per chip, CUT from ISSUE 21's table to fit 1200 s cold: "
        "reduce/waves/service 16M pairs (table 64M/64M/16M), sort 16M "
        "(16M), join 16M x 1M (16M x 1M), pagerank 1M vertices x %d "
        "edges (x 16)%s" % (PR_DEGREE, "; all divided by %d" % scale
                            if scale != 1 else ""))
    for step in CONTEXT_STEPS:
        step(ctx, n, seed, scale, counter)
    if n > 1:
        if not ex.exchange_wire_bytes > 0:
            raise SmokeFailure("no all_to_all moved a byte on a %d-device "
                               "mesh" % n)
        log("[mesh] exchange_wire_bytes %d" % ex.exchange_wire_bytes)
    ctx.stop()
    step_service(n, seed, scale, counter)
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    log("[env] peak HBM per device: %s" % peaks)
    if not on_cpu and not all(peaks):
        raise SmokeFailure("a device of the mesh was never used: "
                           "peak_bytes_in_use %s" % peaks)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=1,
                    help="divide every size by this (rehearsal only)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib
    from importlib import metadata
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log("[env] device %s; jax %s jaxlib %s libtpu %s python %s"
        % (json.dumps(device), jax.__version__, jaxlib.__version__,
           metadata.version("libtpu"), sys.version.split()[0]))
    if dev.platform != "tpu":
        print("chip_smoke: jax found no TPU (platform %r); nothing was "
              "run" % dev.platform, file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    run(args.seed, args.scale, CompileCounter().install())
    log("[env] total wall %.1fs" % (time.perf_counter() - t0))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
