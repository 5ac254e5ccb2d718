"""Benchmark: tpu master vs process master on the BASELINE.md configs.

Headline JSON line:
  {"metric": "reduceByKey_GBps_per_chip", "value": N, "unit": "GB/s/chip",
   "vs_baseline": N, "pct_of_sort_roofline": N}
vs_baseline is the tpu-master speedup over the reference-semantics
`-m process` CPU baseline on the same workload (BASELINE.md: the reference
publishes no numbers; the process master IS the baseline).
pct_of_sort_roofline is value / the chip's OWN single-operand `jnp.sort`
throughput measured in the same session — distance to "actually fast",
not just distance to the CPU baseline (VERDICT r3 #5).

Additional lines: out-of-core reduceByKey, join/cogroup (BASELINE config
#2), DStream reduceByKeyAndWindow (config #4), file wordcount (config
#0), sortByKey+groupByKey (config #1) — every row of BASELINE.md's
configs table emits a JSON line.

The process runs execute FIRST, before jax is imported, so their fork
pools are jax-free (fork after jax import can deadlock).
"""

import json
import os
import sys
import time

N_PAIRS = int(os.environ.get("BENCH_PAIRS", 16_000_000))
# with a REAL device reachable the default rises to a non-toy size
# (main() sets this after the probe; BENCH_PAIRS always wins)
N_PAIRS_DEVICE_DEFAULT = 64_000_000
N_KEYS = int(os.environ.get("BENCH_KEYS", 65_536))
# two int64 columns (16 bytes/pair) — computed from the real dtypes in
# make_data below, kept in sync by an assert there
BYTES = N_PAIRS * 16


def make_data():
    # scrambled int keys, deterministic; columnar (numpy) input — the
    # ingestion analog of the reference's file sources.  Both masters get
    # the same columns: the process master iterates them as Python rows
    # (its real execution model), the tpu master ingests them into HBM.
    import numpy as np
    from dpark_tpu import Columns
    i = np.arange(N_PAIRS, dtype=np.int64)
    keys = (i * 2654435761) % N_KEYS
    vals = i & 0xFFFF
    assert keys.nbytes + vals.nbytes == BYTES, "BYTES out of sync"
    return Columns(keys, vals)


def run_once(ctx, data, n_parts, expect_keys=None):
    t0 = time.perf_counter()
    r = (ctx.parallelize(data, n_parts)
         .reduceByKey(lambda a, b: a + b, n_parts))
    n = r.count()
    dt = time.perf_counter() - t0
    if expect_keys is not None:
        assert n == expect_keys, (n, expect_keys)
    return dt


def bench_process(data):
    from dpark_tpu import DparkContext
    nproc = min(8, os.cpu_count() or 4)
    ctx = DparkContext("process:%d" % nproc)
    ctx.start()
    dt = run_once(ctx, data, nproc, min(N_KEYS, N_PAIRS))
    ctx.stop()
    return dt


def _pad_stats(ex):
    """Pad efficiency with an honest label: wire padding when an
    exchange actually moved bytes, ingest padding on a single-chip
    identity exchange (advisor r3: never present one as the other)."""
    real = ex.exchange_real_rows
    if ex.exchange_slot_rows:
        return {"pad_efficiency": round(
                    real / max(1, ex.exchange_slot_rows), 4),
                "pad_kind": "wire"}
    return {"pad_efficiency": round(
                real / max(1, ex.ingest_slot_rows), 4),
            "pad_kind": "ingest"}


def _pipeline_stats(ctx):
    """The streamed map stage's overlapped-wave pipeline aggregates
    (scheduler.pipeline_summary), or None off the streamed paths."""
    summary = getattr(ctx.scheduler, "pipeline_summary", None)
    return summary() if summary is not None else None


def _sort_roofline_gbps():
    """The chip's own single-operand `jnp.sort` throughput (GB/s) at the
    benchmark size — the per-session roofline every headline metric is
    reported against."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    n = min(N_PAIRS, 64_000_000)
    x = jax.device_put(np.arange(n, dtype=np.int32)[::-1].copy())
    jnp.sort(x).block_until_ready()          # compile
    t0 = time.perf_counter()
    jnp.sort(x).block_until_ready()
    dt = time.perf_counter() - t0
    return round(x.nbytes / dt / 1e9, 3)


def bench_tpu(data):
    import jax
    if os.environ.get("BENCH_PLATFORM"):     # e.g. cpu mesh for CI
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import DparkContext
    ctx = DparkContext("tpu")
    ctx.start()
    ex = ctx.scheduler.executor
    ndev = ex.ndev
    # warm-up: compile the stage programs at the same size class
    run_once(ctx, data, ndev)
    best = min(run_once(ctx, data, ndev, min(N_KEYS, N_PAIRS))
               for _ in range(3))
    stats = dict({"wire_bytes": ex.exchange_wire_bytes,
                  "sort_roofline_gbps": _sort_roofline_gbps()},
                 **_pad_stats(ex))
    ctx.stop()
    return best, ndev, stats


def _tpu_phase():
    """Child-process entry: run the tpu benchmark and print its result
    as one line (the parent stays off jax: one process at a time may
    hold the chip)."""
    data = make_data()
    t_tpu, ndev, stats = bench_tpu(data)
    print("TPU_RESULT %s" % json.dumps(
        dict(stats, t=t_tpu, ndev=ndev)), flush=True)


# out-of-core config: sized by env knob, routed through the wave-stream
# path (ingest -> exchange -> merge waves with HBM holding one chunk),
# reporting bounded RSS/HBM next to throughput (VERDICT r2 ask #3: the
# flagship capability must be visible in the driver-captured artifact)
OOC_GB = float(os.environ.get("BENCH_OOC_GB", "0.25"))
OOC_KEYS = 1_000_000


def _ooc_phase():
    """Child-process entry: streamed out-of-core reduceByKey."""
    import resource

    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext, conf
    n = int(OOC_GB * (1 << 30)) // 16
    i = np.arange(n, dtype=np.int64)
    data = Columns((i * 2654435761) % OOC_KEYS, i & 0xFFFF)
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    # exactly >=2 waves per device so the wave-stream machinery carries
    # the run even at sub-HBM benchmark sizes (a real >HBM run hits the
    # same code path with the auto HBM-sized chunk); an explicit number
    # here overrides "auto" — the streamed path MUST run for this metric
    conf.STREAM_CHUNK_ROWS = max(1, n // (ndev * 2))
    t0 = time.perf_counter()
    cnt = (ctx.parallelize(data, ndev)
           .reduceByKey(lambda a, b: a + b, ndev).count())
    dt = time.perf_counter() - t0
    assert cnt == min(OOC_KEYS, n), (cnt, OOC_KEYS)
    ex = ctx.scheduler.executor
    rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss \
        / (1 << 20)
    payload = {
        "data_gb": round(OOC_GB, 3),
        "seconds": round(dt, 3),
        "gbps_per_chip": round(OOC_GB / dt / ndev, 4),
        "max_rss_gb": round(rss_gb, 3),
        "hbm_store_gb": round(ex._store_bytes / (1 << 30), 4),
        "exchange_wire_gb": round(ex.exchange_wire_bytes / (1 << 30),
                                  4),
        "chips": ndev,
    }
    payload.update(_pad_stats(ex))
    pipe = _pipeline_stats(ctx)
    if pipe is not None:
        payload["pipeline"] = pipe
    # per-phase table + fallback reasons: the bench-smoke schema gate
    # (tools/bench_smoke_check.py) asserts both fields are present
    phases = getattr(ctx.scheduler, "phase_table", lambda: None)()
    if phases is not None:
        payload["phases"] = phases
    payload["fallback_reasons"] = getattr(
        ctx.scheduler, "fallback_reasons", lambda: [])()
    # chaos/recovery accounting (ISSUE 5 satellite): per-site injected
    # fault counters and the degrade/resubmit/retry summary — gated by
    # tools/bench_smoke_check.py so a refactor cannot silently drop
    # the recovery observability
    recovery = getattr(ctx.scheduler, "recovery_summary",
                       lambda: {})() or {}
    payload["faults"] = recovery.pop("faults", {})
    # coded-shuffle decode counters (ISSUE 6): repair/straggler_win/
    # decode_failures + the active mode, schema-gated like faults
    payload["decodes"] = recovery.pop("decodes", {})
    payload["degrades"] = recovery
    # adaptive-execution accounting (ISSUE 7): mode, store hit/steer
    # counters, and the decisions taken (predicted-vs-observed ms) —
    # schema-gated like faults/decodes
    from dpark_tpu import adapt
    payload["adapt"] = adapt.summary()
    # trace plane (ISSUE 8): mode + span counts + the critical-path
    # summary of the longest traced job (which stage/phase chain bound
    # wall time) — so the perf trajectory records WHERE time went, not
    # just how much.  {"mode": "off", "spans": 0, ...} when untraced;
    # schema-gated like faults/decodes/adapt.
    from dpark_tpu import trace
    payload["trace"] = trace.summary()
    # health plane (ISSUE 14): per-site latency-tail summaries + event
    # rates — {"mode": "on", "sites": {}} when nothing was traced
    # (sketches fold off the trace plane); schema-gated like trace
    from dpark_tpu import health
    payload["health"] = health.summary()
    # resource attribution (ISSUE 15): per-tenant account rollup +
    # conservation — {"mode": "off", "tenants": {}} when off;
    # schema-gated like health
    from dpark_tpu import ledger
    payload["ledger"] = ledger.summary()
    ctx.stop()
    print("OOC_RESULT %s" % json.dumps(payload), flush=True)


def _tuple_phase():
    """Child-process entry: composite-key A/B (ISSUE 3 acceptance) —
    the SAME reduceByKey workload keyed by one int column vs by a
    2-int-tuple key, both on the tpu master.  Before tuple keys rode
    the device, the B side silently ran the object path (orders of
    magnitude slower); the ratio is the regression gate."""
    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext
    n = min(N_PAIRS, int(os.environ.get("BENCH_TUPLE_PAIRS",
                                        N_PAIRS)))
    i = np.arange(n, dtype=np.int64)
    k = (i * 2654435761) % N_KEYS
    data = Columns(k, i & 0xFFFF)
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev

    def scalar_run():
        # the map mirrors the tuple side's key-split op, so the A/B
        # isolates KEY WIDTH (one extra sort/exchange column), not an
        # extra fused map
        t0 = time.perf_counter()
        cnt = (ctx.parallelize(data, ndev)
               .map(lambda kv: (kv[0] // 64 * 64 + kv[0] % 64, kv[1]))
               .reduceByKey(lambda a, b: a + b, ndev).count())
        assert cnt == min(N_KEYS, n), cnt
        return time.perf_counter() - t0

    def tuple_run():
        # same rows, key split into a 2-int tuple (k // 64, k % 64) —
        # same distinct-key count, same combine volume
        t0 = time.perf_counter()
        cnt = (ctx.parallelize(data, ndev)
               .map(lambda kv: ((kv[0] // 64, kv[0] % 64), kv[1]))
               .reduceByKey(lambda a, b: a + b, ndev).count())
        assert cnt == min(N_KEYS, n), cnt
        return time.perf_counter() - t0

    scalar_run(); tuple_run()            # warm-up compiles
    t_scalar = min(scalar_run() for _ in range(2))
    t_tuple = min(tuple_run() for _ in range(2))
    # the tuple job must have ridden the array path, or the ratio is
    # measuring the very fallback this PR removes
    kinds = set()
    for rec in ctx.scheduler.history:
        for st in rec.get("stage_info", ()):
            kinds.add(st.get("kind"))
    ctx.stop()
    print("TUPLE_RESULT %s" % json.dumps(
        {"t_scalar": t_scalar, "t_tuple": t_tuple, "ndev": ndev,
         "pairs": n, "array_path": "array" in kinds}), flush=True)


def _groupmap_fn(vs):
    """The A/B's per-group consumer: a second-moment accumulator —
    traceable + zero-pad-invariant but NOT one of the five provable
    aggregates, so only the ISSUE 4 segmented apply keeps it on
    device.  Natural host code too — the same callable folds Python
    lists on the object path."""
    return sum(3 * v * v + 2 * v for v in vs)


def _groupmap_phase():
    """Child-process entry: device segmented apply A/B (ISSUE 4
    acceptance) — the SAME groupByKey().mapValues(traceable per-group
    fn) job with conf.SEG_MAP on (SegMapOp: all-array, no host bridge)
    vs off (the pre-PR host object path through the export bridge).
    The ratio is the regression gate: >= 5x on the 2-dev CPU mesh."""
    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext, conf
    n = min(N_PAIRS, int(os.environ.get("BENCH_GROUPMAP_PAIRS",
                                        2_000_000)))
    nkeys = min(N_KEYS, max(16, n // 64))
    i = np.arange(n, dtype=np.int64)
    k = (i * 2654435761) % nkeys
    data = Columns(k, i & 0xFFFF)
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev

    def run():
        t0 = time.perf_counter()
        cnt = (ctx.parallelize(data, ndev).groupByKey(ndev)
               .mapValues(_groupmap_fn).count())
        assert cnt == min(nkeys, n), cnt
        wall = time.perf_counter() - t0
        # the CONSUME stage's own seconds: both sides share the same
        # device no-combine shuffle write, so the whole-job wall would
        # dilute the quantity under test (segmented apply vs the
        # object path's export-bridge + per-group Python fold)
        rec = ctx.scheduler.history[-1]
        consume = sum((st.get("seconds") or 0.0)
                      for st in rec.get("stage_info", ())
                      if not st.get("shuffle"))
        return wall, consume

    conf.SEG_MAP = True
    run()                               # warm-up compiles
    t_dev, c_dev = min(run() for _ in range(2))
    # EVERY stage of the device-side job must be array-kind — a
    # "kind contains array anywhere" check is vacuously true (the
    # shuffle write always rides) and would let a consume-stage
    # fallback measure host-vs-host unnoticed
    rec = ctx.scheduler.history[-1]
    array_path = bool(rec.get("stage_info")) and all(
        str(st.get("kind", "")).startswith("array")
        for st in rec["stage_info"])
    conf.SEG_MAP = False
    try:
        t_host, c_host = min(run() for _ in range(2))
    finally:
        conf.SEG_MAP = True
    ctx.stop()
    print("GROUPMAP_RESULT %s" % json.dumps(
        {"t_device": c_dev, "t_host": c_host,
         "wall_device": t_dev, "wall_host": t_host, "ndev": ndev,
         "pairs": n, "keys": nkeys,
         "device_array_path": array_path}), flush=True)


def _table_phase():
    """Child-process entry: columnar query plane A/B (ISSUE 13
    acceptance) — the SAME select+filter+group-by SQL-shaped query
    over the SAME tabular part files, once through the device query
    plan (column-pruned vectorized scan + device group exchange,
    DPARK_QUERY on) and once through the pre-plan host row path
    (per-row Python eval + host dict aggregation, DPARK_QUERY=0).
    Both sides pay the full query wall including the scan."""
    import tempfile

    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import DparkContext, conf
    from dpark_tpu.tabular import write_tabular
    n = int(os.environ.get("BENCH_TABLE_ROWS", 2_000_000))
    d = tempfile.mkdtemp(prefix="bench_table_")
    i = np.arange(n, dtype=np.int64)
    cols = [((i * 2654435761) % 1000).tolist(),      # k: group key
            (i % 100).tolist(),                      # a: filter col
            (i % 7).tolist(),                        # b: sum arg
            ((i % 13) * 0.5).tolist(),               # f: avg arg
            ["s%d" % (x % 5) for x in range(n)]]     # s: never read
    write_tabular(os.path.join(d, "part-00000.tab"),
                  ["k", "a", "b", "f", "s"], zip(*cols),
                  chunk_rows=1 << 16)
    del cols
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev

    def run():
        t = ctx.tabular(d).asTable("t")
        q = t.where("a >= 10").groupBy(
            "k", "sum(b) as sb", "count(*) as c", "avg(f) as af")
        t0 = time.perf_counter()
        rows = sorted(q.collect())
        return time.perf_counter() - t0, rows, q

    conf.QUERY_PLAN = True
    run()                               # warm-up compiles
    n0 = len(ctx.scheduler.history)
    best = None
    for _ in range(2):
        dt, rows_dev, q = run()
        if best is None or dt < best[0]:
            best = (dt, rows_dev, q)
    t_dev, rows_dev, q = best
    pq = q._planned()
    recs = ctx.scheduler.history[n0:]
    all_array = bool(recs) and all(
        str(st.get("kind", "")).startswith("array")
        and not st.get("fallback_reason")
        for rec in recs for st in rec.get("stage_info", []))
    scan = {k: (sorted(v) if isinstance(v, set) else v)
            for k, v in (pq.scan_stats if pq else {}).items()}
    conf.QUERY_PLAN = False
    try:
        t_host, rows_host, _ = run()
    finally:
        conf.QUERY_PLAN = True
    ctx.stop()
    print("TABLE_RESULT %s" % json.dumps(
        {"t_device": t_dev, "t_host": t_host, "rows": n,
         "ndev": ndev, "parity": rows_dev == rows_host,
         "device_all_array": all_array, "scan": scan,
         "columns_total": 5}), flush=True)


# BASELINE config #2: join/cogroup of two keyed RDDs (TPC-H
# lineitem⋈orders subset shape: big fact table, smaller key table,
# every fact key hits).  Sizes are row counts; device default rises.
JOIN_FACT = int(os.environ.get("BENCH_JOIN_FACT", 2_000_000))
JOIN_DIM = int(os.environ.get("BENCH_JOIN_DIM", 500_000))
JOIN_FACT_DEVICE_DEFAULT = 16_000_000


def make_join_data():
    import numpy as np
    from dpark_tpu import Columns
    i = np.arange(JOIN_FACT, dtype=np.int64)
    fact = Columns((i * 2654435761) % JOIN_DIM, i & 0xFFFF)   # lineitem
    j = np.arange(JOIN_DIM, dtype=np.int64)
    dim = Columns(j, (j * 31) & 0xFF)                          # orders
    return fact, dim


def run_join_once(ctx, fact, dim, n_parts):
    t0 = time.perf_counter()
    a = ctx.parallelize(fact, n_parts)
    b = ctx.parallelize(dim, n_parts)
    n = a.join(b, n_parts).count()
    dt = time.perf_counter() - t0
    assert n == JOIN_FACT, (n, JOIN_FACT)
    return dt


def bench_join_process():
    from dpark_tpu import DparkContext
    fact, dim = make_join_data()
    nproc = min(8, os.cpu_count() or 4)
    ctx = DparkContext("process:%d" % nproc)
    ctx.start()
    dt = run_join_once(ctx, fact, dim, nproc)
    ctx.stop()
    return dt


def _join_phase():
    """Child-process entry: tpu join/cogroup (BASELINE config #2)."""
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import DparkContext
    fact, dim = make_join_data()
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    run_join_once(ctx, fact, dim, ndev)           # warm-up compile
    best = min(run_join_once(ctx, fact, dim, ndev) for _ in range(2))
    ctx.stop()
    print("JOIN_RESULT %s" % json.dumps({"t": best, "ndev": ndev}),
          flush=True)


# BASELINE config #0: wordcount over a REAL text file
# (textFile -> flatMap -> map -> reduceByKey), deterministic corpus.
WC_MB = float(os.environ.get("BENCH_WC_MB", 64))
WC_MB_DEVICE_DEFAULT = 512.0
WC_WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta",
            "eta", "theta", "iota", "kappa", "lam", "mu", "nu", "xi"]


def _wc_corpus():
    import hashlib
    tag = hashlib.md5(("wc-%s" % WC_MB).encode()).hexdigest()[:8]
    path = "/tmp/dpark_bench_wc_%s.txt" % tag
    if not os.path.exists(path):
        import random as _random
        rng = _random.Random(11)
        target = int(WC_MB * (1 << 20))
        with open(path + ".tmp", "w") as f:
            written = 0
            while written < target:
                line = " ".join(rng.choices(WC_WORDS, k=10)) + "\n"
                f.write(line)
                written += len(line)
        os.replace(path + ".tmp", path)
    return path


def _wc_run(ctx, path):
    t0 = time.perf_counter()
    n = (ctx.textFile(path)
         .flatMap(lambda line: line.split())
         .map(lambda w: (w, 1))
         .reduceByKey(lambda a, b: a + b, 8).count())
    dt = time.perf_counter() - t0
    assert n == len(WC_WORDS), (n, len(WC_WORDS))
    return dt


def bench_wc_process(path):
    from dpark_tpu import DparkContext
    nproc = min(8, os.cpu_count() or 4)
    ctx = DparkContext("process:%d" % nproc)
    ctx.start()
    dt = _wc_run(ctx, path)
    ctx.stop()
    return dt


def _wc_phase():
    """Child-process entry: tpu wordcount (BASELINE config #0)."""
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import DparkContext
    path = _wc_corpus()
    ctx = DparkContext("tpu")
    ctx.start()
    _wc_run(ctx, path)                            # warm-up compile
    dt = _wc_run(ctx, path)
    ctx.stop()
    print("WC_RESULT %s" % json.dumps({"t": dt}), flush=True)


# BASELINE config #1: sortByKey + groupByKey over synthetic (int, int)
# pairs — the no-combine exchange paths (range + hash).
SG_PAIRS = int(os.environ.get("BENCH_SG_PAIRS", 2_000_000))
SG_PAIRS_DEVICE_DEFAULT = 10_000_000
SG_KEYS = 100_000


def make_sg_data():
    import numpy as np
    from dpark_tpu import Columns
    i = np.arange(SG_PAIRS, dtype=np.int64)
    return Columns((i * 2654435761) % SG_KEYS, i & 0xFFFF)


def _sg_run(ctx, data, n_parts):
    t0 = time.perf_counter()
    r = ctx.parallelize(data, n_parts)
    ns = r.sortByKey(numSplits=n_parts).count()
    ng = r.groupByKey(n_parts).count()
    dt = time.perf_counter() - t0
    assert ns == SG_PAIRS and ng == min(SG_KEYS, SG_PAIRS), (ns, ng)
    return dt


def bench_sg_process():
    from dpark_tpu import DparkContext
    data = make_sg_data()
    nproc = min(8, os.cpu_count() or 4)
    ctx = DparkContext("process:%d" % nproc)
    ctx.start()
    dt = _sg_run(ctx, data, nproc)
    ctx.stop()
    return dt


def _sg_phase():
    """Child-process entry: tpu sortByKey+groupByKey (config #1)."""
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import DparkContext
    data = make_sg_data()
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    _sg_run(ctx, data, ndev)                      # warm-up compile
    dt = _sg_run(ctx, data, ndev)
    out = {"t": dt, "ndev": ndev}
    pipe = _pipeline_stats(ctx)
    if pipe is not None:        # only when the input streamed in waves
        out["pipeline"] = pipe
    ctx.stop()
    print("SG_RESULT %s" % json.dumps(out), flush=True)


# BASELINE config #4: DStream reduceByKeyAndWindow micro-batches.
# records per batch x batches, 2-batch window with inverse-reduce.
STREAM_RECS = int(os.environ.get("BENCH_STREAM_RECS", 200_000))
STREAM_BATCHES = int(os.environ.get("BENCH_STREAM_BATCHES", 8))
STREAM_KEYS = 4_096


def _stream_run(ctx):
    """Drive reduceByKeyAndWindow over a deterministic queueStream with
    the manual clock (the timer would measure sleep, not work); returns
    wall seconds over all batches."""
    import operator

    import numpy as np
    from dpark_tpu.dstream import StreamingContext
    rng = np.random.RandomState(7)
    batches = []
    for _ in range(STREAM_BATCHES):
        ks = rng.randint(0, STREAM_KEYS, STREAM_RECS)
        vs = rng.randint(0, 100, STREAM_RECS)
        batches.append(list(zip(ks.tolist(), vs.tolist())))
    ssc = StreamingContext(ctx, 1.0)
    out = []
    q = ssc.queueStream(batches)
    q.reduceByKeyAndWindow(operator.add, 2.0,
                           invFunc=operator.sub).collect_batches(out)
    ctx.start()
    for ins in ssc.input_streams:
        ins.start()
    ssc.zero_time = 1000.0
    t0 = time.perf_counter()
    for k in range(1, STREAM_BATCHES + 1):
        ssc.run_batch(1000.0 + k * ssc.batch_duration)
    dt = time.perf_counter() - t0
    assert len(out) == STREAM_BATCHES and len(out[-1][1]) == STREAM_KEYS
    return dt


def bench_stream_process():
    from dpark_tpu import DparkContext
    nproc = min(8, os.cpu_count() or 4)
    ctx = DparkContext("process:%d" % nproc)
    dt = _stream_run(ctx)
    ctx.stop()
    return dt


def _stream_phase():
    """Child-process entry: tpu DStream window (BASELINE config #4)."""
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import DparkContext, panes
    ctx = DparkContext("tpu")
    _stream_run(ctx)                              # warm-up compile
    dt = _stream_run(ctx)
    # pane-plane accounting (ISSUE 10): the window above rides the
    # pane path — report the last driven stream's live stats so the
    # bench artifact records pane mode/counts next to the throughput
    stats = panes.stream_stats()
    pane_info = list(stats.values())[-1] if stats else {}
    ctx.stop()
    print("STREAM_RESULT %s" % json.dumps(
        {"t": dt, "panes": pane_info}), flush=True)


def _coded_phase():
    """Child-process entry: coded-shuffle overhead A/B (ISSUE 6
    acceptance) — the SAME shuffle-heavy host-path reduceByKey job
    with the code off vs rs(4,2), NO faults injected.  The coded side
    pays encode at map time plus the k-of-n framed shard reads at
    reduce time; the acceptance bound is <= 15% wall overhead.  Runs
    on the local master: the host bucket exchange is the path the
    parity shards ride (the in-device all_to_all never touches
    them)."""
    from dpark_tpu import DparkContext, coding
    n = int(os.environ.get("BENCH_CODED_PAIRS", "400000"))
    parts = 8
    ctx = DparkContext("local")

    def run():
        t0 = time.perf_counter()
        cnt = (ctx.parallelize(range(n), parts)
               .map(lambda i: (i % 10007, i))
               .reduceByKey(lambda a, b: a + b, parts).count())
        assert cnt == min(10007, n), cnt
        return time.perf_counter() - t0

    coding.configure(None)
    run()                               # warm imports / page cache
    t_off = min(run() for _ in range(2))
    coding.configure("rs(4,2)")
    coding.reset_counters()
    try:
        t_on = min(run() for _ in range(2))
        stats = coding.stats()
    finally:
        coding.configure(None)
    ctx.stop()
    print("CODED_RESULT %s" % json.dumps(
        {"t_off": t_off, "t_on": t_on, "decodes": stats, "pairs": n}),
        flush=True)


_BULK_PEER_SCRIPT = r'''
import os, sys, time
import numpy as np
n, wd = int(sys.argv[1]), sys.argv[2]
from dpark_tpu import shuffle as sm
from dpark_tpu.dcn import BucketServer
i = np.arange(n, dtype=np.int64)
keys = (i * 2654435761) % 100003
vals = i & 0xFFFF
# rows are materialized ONCE (conservative: the real bridge rebuilds
# them from device slices per fetch) — the bridge still pays
# pickle+compress per request, which is its real per-byte cost
rows = list(zip(keys.tolist(), vals.tolist()))
sm.HBM_EXPORTERS["bench"] = lambda sid, m, r, shard=None: rows
sm.HBM_COL_EXPORTERS["bench"] = \
    lambda sid, m, r: ({"no_combine": False}, [keys, vals])
srv = BucketServer(wd, host="127.0.0.1").start()
print("ADDR %s" % srv.addr, flush=True)
time.sleep(600)
'''


def _bulk_phase():
    """Child-process entry: bulk-channel vs pickled-bridge A/B
    (ISSUE 12 acceptance).  A PEER PROCESS serves the same
    HBM-shaped bucket both ways over same-box loopback: the bridge
    path (single-frame ``("bucket", ...)`` — server pickles rows,
    client unpickles then re-columnarizes) vs the bulk path (chunked
    ``bulk_bucket`` stream of RAW COLUMN BYTES assembled zero-copy).
    Both sides end at numpy columns on the receiving controller;
    bytes/s is logical column bytes over the median fetch, p99 over
    the rep distribution.  Acceptance: bulk >= 2x the bridge's
    bytes/s."""
    import pickle
    import statistics
    import subprocess
    import tempfile

    import numpy as np
    from dpark_tpu import bulkplane, dcn
    from dpark_tpu.utils import decompress
    n = int(os.environ.get("BENCH_BULK_ROWS", "2000000"))
    reps = max(3, int(os.environ.get("BENCH_BULK_REPS", "9")))
    tmp = tempfile.mkdtemp(prefix="dpark-bulk-ab-")
    script = os.path.join(tmp, "peer.py")
    with open(script, "w") as f:
        f.write(_BULK_PEER_SCRIPT)
    here = os.path.dirname(os.path.abspath(__file__))
    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = here + os.pathsep + \
        child_env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, script, str(n), tmp],
        stdout=subprocess.PIPE, text=True, env=child_env)
    try:
        addr = proc.stdout.readline().split()[1]
        logical = n * 16                      # two int64 columns

        def bridge_fetch():
            payload = dcn.fetch(addr, ("bucket", 0, 0, 0))
            items = pickle.loads(decompress(payload))
            ks = np.fromiter((kv[0] for kv in items), dtype=np.int64,
                             count=len(items))
            vs = np.fromiter((kv[1] for kv in items), dtype=np.int64,
                             count=len(items))
            return ks, vs, items

        def bulk_fetch():
            meta, view = bulkplane.fetch(addr,
                                         ("bulk_bucket", 0, 0, 0))
            return bulkplane.cols_from_buf(meta, view)

        # warm both paths (connects, page cache, the peer's pickle of
        # rows is per-request by design), then verify BIT-PARITY
        bks, bvs, items = bridge_fetch()
        cols = bulk_fetch()
        parity = (list(zip(cols[0].tolist(), cols[1].tolist()))
                  == items
                  and bks.tolist() == cols[0].tolist()
                  and bvs.tolist() == cols[1].tolist())
        t_bridge, t_bulk = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            bridge_fetch()
            t_bridge.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            bulk_fetch()
            t_bulk.append(time.perf_counter() - t0)

        def p99(ts):
            s = sorted(ts)
            return s[min(len(s) - 1, int(0.99 * len(s)))]

        bridge_bps = logical / statistics.median(t_bridge)
        bulk_bps = logical / statistics.median(t_bulk)
        out = {"rows": n, "reps": reps,
               "logical_mb": round(logical / 1e6, 1),
               "bridge_MBps": round(bridge_bps / 1e6, 1),
               "bulk_MBps": round(bulk_bps / 1e6, 1),
               "ratio": round(bulk_bps / max(bridge_bps, 1e-9), 2),
               "p50_bridge_ms": round(
                   statistics.median(t_bridge) * 1e3, 1),
               "p50_bulk_ms": round(
                   statistics.median(t_bulk) * 1e3, 1),
               "p99_bridge_ms": round(p99(t_bridge) * 1e3, 1),
               "p99_bulk_ms": round(p99(t_bulk) * 1e3, 1),
               "parity": bool(parity),
               "bulk_streams": bulkplane.stats()["streams"]}
        print("BULKPLANE_RESULT %s" % json.dumps(out), flush=True)
    finally:
        proc.terminate()
        proc.wait(timeout=30)
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)


def _adapt_phase():
    """Child-process entry: adaptive-execution warm-vs-cold A/B
    (ISSUE 7 acceptance) — the streamed sortgroup config run twice
    with DPARK_ADAPT=on against a deterministic emulated HBM ceiling
    (conf.EMULATED_WAVE_OOM_ROWS).  The COLD run's auto wave budget
    exceeds the ceiling, so it walks the real OOM degradation ladder
    (fail, halve, retry) and persists the outcome; the WARM run seeds
    its budget from the store and streams first try.  The JSON reports
    wall seconds, OOM-ladder retries, and store hits per run — warm
    must show fewer ladder retries (and typically less wall).  A
    pre-warmed DPARK_ADAPT_DIR (the CI two-pass smoke) makes even the
    "cold" run seed from the store: cold ladder_retries == 0 with
    store_hits >= 1 is the cross-process persistence proof."""
    import tempfile

    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext, adapt, conf
    store = os.environ.get("DPARK_ADAPT_DIR") \
        or tempfile.mkdtemp(prefix="dpark-adapt-ab-")
    adapt.configure(mode="on", store_dir=store)
    # the A/B grades the ladder+store loop, not real HBM sizing: pin
    # the auto derivation to a known base (no device memory limit) so
    # base > ceiling > base/2 holds on every backend, and the ladder's
    # single halving lands under the ceiling deterministically
    base = int(os.environ.get("BENCH_ADAPT_BASE_ROWS", 1 << 18))
    conf._hbm_bytes_limit = lambda: 0
    conf._STREAM_CHUNK_ROWS_FALLBACK = base
    conf.EMULATED_WAVE_OOM_ROWS = int(os.environ.get(
        "BENCH_ADAPT_CEIL_ROWS", base * 3 // 4))
    conf.STREAM_CHUNK_ROWS = "auto"
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    # each device's slice must exceed the base wave budget or the
    # in-core path runs and nothing streams (no ladder to grade)
    n = int(os.environ.get("BENCH_ADAPT_PAIRS",
                           str(base * 3 // 2 * ndev)))
    i = np.arange(n, dtype=np.int64)
    data = Columns((i * 2654435761) % 100_000, i & 0xFFFF)

    def run():
        hits0 = adapt.summary()["store_hits"]
        # count ladder walks from the per-stage job records, NOT from
        # degrade_reasons() — that helper de-duplicates identical
        # reason strings across the whole history, so a warm run
        # re-walking the ladder with the same budgets would be
        # invisible and the A/B could false-pass
        jobs0 = len(ctx.scheduler.history)
        t0 = time.perf_counter()
        r = ctx.parallelize(data, ndev)
        ns = r.sortByKey(numSplits=ndev).count()
        ng = r.groupByKey(ndev).count()
        wall = time.perf_counter() - t0
        assert ns == n and ng == min(100_000, n), (ns, ng)
        s = adapt.summary()
        ladder = sum(
            1 for rec in ctx.scheduler.history[jobs0:]
            for st in rec.get("stage_info", ())
            if "wave budget" in (st.get("degrade_reason") or ""))
        return {"wall_s": round(wall, 3),
                "ladder_retries": ladder,
                "store_hits": s["store_hits"] - hits0}

    cold = run()
    warm = run()
    out = {"cold": cold, "warm": warm, "pairs": n, "ndev": ndev,
           "adapt": adapt.summary()}
    ctx.stop()
    print("ADAPT_RESULT %s" % json.dumps(out), flush=True)


def _code_adapt_phase():
    """Child-process entry: straggler-adaptive coding + skew re-plan
    A/B (ISSUE 19 acceptance).

    adaptive_code: two shuffle exchanges on one local master — a HOT
    site whose learn-pass fetches consume parity under seeded shard
    failures, and a COLD site with tight recorded tails.  The static
    leg codes BOTH exchanges rs(4,2); the adaptive leg
    (DPARK_CODE_ADAPT over the same global code) re-prices per
    exchange — hot stays escalated (it demonstrably decoded), cold
    PINS UNCODED and sheds its parity bytes.  Both legs time the same
    graded pass under the same injected per-peer fetch delay, so the
    acceptance reads directly off the JSON: adaptive wall <= 1.1x
    static at LOWER total parity bytes.

    skew_replan: a dominant-bucket reduceByKey on the multiprocess
    master — with DPARK_REPLAN off, one reduce task drags ~the whole
    exchange; on, the mid-job salted re-split spreads it across the
    worker pool with zero map recomputes, and the SECOND run
    pre-salts at plan time (same stage count as the off leg, only
    the salt differs — the steady-state improvement)."""
    import operator
    import tempfile

    from dpark_tpu import DparkContext, adapt, coding, conf, faults
    from dpark_tpu.health import Sketch
    from dpark_tpu.utils.phash import portable_hash

    n = int(os.environ.get("BENCH_CODE_ADAPT_PAIRS", "200000"))
    reps = max(2, int(os.environ.get("BENCH_CODE_ADAPT_REPS", "3")))
    delay_spec = os.environ.get(
        "BENCH_CODE_ADAPT_DELAY",
        "shuffle.fetch:p=0.4,seed=9,kind=delay,ms=15")
    fail_spec = "shuffle.fetch:p=0.2,seed=7"

    def hot(c):
        return (c.parallelize(range(n), 4)
                .map(lambda i: (i % 5003, i))
                .reduceByKey(operator.add, 4).count())

    def cold(c):
        return (c.parallelize(range(n), 4)
                .map(lambda i: (i % 5003, i))
                .reduceByKey(operator.add, 4).count())

    def graded_pass(ctx):
        """Time cold FIRST (its code choice must not see the delayed
        fetches), then hot under the injected per-peer delay; parity
        is the delta over exactly this window."""
        p0 = coding.parity_bytes()
        t_cold = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            assert cold(ctx) == min(5003, n)
            t_cold = min(t_cold, time.perf_counter() - t0)
        faults.configure(delay_spec)
        try:
            t_hot = 1e9
            for _ in range(reps):
                t0 = time.perf_counter()
                assert hot(ctx) == min(5003, n)
                t_hot = min(t_hot, time.perf_counter() - t0)
        finally:
            faults.configure(None)
        return t_hot, t_cold, coding.parity_bytes() - p0

    # --- static leg: one global rs(4,2) codes every exchange --------
    adapt.configure(mode="off")
    conf.CODE_ADAPT = False
    coding.configure("rs(4,2)")
    coding.clear_shuffle_codes()
    ctx = DparkContext("local")
    ctx.start()
    hot(ctx)
    cold(ctx)                           # warm imports / page cache
    t_hot_s, t_cold_s, parity_static = graded_pass(ctx)
    ctx.stop()

    # --- adaptive leg: same global, per-exchange re-pricing ---------
    adapt.configure(mode="on", store_dir=tempfile.mkdtemp(
        prefix="dpark-code-adapt-"))
    conf.CODE_ADAPT = True
    coding.configure("rs(4,2)")
    coding.clear_shuffle_codes()
    ctx = DparkContext("local")
    ctx.start()
    faults.configure(fail_spec)         # learn pass: hot decodes
    try:
        hot(ctx)
    finally:
        faults.configure(None)
    cold(ctx)                           # learn pass: cold stays clean
    # the serving peer's fetch-tail record (PR 14's input): tight —
    # only OBSERVED decode consumption may escalate an exchange
    sk = Sketch()
    for _ in range(35):
        sk.add(0.005)
    adapt.record_site_tail("fetch.bucket:local", sk.to_dict())
    t_hot_a, t_cold_a, parity_adapt = graded_pass(ctx)
    hist = coding.code_history()
    hot_escalated = any(c["applied"] and c["code"] != "off"
                        for c in hist)
    cold_pinned = any(c["applied"] and c["code"] == "off"
                      for c in hist)
    ctx.stop()
    coding.configure(None)
    coding.clear_shuffle_codes()
    conf.CODE_ADAPT = False

    # --- skew re-plan A/B on the multiprocess master ----------------
    # every key collides into ONE hash bucket; incompressible ~50-byte
    # values make the dominant bucket's fetch+merge the reduce-side
    # cost the re-split spreads across the worker pool
    nk = int(os.environ.get("BENCH_REPLAN_KEYS", "300000"))
    width = 4
    skew_keys = [k for k in range(nk * 5)
                 if portable_hash(k) % width == 0][:nk]
    skew_data = [(k, ("%d" % (k * 2654435761)) * 5)
                 for k in skew_keys] * 2
    expect = len(skew_keys)

    def skew(c):
        return (c.parallelize(skew_data, 4)
                .reduceByKey(operator.add, width).count())

    def reduce_wall(rec):
        # the RESULT stage's wall — the reduce side the re-plan grades
        return [st.get("seconds") for st in rec.get("stage_info", ())
                if not st.get("shuffle")][-1]

    adapt.configure(mode="on", store_dir=tempfile.mkdtemp(
        prefix="dpark-replan-"))
    old_replan = (conf.REPLAN, conf.REPLAN_MIN_BYTES)
    conf.REPLAN = False
    ctxp = DparkContext("process:2")
    ctxp.start()
    try:
        assert skew(ctxp) == expect     # warm the forkserver pool
        t_off = red_off = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            assert skew(ctxp) == expect
            t_off = min(t_off, time.perf_counter() - t0)
            red_off = min(red_off,
                          reduce_wall(ctxp.scheduler.history[-1]))
        conf.REPLAN = True
        conf.REPLAN_MIN_BYTES = 64
        t0 = time.perf_counter()
        assert skew(ctxp) == expect     # re-plans mid-job
        t_replan = time.perf_counter() - t0
        rec = ctxp.scheduler.history[-1]
        t_presalt = red_presalt = 1e9   # steady state: salted at plan
        for _ in range(reps):
            t0 = time.perf_counter()
            assert skew(ctxp) == expect
            t_presalt = min(t_presalt, time.perf_counter() - t0)
            red_presalt = min(red_presalt,
                              reduce_wall(ctxp.scheduler.history[-1]))
        rec2 = ctxp.scheduler.history[-1]
        replan = {
            "t_off_s": round(t_off, 3),
            "t_replan_s": round(t_replan, 3),
            "t_presalt_s": round(t_presalt, 3),
            "reduce_off_s": round(red_off, 3),
            "reduce_presalt_s": round(red_presalt, 3),
            "replans": int(rec.get("replans") or 0),
            "resubmits": int(rec.get("resubmits") or 0),
            "recomputes": int(rec.get("recomputes") or 0),
            "replan_reason": next(
                (st.get("replan_reason")
                 for st in rec.get("stage_info", ())
                 if st.get("replan_reason")), None),
            "presalt_replans": int(rec2.get("replans") or 0),
            "keys": nk, "width": width}
    finally:
        ctxp.stop()
        (conf.REPLAN, conf.REPLAN_MIN_BYTES) = old_replan
        adapt.configure(mode="observe")

    print("CODE_ADAPT_RESULT %s" % json.dumps(
        {"static": {"t_hot_s": round(t_hot_s, 3),
                    "t_cold_s": round(t_cold_s, 3),
                    "parity_bytes": parity_static},
         "adaptive": {"t_hot_s": round(t_hot_a, 3),
                      "t_cold_s": round(t_cold_a, 3),
                      "parity_bytes": parity_adapt},
         "hot_escalated": hot_escalated,
         "cold_pinned_uncoded": cold_pinned,
         "pairs": n, "reps": reps,
         "replan": replan}), flush=True)


def _svc_add(a, b):
    # module-level on purpose: the warm-submit A/B re-builds the DAG,
    # and a stable function identity is what lets the program cache
    # prove "0 re-compiles" on the second submission
    return a + b


def _svc_distinct(vs):
    # set() forces the host object path — the concurrent A/B wants one
    # device-bound job and one host-bound job so the service's slot
    # threads can genuinely overlap them
    return len(set(vs))


def _service_phase():
    """Child-process entry: resident-service A/B (ISSUE 9 acceptance).

    warm-submit: the same DAG submitted twice to one resident server —
    the second submission must hit the compiled-program cache for
    every stage (0 compiles, asserted from the cache counters) and
    show a far lower submit-to-first-wave latency.

    concurrent: one device-bound job and one host-bound job, solo then
    concurrently — the combined wall vs the slower solo wall measures
    how much of the mesh the fair dispatcher keeps busy."""
    import threading

    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext
    from dpark_tpu import conf as _conf
    n = int(os.environ.get("BENCH_SERVICE_PAIRS",
                           os.environ.get("BENCH_PAIRS", "500000")))
    # per-tenant SLO accounting (ISSUE 14): declare a generous default
    # target so the A/B records attainment for the service cell (the
    # smoke gate asserts the tenants section is present and graded)
    _conf.SERVICE_SLO_MS = float(os.environ.get(
        "BENCH_SERVICE_SLO_MS", "60000"))
    ctx = DparkContext("service:tpu")
    ctx.start()
    sched = ctx.scheduler
    ndev = sched.executor.ndev
    i = np.arange(n, dtype=np.int64)
    data = Columns((i * 2654435761) % 4096, np.ones(n, np.int64))

    def submit():
        t0 = time.perf_counter()
        out = dict(ctx.parallelize(data, ndev)
                   .reduceByKey(_svc_add, ndev).collect())
        return time.perf_counter() - t0, out

    ex = sched.executor
    pc0 = ex.program_cache_stats()
    t_cold, out_cold = submit()
    rec_cold = dict(sched.history[-1])
    pc1 = ex.program_cache_stats()
    t_warm, out_warm = submit()
    rec_warm = dict(sched.history[-1])
    pc2 = ex.program_cache_stats()
    assert out_cold == out_warm, "warm submission changed the answer"
    cold = {"wall_s": round(t_cold, 3),
            "first_wave_ms": rec_cold.get("first_wave_ms"),
            "compiles": pc1["misses"] - pc0["misses"],
            "cache_hits": pc1["hits"] - pc0["hits"]}
    warm = {"wall_s": round(t_warm, 3),
            "first_wave_ms": rec_warm.get("first_wave_ms"),
            "compiles": pc2["misses"] - pc1["misses"],
            "cache_hits": pc2["hits"] - pc1["hits"]}

    datb = [(int(k), int(v))
            for k, v in zip(i[:n // 4] % 257, i[:n // 4])]

    # the concurrent cell runs as TWO named tenants (ISSUE 15): the
    # ledger must attribute each one's mesh consumption separately,
    # and their device-seconds must reconcile with mesh busy time.
    # Tracing starts HERE, not around the warm/cold submits above —
    # service_warm_submit must keep measuring what it always did
    # (PR 9's acceptance record is untraced), and conservation grades
    # over the meter delta of the traced window only.
    from dpark_tpu import ledger, trace
    trace.configure("ring")
    ledger.configure("on")
    meter0 = ledger.mesh_meter(sched)
    from dpark_tpu.service import ClientScheduler
    ten_a = ClientScheduler(sched.server, client="tenant-a")
    ten_b = ClientScheduler(sched.server, client="tenant-b")

    def _collect(tenant, rdd):
        return dict(x for part in tenant.run_job(
            rdd, lambda it: list(it)) for x in part)

    def job_a():
        return _collect(ten_a, ctx.parallelize(data, ndev)
                        .reduceByKey(_svc_add, ndev))

    def job_b():
        return _collect(ten_b, ctx.parallelize(datb, 4).groupByKey(4)
                        .mapValue(_svc_distinct))

    t0 = time.perf_counter()
    ref_a = job_a()
    t_a = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref_b = job_b()
    t_b = time.perf_counter() - t0
    got = {}
    th = threading.Thread(target=lambda: got.update(a=job_a()))
    t0 = time.perf_counter()
    th.start()
    got["b"] = job_b()
    th.join()
    t_conc = time.perf_counter() - t0
    parity = got["a"] == ref_a and got["b"] == ref_b
    conc = {"t_a_solo_s": round(t_a, 3), "t_b_solo_s": round(t_b, 3),
            "t_concurrent_s": round(t_conc, 3),
            "ratio_vs_slower_solo": round(
                t_conc / max(t_a, t_b, 1e-9), 3),
            "parity": bool(parity)}
    jobs = [{"id": r["id"], "client": r.get("client"),
             "queue_wait_ms": r.get("queue_wait_ms")}
            for r in sched.history if r.get("service")]
    stats = sched.service_stats()
    meter_delta = ledger.meter_delta(meter0,
                                     ledger.mesh_meter(sched))
    out = {"cold": cold, "warm": warm, "concurrent": conc,
           "pairs": n, "ndev": ndev,
           "service": stats, "jobs": jobs,
           # per-tenant SLO attainment (ISSUE 14)
           "slo": stats.get("tenants", {}),
           # per-tenant resource attribution + the conservation check
           # (ISSUE 15 acceptance: attributed device-seconds within
           # 10% of measured mesh busy time across the two tenants)
           "ledger": {"tenants": ledger.tenant_totals(),
                      "conservation": ledger.conservation(
                          meter=meter_delta)}}
    trace.configure("off")
    from dpark_tpu import service as service_mod
    service_mod.shutdown()
    print("SERVICE_RESULT %s" % json.dumps(out), flush=True)


def _aot_step_phase():
    """Grandchild entry for the AOT restart A/B: ONE fresh process
    submitting the module-level reduceByKey DAG once against whatever
    DPARK_AOT_CACHE_DIR already holds.  Reports the first-submission
    wall, the number of BACKEND compiles (via jax.monitoring — a fresh
    process always misses the in-memory program-cache tier, so those
    counters cannot distinguish a disk hit from a recompile), and the
    AOT plane's own counters."""
    import numpy as np
    import jax
    compiles = [0]
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: compiles.__setitem__(
                0, compiles[0] + 1)
            if "backend_compile" in event else None)
    except Exception:
        compiles[0] = -1        # listener unavailable: mark unknown
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext, aotcache
    n = int(os.environ.get("BENCH_AOT_PAIRS",
                           os.environ.get("BENCH_PAIRS", "200000")))
    i = np.arange(n, dtype=np.int64)
    data = Columns((i * 2654435761) % 4096, np.ones(n, np.int64))
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    t0 = time.perf_counter()
    out = dict(ctx.parallelize(data, ndev)
               .reduceByKey(_svc_add, ndev).collect())
    wall = time.perf_counter() - t0
    # order-independent checksum: the cold and warm PROCESS must agree
    # on the answer, and neither side can ship the whole dict up
    csum = sum((int(k) * 1000003 + int(v)) % ((1 << 61) - 1)
               for k, v in out.items()) % ((1 << 61) - 1)
    payload = {"wall_s": round(wall, 4),
               "backend_compiles": compiles[0],
               "keys": len(out), "checksum": csum,
               "aot": aotcache.stats(), "ndev": ndev}
    ctx.stop()
    print("AOT_STEP %s" % json.dumps(payload), flush=True)


def _aot_phase():
    """Child entry: AOT restart A/B (ISSUE 17 acceptance).  Two FRESH
    processes submit the identical DAG sharing one on-disk AOT cache
    dir: the cold one populates it (backend compiles > 0, stores > 0),
    the warm one must deserialize every executable back off disk —
    0 backend compiles — and agree bit-for-bit on the answer."""
    import shutil
    import tempfile
    root = tempfile.mkdtemp(prefix="dpark-aot-bench-")
    step_env = {"DPARK_AOT_CACHE": "on",
                "DPARK_AOT_CACHE_DIR": os.path.join(root, "cache"),
                "DPARK_ADAPT_DIR": os.path.join(root, "adapt"),
                "DPARK_WORK_DIR": os.path.join(root, "work")}
    timeout = int(os.environ.get("BENCH_AOT_STEP_TIMEOUT", "300"))
    try:
        cold = _run_child("--aot-step", timeout, env=step_env,
                          ok_prefix="AOT_STEP ")
        warm = _run_child("--aot-step", timeout, env=step_env,
                          ok_prefix="AOT_STEP ")
        if cold is None or warm is None:
            raise SystemExit("aot restart step child failed")
        c, w = json.loads(cold), json.loads(warm)
        out = {"cold": c, "warm": w,
               "parity": bool(c["checksum"] == w["checksum"]
                              and c["keys"] == w["keys"])}
        print("AOT_RESULT %s" % json.dumps(out), flush=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _recovery_step_phase():
    """Grandchild entry for the crash-recovery certification (ISSUE
    20): ONE controller process running a 2-stage reduceByKey on the
    pure-Python local master under whatever DPARK_JOURNAL /
    DPARK_FAULTS the parent armed.  When the chaos spec kills it at
    the first reduce fetch it dies with os._exit(137) AFTER the map
    stage journaled; the next invocation over the same journal dir
    must replay that stage (resumed_stages >= 1, 0 recomputes) and
    agree on the order-independent checksum."""
    from dpark_tpu import DparkContext, journal, trace
    trace.configure("ring")
    n = int(os.environ.get("BENCH_RECOVERY_PAIRS", "100000"))
    ctx = DparkContext("local")
    ctx.start()
    t0 = time.perf_counter()
    out = dict(ctx.parallelize([(i % 4096, i) for i in range(n)], 8)
               .reduceByKey(_svc_add, 8).collect())
    wall = time.perf_counter() - t0
    csum = sum((int(k) * 1000003 + int(v)) % ((1 << 61) - 1)
               for k, v in out.items()) % ((1 << 61) - 1)
    rec = ctx.scheduler.history[-1]
    replay_traced = any(ev.get("name") == "journal.replay"
                        for ev in trace.snapshot())
    payload = {"wall_s": round(wall, 4), "keys": len(out),
               "checksum": csum,
               "resumed_stages": rec.get("resumed_stages") or 0,
               "seeded_partitions": rec.get("seeded_partitions") or 0,
               "recomputes": rec.get("recomputes", 0),
               "resubmits": rec.get("resubmits", 0),
               "replay_traced": replay_traced,
               "journal": journal.stats()}
    ctx.stop()
    print("RECOVERY_STEP %s" % json.dumps(payload), flush=True)


def _recovery_phase():
    """Child entry: kill -9 chaos certification + journal overhead A/B
    (ISSUE 20 acceptance).  Four fresh controller processes: journal
    OFF baseline, journal ON (the <=1.02x overhead pair), a VICTIM
    that the chaos plane os._exit(137)s at its first reduce fetch (no
    ok-line — the kill is the expected outcome), and a RESUME run over
    the victim's journal + work dirs that must complete bit-identically
    with resumed_stages >= 1 and 0 recomputes."""
    import shutil
    import tempfile
    root = tempfile.mkdtemp(prefix="dpark-recovery-bench-")
    timeout = int(os.environ.get("BENCH_RECOVERY_STEP_TIMEOUT", "180"))

    def env_for(tag, journal="on", faults=""):
        return {"DPARK_JOURNAL": journal,
                "DPARK_JOURNAL_DIR": os.path.join(root, tag, "jnl"),
                "DPARK_WORK_DIR": os.path.join(root, tag, "work"),
                "DPARK_FAULTS": faults,
                "JAX_PLATFORMS": "cpu"}

    try:
        off = _run_child("--recovery-step", timeout,
                         env=env_for("off", journal="off"),
                         ok_prefix="RECOVERY_STEP ")
        on = _run_child("--recovery-step", timeout, env=env_for("on"),
                        ok_prefix="RECOVERY_STEP ")
        chaos_env = env_for("chaos")
        victim = _run_child(
            "--recovery-step", timeout,
            env=dict(chaos_env,
                     DPARK_FAULTS="shuffle.fetch:nth=1,kind=kill"),
            ok_prefix="RECOVERY_STEP ")
        resume = _run_child("--recovery-step", timeout, env=chaos_env,
                            ok_prefix="RECOVERY_STEP ")
        if off is None or on is None or resume is None:
            raise SystemExit("recovery step child failed")
        o, j, r = json.loads(off), json.loads(on), json.loads(resume)
        out = {"off": o, "on": j, "resume": r,
               "victim_killed": victim is None,
               "overhead": round(j["wall_s"] / max(o["wall_s"], 1e-9),
                                 3),
               "parity": bool(o["checksum"] == j["checksum"]
                              == r["checksum"]),
               "resumed_stages": r.get("resumed_stages", 0),
               "recomputes": r.get("recomputes", 0),
               "replay_traced": bool(r.get("replay_traced"))}
        print("RECOVERY_RESULT %s" % json.dumps(out), flush=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _reuse_data(d, n):
    """Deterministic tabular part file for the reuse cells (written
    once per dir — the restart step's two processes must fingerprint
    identically)."""
    import numpy as np
    from dpark_tpu.tabular import write_tabular
    part = os.path.join(d, "part-00000.tab")
    if os.path.exists(part):
        return part
    os.makedirs(d, exist_ok=True)
    i = np.arange(n, dtype=np.int64)
    write_tabular(part, ["t", "k", "f"],
                  zip(i.tolist(), ((i * 2654435761) % 997).tolist(),
                      ((i % 1000) * 0.25).tolist()),
                  chunk_rows=1 << 14)
    return part


def _reuse_checksum(rows):
    import zlib
    return zlib.crc32(repr(rows).encode("utf-8")) & 0xFFFFFFFF


def _reuse_scan(pq):
    """JSON-safe scan_stats (columns_read is a set)."""
    return {k: (sorted(v) if isinstance(v, set) else v)
            for k, v in (pq.scan_stats if pq is not None else {})
            .items()}


def _reuse_phase():
    """Child entry: shared-computation plane A/B (ISSUE 18
    acceptance).  Cell 1 — two named tenants run the IDENTICAL
    ctx.sql group-by: tenant-a pays the scan + device exchange and
    populates the cache; tenant-b's run must plan into a full cache
    hit (zero scan chunks, ledger-proven: no device-seconds, a
    resultcache hit billed to tenant-b).  Cell 2 — partial-aggregate
    reuse: a cached aggregate over 95% of the rows serves a wider
    query through a residual scan of the remaining 5%, beating the
    cold run while staying bit-identical to the plane-off answer."""
    import tempfile

    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import ledger, resultcache, trace
    from dpark_tpu import service as service_mod
    n = int(os.environ.get("BENCH_REUSE_ROWS", "200000"))
    mode = os.environ.get("BENCH_REUSE_CACHE", "mem")
    d = tempfile.mkdtemp(prefix="bench_reuse_")
    _reuse_data(d, n)
    trace.configure("ring")
    ledger.configure("on")
    resultcache.configure(mode)
    server = service_mod.get_server("local")
    server.start()
    ctx_a = service_mod._context_for(server, "tenant-a")
    ctx_b = service_mod._context_for(server, "tenant-b")
    sql = ("select k, sum(f) as s, count(t) as c from events "
           "where t >= 1000 group by k")

    def run_sql(ctx):
        t = ctx.tabular(d, ["t", "k", "f"]).asTable("events")
        q = ctx.sql(sql, events=t)
        t0 = time.perf_counter()
        rows = sorted(q.collect())
        return time.perf_counter() - t0, rows, q

    t_cold, rows_a, qa = run_sql(ctx_a)
    t_warm, rows_b, qb = run_sql(ctx_b)
    st = resultcache.stats() or {}
    pq_b = qb._planned()
    scan_warm = _reuse_scan(pq_b)
    pq_a = qa._planned()
    scan_cold = _reuse_scan(pq_a)
    # ledger proof BEFORE the partial cell muddies tenant-b: the
    # served tenant must show a resultcache hit and NO device time
    tenants = ledger.tenant_totals()
    reuse_cell = {
        "t_cold_s": round(t_cold, 4), "t_warm_s": round(t_warm, 4),
        "speedup": round(t_cold / max(t_warm, 1e-9), 2),
        "parity": bool(rows_a == rows_b),
        "scan_cold": scan_cold, "scan_warm": scan_warm,
        "hits": st.get("hits", 0), "stores": st.get("stores", 0),
        "tenant_b": tenants.get("tenant-b", {}),
        "tenant_a_device_s": tenants.get("tenant-a", {})
        .get("device_seconds", 0.0)}

    # cell 2: partial-aggregate reuse.  Fresh plane so the cell
    # stands alone; the cached entry covers t >= n/20 (95% of rows),
    # the reuse query wants everything — the probe merges the cached
    # aggregate with a residual scan of t <= n/20-1 (chunk-skipped
    # to ~5% of the file).
    resultcache.configure(mode)
    lo = n // 20

    def run_where(ctx, where):
        q = ctx.tabular(d, ["t", "k", "f"]).asTable("events") \
            .where(where).groupBy("k", "sum(f) as s", "count(t) as c")
        t0 = time.perf_counter()
        rows = sorted(q.collect())
        return time.perf_counter() - t0, rows, q

    t_pcold, _, _ = run_where(ctx_a, "t >= %d" % lo)
    t_preuse, rows_part, qp = run_where(ctx_b, "t >= 0")
    stp = resultcache.stats() or {}
    pq_p = qp._planned()
    scan_part = _reuse_scan(pq_p)
    resultcache.configure("off")
    _, rows_off, _ = run_where(ctx_b, "t >= 0")
    partial_cell = {
        "t_cold_s": round(t_pcold, 4),
        "t_reuse_s": round(t_preuse, 4),
        "speedup": round(t_pcold / max(t_preuse, 1e-9), 2),
        "parity": bool(rows_part == rows_off),
        "partial_hits": stp.get("partial_hits", 0),
        "scan_reuse": scan_part}

    out = {"mode": mode, "rows": n, "reuse": reuse_cell,
           "partial": partial_cell,
           "conservation": ledger.conservation()}
    trace.configure("off")
    service_mod.shutdown()
    print("REUSE_RESULT %s" % json.dumps(out), flush=True)


def _reuse_step_phase():
    """Grandchild entry for the disk-tier restart smoke: ONE fresh
    process running the reuse query against whatever
    DPARK_RESULT_CACHE_DIR already holds (DPARK_RESULT_CACHE=disk in
    the env).  The first run scans and stores; a second process must
    boot the entry back and serve it with zero scan chunks and a
    bit-identical checksum."""
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import resultcache
    from dpark_tpu import service as service_mod
    n = int(os.environ.get("BENCH_REUSE_ROWS", "200000"))
    d = os.environ["DPARK_REUSE_DATA"]
    _reuse_data(d, n)
    server = service_mod.get_server("local")
    server.start()          # disk mode: boots hot entries to memory
    ctx = service_mod._context_for(server, "tenant-restart")
    t0 = time.perf_counter()
    q = ctx.tabular(d, ["t", "k", "f"]).asTable("events") \
        .where("t >= 1000").groupBy("k", "sum(f) as s",
                                    "count(t) as c")
    rows = sorted(q.collect())
    wall = time.perf_counter() - t0
    pq = q._planned()
    st = resultcache.stats() or {}
    out = {"wall_s": round(wall, 4), "groups": len(rows),
           "checksum": _reuse_checksum(rows),
           "scan": _reuse_scan(pq),
           "hits": st.get("hits", 0), "stores": st.get("stores", 0),
           "preloaded": st.get("preloaded", 0),
           "boot": getattr(server, "_rc_boot", None)}
    service_mod.shutdown()
    print("REUSE_STEP %s" % json.dumps(out), flush=True)


def _health_phase():
    """Child-process entry: health-plane overhead A/B (ISSUE 14
    acceptance).  The same ring-traced device reduceByKey with the
    streaming health sink OFF vs ON — folding every span into the
    sketches must cost <= 3% wall.  Also reports the nonzero site
    count the CI smoke gates (the sink actually observed the run)."""
    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext, health, trace
    n = int(os.environ.get("BENCH_HEALTH_PAIRS",
                           os.environ.get("BENCH_PAIRS", "500000")))
    i = np.arange(n, dtype=np.int64)
    data = Columns((i * 2654435761) % 4096, i & 0xFFFF)
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    trace.configure("ring")

    def run():
        t0 = time.perf_counter()
        cnt = (ctx.parallelize(data, ndev)
               .reduceByKey(_svc_add, ndev).count())
        assert cnt == min(4096, n), cnt
        return time.perf_counter() - t0

    reps = int(os.environ.get("BENCH_HEALTH_REPS", "3"))
    health.configure("off")
    run()                                      # warm-up compile
    t_off = min(run() for _ in range(reps))
    health.configure("on")
    run()                                      # fold path warm
    t_on = min(run() for _ in range(reps))
    sites = len(health.summary()["sites"])
    trace.configure("off")
    payload = {"t_off": round(t_off, 4), "t_on": round(t_on, 4),
               "sites": sites, "pairs": n, "ndev": ndev}
    ctx.stop()
    print("HEALTH_RESULT %s" % json.dumps(payload), flush=True)


def _ledger_phase():
    """Child-process entry: ledger-plane overhead A/B (ISSUE 15
    acceptance, riding the health_plane_overhead pattern).  The same
    ring-traced device reduceByKey with the attribution sink OFF vs
    ON — folding every span into the per-(tenant, job, stage,
    program) accounts must cost <= 3% wall.  Also reports the nonzero
    account count and the conservation check (attributed
    device-seconds vs measured mesh-lock busy time) the CI smoke
    gates."""
    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext, ledger, trace
    n = int(os.environ.get("BENCH_LEDGER_PAIRS",
                           os.environ.get("BENCH_PAIRS", "500000")))
    i = np.arange(n, dtype=np.int64)
    data = Columns((i * 2654435761) % 4096, i & 0xFFFF)
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    trace.configure("ring")

    def run():
        t0 = time.perf_counter()
        cnt = (ctx.parallelize(data, ndev)
               .reduceByKey(_svc_add, ndev).count())
        assert cnt == min(4096, n), cnt
        return time.perf_counter() - t0

    reps = int(os.environ.get("BENCH_LEDGER_REPS", "3"))
    ledger.configure("off")
    run()                                      # warm-up compile
    t_off = min(run() for _ in range(reps))
    # conservation is graded over the ON window only: the sink starts
    # empty here, so the meter baseline must too (the off leg's mesh
    # time was deliberately unobserved)
    meter0 = ledger.mesh_meter(ctx.scheduler)
    ledger.configure("on")
    run()                                      # fold path warm
    t_on = min(run() for _ in range(reps))
    summ = ledger.summary()
    cons = ledger.conservation(meter=ledger.meter_delta(
        meter0, ledger.mesh_meter(ctx.scheduler)))
    trace.configure("off")
    payload = {"t_off": round(t_off, 4), "t_on": round(t_on, 4),
               "accounts": summ["accounts"],
               "tenants": summ["tenants"],
               "conservation": cons, "pairs": n, "ndev": ndev}
    ctx.stop()
    print("LEDGER_RESULT %s" % json.dumps(payload), flush=True)


def _lockcheck_phase():
    """Child-process entry: lock-sanitizer overhead A/B (ISSUE 16
    acceptance).  The same ring-traced device reduceByKey with the
    named-lock registry OFF (one `is None` check per acquisition, the
    plane contract) vs RECORD (per-thread order stacks + process-wide
    edge merge) — arming the sanitizer must cost <= 3% wall.  Also
    reports the acquisition/edge counts and that the observed graph
    stayed acyclic (a cycle here is a real ordering bug, not an
    overhead artifact)."""
    import numpy as np
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    from dpark_tpu import Columns, DparkContext, locks, trace
    n = int(os.environ.get("BENCH_LOCKCHECK_PAIRS",
                           os.environ.get("BENCH_PAIRS", "500000")))
    i = np.arange(n, dtype=np.int64)
    data = Columns((i * 2654435761) % 4096, i & 0xFFFF)
    ctx = DparkContext("tpu")
    ctx.start()
    ndev = ctx.scheduler.executor.ndev
    trace.configure("ring")

    def run():
        t0 = time.perf_counter()
        cnt = (ctx.parallelize(data, ndev)
               .reduceByKey(_svc_add, ndev).count())
        assert cnt == min(4096, n), cnt
        return time.perf_counter() - t0

    reps = int(os.environ.get("BENCH_LOCKCHECK_REPS", "7"))
    locks.configure("off")
    run()                                      # warm-up compile
    locks.configure("record")
    run()                                      # record path warm
    offs, ons = [], []
    rep = None
    for _ in range(reps):          # interleaved A/B: clock drift and
        locks.configure("off")     # cache effects hit both sides
        offs.append(run())
        locks.configure("record")  # fresh sanitizer per pass; `rep`
        ons.append(run())          # keeps the final pass's graph
        rep = locks.report()
    # the headline ratio is the MEDIAN of per-pass paired ratios:
    # adjacent off/on passes share whatever the box was doing, so the
    # pair cancels drift that min-of-walls across the whole block
    # does not (observed 1.09x "overhead" from pure scheduler noise)
    paired = sorted(b / max(a, 1e-9) for a, b in zip(offs, ons))
    ratio = paired[len(paired) // 2]
    t_off, t_on = min(offs), min(ons)
    locks.configure("off")
    trace.configure("off")
    payload = {"t_off": round(t_off, 4), "t_on": round(t_on, 4),
               "ratio": round(ratio, 3),
               "acquisitions": rep["acquisitions"],
               "locks": len(rep["locks"]), "edges": len(rep["edges"]),
               "cycles": len(rep["cycles"]),
               "order_violations": len(rep["order_violations"]),
               "pairs": n, "ndev": ndev}
    ctx.stop()
    print("LOCKCHECK_RESULT %s" % json.dumps(payload), flush=True)


def _probe_phase():
    """Child-process entry: initialize the device backend and say what
    it is — the parent sizes the workload by it and refuses a CPU it
    did not ask for."""
    import jax
    if os.environ.get("BENCH_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["BENCH_PLATFORM"])
    devs = jax.devices()
    import jax.numpy as jnp
    jnp.ones((8,)).block_until_ready()       # end-to-end: compile + run
    print("PROBE_OK %d %s" % (len(devs), devs[0].platform), flush=True)


_FAILED_PHASES = []


def _run_child(arg, timeout, env=None, ok_prefix="TPU_RESULT "):
    """Run `python bench.py <arg>` in its own process group with a hard
    timeout; return the payload line, or None after recording the phase
    in _FAILED_PHASES (main() then exits non-zero).  File-backed output
    + the process group SIGKILL mean a hung child cannot hang the
    parent or leak grandchildren."""
    import signal
    import subprocess
    import tempfile
    child_env = dict(os.environ, **(env or {}))
    with tempfile.TemporaryFile("w+") as so, \
            tempfile.TemporaryFile("w+") as se:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), arg],
            stdout=so, stderr=se, text=True, start_new_session=True,
            env=child_env)
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except OSError:
                pass
            proc.wait()
            print("# %s timed out after %ss" % (arg, timeout),
                  file=sys.stderr)
            _FAILED_PHASES.append(arg)
            return None
        so.seek(0)
        for line in so.read().splitlines():
            if line.startswith(ok_prefix):
                return line[len(ok_prefix):]
        se.seek(0)
        print("# %s failed:\n%s" % (arg, se.read()[-1500:]),
              file=sys.stderr)
        _FAILED_PHASES.append(arg)
        return None


def _probe_device():
    """One probe of device init in a child: the platform jax found, or
    SystemExit when it found none — or found the CPU although
    BENCH_PLATFORM did not ask for it (jax falls back to the CPU
    silently when no accelerator initialises)."""
    got = _run_child("--probe",
                     int(os.environ.get("BENCH_PROBE_TIMEOUT", 120)),
                     ok_prefix="PROBE_OK ")
    if got is None:
        sys.exit("bench: the device probe failed; nothing was measured")
    n, platform = got.split()
    want = os.environ.get("BENCH_PLATFORM")
    if (platform != want) if want else (platform == "cpu"):
        sys.exit("bench: jax found platform %r, wanted %s; nothing was "
                 "measured (BENCH_PLATFORM=cpu asks for the CPU mesh)"
                 % (platform, want or "an accelerator"))
    print("# device probe ok: %s x%s" % (platform, n), file=sys.stderr)
    return platform


def _run_tpu_with_timeout(timeout, env=None):
    got = _run_child("--tpu-only", timeout, env=env)
    if got is None:
        return None
    stats = json.loads(got)
    return stats.pop("t"), stats.pop("ndev"), stats


def main():
    global N_PAIRS, BYTES
    if "--tpu-only" in sys.argv:
        _tpu_phase()
        return
    if "--ooc-only" in sys.argv:
        _ooc_phase()
        return
    if "--join-only" in sys.argv:
        _join_phase()
        return
    if "--tuple-only" in sys.argv:
        _tuple_phase()
        return
    if "--groupmap-only" in sys.argv:
        _groupmap_phase()
        return
    if "--stream-only" in sys.argv:
        _stream_phase()
        return
    if "--wc-only" in sys.argv:
        _wc_phase()
        return
    if "--sg-only" in sys.argv:
        _sg_phase()
        return
    if "--coded-only" in sys.argv:
        _coded_phase()
        return
    if "--bulk-only" in sys.argv:
        _bulk_phase()
        return
    if "--adapt-only" in sys.argv:
        _adapt_phase()
        return
    if "--code-adapt-only" in sys.argv:
        _code_adapt_phase()
        return
    if "--service-only" in sys.argv:
        _service_phase()
        return
    if "--aot-only" in sys.argv:
        _aot_phase()
        return
    if "--aot-step" in sys.argv:
        _aot_step_phase()
        return
    if "--reuse-only" in sys.argv:
        _reuse_phase()
        return
    if "--recovery-only" in sys.argv:
        _recovery_phase()
        return
    if "--recovery-step" in sys.argv:
        _recovery_step_phase()
        return
    if "--reuse-step" in sys.argv:
        _reuse_step_phase()
        return
    if "--health-only" in sys.argv:
        _health_phase()
        return
    if "--ledger-only" in sys.argv:
        _ledger_phase()
        return
    if "--lockcheck-only" in sys.argv:
        _lockcheck_phase()
        return
    if "--table-only" in sys.argv:
        _table_phase()
        return
    if "--probe" in sys.argv:
        _probe_phase()
        return
    # probe FIRST: an accelerator raises the default workload out of
    # toy range, and a machine without one is refused before anything
    # is timed.  An explicitly requested platform (BENCH_PLATFORM=cpu
    # in CI) keeps the toy size.
    global JOIN_FACT, WC_MB, SG_PAIRS
    platform = _probe_device()
    if platform != "cpu":
        if "BENCH_PAIRS" not in os.environ:
            N_PAIRS = N_PAIRS_DEVICE_DEFAULT
            BYTES = N_PAIRS * 16
            os.environ["BENCH_PAIRS"] = str(N_PAIRS)   # child agrees
        if "BENCH_JOIN_FACT" not in os.environ:
            JOIN_FACT = JOIN_FACT_DEVICE_DEFAULT
            os.environ["BENCH_JOIN_FACT"] = str(JOIN_FACT)
        if "BENCH_WC_MB" not in os.environ:
            WC_MB = WC_MB_DEVICE_DEFAULT
            os.environ["BENCH_WC_MB"] = str(WC_MB)
        if "BENCH_SG_PAIRS" not in os.environ:
            SG_PAIRS = SG_PAIRS_DEVICE_DEFAULT
            os.environ["BENCH_SG_PAIRS"] = str(SG_PAIRS)
    data = make_data()
    t_proc = bench_process(data)
    del data                 # the child regenerates its own copy
    extras = os.environ.get("BENCH_EXTRAS", "1") != "0"
    t_join_proc = bench_join_process() if extras else None
    t_stream_proc = bench_stream_process() if extras else None
    t_wc_proc = bench_wc_process(_wc_corpus()) if extras else None
    t_sg_proc = bench_sg_process() if extras else None
    tpu = _run_tpu_with_timeout(
        int(os.environ.get("BENCH_TPU_TIMEOUT", 900)))
    if tpu is None:
        sys.exit("bench: the tpu phase produced no result; no metric "
                 "line was printed")
    t_tpu, ndev, stats = tpu
    gbps_chip = BYTES / t_tpu / 1e9 / ndev
    gbps_proc = BYTES / t_proc / 1e9
    sort_roof = stats.get("sort_roofline_gbps", 0.0)
    out = {
        "metric": "reduceByKey_GBps_per_chip",
        "platform": platform,
        "value": round(gbps_chip, 4),
        "unit": "GB/s/chip",
        "vs_baseline": round(t_proc / t_tpu, 2),
    }
    if sort_roof:
        # distance to the chip's own jnp.sort bound, same session
        out["pct_of_sort_roofline"] = round(
            100.0 * gbps_chip / sort_roof, 2)
        out["sort_roofline_gbps"] = sort_roof
    out["pad_efficiency"] = stats.get("pad_efficiency")
    out["pad_kind"] = stats.get("pad_kind")
    print(json.dumps(out))
    print("# pairs=%d keys=%d chips=%d tpu=%.3fs process=%.3fs "
          "(process=%.4f GB/s) exchange_wire_bytes=%d "
          "pad_efficiency=%s (%s)"
          % (N_PAIRS, N_KEYS, ndev, t_tpu, t_proc, gbps_proc,
             stats.get("wire_bytes", 0), stats.get("pad_efficiency"),
             stats.get("pad_kind")),
          file=sys.stderr)
    child_timeout = int(os.environ.get("BENCH_TPU_TIMEOUT", 900))

    # second line: the out-of-core wave-stream config
    if os.environ.get("BENCH_OOC_GB") != "0":
        got = _run_child("--ooc-only", child_timeout,
                         ok_prefix="OOC_RESULT ")
        if got is not None:
            ooc = json.loads(got)
            ooc = dict({"metric": "ooc_reduceByKey_GBps_per_chip",
                        "value": ooc.pop("gbps_per_chip"),
                        "unit": "GB/s/chip"}, **ooc)
            if sort_roof:
                ooc["pct_of_sort_roofline"] = round(
                    100.0 * ooc["value"] / sort_roof, 2)
            print(json.dumps(ooc))
    # composite-key A/B (ISSUE 3 acceptance): tuple-key reduceByKey
    # wall vs the equivalent scalar-key job — must be within 1.3x now
    # that tuple keys ride the device (the object path was 10x+ off)
    if os.environ.get("BENCH_TUPLE", "1") != "0":
        got = _run_child("--tuple-only", child_timeout,
                         ok_prefix="TUPLE_RESULT ")
        if got is not None:
            tp = json.loads(got)
            tout = {"metric": "tuple_key_reduce_vs_scalar",
                    "value": round(tp["t_tuple"]
                                   / max(tp["t_scalar"], 1e-9), 3),
                    "unit": "x (lower is better; <=1.3 passes)",
                    "t_scalar_s": round(tp["t_scalar"], 3),
                    "t_tuple_s": round(tp["t_tuple"], 3),
                    "pairs": tp["pairs"], "chips": tp["ndev"],
                    "tuple_rode_array_path": tp["array_path"]}
            print(json.dumps(tout))
    # segmented-apply A/B (ISSUE 4 acceptance): the same grouped
    # consumer on the device SegMapOp path vs the host object path —
    # the widest remaining host pocket after tuple keys went device
    if os.environ.get("BENCH_GROUPMAP", "1") != "0":
        got = _run_child("--groupmap-only", child_timeout,
                         ok_prefix="GROUPMAP_RESULT ")
        if got is not None:
            gm = json.loads(got)
            gout = {"metric": "group_mapvalues_device_vs_host",
                    # consume-stage seconds: both sides share the same
                    # device shuffle write, so the ratio isolates the
                    # segmented apply vs the object path
                    "value": round(gm["t_host"]
                                   / max(gm["t_device"], 1e-9), 3),
                    "unit": "x (higher is better; >=5 passes)",
                    "t_device_s": round(gm["t_device"], 3),
                    "t_host_s": round(gm["t_host"], 3),
                    "wall_device_s": round(gm["wall_device"], 3),
                    "wall_host_s": round(gm["wall_host"], 3),
                    "pairs": gm["pairs"], "chips": gm["ndev"],
                    "device_rode_array_path": gm["device_array_path"]}
            print(json.dumps(gout))
    # coded-shuffle overhead A/B (ISSUE 6 acceptance): the same
    # shuffle-heavy host-path job with the erasure code off vs
    # rs(4,2), no faults — the premium paid for decode-not-recompute
    # recovery must stay <= 15% wall
    if os.environ.get("BENCH_CODED", "1") != "0":
        got = _run_child("--coded-only", child_timeout,
                         ok_prefix="CODED_RESULT ")
        if got is not None:
            c = json.loads(got)
            cout = {"metric": "coded_shuffle_overhead",
                    "value": round(c["t_on"]
                                   / max(c["t_off"], 1e-9), 3),
                    "unit": "x (lower is better; <=1.15 passes)",
                    "t_off_s": round(c["t_off"], 3),
                    "t_on_s": round(c["t_on"], 3),
                    "pairs": c["pairs"],
                    "coding": c["decodes"]}
            print(json.dumps(cout))
    # columnar query plane A/B (ISSUE 13 acceptance): the same
    # select+filter+group-by query over the same tabular input — the
    # scan-pruned device plan (vectorized column scan + device group
    # exchange) vs the pre-plan host row path (per-row Python eval).
    # >= 3x at 2M rows on the 2-dev CPU mesh with bit-identical rows.
    if os.environ.get("BENCH_TABLE", "1") != "0":
        got = _run_child("--table-only", child_timeout,
                         ok_prefix="TABLE_RESULT ")
        if got is not None:
            tb = json.loads(got)
            tbo = {"metric": "table_query_device_vs_host",
                   "value": round(tb["t_host"]
                                  / max(tb["t_device"], 1e-9), 3),
                   "unit": "x (higher is better; >=3 passes)",
                   "t_device_s": round(tb["t_device"], 3),
                   "t_host_s": round(tb["t_host"], 3),
                   "rows": tb["rows"], "chips": tb["ndev"],
                   "parity": tb["parity"],
                   "device_all_array": tb["device_all_array"],
                   "columns_total": tb["columns_total"],
                   "scan": tb["scan"]}
            print(json.dumps(tbo))
    # bulk-channel vs pickled-bridge A/B (ISSUE 12 acceptance): the
    # same HBM-shaped bucket fetched cross-process over loopback both
    # ways — the chunked raw-column bulk stream must move >= 2x the
    # bytes/s of the single-frame pickled host bridge, with fetch p99
    # for both recorded
    if os.environ.get("BENCH_BULK", "1") != "0":
        got = _run_child("--bulk-only", child_timeout,
                         ok_prefix="BULKPLANE_RESULT ")
        if got is not None:
            b = json.loads(got)
            bout = {"metric": "bulk_channel_vs_bridge",
                    "value": b["ratio"],
                    "unit": "x bytes/s (higher is better; >=2 passes)",
                    "bridge_MBps": b["bridge_MBps"],
                    "bulk_MBps": b["bulk_MBps"],
                    "p99_bridge_ms": b["p99_bridge_ms"],
                    "p99_bulk_ms": b["p99_bulk_ms"],
                    "p50_bridge_ms": b["p50_bridge_ms"],
                    "p50_bulk_ms": b["p50_bulk_ms"],
                    "rows": b["rows"], "reps": b["reps"],
                    "parity": b["parity"],
                    "bulk_streams": b["bulk_streams"]}
            print(json.dumps(bout))
    # adaptive-execution warm-vs-cold A/B (ISSUE 7 acceptance): the
    # streamed sortgroup/groupmap config run twice with DPARK_ADAPT=on
    # against a deterministic emulated HBM ceiling — the warm run must
    # seed its wave budget from the store (fewer OOM-ladder retries,
    # typically less wall) instead of re-walking the halving ladder
    if os.environ.get("BENCH_ADAPT", "1") != "0":
        got = _run_child("--adapt-only", child_timeout,
                         ok_prefix="ADAPT_RESULT ")
        if got is not None:
            a = json.loads(got)
            aout = {"metric": "adapt_warm_vs_cold",
                    "value": round(a["warm"]["wall_s"]
                                   / max(a["cold"]["wall_s"], 1e-9), 3),
                    "unit": ("x wall (lower is better; warm must also "
                             "drop ladder retries)"),
                    "cold": a["cold"], "warm": a["warm"],
                    "pairs": a["pairs"], "chips": a["ndev"],
                    "adapt": a["adapt"]}
            print(json.dumps(aout))
    # straggler-adaptive coding + skew re-plan A/B (ISSUE 19
    # acceptance): per-exchange (k,m) re-pricing must hold wall within
    # 1.1x of a global static rs(4,2) under the same injected fetch
    # delay while shedding the tight-tailed exchange's parity bytes;
    # the skew re-plan leg reports the dominant-bucket reduce wall
    # off-vs-presalted with zero resubmits/recomputes
    if os.environ.get("BENCH_CODE_ADAPT", "1") != "0":
        got = _run_child("--code-adapt-only", child_timeout,
                         ok_prefix="CODE_ADAPT_RESULT ")
        if got is not None:
            ca = json.loads(got)
            st, ad = ca["static"], ca["adaptive"]
            wall_s = st["t_hot_s"] + st["t_cold_s"]
            wall_a = ad["t_hot_s"] + ad["t_cold_s"]
            caout = {"metric": "adaptive_code",
                     "value": round(wall_a / max(wall_s, 1e-9), 3),
                     "unit": ("x wall vs static rs(4,2) (lower is "
                              "better; <=1.1 at lower parity passes)"),
                     "static": st, "adaptive": ad,
                     "parity_ratio": round(
                         ad["parity_bytes"]
                         / max(st["parity_bytes"], 1), 3),
                     "hot_escalated": ca["hot_escalated"],
                     "cold_pinned_uncoded": ca["cold_pinned_uncoded"],
                     "pairs": ca["pairs"], "reps": ca["reps"]}
            print(json.dumps(caout))
            rp = ca["replan"]
            rpout = {"metric": "skew_replan",
                     "value": round(rp["reduce_off_s"]
                                    / max(rp["reduce_presalt_s"],
                                          1e-9), 3),
                     "unit": ("x reduce-stage wall, skewed vs "
                              "pre-salted (higher is better)"),
                     **rp}
            print(json.dumps(rpout))
    # resident-service A/B (ISSUE 9 acceptance): a warm re-submission
    # of an identical DAG to the resident server must perform 0 stage
    # re-compiles (cache counters) and cut submit-to-first-wave
    # latency >= 3x vs the cold submission; the concurrent section
    # reports two jobs' combined wall vs the slower solo wall
    if os.environ.get("BENCH_SERVICE", "1") != "0":
        got = _run_child("--service-only", child_timeout,
                         ok_prefix="SERVICE_RESULT ")
        if got is not None:
            s = json.loads(got)
            warm_fw = (s["warm"].get("first_wave_ms") or 1e9)
            cold_fw = (s["cold"].get("first_wave_ms") or 0)
            svout = {"metric": "service_warm_submit",
                     "value": round(cold_fw / max(warm_fw, 1e-9), 2),
                     "unit": ("x submit-to-first-wave latency "
                              "(higher is better; >=3 passes, with 0 "
                              "warm compiles)"),
                     "cold": s["cold"], "warm": s["warm"],
                     "concurrent": s["concurrent"],
                     "service": s["service"], "jobs": s["jobs"],
                     "slo": s.get("slo", {}),
                     "ledger": s.get("ledger", {}),
                     "pairs": s["pairs"], "chips": s["ndev"]}
            print(json.dumps(svout))
    # instant-on restart A/B (ISSUE 17 acceptance): a fresh process
    # whose on-disk AOT executable cache was populated by a prior
    # process must submit its first DAG with ZERO backend compiles —
    # every executable deserializes straight off disk — and match the
    # cold process's answer bit-for-bit
    if os.environ.get("BENCH_AOT", "1") != "0":
        got = _run_child("--aot-only", child_timeout,
                         ok_prefix="AOT_RESULT ")
        if got is not None:
            ab = json.loads(got)
            rst = {"metric": "aot_restart",
                   "value": round(ab["cold"]["wall_s"]
                                  / max(ab["warm"]["wall_s"], 1e-9),
                                  3),
                   "unit": ("x first-submission wall (higher is "
                            "better; warm process must report 0 "
                            "backend compiles)"),
                   "cold": ab["cold"], "warm": ab["warm"],
                   "parity": ab["parity"]}
            print(json.dumps(rst))
    # shared-computation reuse A/B (ISSUE 18 acceptance): tenant-b's
    # identical ctx.sql query must plan into a full result-cache hit
    # (zero scan chunks, ledger-proven: no device-seconds, the hit
    # billed to tenant-b), and the partial-aggregate cell must beat
    # its cold run while staying bit-identical to the uncached plan
    if os.environ.get("BENCH_REUSE", "1") != "0":
        got = _run_child("--reuse-only", child_timeout,
                         ok_prefix="REUSE_RESULT ")
        if got is not None:
            ru = json.loads(got)
            rout = {"metric": "result_reuse",
                    "value": round(ru["reuse"]["speedup"], 2),
                    "unit": ("x repeated-query wall (higher is "
                             "better; >=5 passes, zero scan chunks "
                             "on the hit)"),
                    "reuse": ru["reuse"], "partial": ru["partial"],
                    "mode": ru["mode"], "rows": ru["rows"]}
            print(json.dumps(rout))
    # health-plane overhead A/B (ISSUE 14 acceptance): the same
    # ring-traced job with the streaming sketch sink off vs on —
    # folding every span must cost <= 3% wall, with nonzero site
    # sketches proving the sink observed the run
    if os.environ.get("BENCH_HEALTH", "1") != "0":
        got = _run_child("--health-only", child_timeout,
                         ok_prefix="HEALTH_RESULT ")
        if got is not None:
            h = json.loads(got)
            hout = {"metric": "health_plane_overhead",
                    "value": round(h["t_on"]
                                   / max(h["t_off"], 1e-9), 3),
                    "unit": "x wall (lower is better; <=1.03 passes)",
                    "t_off_s": h["t_off"], "t_on_s": h["t_on"],
                    "sites": h["sites"], "pairs": h["pairs"],
                    "chips": h["ndev"]}
            print(json.dumps(hout))
    # ledger-plane overhead A/B (ISSUE 15 acceptance): the same
    # ring-traced job with the attribution sink off vs on — folding
    # every span into the per-tenant accounts must cost <= 3% wall,
    # with nonzero accounts and the conservation check attached
    if os.environ.get("BENCH_LEDGER", "1") != "0":
        got = _run_child("--ledger-only", child_timeout,
                         ok_prefix="LEDGER_RESULT ")
        if got is not None:
            led = json.loads(got)
            lout = {"metric": "ledger_plane_overhead",
                    "value": round(led["t_on"]
                                   / max(led["t_off"], 1e-9), 3),
                    "unit": "x wall (lower is better; <=1.03 passes)",
                    "t_off_s": led["t_off"], "t_on_s": led["t_on"],
                    "accounts": led["accounts"],
                    "tenants": led["tenants"],
                    "conservation": led["conservation"],
                    "pairs": led["pairs"], "chips": led["ndev"]}
            print(json.dumps(lout))
    # lock-sanitizer overhead A/B (ISSUE 16 acceptance): the same
    # ring-traced job with the named-lock registry off vs record —
    # arming the order recorder must cost <= 1.03x wall, and the
    # observed graph must stay acyclic
    if os.environ.get("BENCH_LOCKCHECK", "1") != "0":
        got = _run_child("--lockcheck-only", child_timeout,
                         ok_prefix="LOCKCHECK_RESULT ")
        if got is not None:
            lk = json.loads(got)
            kout = {"metric": "lockcheck_overhead",
                    "value": lk.get("ratio",
                                    round(lk["t_on"]
                                          / max(lk["t_off"], 1e-9),
                                          3)),
                    "unit": "x wall (lower is better; <=1.03 passes)",
                    "t_off_s": lk["t_off"], "t_on_s": lk["t_on"],
                    "acquisitions": lk["acquisitions"],
                    "locks": lk["locks"], "edges": lk["edges"],
                    "cycles": lk["cycles"],
                    "order_violations": lk["order_violations"],
                    "pairs": lk["pairs"], "chips": lk["ndev"]}
            print(json.dumps(kout))
    # crash-recovery chaos certification (ISSUE 20 acceptance): a
    # controller kill -9ed at its first reduce fetch — after the map
    # stage journaled — restarts and completes the SAME job
    # bit-identically, replaying the completed stage from the journal
    # (0 recomputes), with journal-on overhead <= 1.02x
    if os.environ.get("BENCH_RECOVERY", "1") != "0":
        got = _run_child("--recovery-only", child_timeout,
                         ok_prefix="RECOVERY_RESULT ")
        if got is not None:
            rv = json.loads(got)
            rout = {"metric": "journal_recovery",
                    "value": rv["overhead"],
                    "unit": ("x journal-on wall (lower is better; "
                             "<=1.02 passes; the resume run must "
                             "replay >=1 stage with 0 recomputes)"),
                    "parity": rv["parity"],
                    "victim_killed": rv["victim_killed"],
                    "resumed_stages": rv["resumed_stages"],
                    "recomputes": rv["recomputes"],
                    "replay_traced": rv["replay_traced"],
                    "off": rv["off"], "on": rv["on"],
                    "resume": rv["resume"]}
            print(json.dumps(rout))
    if not extras:
        return
    # third line: join/cogroup, BASELINE config #2
    got = _run_child("--join-only", child_timeout,
                     ok_prefix="JOIN_RESULT ")
    if got is not None:
        j = json.loads(got)
        jbytes = (JOIN_FACT + JOIN_DIM) * 16
        jout = {"metric": "join_GBps_per_chip",
                "value": round(jbytes / j["t"] / 1e9 / j["ndev"], 4),
                "unit": "GB/s/chip",
                "vs_baseline": round(t_join_proc / j["t"], 2),
                "fact_rows": JOIN_FACT, "dim_rows": JOIN_DIM,
                "chips": j["ndev"]}
        if sort_roof:
            jout["pct_of_sort_roofline"] = round(
                100.0 * jout["value"] / sort_roof, 2)
        print(json.dumps(jout))
    # fourth line: DStream reduceByKeyAndWindow, BASELINE config #4
    got = _run_child("--stream-only", child_timeout,
                     ok_prefix="STREAM_RESULT ")
    if got is not None:
        s = json.loads(got)
        total = STREAM_RECS * STREAM_BATCHES
        sout = {"metric": "dstream_window_Mrecords_per_s",
                "value": round(total / s["t"] / 1e6, 4),
                "unit": "Mrecords/s",
                "vs_baseline": round(t_stream_proc / s["t"], 2),
                "recs_per_batch": STREAM_RECS,
                "batches": STREAM_BATCHES,
                "panes": s.get("panes", {})}
        print(json.dumps(sout))
    # fifth line: file wordcount, BASELINE config #0
    got = _run_child("--wc-only", child_timeout,
                     ok_prefix="WC_RESULT ")
    if got is not None:
        w = json.loads(got)
        wout = {"metric": "wordcount_MBps",
                "value": round(WC_MB / w["t"], 2),
                "unit": "MB/s",
                "vs_baseline": round(t_wc_proc / w["t"], 2),
                "corpus_mb": WC_MB}
        print(json.dumps(wout))
    # sixth line: sortByKey + groupByKey, BASELINE config #1
    got = _run_child("--sg-only", child_timeout,
                     ok_prefix="SG_RESULT ")
    if got is not None:
        g = json.loads(got)
        gout = {"metric": "sortgroup_Mpairs_per_s",
                "value": round(SG_PAIRS / g["t"] / 1e6, 4),
                "unit": "Mpairs/s",
                "vs_baseline": round(t_sg_proc / g["t"], 2),
                "pairs": SG_PAIRS, "chips": g.get("ndev")}
        if g.get("pipeline"):
            gout["pipeline"] = g["pipeline"]
        print(json.dumps(gout))


if __name__ == "__main__":
    main()
    if _FAILED_PHASES:
        sys.exit("bench: phases that produced no result line: %s"
                 % ", ".join(_FAILED_PHASES))
