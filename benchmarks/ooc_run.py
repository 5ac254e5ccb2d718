"""Out-of-core demonstration runs (SURVEY.md 7.2 item 4 / round-2 plan):
a >=10GB synthetic dataset through the north-star configs with bounded
RSS and HBM, on either master.

  python benchmarks/ooc_run.py --config wordcount --master tpu --gb 10
  python benchmarks/ooc_run.py --config sortgroup --master tpu --gb 10

Prints one JSON line: wall seconds, max RSS, HBM budget, spool bytes.
The text corpus is generated once under --data-dir and reused.
"""

import argparse
import json
import os
import resource
import sys
import time


def gen_corpus(path, gb):
    """~gb GB of whitespace text, written in repeated 8MB blocks."""
    import random
    if os.path.exists(path) and os.path.getsize(path) >= gb * (1 << 30):
        return
    rng = random.Random(1234)
    words = ["%s%d" % (w, i) for i in range(2000)
             for w in ("tok", "key", "val")][:5000]
    lines = []
    size = 0
    while size < (8 << 20):
        line = " ".join(rng.choices(words, k=10)) + "\n"
        lines.append(line)
        size += len(line)
    block = "".join(lines).encode()
    with open(path, "wb") as f:
        written = 0
        target = gb * (1 << 30)
        while written < target:
            f.write(block)
            written += len(block)


def run_wordcount(ctx, path, n_parts):
    r = (ctx.textFile(path)
         .flatMap(lambda line: line.split())
         .map(lambda w: (w, 1))
         .reduceByKey(lambda a, b: a + b, n_parts))
    top = r.top(5, key=lambda kv: kv[1])
    return {"top": top[0][1], "distinct": r.count()}


def run_sortgroup(ctx, gb, n_parts, reduce_parts=64):
    """Config #1 over columnar input: sortByKey + groupByKey with the
    spilled-run streaming path (HBM + spool bounded; input in RAM).
    reduce_parts > mesh keeps each reduce partition small — the rid
    column rides the exchange."""
    import numpy as np
    from dpark_tpu import Columns, conf
    if os.environ.get("DPARK_TPU_PLATFORM") == "cpu":
        # smaller waves: on the CPU-emulated mesh every device buffer
        # lives in host RSS, so the wave working-set multiplier (~10x
        # across the program pipeline) must stay a fraction of the
        # input; a real chip keeps full waves
        conf.STREAM_CHUNK_ROWS = 1 << 20
    n = int(gb * (1 << 30)) // 16         # two int64 columns
    keys = (np.arange(n, dtype=np.int64) * 2654435761) % (10 ** 9)
    vals = np.arange(n, dtype=np.int64) & 0xFFFF
    data = Columns(keys, vals)
    s = ctx.parallelize(data, n_parts).sortByKey(
        numSplits=reduce_parts)
    first_keys = [k for k, _ in s.take(3)]
    g = (ctx.parallelize(data, n_parts)
         .map(lambda kv: (kv[0] % 1000, kv[1]))
         .reduceByKey(lambda a, b: a + b, n_parts))
    return {"sort_head": first_keys, "groups": g.count()}


def _ooc_group_fn(vs):
    """Traceable, zero-pad-invariant, NOT a provable aggregate: only
    the ISSUE 4 segmented apply keeps this grouped consumer on device."""
    return sum(v * v for v in vs)


def run_groupmap(ctx, gb, n_parts, reduce_parts=None):
    """Streamed group_mapvalues A/B: the
    no-combine groupByKey write runs through the spilled-run wave
    stream (chunked waves, key-sorted runs on disk), then the SAME
    mapValues(traceable fn) consumer runs once with conf.SEG_MAP on
    (the premerged runs load back as a device batch and the segmented
    apply answers all-array) and once with it off (the pre-PR host
    export-bridge path)."""
    import numpy as np
    from dpark_tpu import Columns, conf
    if os.environ.get("DPARK_TPU_PLATFORM") == "cpu":
        conf.STREAM_CHUNK_ROWS = 1 << 20
    ctx.start()
    ex = getattr(ctx.scheduler, "executor", None)
    if reduce_parts is None:
        # the seg consume only rides with r <= mesh size; defaulting
        # past the mesh would silently measure host-vs-host
        reduce_parts = ex.ndev if ex is not None else 8
    n = int(gb * (1 << 30)) // 16         # two int64 columns
    keys = (np.arange(n, dtype=np.int64) * 2654435761) % 100_000
    vals = np.arange(n, dtype=np.int64) & 0xFFFF
    data = Columns(keys, vals)

    def once():
        t0 = time.time()
        cnt = (ctx.parallelize(data, n_parts)
               .groupByKey(reduce_parts)
               .mapValues(_ooc_group_fn).count())
        return time.time() - t0, cnt

    conf.SEG_MAP = True
    t_dev, groups = once()
    # every stage of the device-side job must be array-kind (a
    # contains-"array" check over all stages is vacuously true)
    rec = ctx.scheduler.history[-1]
    dev_array = bool(rec.get("stage_info")) and all(
        str(st.get("kind", "")).startswith("array")
        for st in rec["stage_info"])
    conf.SEG_MAP = False
    try:
        t_host, groups_host = once()
    finally:
        conf.SEG_MAP = True
    assert groups == groups_host, (groups, groups_host)
    return {"groups": groups,
            "groupmap_device_s": round(t_dev, 1),
            "groupmap_host_s": round(t_host, 1),
            "groupmap_device_array_path": dev_array,
            "groupmap_device_vs_host": round(t_host
                                             / max(t_dev, 1e-9), 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", choices=["wordcount", "sortgroup",
                                         "groupmap"],
                    default="wordcount")
    ap.add_argument("--master", default="tpu")
    ap.add_argument("--gb", type=float, default=10.0)
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--data-dir", default="/tmp/dpark_ooc")
    args = ap.parse_args()

    from dpark_tpu import DparkContext, conf
    ctx = DparkContext(args.master)

    os.makedirs(args.data_dir, exist_ok=True)
    t0 = time.time()
    out = {"config": args.config, "master": args.master, "gb": args.gb}
    if args.config == "wordcount":
        path = os.path.join(args.data_dir,
                            "corpus_%dg.txt" % int(args.gb))
        gen_corpus(path, args.gb)
        out["gen_s"] = round(time.time() - t0, 1)
        t0 = time.time()
        out.update(run_wordcount(ctx, path, args.parts))
    elif args.config == "groupmap":
        out.update(run_groupmap(ctx, args.gb, args.parts))
    else:
        out.update(run_sortgroup(ctx, args.gb, args.parts))
    out["wall_s"] = round(time.time() - t0, 1)
    out["max_rss_gb"] = round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / (1 << 20),
        2)
    out["hbm_budget_gb"] = round(conf.SHUFFLE_HBM_BUDGET / (1 << 30), 2)
    ex = getattr(ctx.scheduler, "executor", None)
    if ex is not None:
        # the platform jax found (DPARK_TPU_PLATFORM overrides), named
        # on the line so a CPU-mesh run is never read as a device's
        dev = ex.mesh.devices.flat[0]
        out["platform"] = dev.platform
        out["device_kind"] = dev.device_kind
        out["chips"] = ex.ndev
        out["hbm_used_gb"] = round(
            (ex._store_bytes + ex._result_bytes) / (1 << 30), 3)
    # overlapped wave pipeline: aggregate ingest/compute/exchange/spill
    # ms + device-idle fraction of the deepest streamed stage
    pipe = getattr(ctx.scheduler, "pipeline_summary", lambda: None)()
    if pipe is not None:
        out["pipeline"] = pipe
    # per-phase wall-time table (ingest/tokenize, narrow, exchange,
    # spill, export) + every recorded why-the-array-path-was-left
    # reason
    phases = getattr(ctx.scheduler, "phase_table", lambda: None)()
    if phases is not None:
        out["phases"] = phases
    out["fallback_reasons"] = getattr(
        ctx.scheduler, "fallback_reasons", lambda: [])()
    # chaos/recovery accounting (ISSUE 5): per-site injected fault
    # counters + degrade/resubmit/retry summary
    recovery = getattr(ctx.scheduler, "recovery_summary",
                       lambda: {})() or {}
    out["faults"] = recovery.pop("faults", {})
    # coded-shuffle decode counters (ISSUE 6)
    out["decodes"] = recovery.pop("decodes", {})
    out["degrades"] = recovery
    # adaptive-execution accounting (ISSUE 7): mode, store hit/steer
    # counters, and the decisions taken
    from dpark_tpu import adapt
    out["adapt"] = adapt.summary()
    # trace plane (ISSUE 8): span counts + critical-path summary of
    # the longest traced job
    from dpark_tpu import trace
    out["trace"] = trace.summary()
    # health plane (ISSUE 14): per-site latency-tail summaries + event
    # rates (empty sites when nothing was traced — the sketches fold
    # off the trace plane)
    from dpark_tpu import health
    out["health"] = health.summary()
    ctx.stop()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
