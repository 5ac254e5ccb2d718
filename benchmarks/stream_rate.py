"""Sustained-ingest streaming benchmark (ISSUE 10): records/s at a
fixed p99 batch latency, plus the window-scaling A/B that proves the
pane plane's complexity claims.

Two JSON lines (schema-gated by the CI `stream` job):

  stream_rate             ramp the per-batch record count over a
                          reduceByKeyAndWindow pipeline driven by the
                          MANUAL clock (the timer would measure sleep)
                          and report the highest rate whose p99
                          per-tick wall stays within the batch budget
                          — the serving-adjacent "how much can this
                          pipeline sustain" number.
  stream_window_scaling   median steady-state per-tick wall as the
                          window/slide ratio grows 4 -> 32, three
                          series: the pre-pane whole-window recompute
                          (linear in w), the non-invertible pane tree
                          (O(log w) merged branches), and the
                          invertible pane path (O(1) panes per slide).
                          `value` is the pane-tree growth factor
                          w=32 vs w=4; `old_growth` the recompute
                          path's.

Sizes shrink under --smoke (CI boxes grade schema, not throughput;
a rate that counts is a cell of the chip benchmark under perf/).  The tick
walls recorded here also seed the adapt store's pane-cost entries
(adapt.record_pane_cost), so a DPARK_ADAPT=on run after this bench
picks tree-vs-flat split points from these observations.
"""

import json
import operator
import os
import sys
import time


def _master():
    return os.environ.get("BENCH_STREAM_MASTER", "local")


def _mk_batches(nbatches, recs, keys, seed=7):
    import numpy as np
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(nbatches):
        ks = rng.randint(0, keys, recs)
        vs = rng.randint(0, 100, recs)
        out.append(list(zip(ks.tolist(), vs.tolist())))
    return out


def _drive(ctx, batches, window, invFunc, panes_on):
    """Run the windowed pipeline over a deterministic queueStream with
    the manual clock; returns per-tick wall seconds."""
    from dpark_tpu import conf
    from dpark_tpu.dstream import StreamingContext
    was = conf.STREAM_PANES
    conf.STREAM_PANES = panes_on
    try:
        ssc = StreamingContext(ctx, 1.0)
        out = []
        q = ssc.queueStream(batches)
        q.reduceByKeyAndWindow(operator.add, float(window),
                               invFunc=invFunc).collect_batches(out)
        ctx.start()
        for ins in ssc.input_streams:
            ins.start()
        ssc.zero_time = 1000.0
        walls = []
        for k in range(1, len(batches) + 1):
            t0 = time.perf_counter()
            ssc.run_batch(1000.0 + k * ssc.batch_duration)
            walls.append(time.perf_counter() - t0)
        assert out, "stream produced no batches"
        return walls
    finally:
        conf.STREAM_PANES = was


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _p99(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * 0.99))]


def _steady(walls, window):
    """Ticks after the window filled (cold start + compile warmup)."""
    return walls[min(len(walls) - 1, int(window) + 2):] or walls


def bench_window_scaling(smoke):
    """Per-tick wall vs window/slide ratio, slide = 1 batch."""
    from dpark_tpu import DparkContext
    ratios = [4, 8, 16, 32]
    recs = 2_000 if smoke else 50_000
    keys = 97 if smoke else 4_096
    series = {"old_ms": [], "pane_ms": [], "inv_ms": []}
    for w in ratios:
        nb = w + (8 if smoke else 16)
        batches = _mk_batches(nb, recs, keys)
        for name, inv, panes_on in (("old_ms", None, False),
                                    ("pane_ms", None, True),
                                    ("inv_ms", operator.sub, True)):
            ctx = DparkContext(_master())
            walls = _drive(ctx, [list(b) for b in batches], w, inv,
                           panes_on)
            ctx.stop()
            series[name].append(
                round(_median(_steady(walls, w)) * 1000.0, 2))
    growth = {k: round(v[-1] / max(v[0], 1e-9), 2)
              for k, v in series.items()}
    return {"metric": "stream_window_scaling",
            "value": growth["pane_ms"], "unit": "x",
            "ratios": ratios, "recs_per_batch": recs,
            "pane_ms": series["pane_ms"], "inv_ms": series["inv_ms"],
            "old_ms": series["old_ms"],
            "pane_growth": growth["pane_ms"],
            "inv_growth": growth["inv_ms"],
            "old_growth": growth["old_ms"]}


def bench_stream_rate(smoke):
    """Highest sustainable ingest rate: ramp recs/batch geometrically
    while the p99 per-tick wall fits the batch budget."""
    from dpark_tpu import DparkContext, panes
    batch_s = float(os.environ.get("BENCH_STREAM_BATCH_S",
                                   "0.25" if smoke else "1.0"))
    window = 8.0 * batch_s
    nb = 16 if smoke else 40
    keys = 97 if smoke else 4_096
    start = 2_000 if smoke else 25_000
    cap = 16_000 if smoke else 1_600_000
    best = None
    tried = []
    last_panes = {}
    recs = start
    while recs <= cap:
        batches = _mk_batches(nb, recs, keys)
        ctx = DparkContext(_master())
        walls = _drive(ctx, batches, window / batch_s, operator.sub,
                       True)
        stats = panes.stream_stats()
        last_panes = list(stats.values())[-1] if stats else last_panes
        ctx.stop()
        steady = _steady(walls, window / batch_s)
        p99_ms = round(_p99(steady) * 1000.0, 2)
        point = {"recs_per_batch": recs, "p99_batch_ms": p99_ms,
                 "rate_records_per_s": round(recs / batch_s, 1)}
        tried.append(point)
        if p99_ms <= batch_s * 1000.0:
            best = dict(point, panes=last_panes)
            recs *= 2
        else:
            break
    if best is None:
        # even the floor rate overran the budget: report it honestly
        # (sustained=false) WITH its pane stats — the schema gates
        # check pane-mode indicators, never wall ratios
        best = dict(tried[0], panes=last_panes)
    return {"metric": "stream_rate",
            "value": best["rate_records_per_s"],
            "unit": "records/s",
            "p99_batch_ms": best["p99_batch_ms"],
            "batch_s": batch_s,
            "target_p99_ms": batch_s * 1000.0,
            "sustained": best["p99_batch_ms"] <= batch_s * 1000.0,
            "recs_per_batch": best["recs_per_batch"],
            "rates_tried": tried,
            "panes": best.get("panes", {})}


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    print(json.dumps(bench_window_scaling(smoke)), flush=True)
    print(json.dumps(bench_stream_rate(smoke)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
