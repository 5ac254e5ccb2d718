"""North-star config #4: Bagel PageRank superstep wall-clock.

Compares the device-native vectorized Pregel (bagel.run_pregel on the
tpu master) against the reference-shaped OBJECT Bagel on the process
master, on the same random graph.  Prints one JSON line per run.

  python benchmarks/pagerank_bench.py --vertices 200000 --degree 8
"""

import argparse
import json
import os
import subprocess
import sys
import time


def gen_graph(n, degree, seed=7):
    import numpy as np
    rng = np.random.RandomState(seed)
    ids = np.arange(n, dtype=np.int64)
    src = np.repeat(ids, degree)
    dst = rng.randint(0, n, n * degree).astype(np.int64)
    return ids, src, dst


def run_device(n, degree, steps):
    import jax
    import numpy as np
    from dpark_tpu import DparkContext
    from dpark_tpu.bagel import run_pregel
    ctx = DparkContext("tpu")
    ctx.start()
    platform = ctx.scheduler.executor.mesh.devices.flat[0].platform
    ids, src, dst = gen_graph(n, degree)

    def compute(value, msg, has_msg, active, agg, superstep):
        is0 = superstep == 0
        new = is0 * value + (1 - is0) * (0.15 / n + 0.85 * msg)
        return new, superstep < steps

    def send(v, e, deg):
        return v / deg

    t0 = time.perf_counter()
    _, ranks, _ = run_pregel(ctx, ids, np.full(n, 1.0 / n), (src, dst),
                             compute, send, combine="add",
                             max_superstep=steps + 1)
    wall = time.perf_counter() - t0
    used = ctx.scheduler._pregel_device_used
    ctx.stop()
    return wall, float(ranks.sum()), used, platform


class ObjectPR:
    """Reference-shaped object compute (module-level: fork workers must
    unpickle it)."""

    def __init__(self, n, steps):
        self.n = n
        self.steps = steps

    def __call__(self, vert, msg_sum, agg, superstep):
        from dpark_tpu.bagel import Message, Vertex
        if superstep == 0:
            value = vert.value
        else:
            value = 0.15 / self.n + 0.85 * (msg_sum or 0.0)
        active = superstep < self.steps
        v = Vertex(vert.id, value, vert.outEdges, active)
        if active and vert.outEdges:
            share = value / len(vert.outEdges)
            return (v, [Message(e.target_id, share)
                        for e in vert.outEdges])
        return (v, [])


def run_object(n, degree, steps):
    import operator
    from dpark_tpu import DparkContext
    from dpark_tpu.bagel import Bagel, BasicCombiner, Edge, Vertex
    ctx = DparkContext("process:8")
    ids, src, dst = gen_graph(n, degree)
    outs = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        outs.setdefault(s, []).append(d)
    PR = lambda: ObjectPR(n, steps)         # noqa: E731

    verts = ctx.parallelize(
        [(int(i), Vertex(int(i), 1.0 / n,
                         [Edge(t) for t in outs.get(int(i), [])]))
         for i in ids], 8)
    msgs = ctx.parallelize([], 8)
    t0 = time.perf_counter()
    final = Bagel.run(ctx, verts, msgs, PR(),
                      combiner=BasicCombiner(operator.add),
                      max_superstep=steps + 1)
    total = sum(v.value for _, v in final.collect())
    wall = time.perf_counter() - t0
    ctx.stop()
    return wall, total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=200_000)
    ap.add_argument("--degree", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--mode", choices=["both", "device", "object"],
                    default="both")
    args = ap.parse_args()

    if args.mode in ("both", "object"):
        # object path FIRST and in this process only if device is not
        # also requested (fork pools must stay jax-free)
        if args.mode == "both":
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--mode", "object",
                 "--vertices", str(args.vertices),
                 "--degree", str(args.degree),
                 "--steps", str(args.steps)],
                capture_output=True, text=True)
            sys.stderr.write(out.stderr[-2000:])
            print(out.stdout, end="")
            if out.returncode != 0 or not out.stdout.strip():
                sys.exit("object-mode child failed (rc=%d)"
                         % out.returncode)
            obj = json.loads(out.stdout.splitlines()[-1])
        else:
            wall, total = run_object(args.vertices, args.degree,
                                     args.steps)
            print(json.dumps({
                "metric": "bagel_pagerank_s", "mode": "object_process",
                "vertices": args.vertices, "degree": args.degree,
                "steps": args.steps, "value": round(wall, 3),
                "rank_mass": round(total, 6)}))
            return
    if args.mode in ("both", "device"):
        # the platform is what jax finds unless DPARK_TPU_PLATFORM says
        # otherwise; the output names it
        wall, total, used, platform = run_device(
            args.vertices, args.degree, args.steps)
        rec = {"metric": "bagel_pagerank_s", "mode": "device_pregel",
               "vertices": args.vertices, "degree": args.degree,
               "steps": args.steps, "value": round(wall, 3),
               "rank_mass": round(total, 6), "device_used": used,
               "platform": platform}
        if args.mode == "both":
            rec["vs_object"] = round(obj["value"] / wall, 2)
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
