"""The runner: one process, one cell, one closed loop.

    set-up   context start, data and references from the seed (numpy),
             tables loaded to HBM, warm-up jobs (every program the window
             will use compiles here); all of it is `setup_s`
    window   for --seconds: draw a job, run it on the caller's clock, then
             check its answer (outside the job's wall, inside the window);
             a job is `failed` on a wrong answer, a stage off the array
             path, an evicted resident table, or another HBM regime
             (spilling dead stores or not) than the warm-up ended in
    profile  traced runs only, after the window: a few more jobs under
             jax.profiler, each inside a TraceAnnotation
    report   --trace 0: the cell's end-to-end metrics
             --trace 1: its per-layer metrics and a breakdown
             every metric comes from a reader file found by its name

From the program it takes the system under test (DparkContext("tpu")),
its ring spans, its counters and the scheduler's job records.  Nothing
here falls back to the CPU: without a TPU of the cell's chip count the
process ends non-zero with no result line (--rehearse is the marked
exception, for rehearsals and tests).
"""

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time

from perf.lib import compiles, manifest, peaks, xplane
from perf.lib import traffic as traffic_mod

EXIT_USAGE = 2
EXIT_NO_DEVICE = 3
BETWEEN_JOBS = "between jobs (answer check, RDD build)"
IN_JOB_DRIVER = "in a job, outside stage.exec (driver, rows to host)"


def log(msg):
    print(msg, flush=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="perf/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="K",
                    help="REHEARSAL ONLY: divide sizes by K and run on "
                         "virtual CPU devices; the output is marked")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb into DIR")
    return ap.parse_args(argv)


def load_job_module(name):
    return manifest.load_module(manifest.job_module_path(name))


def load_reader(kind, name):
    return manifest.load_module(manifest.reader_path(kind, name))


def prepare_env(args, chips, workdir):
    """What must be set before jax and dpark_tpu are imported.
    DPARK_WORK_DIR is the one program switch every run sets (no adapt
    store or spool of an earlier run steers this one); a traced run also
    turns the program's span ring on."""
    os.environ["DPARK_WORK_DIR"] = workdir
    # libtpu's own log files default to /tmp/tpu_logs, a fixed path that
    # two checkouts would share
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(workdir, "tpu_logs"))
    if args.trace:
        os.environ["DPARK_TRACE"] = "ring"
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count=%d" % chips)
        os.environ["XLA_FLAGS"] = " ".join(flags)


def describe_device(rehearse):
    import jax
    import jaxlib
    from importlib import metadata
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "none"
    log("[env] device %s; jax %s jaxlib %s libtpu %s python %s%s"
        % (json.dumps(device), jax.__version__, jaxlib.__version__, libtpu,
           sys.version.split()[0],
           "; REHEARSAL, sizes / %d" % rehearse if rehearse else ""))
    return device


def memory_peaks():
    """peak_bytes_in_use of every chip (0 where the backend reports none,
    which is the CPU of a rehearsal)."""
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()]


def on_device_path(scheduler, records):
    """Did these job records run every stage on the array path, with no
    fallback or degrade reason recorded?  (After
    chip_smoke.assert_device_path, PR 21.)"""
    kinds = [str(st.get("kind")) for rec in records
             for st in rec["stage_info"]]
    return (bool(kinds) and all(k.startswith("array") for k in kinds)
            and not scheduler.fallback_reasons()
            and not scheduler.degrade_reasons())


class SpillWatch:
    """How often and how long the executor spills dead shuffle stores to
    disk (once stores plus cached tables pass SHUFFLE_HBM_BUDGET), read by
    wrapping its one spill routine from outside: the program has no span
    or counter there yet.  Every run needs it, to tell which HBM regime a
    job ran in.  Where the routine is gone the watch counts nothing, and
    the loop refuses to go on as soon as a store is evicted unseen."""

    HOOK = "_spill_shuffle_to_disk"

    def __init__(self, executor):
        self.count = 0
        self.seconds = 0.0
        inner = getattr(executor, self.HOOK, None)
        self.hooked = inner is not None
        if not self.hooked:
            return

        def timed(*a, **kw):
            t0 = time.perf_counter()
            try:
                return inner(*a, **kw)
            finally:
                self.count += 1
                self.seconds += time.perf_counter() - t0
        setattr(executor, self.HOOK, timed)


class Loop:
    """The cell's tables and references, and the one way a job is run
    and judged."""

    def __init__(self, ctx, job, data, traced):
        self.ctx = ctx
        self.scheduler = ctx.scheduler
        self.ex = ctx.scheduler.executor
        self.job = job
        self.data = data
        self.ndev = self.ex.ndev
        self.rows = job.input_rows(data)
        self.spill = SpillWatch(self.ex)
        self.traced = traced
        self.refs = {}
        self.tables = None
        # did the last warm-up job spill?  Every later job has to run in
        # that regime (set by warm_up)
        self.regime = None

    def make_references(self, entries):
        """Every (partition, query, action) the window can draw, made in
        set-up so that the window only compares."""
        from concurrent.futures import ThreadPoolExecutor
        keys = [(p, e["query"], e["action"]) for e in entries
                for p in range(self.job.n_partitions(self.data))]
        with ThreadPoolExecutor(max_workers=4) as pool:
            made = pool.map(
                lambda k: self.job.reference(self.data, *k), keys)
            self.refs.update(zip(keys, made))

    def run_one(self, index, part, query, action, mark=False):
        """One job on the caller's clock, then its check."""
        import jax
        key = (part, query, action)
        if key not in self.refs:        # a set-up action's, made on demand
            self.refs[key] = self.job.reference(self.data, *key)
        last = self.scheduler.history[-1]["id"] \
            if self.scheduler.history else -1
        spills0, spill0 = self.spill.count, self.spill.seconds
        held = set(self.ex.shuffle_store)
        scope = jax.profiler.TraceAnnotation(xplane.JOB_MARK, index=index) \
            if mark else contextlib.nullcontext()
        with scope:
            wall0 = time.time()
            t0 = time.perf_counter()
            result = self.job.run(self.ctx, self.tables, part, query,
                                  action, self.ndev)
            t1 = time.perf_counter()
            wall_s = t1 - t0
        records = [r for r in self.scheduler.history if r["id"] > last]
        right = bool(self.job.verdict(result, self.refs[key], action))
        device = on_device_path(self.scheduler, records)
        resident = set(self.tables["resident_ids"]) \
            <= set(self.ex.result_cache_ids())
        spills = self.spill.count - spills0
        if not self.spill.hooked and held - set(self.ex.shuffle_store):
            raise RuntimeError(
                "the executor evicted shuffle stores and has no %s for the "
                "benchmark to watch: spill_job_ms and the regime check "
                "would be blind (perf/lib/runner.py, SpillWatch)"
                % SpillWatch.HOOK)
        steady = self.regime is None or (spills > 0) == self.regime
        obs = {"index": index, "query": query, "wall_s": wall_s,
               "t0_wall": wall0, "t0": t0, "t1": t1, "rows": self.rows,
               "spills": spills,
               "spill_s": self.spill.seconds - spill0,
               "ok": right and device and resident and steady}
        if self.traced:
            from dpark_tpu import trace
            ids = {r["id"] for r in records}
            obs["spans"] = [r for r in trace.snapshot()
                            if r.get("job") in ids]
        if not obs["ok"]:
            log("[job] %d FAILED: right_answer=%s device_path=%s "
                "resident=%s same_hbm_regime=%s (spills %d) fallback=%s "
                "degrade=%s"
                % (index, right, device, resident, steady, spills,
                   self.scheduler.fallback_reasons(),
                   self.scheduler.degrade_reasons()))
        return obs


def warm_up(loop, params, stream):
    """Per entry of the mix: `warmup_jobs` checked jobs, then each of its
    set-up actions once.  The last warm-up job fixes the HBM regime
    (spilling dead stores every job, or never) that every later job must
    share.  Returns whether every check passed."""
    ok = True
    last = None
    for entry in params["jobs"]:
        part = None
        for _ in range(int(params["warmup_jobs"])):
            part = next(stream)[1]
            last = loop.run_one(-1, part, entry["query"], entry["action"])
            ok &= last["ok"]
        for action in entry.get("setup_actions", ()):
            ok &= loop.run_one(-1, part, entry["query"], action)["ok"]
    loop.regime = last["spills"] > 0
    return ok


def log_hbm(ex, when):
    """The executor's own HBM accounting, for the log: which side of
    SHUFFLE_HBM_BUDGET the run is on."""
    log("[hbm] %s: %d shuffle stores of %d bytes, %d cached results of %d "
        "bytes" % (when, len(ex.shuffle_store),
                   getattr(ex, "_store_bytes", -1), len(ex.result_cache),
                   getattr(ex, "_result_bytes", -1)))


def executor_counters(ex):
    """Every public number the executor keeps on itself (attributes and
    properties: exchange_wire_bytes, exchange_real_rows,
    exchange_slot_rows, ingest_slot_rows, export_seconds and whatever a
    later PR adds), by name.  Readers get the window's deltas."""
    names = set(vars(ex)) | {n for n, v in vars(type(ex)).items()
                             if isinstance(v, property)}
    out = {}
    for name in names:
        if name.startswith("_"):
            continue
        try:
            value = getattr(ex, name)
        except Exception:
            continue
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            out[name] = value
    return out


def profile_jobs(loop, params, stream, first_index, workdir, keep,
                 platform):
    """A few more jobs under jax.profiler, each marked.  Returns (their
    observations, the trace reduced for `platform` or None)."""
    import jax
    trace_dir = os.path.join(workdir, "xprof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    jobs = []
    try:
        for i in range(int(params["profile_jobs"])):
            entry, part = next(stream)
            jobs.append(loop.run_one(first_index + i, part, entry["query"],
                                     entry["action"], mark=True))
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        log("[trace] the profiler wrote no .xplane.pb")
        return jobs, None
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(found[-1], keep)
    return jobs, xplane.reduce(found[-1], platform)


def host_segments(profiled, reduced):
    """What the host was doing during the profiled jobs, as
    non-overlapping (start_ns, end_ns, label) on the trace's clock.  A
    job's ring spans are on time.time(); the job's annotation, entered
    at `t0_wall`, ties the two clocks together."""
    marks = {j["index"]: j for j in reduced["jobs"]}
    segs = []
    for obs in profiled:
        mark = marks.get(obs["index"])
        if mark is None:
            continue
        lo, hi = mark["start_ns"], mark["end_ns"]
        shift = lo - obs["t0_wall"] * 1e9
        at = lo
        spans = sorted((s for s in obs.get("spans", ())
                        if s["name"] == "stage.exec"),
                       key=lambda s: s["ts"])
        for s in spans:
            a = max(lo, s["ts"] * 1e9 + shift)
            b = min(hi, (s["ts"] + s["dur"]) * 1e9 + shift)
            if b <= a or a < at:
                continue
            if a > at:
                segs.append((at, a, IN_JOB_DRIVER))
            source = (s.get("args") or {}).get("source", "?")
            segs.append((a, b, "in stage.exec (source %s)" % source))
            at = b
        if hi > at:
            segs.append((at, hi, IN_JOB_DRIVER))
    return segs


def check_scale(config, params, resident_bytes, ndev):
    """The configuration's file bounds what a traffic file may ask for
    (the keys its `reduced` names)."""
    most = config.get("resident_bytes_per_chip_max")
    if most is not None and resident_bytes / ndev > most:
        raise ValueError("the traffic file keeps %d bytes a chip resident, "
                         "over the configuration's %d"
                         % (resident_bytes / ndev, most))
    most = config.get("rows_per_job_max")
    if most is not None and int(params["rows_per_job"]) > most:
        raise ValueError("rows_per_job %s is over the configuration's %d"
                         % (params["rows_per_job"], most))


def log_walls(jobs):
    """The window's job walls and the jobs far off the median, so that a
    run whose throughput and median disagree explains itself."""
    walls = sorted(j["wall_s"] for j in jobs)
    if not walls:
        return
    mid = walls[len(walls) // 2]
    slow = [(j["index"], round(j["wall_s"], 4)) for j in jobs
            if j["wall_s"] > 1.25 * mid]
    log("[window] job walls: min %.4f median %.4f max %.4f s, sum %.3f s; "
        "%d over 1.25 x median: %s; spills a job: %s"
        % (walls[0], mid, walls[-1], sum(walls), len(slow), slow[:12],
           sorted({j["spills"] for j in jobs})))


def read_metrics(kind, metrics, obs):
    """{name: {"value", "unit"}} from each metric's reader file; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = load_reader(kind, m["name"]).read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def measure(args, resolved, ctx, counter, device, workdir, t_start):
    config, params = resolved["config"], resolved["traffic"]
    job = load_job_module(config["job_module"])
    data = job.make_data(config, params, args.seed, max(1, args.rehearse))
    loop = Loop(ctx, job, data, bool(args.trace))
    check_scale(config, params, job.resident_bytes(data), loop.ndev)
    loop.make_references(params["jobs"])
    loop.tables = job.load(ctx, data, loop.ndev)
    stream = traffic_mod.schedule(params, args.seed, job.n_partitions(data))
    setup_ok = warm_up(loop, params, stream)
    log_hbm(loop.ex, "after warm-up")
    log("[setup] %d resident partitions of %d rows; warm-up %s; compiles "
        "so far %s" % (job.n_partitions(data), data["rows"],
                       "passed" if setup_ok else "FAILED",
                       json.dumps(counter.since((0, 0, 0.0)))))

    setup_s = time.perf_counter() - t_start
    snap = counter.snapshot()
    counters0 = executor_counters(loop.ex)
    jobs = []
    arrivals = traffic_mod.arrivals(params, args.seed)  # None: closed loop
    w0 = time.perf_counter()
    while time.perf_counter() - w0 < args.seconds:
        due = None
        if arrivals is not None:
            due = w0 + next(arrivals)
            if due >= w0 + args.seconds:
                break
            time.sleep(max(0.0, due - time.perf_counter()))
        entry, part = next(stream)
        jobs.append(loop.run_one(len(jobs), part, entry["query"],
                                 entry["action"]))
        if due is not None:
            jobs[-1].update(latency_s=jobs[-1]["t1"] - due,
                            late_s=jobs[-1]["t0"] - due)
    window_s = time.perf_counter() - w0
    window_compiles = counter.since(snap)
    log_hbm(loop.ex, "after the window")
    counters1 = executor_counters(loop.ex)

    profiled, reduced = [], None
    if args.trace:
        profiled, reduced = profile_jobs(loop, params, stream, len(jobs),
                                         workdir, args.keep_trace,
                                         device["platform"])
    everything = jobs + profiled
    failed = sum(1 for j in everything if not j["ok"])
    hbm_peaks = memory_peaks()
    log("[env] peak HBM per device: %s" % hbm_peaks)
    device = dict(device, memory_peak_bytes=max(hbm_peaks))
    if args.rehearse:
        device["rehearse"] = args.rehearse
    obs = {
        "cell": resolved["cell"], "config": config, "traffic": params,
        "ndev": loop.ndev, "setup_s": setup_s, "window_s": window_s,
        "jobs": jobs, "profiled_jobs": profiled,
        # a rehearsal's trace is of the CPU: no reader may take a device
        # number from it
        "profile": None if args.rehearse else reduced,
        "compiles": window_compiles,
        "counters": {k: counters1[k] - counters0[k] for k in counters0
                     if k in counters1},
        "memory_peak_bytes": device["memory_peak_bytes"],
        "least": {e["query"]: job.least(config, params, data, loop.ndev,
                                        e["query"])
                  for e in params["jobs"]},
        "peaks": None if args.rehearse
        else peaks.for_device(device["kind"]),
    }
    line = {"correct": bool(setup_ok and everything and not failed),
            "attempted": len(everything), "failed": failed}
    if not args.trace:
        line["metrics"] = read_metrics("end_to_end", resolved["end_to_end"],
                                       obs)
    else:
        line["metrics"] = read_metrics("per_layer", resolved["per_layer"],
                                       obs)
        if reduced is not None:
            traced = {"busy_s": reduced["busy_s"],
                      "window_s": reduced["window_s"],
                      "breakdown": {
                          "device_ops": reduced["device_ops"],
                          "idle_gaps": xplane.label_gaps(
                              reduced["gaps_ns"],
                              host_segments(profiled, reduced),
                              BETWEEN_JOBS)}}
            if args.rehearse:
                # host-line events of the CPU, kept off every key the
                # driver reads: they only prove the code path
                line["rehearsal_only"] = traced
            else:
                line["breakdown"] = traced.pop("breakdown")
                device.update(traced)
    line["device"] = device
    log_walls(jobs)
    log("[window] %d jobs in %.3f s, %d failed; compiles in the window %s"
        % (len(jobs), window_s, failed, json.dumps(window_compiles)))
    return line


def main(argv, t_start=None):
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        resolved = manifest.resolve(manifest.load(),
                                    args.workload)
        traffic_mod.validate(resolved["traffic"])
    except (manifest.ManifestError, ValueError) as e:
        print("perf/run.py: %s" % e, file=sys.stderr)
        return EXIT_USAGE
    chips = int(resolved["cell"]["chips"])
    workdir = tempfile.mkdtemp(prefix="dpark_perf_")
    try:
        prepare_env(args, chips, workdir)
        try:
            import jax
            from dpark_tpu import DparkContext
        except ImportError as e:
            print("perf/run.py: the program is not in this checkout (%s); "
                  "nothing was run" % e, file=sys.stderr)
            return EXIT_NO_DEVICE
        device = describe_device(args.rehearse)
        want = "cpu" if args.rehearse else "tpu"
        if device["platform"] != want or device["count"] != chips:
            print("perf/run.py: cell %s needs %d %s device(s), jax found %s;"
                  " nothing was run" % (args.workload, chips, want, device),
                  file=sys.stderr)
            return EXIT_NO_DEVICE
        # every program goes to the persistent cache, however quick its
        # compile, so that only a checkout's first run of a cell compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        counter = compiles.CompileCounter().install()
        ctx = DparkContext("tpu")
        ctx.start()
        try:
            log("[env] compile cache dir in force: %s"
                % jax.config.jax_compilation_cache_dir)
            line = measure(args, resolved, ctx, counter, device, workdir,
                           t_start)
        finally:
            ctx.stop()
        print(json.dumps(line), flush=True)
        return 0
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
