"""Trace plane (ISSUE 8 tentpole): low-overhead structured spans and
events across the scheduler, executor, shuffle, coding, dcn, and adapt
seams — per-job/stage/task timelines instead of (only) aggregate
counters, and the mechanism that finally surfaces WORKER-process
observations on the driver.

Modes (``DPARK_TRACE`` env var / conf knob):

    off     no plane installed — one ``is None`` check per site
            (mirroring faults.py; results are bit-identical to any
            traced run, asserted across the chaos matrix in
            tests/test_trace.py)
    ring    spans land in a bounded in-memory ring
            (conf.TRACE_RING_SPANS) — the web UI's /api/trace serves
            it live; nothing touches disk
    spool   ring PLUS per-process crc-framed JSON-lines spool files
            under conf.DPARK_TRACE_DIR (the adapt.py framing: each
            line is ``<crc32 hex> <json>`` appended with one O_APPEND
            write, so concurrent processes interleave whole lines and
            corrupt/truncated lines skip at load).  Worker processes
            spool into the same directory under their own
            ``trace-<host>-<pid>.jsonl``; their cumulative counter
            events land in a small sibling
            ``counters-<host>-<pid>.jsonl`` (so the per-job merge
            never re-parses the span spool), which is how
            multiprocess fault/decode counters merge back into the
            driver's job records (the per-process caveat of PRs 5-7).
            The health plane's per-site latency digests (ISSUE 14)
            get their own ``health-<host>-<pid>.jsonl`` — ONE record,
            atomically rewritten latest-wins, because the cumulative
            digests change with nearly every task and would grow the
            append-only counters file one full-digest line per task.

Span taxonomy (name / cat):

    job, stage, task         "sched"   driver-side lifecycle (job ->
                                       stage -> task parented by the
                                       job/stage/task fields); `job`
                                       runs from the end of job.begin
                                       to the start of job.finish, its
                                       dur unrounded
    preflight                "sched"   analysis.preflight of one
                                       action, DparkContext.runJob
                                       (args: mode): the lint's lineage
                                       walk and AST pass; measured
                                       before the job's id exists and
                                       emitted under it by _begin_job
    job.begin                "sched"   the way into a job, DAGScheduler
                                       ._begin_job (args: stages, the
                                       graph's): new_stage's DAG walk,
                                       _new_job_record, _job_started
    stage.run                "sched"   one submission of a stage's
                                       tasks, DAGScheduler._run_tasks
                                       (args: tasks, shuffle): plan,
                                       join and stage.exec lie inside;
                                       its self time is the scheduler's
                                       own work for the stage
    adapt.path               "adapt"   the cost model's part of a stage's
                                       submission, TPUScheduler.
                                       submit_tasks / _run_array_stage
                                       (args: step — choose: the plan's
                                       sha1 signature and choose_path;
                                       observe: observe_path's append
                                       to the adapt store); two a stage
                                       inside stage.run
    result.rows              "sched"   the action's function over a
                                       result stage's Python rows,
                                       task.func(iter(rows)) and its
                                       report, TPUScheduler.
                                       _run_array_stage (args: tasks,
                                       rows); inside stage.run, after
                                       stage.exec
    job.finish               "sched"   the way out of a job, DAGScheduler
                                       ._finish_job: the finalizers
                                       (decodes, exchanges, adapt,
                                       health) and _job_finished
    store.release            "exec"    one drain of dead shuffles that
                                       freed at least one HBM store,
                                       TPUScheduler._drain_unreachable
                                       (args: stores, bytes); stamped
                                       after the drain, inside
                                       job.begin or job.finish (or a
                                       stage.exec whose eviction
                                       drains first)
    task.run                 "worker"  a task executing in whichever
                                       process ran it (the worker
                                       timeline of a multiproc run)
    stage.exec, wave         "exec"    device stage execution and the
                                       per-wave stream pipeline
    plan                     "exec"    fuse.analyze_stage of one stage
                                       on the tpu master's driver
                                       (args: ok — a plan came back)
    launch                   "exec"    the host's call of a compiled
                                       stage program, JAXExecutor.
                                       _launch (args: program — narrow,
                                       reduce, exchange, minmax,
                                       join_count, join_expand,
                                       wave_sort, wave_prereduce,
                                       distinct, sample, pregel.init,
                                       pregel.step, pregel.gen): jit cache
                                       lookup, argument handling, a
                                       compile if one happens; NOT
                                       device time.
                                       One span is one count of the
                                       executor's program_launches
    eager                    "exec"    the host's dispatch of eager jnp
                                       operations outside any compiled
                                       program, several XLA dispatches
                                       under one span and none counted
                                       in program_launches (args: site
                                       — keycheck, the cached-key guard
                                       JAXExecutor._check_cached_keys)
    readback                 "exec"    one blocking device-to-host
                                       read, layout.host_read (args:
                                       site — the caller's literal,
                                       e.g. exchange.counts, or device
                                       Pregel's pregel.init,
                                       pregel.active, pregel.msgs and
                                       pregel.collect; bytes;
                                       wait_s): the host waited wait_s
                                       for the device to produce the
                                       value (block_until_ready), then
                                       the copy
    egest                    "exec"    layout.egest: a result batch to
                                       Python rows (args: rows, bytes);
                                       its readbacks nest inside.  Also
                                       a Pregel run's final state to
                                       numpy columns, DevicePregel.
                                       _collect (args: site —
                                       pregel.collect; rows, bytes)
    ingest                   "exec"    layout.ingest of a stage's host
                                       numpy source (args: rows).  Also
                                       the load of a resident Pregel
                                       graph, DeviceGraph (args: site —
                                       pregel.graph; rows: vertices and
                                       arcs; bytes): under no job
    pregel.superstep         "exec"    one superstep of device Pregel,
                                       backend/tpu/bagel.py:
                                       DevicePregel.run (args: s,
                                       active, msgs, rounds — the
                                       exchange's, 0 where the
                                       messages came delivered — and
                                       delivery: static where this
                                       superstep's messages are
                                       combined over the load's
                                       destination order and read at
                                       a fixed slot, one device;
                                       exchange where they are
                                       bucketized), inside the run's
                                       stage.exec (source pregel): the
                                       exchange's launches and reads,
                                       the step program's launch, the
                                       read of the active count, the gen
                                       program's launch and the read of
                                       the message count.  A run is one
                                       job: TPUScheduler.run_pregel
                                       emits job.begin, job, stage.run,
                                       stage.exec and job.finish as
                                       runJob does.  The executor counts
                                       pregel_supersteps,
                                       pregel_messages (the msgs above,
                                       summed), pregel_graph_loads and
                                       pregel_static_supersteps (the
                                       supersteps of delivery static)
    query.plan               "query"   query/planner.py: plan_query of
                                       one table action (args: mode —
                                       scan, group, join or join_group;
                                       source — device where every Scan
                                       reads a table resident on the
                                       device, files for tabular part
                                       files, driver for driver-resident
                                       slices; rules — decisions
                                       recorded; joins — the joins of
                                       the chain, 0 without one);
                                       measured before the
                                       job's id exists, kept on the
                                       PlannedQuery and emitted by its
                                       _job under the id of the job the
                                       action opened; a plan that leads
                                       to no job of its own (declined,
                                       answered from a cache, explain())
                                       leaves no span
    query.finish             "query"   PlannedQuery._run after its job
                                       returned: the collected rows
                                       decoded (key words to bytes,
                                       dictionary ids to strings),
                                       averages divided, HAVING /
                                       projections / ORDER BY evaluated
                                       (args: rows; path — columns
                                       where a grouped finish ran over
                                       numpy columns, _finish_columns,
                                       else rows); stamped with the
                                       id of the job it finishes.  The
                                       executor counts scan_rows_device
                                       (rows of a resident table that
                                       a stage program was launched
                                       over, JAXExecutor._source_outs)
                                       and scan_rows_host (rows
                                       _ScanSeg.run evaluated on the
                                       driver in numpy and reported to
                                       note_host_scan),
                                       query_joins_device (the joins
                                       of a job whose every side was a
                                       resident table, reported by
                                       PlannedQuery._job to
                                       note_device_joins) and
                                       query_finishes_columnar (the
                                       finishes over columns, reported
                                       by PlannedQuery._run to
                                       note_columnar_finish)
    sort.sample              "exec"    the read of sortByKey's bounds
                                       sample, JAXExecutor._sample_keys
                                       (args: splits, rows, bytes: the
                                       first keys of every split, which
                                       is all that reaches the host);
                                       an `egest` nests inside
    hbm.spill                "exec"    one dead HBM shuffle store to
                                       disk buckets, JAXExecutor.
                                       _spill_shuffle_to_disk (args:
                                       sid, bytes); its readbacks nest
                                       inside
    compile, dispatch        "exec"    program cache misses / program
                                       dispatches (instant events); a
                                       program that partitions by range
                                       or sorts by key says dst= hash |
                                       range, key= int | tuple | bytes,
                                       order= signed | unsigned; the
                                       exchange program says cap=,
                                       slot=, nleaves= and send= slices
                                       (collectives.exchange_round cuts
                                       a destination's block out of the
                                       sorted leaves; no other form)
    phase.ingest_tokenize,   "phase"   per-stage phase totals emitted
    phase.narrow,                      from the SAME _StreamStats
    phase.exchange,                    snapshot scheduler.phase_table()
    phase.spill,                       reads, so the critical-path
    phase.export                       analyzer reconciles with it
    fetch.bucket             "shuffle" one reduce-side bucket fetch
    spill.write, spill.read  "shuffle" spill-run / spill-chunk I/O
    decode.*                 "coding"  erasure-decode outcomes
    dcn.connect,             "dcn"     peer connects / single-frame
    dcn.transfer                       request bytes (the pickled
                                       host bridge)
    dcn.bulk.fetch,          "dcn"     bulk data plane (ISSUE 12):
    dcn.bulk.serve                     chunk-framed streams, bytes +
                                       attempt count in args.  KEPT
                                       DISTINCT from dcn.transfer —
                                       the 2-process parity suite
                                       asserts the hot path emitted
                                       ONLY dcn.bulk.* spans (the
                                       pickled bridge never ran)
    adapt.decision           "adapt"   cost-model choices
    stream.batch             "stream"  one micro-batch tick of an
                                       output chain (driver side)
    stream.pane.build,       "stream"  pane-plane lifecycle (ISSUE
    stream.tree.merge,                 10): pane partials built, merge
    stream.late.patch,                 -tree nodes merged, late-data
    stream.window.emit                 pane patches, and the per-tick
                                       window emit with its branch
                                       count (instant events keyed by
                                       stream id + pane index)
    process.counters         "counters" cumulative per-process fault/
                                       decode counters (the merge
                                       substrate, see
                                       merged_worker_counters)
    journal.replay           "sched"   crash-journal replay (ISSUE
                                       20): one instant event per job
                                       whose completed stages were
                                       seeded from the journal, with
                                       resumed_stages and
                                       seeded_partitions in args —
                                       the chaos certification greps
                                       for this

Records are flat dicts: name, cat, ts (epoch seconds), dur (seconds),
pid, host, tid, optional job/stage/task ints, optional args.  The
job/stage/task fields inherit from a thread-local context installed by
the scheduler (``ctx()``), so deep callees (a shuffle fetch inside a
worker task) parent correctly without plumbing ids through every
signature.

While a span() is open (not emit()/event(), which are stamped after
the fact) the thread also holds a jax.profiler.TraceAnnotation of the
same name and scalar args, so with DPARK_XPROF_DIR set (or under any
jax.profiler session) the program's spans stand in the profiler's own
trace, on its clock, above the device's operations.  Processes that
never loaded jax hold none.

On top: ``to_chrome()`` exports merged Chrome trace-event JSON (load
in Perfetto via chrome://tracing or ui.perfetto.dev), and
``critical_path()`` runs a longest-path analysis over the stage DAG
with per-phase blocked fractions.  ``tools/dtrace`` is the CLI.
"""

import json
import os
import socket
import sys
import threading
import time
from collections import deque

from dpark_tpu import conf
from dpark_tpu import locks
from dpark_tpu import health as _health
from dpark_tpu import ledger as _ledger

MODES = ("off", "ring", "spool")

# always-armed flight ring (ISSUE 14): warning-and-above events land
# here EVEN IN OFF MODE (a bounded in-memory deque — the cost is one
# append at failure sites, which are rare by definition), so a
# post-mortem flight dump has the recent warning context no matter
# what DPARK_TRACE was.  health.flight_dump snapshots it.
_FLIGHT = deque(maxlen=512)

# see TracePlane.run: disambiguates runs minted in the same millisecond
import itertools
_RUN_SEQ = itertools.count(1)

# phase-span names, in scheduler.phase_table() order — the critical
# path analyzer and the reconciliation test share this list
PHASES = ("ingest_tokenize", "narrow", "exchange", "spill", "export")

_PLANE = None
_tls = threading.local()


class _Noop:
    """Shared do-nothing context manager: span()/ctx() with no plane
    installed return this singleton — no allocation on the off path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def _crc(blob):
    from dpark_tpu.shuffle import spill_crc
    return spill_crc(blob)


class TracePlane:
    def __init__(self, mode, trace_dir, run=None):
        self.mode = mode
        self.dir = trace_dir
        self.ring = deque(maxlen=max(16, int(
            getattr(conf, "TRACE_RING_SPANS", 4096))))
        self.lock = locks.named_lock("trace.plane")
        self.pid = os.getpid()
        self.host = socket.gethostname()
        # every record is stamped with a run id: job ids restart at 1
        # per scheduler, so a spool dir surviving across runs (the
        # default /tmp location) would otherwise merge two runs'
        # "job 1" spans into one bogus timeline.  The driver generates
        # it; workers inherit it through the shipped task environment.
        # A process-local sequence joins the pid+millis stamp: two
        # configure() calls inside one millisecond (fast boxes, tests)
        # must still mint DISTINCT runs.
        self.run = run or "%d-%x-%x" % (self.pid,
                                        int(time.time() * 1000),
                                        next(_RUN_SEQ))
        self.emitted = 0
        self.dropped = 0
        self.spool_path = None
        self.counters_path = None
        self._fd = None
        self._cfd = None
        self._spool_bytes = 0
        self._last_counters = None
        if mode == "spool":
            os.makedirs(trace_dir, exist_ok=True)
            self.spool_path = os.path.join(
                trace_dir, "trace-%s-%d.jsonl" % (self.host, self.pid))
            # counter events go to their own small file so the
            # per-job worker-counter merge never re-parses the span
            # spool (which can run to the DPARK_TRACE_SPOOL_MAX_BYTES
            # cap per process)
            self.counters_path = os.path.join(
                trace_dir, "counters-%s-%d.jsonl" % (self.host,
                                                     self.pid))

    def make(self, name, cat, ts, dur, args):
        """Build one record, folding in the thread-local context.
        job/stage/task may arrive via `args` (explicit wins)."""
        rec = {"name": name, "cat": cat, "ts": round(ts, 6),
               "dur": round(dur, 6), "pid": self.pid,
               "host": self.host, "run": self.run,
               "tid": threading.get_ident() & 0xFFFFFFFF}
        cur = getattr(_tls, "ctx", None)
        for field in ("job", "stage", "task"):
            v = args.pop(field, None)
            if v is None and cur is not None:
                v = cur.get(field)
            if v is not None:
                rec[field] = v
        if args:
            rec["args"] = args
        return rec

    def record(self, rec, always=False):
        """Land one record in the ring (and the spool in spool mode).
        Counter events (`cat == "counters"`) are the cross-process
        merge substrate: they route to the separate counters file,
        bypass the span byte cap, and must never be dropped."""
        sink = _health._SINK
        if sink is not None:
            # health plane (ISSUE 14): fold the record into the
            # streaming sketches as it is emitted — no spool
            # re-parsing, bounded memory, and a fold failure never
            # perturbs the traced job
            try:
                sink.fold(rec)
            except Exception:
                pass
        lsink = _ledger._SINK
        if lsink is not None:
            # resource attribution plane (ISSUE 15): the second record
            # sink — per-(tenant, job, stage, program) accounts fold
            # online under the same never-perturb contract
            try:
                lsink.fold(rec)
            except Exception:
                pass
        args = rec.get("args")
        if args is not None and "error" in args:
            # error-carrying spans mirror into the always-armed flight
            # ring so a later dump has the failure context
            _FLIGHT.append(rec)
        counters = always or rec.get("cat") == "counters"
        with self.lock:
            self.ring.append(rec)
            self.emitted += 1
            if self.spool_path is None:
                return
            if not counters:
                cap = int(getattr(conf, "TRACE_SPOOL_MAX_BYTES", 0)
                          or 0)
                if cap and self._spool_bytes >= cap:
                    self.dropped += 1
                    return
            try:
                from dpark_tpu.utils import frame_jsonl
                line = frame_jsonl(rec)
                if counters:
                    if self._cfd is None:
                        self._cfd = os.open(
                            self.counters_path,
                            os.O_APPEND | os.O_CREAT | os.O_WRONLY,
                            0o644)
                    os.write(self._cfd, line)
                else:
                    if self._fd is None:
                        self._fd = os.open(
                            self.spool_path,
                            os.O_APPEND | os.O_CREAT | os.O_WRONLY,
                            0o644)
                    os.write(self._fd, line)
                    self._spool_bytes += len(line)
            except Exception:
                self.dropped += 1

    def close(self):
        with self.lock:
            for attr in ("_fd", "_cfd"):
                fd = getattr(self, attr)
                if fd is not None:
                    try:
                        os.close(fd)
                    except OSError:
                        pass
                    setattr(self, attr, None)


def _annotation(name, args):
    """An entered jax.profiler.TraceAnnotation carrying the span's name
    and args (non-scalars as strings), or None in a process that has
    not loaded jax: no profiler session can be running there, and a
    worker must not import jax for a span's sake."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:            # no jax here, or one half imported
        return None
    mark = profiler.TraceAnnotation(name, **{
        k: v if isinstance(v, (bool, int, float, str)) else str(v)
        for k, v in args.items()})
    mark.__enter__()
    return mark


class _Span:
    """Context manager emitting one complete span on exit (errors ride
    as an `error` arg so a failed fetch is visible on the timeline).
    While it is open the same interval is a TraceAnnotation in the jax
    profiler's trace (see _annotation)."""
    __slots__ = ("plane", "name", "cat", "args", "t0", "mark")

    def __init__(self, plane, name, cat, args):
        self.plane = plane
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self.mark = _annotation(self.name, self.args)
        self.t0 = time.time()
        return self

    def __exit__(self, etype, evalue, tb):
        if self.mark is not None:
            self.mark.__exit__(etype, evalue, tb)
        args = self.args
        if etype is not None:
            args = dict(args, error=etype.__name__)
        self.plane.record(self.plane.make(
            self.name, self.cat, self.t0, time.time() - self.t0, args))
        return False


class _Ctx:
    """Thread-local job/stage/task defaults for nested spans."""
    __slots__ = ("fields", "prev")

    def __init__(self, fields):
        self.fields = fields

    def __enter__(self):
        self.prev = getattr(_tls, "ctx", None)
        merged = dict(self.prev) if self.prev else {}
        merged.update(self.fields)
        _tls.ctx = merged
        return self

    def __exit__(self, *exc):
        _tls.ctx = self.prev
        return False


# ---------------------------------------------------------------------------
# configuration / lifecycle
# ---------------------------------------------------------------------------

def configure(mode=None, trace_dir=None, run=None):
    """Install the trace plane ("off"/None/"" clears it).  Arguments
    fall back to conf.DPARK_TRACE / conf.DPARK_TRACE_DIR.  `run` pins
    the run id (worker processes pass the driver's, shipped via the
    task environment); None starts a fresh run.  Returns the installed
    TracePlane or None."""
    global _PLANE
    if mode is None:
        mode = str(getattr(conf, "DPARK_TRACE", "off") or "off")
    mode = str(mode).lower()
    if mode not in MODES:
        raise ValueError("DPARK_TRACE=%r (expected off|ring|spool)"
                         % mode)
    if _PLANE is not None:
        _PLANE.close()
    if mode == "off":
        _PLANE = None
        return None
    if trace_dir is None:
        trace_dir = getattr(conf, "DPARK_TRACE_DIR", None) \
            or os.path.join(conf.DPARK_WORK_DIR, "trace")
    _PLANE = TracePlane(mode, str(trace_dir), run=run)
    return _PLANE


def active():
    return _PLANE is not None


def mode():
    return _PLANE.mode if _PLANE is not None else "off"


def run_id():
    return _PLANE.run if _PLANE is not None else None


def trace_dir():
    return _PLANE.dir if _PLANE is not None else (
        getattr(conf, "DPARK_TRACE_DIR", None)
        or os.path.join(conf.DPARK_WORK_DIR, "trace"))


# ---------------------------------------------------------------------------
# emission (every entry point is one `is None` check when off)
# ---------------------------------------------------------------------------

def span(name, cat="", **args):
    """Context manager timing a block.  No-op singleton when off."""
    plane = _PLANE
    if plane is None:
        return _NOOP
    return _Span(plane, name, cat, args)


def event(name, cat="", **args):
    """Instant event (dur=0)."""
    plane = _PLANE
    if plane is None:
        return
    plane.record(plane.make(name, cat, time.time(), 0.0, args))


def emit(name, cat, ts, dur, **args):
    """Record a span RETROACTIVELY from measured start/duration (the
    scheduler's task spans are emitted at completion-event time)."""
    plane = _PLANE
    if plane is None:
        return
    plane.record(plane.make(name, cat, ts, dur, args))


def ctx(**fields):
    """Thread-local span context: spans inside the block inherit
    job/stage/task unless set explicitly."""
    if _PLANE is None:
        return _NOOP
    return _Ctx({k: v for k, v in fields.items() if v is not None})


def current_ctx():
    """The calling thread's span-context fields (job/stage/task), or
    None — pool-thread spawners capture this and re-install it in
    their workers so nested spans parent across the thread hop."""
    return getattr(_tls, "ctx", None)


def flight(name, cat="", **args):
    """Warning-and-above instant event: ALWAYS lands in the bounded
    flight ring (even with DPARK_TRACE=off — the ISSUE 14 flight
    recorder contract), and additionally rides the normal plane when
    one is installed.  Only failure sites call this (job abort, stage
    degrade, exhausted fetch replicas, bulk stream give-up), so the
    off-mode cost is one append per rare bad event."""
    plane = _PLANE
    if plane is not None:
        rec = plane.make(name, cat, time.time(), 0.0, dict(args))
        rec["sev"] = "warn"
        # record() already mirrors error-carrying records into the
        # flight ring — only append here when it won't, so one
        # failure never occupies two ring slots
        plane.record(rec)
        if "error" not in args:
            _FLIGHT.append(rec)
    else:
        rec = {"name": name, "cat": cat,
               "ts": round(time.time(), 6), "dur": 0.0,
               "pid": os.getpid(), "host": socket.gethostname(),
               "tid": threading.get_ident() & 0xFFFFFFFF,
               "sev": "warn"}
        if args:
            rec["args"] = args
        sink = _health._SINK
        if sink is not None:
            try:
                sink.fold(rec)
            except Exception:
                pass
        _FLIGHT.append(rec)


def flight_snapshot():
    """The always-armed warning ring's contents (oldest first)."""
    return list(_FLIGHT)


def emit_process_counters():
    """Append this process's CUMULATIVE fault/decode counters as a
    `counters` event (spool mode only).  Workers call this at task
    end; the driver merges the latest event per process — the
    mechanism that closes the multiprocess counter blindspot."""
    plane = _PLANE
    if plane is None or plane.mode != "spool":
        return
    try:
        from dpark_tpu import coding, faults
        snap = coding.counters_snapshot()
        args = {"faults": faults.stats(),
                "decodes": snap["totals"],
                "decodes_per_shuffle": snap["per_shuffle"]}
        _write_process_health(plane)
        _write_process_ledger(plane)
        # cumulative counters only change when a fault fires or a
        # decode happens — skip the write when nothing did, so a
        # long-lived worker running many tasks doesn't grow the
        # counters file one line per task
        key = json.dumps(args, sort_keys=True)
        if key == plane._last_counters:
            return
        rec = plane.make("process.counters", "counters", time.time(),
                         0.0, args)
        plane.record(rec, always=True)
        plane._last_counters = key
    except Exception:
        pass


def _write_process_health(plane):
    """Health plane (ISSUE 14): rewrite this process's per-site
    latency digests as ONE crc-framed record in its own
    ``health-<host>-<pid>.jsonl`` (tmp+rename, latest-wins), so the
    driver's merged tails include MULTIPROC fetches — the worker-tail
    half of the ROADMAP item 5 handoff.  Digests are cumulative and
    change with nearly every task, so they must NOT ride the
    append-only counters file (it would grow one full-digest line per
    task and is deliberately uncapped); an atomic rewrite keeps the
    on-disk cost O(1) per process no matter how many tasks run."""
    sink = _health._SINK
    if sink is None:
        return
    try:
        digests = sink.site_digests()
        if not digests:
            return
        key = json.dumps(digests, sort_keys=True)
        if key == getattr(plane, "_last_health", None):
            return
        from dpark_tpu.utils import frame_jsonl
        rec = plane.make("process.health", "counters", time.time(),
                         0.0, {"health": digests})
        path = os.path.join(plane.dir, "health-%s-%d.jsonl"
                            % (plane.host, plane.pid))
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(frame_jsonl(rec))
        os.replace(tmp, path)
        plane._last_health = key
    except Exception:
        pass


def _write_process_ledger(plane):
    """Resource attribution plane (ISSUE 15): rewrite this process's
    per-account ledger digests as ONE crc-framed record in its own
    ``ledger-<host>-<pid>.jsonl`` (tmp+rename, latest-wins — the
    health-<host>-<pid>.jsonl idiom), so the driver's merged accounts
    include MULTIPROC workers' fetch/spill activity attributed to the
    jobs that caused it.  Cumulative digests change with nearly every
    task, so the on-disk cost stays O(1) per process."""
    sink = _ledger._SINK
    if sink is None:
        return
    try:
        digests = sink.account_digests()
        if not digests:
            return
        key = json.dumps(digests, sort_keys=True)
        if key == getattr(plane, "_last_ledger", None):
            return
        from dpark_tpu.utils import frame_jsonl
        rec = plane.make("process.ledger", "counters", time.time(),
                         0.0, {"ledger": digests})
        path = os.path.join(plane.dir, "ledger-%s-%d.jsonl"
                            % (plane.host, plane.pid))
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(frame_jsonl(rec))
        os.replace(tmp, path)
        plane._last_ledger = key
    except Exception:
        pass


# jax backend-compile timing (ISSUE 15): jax.monitoring reports the
# REAL XLA compile wall per event — the executor installs this once
# per process and stamps the program signature it is dispatching for
# in a thread-local, so compile.backend spans attribute to the right
# (job, stage, program) account.  One predicate per compile when the
# plane is off; compiles are rare by definition.
_compile_listener_installed = False


def set_compile_sig(sig):
    """Stamp the program signature subsequent backend compiles on THIS
    thread should attribute to (None clears)."""
    _tls.compile_sig = sig


def install_compile_listener():
    """Register the jax.monitoring duration listener that turns
    backend compiles into measured ``compile.backend`` spans.  Safe to
    call repeatedly; a jax without the monitoring API is a no-op."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return True
    try:
        from jax import monitoring

        def _on_duration(event, duration, **kw):
            plane = _PLANE
            if plane is None:
                return
            if not str(event).endswith("backend_compile_duration"):
                return
            try:
                sig = getattr(_tls, "compile_sig", None)
                args = {"sig": sig} if sig else {}
                plane.record(plane.make(
                    "compile.backend", "exec",
                    time.time() - float(duration), float(duration),
                    args))
            except Exception:
                pass

        monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listener_installed = True
        return True
    except Exception:
        return False


def counts():
    """(emitted, dropped) for the installed plane, (0, 0) when off."""
    plane = _PLANE
    if plane is None:
        return (0, 0)
    return (plane.emitted, plane.dropped)


# ---------------------------------------------------------------------------
# reading back: ring snapshots, spool loads, worker-counter merges
# ---------------------------------------------------------------------------

def snapshot():
    """This process's ring contents (oldest first)."""
    plane = _PLANE
    if plane is None:
        return []
    with plane.lock:
        return list(plane.ring)


def _read_framed(path, out):
    """Append one crc-framed JSON-lines file's valid records to `out`,
    skipping corrupt/truncated lines — never an error."""
    from dpark_tpu.utils import unframe_jsonl
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return
    out.extend(unframe_jsonl(raw)[0])


def read_spool(trace_dir=None, prefixes=("trace-", "counters-")):
    """Load every spool file under `trace_dir` (default: the active
    plane's dir) whose name starts with one of `prefixes`, skipping
    corrupt/truncated lines — never an error.  Returns records sorted
    by ts."""
    d = trace_dir if trace_dir is not None \
        else globals()["trace_dir"]()
    out = []
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return out
    for fn in names:
        if not (fn.endswith(".jsonl") and fn.startswith(prefixes)):
            continue
        _read_framed(os.path.join(d, fn), out)
    out.sort(key=lambda r: r.get("ts", 0.0))
    return out


def collected(job=None):
    """Everything this process can see: the merged spool (spool mode —
    includes worker processes) or the local ring, optionally filtered
    to one job id.  Restricted to the CURRENT run — a spool dir
    surviving from an earlier run (the default /tmp location) must not
    leak its same-numbered jobs into this run's timeline."""
    plane = _PLANE
    if plane is None:
        return []
    recs = read_spool(plane.dir) if plane.mode == "spool" \
        else snapshot()
    recs = [r for r in recs if r.get("run") == plane.run]
    if job is not None:
        recs = [r for r in recs if r.get("job") == job]
    return recs


def merged_worker_counters(trace_dir=None, include_self=False,
                           run=None):
    """Sum the LATEST `process.counters` event of every OTHER process
    in the spool: {"faults": {site: {hits, fired}}, "decodes":
    {kind: n}, "decodes_per_shuffle": {sid: {kind: n}},
    "processes": n}.  Counter events are cumulative per process, so
    the newest per (host, pid) is that process's total.  Reads ONLY
    the small per-process counters files, not the span spool — the
    merge runs at every job start/finish and must stay cheap no
    matter how many spans the workers wrote.  `run` restricts to one
    run id (default: the active plane's — dead pids from an earlier
    run sharing the spool dir must not contribute phantom counters);
    pass run=False to merge across runs."""
    if run is None and _PLANE is not None:
        run = _PLANE.run
    me = os.getpid()
    latest = {}
    latest_health = {}
    latest_ledger = {}
    for rec in read_spool(trace_dir, prefixes=("counters-",
                                               "health-",
                                               "ledger-")):
        if rec.get("cat") != "counters":
            continue
        if run and rec.get("run") != run:
            continue
        pid = rec.get("pid")
        if not include_self and pid == me \
                and rec.get("host") == socket.gethostname():
            continue
        args = rec.get("args") or {}
        if rec.get("name") == "process.health":
            # the per-process health digest file (latest-wins
            # rewrite, one record per process — see
            # _write_process_health)
            latest_health[(rec.get("host"), pid)] = \
                args.get("health") or {}
        elif rec.get("name") == "process.ledger":
            # the per-process ledger digest file (ISSUE 15; same
            # latest-wins O(1) idiom — see _write_process_ledger)
            latest_ledger[(rec.get("host"), pid)] = \
                args.get("ledger") or {}
        else:
            latest[(rec.get("host"), pid)] = args
    out = {"faults": {}, "decodes": {}, "decodes_per_shuffle": {},
           "health": {}, "ledger": {}, "processes": len(latest)}
    for digests in latest_health.values():
        for site, digest in digests.items():
            out["health"][site] = _health.merge_digests(
                out["health"].get(site), digest)
    for digests in latest_ledger.values():
        for key, digest in digests.items():
            out["ledger"][key] = _ledger.merge_account_digests(
                out["ledger"].get(key), digest)
    for args in latest.values():
        for site, st in (args.get("faults") or {}).items():
            ent = out["faults"].setdefault(site,
                                           {"hits": 0, "fired": 0})
            ent["hits"] += int(st.get("hits", 0))
            ent["fired"] += int(st.get("fired", 0))
        for kind, v in (args.get("decodes") or {}).items():
            if kind == "mode":
                continue
            out["decodes"][kind] = out["decodes"].get(kind, 0) + int(v)
        for sid, per in (args.get("decodes_per_shuffle")
                         or {}).items():
            try:
                sid = int(sid)        # JSON round-trips keys as str
            except (TypeError, ValueError):
                pass
            ent = out["decodes_per_shuffle"].setdefault(sid, {})
            for kind, v in per.items():
                ent[kind] = ent.get(kind, 0) + int(v)
    return out


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing)
# ---------------------------------------------------------------------------

def to_chrome(records):
    """Merged records -> Chrome trace-event JSON (dict; json.dump it).
    Complete spans become ph="X" with microsecond ts/dur; instant
    events ph="i"; each (host, pid) gets a process_name metadata row
    so worker processes are visually distinct."""
    events = []
    procs = {}
    for rec in records:
        pid = int(rec.get("pid", 0))
        host = rec.get("host", "")
        if (host, pid) not in procs:
            procs[(host, pid)] = True
            events.append({"ph": "M", "name": "process_name",
                           "pid": pid, "tid": 0,
                           "args": {"name": "%s:%d" % (host, pid)}})
        args = dict(rec.get("args") or {})
        for field in ("job", "stage", "task"):
            if field in rec:
                args[field] = rec[field]
        ev = {"name": rec.get("name", "?"),
              "cat": rec.get("cat", "") or "misc",
              "pid": pid, "tid": int(rec.get("tid", 0)),
              "ts": round(float(rec.get("ts", 0.0)) * 1e6, 1),
              "args": args}
        dur = float(rec.get("dur", 0.0))
        if rec.get("cat") == "counters":
            continue                 # merge substrate, not timeline
        if dur > 0:
            ev["ph"] = "X"
            ev["dur"] = round(dur * 1e6, 1)
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# critical-path analysis over the span DAG
# ---------------------------------------------------------------------------

def job_ids(records):
    return sorted({r["job"] for r in records
                   if r.get("name") == "job" and "job" in r})


def critical_path(records, job=None):
    """Longest stage chain bounding one job's wall clock, with the
    per-phase attribution of the chain's stages.

    The DAG: stage spans carry their `parents` (the scheduler's stage
    dependencies); cp(stage) = dur(stage) + max(cp(parent)); the chain
    is read off the argmax backpointers from the terminal stage.
    Phase totals come from the `phase` spans (emitted from the same
    _StreamStats snapshot scheduler.phase_table() reads, so the two
    reconcile) plus fetch spans; the remainder of a stage's wall is
    `other` (host/object work, scheduling).  Returns None when the
    job has no span."""
    if job is None:
        jobs = job_ids(records)
        if not jobs:
            return None
        job = jobs[-1]
    job_span = None
    stages = {}
    for rec in records:
        if rec.get("job") != job:
            continue
        name = rec.get("name")
        if name == "job":
            job_span = rec
        elif name == "stage" and "stage" in rec:
            stages[rec["stage"]] = rec
    if job_span is None and not stages:
        return None
    parents = {sid: [p for p in (rec.get("args", {}).get("parents")
                                 or []) if p in stages]
               for sid, rec in stages.items()}
    # longest path by stage duration (memoized DFS; the stage DAG is
    # acyclic by construction)
    memo = {}

    def cp(sid):
        if sid in memo:
            return memo[sid]
        memo[sid] = (0.0, None)         # cycle guard
        dur = float(stages[sid].get("dur", 0.0))
        best, back = dur, None
        for p in parents.get(sid, ()):
            c, _ = cp(p)
            if dur + c > best:
                best, back = dur + c, p
        memo[sid] = (best, back)
        return memo[sid]

    has_child = {p for ps in parents.values() for p in ps}
    terminals = [s for s in stages if s not in has_child] \
        or list(stages)
    chain = []
    if terminals:
        head = max(terminals, key=lambda s: cp(s)[0])
        while head is not None:
            chain.append(head)
            head = cp(chain[-1])[1]
        chain.reverse()
    # phase attribution over the chain's stages
    phases = {p: 0.0 for p in PHASES}
    phases["fetch"] = 0.0
    chain_set = set(chain)
    for rec in records:
        if rec.get("job") != job or rec.get("stage") not in chain_set:
            continue
        name = rec.get("name", "")
        if rec.get("cat") == "phase" and name.startswith("phase."):
            key = name[len("phase."):]
            phases[key] = phases.get(key, 0.0) \
                + float(rec.get("dur", 0.0))
        elif name == "fetch.bucket":
            phases["fetch"] += float(rec.get("dur", 0.0))
    chain_wall = sum(float(stages[s].get("dur", 0.0)) for s in chain)
    attributed = sum(phases.values())
    phases["other"] = max(0.0, chain_wall - attributed)
    total = max(sum(phases.values()), 1e-9)
    blocked = {k: round(v / total, 4) for k, v in phases.items() if v}
    bound = max(blocked, key=blocked.get) if blocked else None
    return {
        "job": job,
        "wall_s": round(float(job_span.get("dur", chain_wall)), 6)
        if job_span is not None else round(chain_wall, 6),
        "chain": chain,
        "chain_wall_s": round(chain_wall, 6),
        "phases_s": {k: round(v, 6) for k, v in phases.items()},
        "blocked_frac": blocked,
        "bound": bound,
        "spans": sum(1 for r in records if r.get("job") == job),
    }


def summary():
    """The `trace` section for bench artifacts: mode, span counts, and
    (when tracing) the critical-path summary of the longest-running
    traced job."""
    emitted, dropped = counts()
    out = {"mode": mode(), "spans": emitted, "dropped": dropped}
    plane = _PLANE
    if plane is None:
        return out
    if plane.mode == "spool":
        out["dir"] = plane.dir
    try:
        recs = collected()
        best = None
        for j in job_ids(recs):
            cp = critical_path(recs, j)
            if cp and (best is None or cp["wall_s"] > best["wall_s"]):
                best = cp
        out["critical_path"] = best
    except Exception:
        out["critical_path"] = None
    return out


def _init_from_conf():
    m = str(getattr(conf, "DPARK_TRACE", "off") or "off")
    if m != "off":
        configure(m)


_init_from_conf()
