"""DAG scheduler + masters (local, process; tpu lives in backend/tpu).

Reference parity: dpark/schedule.py — Stage (cut at ShuffleDependency
edges), DAGScheduler.runJob as a generator yielding per-partition results,
newStage/getParentStages/getMissingParentStages/submitStage/
submitMissingTasks/taskEnded, FetchFailed -> parent stage resubmit;
LocalScheduler and MultiProcessScheduler masters (SURVEY.md sections 2.1,
3.1, 5.3).  The MesosScheduler has no TPU-era equivalent; multi-host
dispatch belongs to the DCN layer (see backend/).
"""

import collections
import multiprocessing
import pickle
import queue
import threading
import traceback
import weakref

import sys

from dpark_tpu import conf, locks, serialize, trace


def _submodule(name):
    """Resolve a dpark_tpu submodule even when a convenience function in
    dpark_tpu/__init__ shadows the package attribute of the same name."""
    import importlib
    return importlib.import_module("dpark_tpu." + name)


accumulator = _submodule("accumulator")
from dpark_tpu.dependency import ShuffleDependency
from dpark_tpu.env import env
from dpark_tpu.shuffle import FetchFailed
from dpark_tpu.task import ResultTask, ShuffleMapTask
from dpark_tpu.utils.log import Progress, get_logger

logger = get_logger("schedule")

# /metrics phase-seconds histogram bucket edges (seconds); the web
# renderer cumulates these into Prometheus le= buckets
PHASE_BUCKETS = (0.05, 0.25, 1.0, 5.0, 30.0, 120.0)

# dominant-bucket byte fraction (largest reduce bucket / total bucket
# bytes across the exchange) at or above which a completed map side
# counts as skewed enough for _maybe_replan to re-split; buckets must
# be file://-local for the driver to size them (device HBM exchanges
# never re-split — their skew signal is the SegMapOp histogram, adapt
# decision point 3)
REPLAN_SKEW_FRAC = 0.6


class Stage:
    # itertools.count: atomic under the GIL — concurrent drivers on a
    # resident job server (ISSUE 9) mint stage ids from their own
    # threads, and a read-modify-write counter could hand two stages
    # one id
    _next_id = __import__("itertools").count(1)

    def __init__(self, rdd, shuffle_dep, parents):
        self.id = next(Stage._next_id)
        # a stage OWNS neither its rdd nor its dependency: the user's
        # RDD chain does (run_job holds the final rdd for the life of
        # a job, and the lineage holds every dependency below it), so
        # a map stage that outlives its job in shuffle_to_stage keeps
        # no chain alive — once the chain is dropped the dependency
        # dies and its shuffle state is released (_release_unreachable)
        self._rdd = weakref.ref(rdd)
        self._shuffle_dep = None if shuffle_dep is None \
            else weakref.ref(shuffle_dep)      # None for a result stage
        self.parents = parents
        self.num_partitions = len(rdd.splits)
        # per-map-partition output URI when this is a shuffle map stage
        self.output_locs = [None] * self.num_partitions

    @property
    def rdd(self):
        return self._rdd()

    @property
    def shuffle_dep(self):
        """The live ShuffleDependency (None for a result stage, and
        for a map stage whose dependency nothing holds any more)."""
        ref = self._shuffle_dep
        return None if ref is None else ref()

    @property
    def is_shuffle_map(self):
        return self._shuffle_dep is not None

    @property
    def is_available(self):
        if not self.is_shuffle_map:
            return False
        return all(loc is not None for loc in self.output_locs)

    def add_output_loc(self, partition, uri):
        self.output_locs[partition] = uri

    def remove_outputs_by_uri(self, uri):
        for i, loc in enumerate(self.output_locs):
            if loc == uri:
                self.output_locs[i] = None

    def __repr__(self):
        return "<Stage %d on %r>" % (self.id, self.rdd)


def _graph_size(stage):
    """Stages in the graph under `stage`, itself included."""
    seen = set()
    todo = [stage]
    while todo:
        s = todo.pop()
        if s.id not in seen:
            seen.add(s.id)
            todo.extend(s.parents)
    return len(seen)


class DAGScheduler:
    """Walks the RDD dependency graph bottom-up, running stages whose
    parents are available; master-specific subclasses implement
    submit_tasks()."""

    def __init__(self):
        from dpark_tpu.env import env
        self.shuffle_to_stage = {}
        # ids of shuffles whose ShuffleDependency nothing holds any
        # more: appended by the dependency's weakref.finalize (any
        # thread, at any decref: no lock, nothing but the append) and
        # drained by _release_unreachable
        self._unreachable = collections.deque()
        self.started = False
        self.profile = None            # MergedProfile when --profile
        # host health, SHARED with the shuffle fetcher's replica choice
        # through env (trivial on single-host masters; the multi-host
        # DCN paths consult is_blacklisted/offer_choice/rank_hosts);
        # env constructs it unconditionally
        self.host_manager = env.host_manager
        self.history = []              # job records for the web UI
        self._next_job_id = 0
        # guards history-list mutation vs the web server's /metrics
        # snapshot (ISSUE 8 satellite: a scrape mid-job must never
        # throw); per-record field mutation stays lock-free — the
        # snapshot copies defensively.  The archive keeps aggregates
        # of records trimmed out of the 100-job window so /metrics
        # counters never decrease.
        self._metrics_lock = locks.named_lock(
            "schedule.metrics", reentrant=True)
        self._metrics_archive = self._new_metrics()
        # resident job server (ISSUE 9): when attached, stage
        # execution routes through the server's fair dispatcher
        # instead of running inline — one `is None` check per submit
        # seam, so a service-less process pays nothing
        self._service = None
        # per-driver-thread state: with N drivers multiplexed onto one
        # scheduler, the "current" job record is whichever job THIS
        # thread is building/executing (the slot threads set it around
        # each stage execution); _last_record keeps the single-thread
        # fallback for embedders that read it from another thread
        self._tls = threading.local()
        self._last_record = None
        # guards the shared stage graph (shuffle_to_stage) against
        # concurrent run_job invocations from different driver threads
        self._graph_lock = locks.named_lock(
            "schedule.graph", reentrant=True)

    # -- lifecycle -------------------------------------------------------
    def start(self):
        self.started = True

    def stop(self):
        self.started = False

    # -- per-thread current record (ISSUE 9) -----------------------------
    # note_stage() and the executor's _stage_note callback attribute
    # to whichever job the CALLING thread is working on: driver
    # threads set it when they mint a record, the job server's slot
    # threads set it around each stage execution.  Single-threaded
    # schedulers see the exact pre-service behavior through the
    # _last_record fallback.
    @property
    def _current_record(self):
        rec = getattr(self._tls, "record", None)
        return rec if rec is not None else self._last_record

    @_current_record.setter
    def _current_record(self, rec):
        self._tls.record = rec
        self._last_record = rec

    # -- stage graph -----------------------------------------------------
    def new_stage(self, rdd, shuffle_dep):
        with self._graph_lock:
            return Stage(rdd, shuffle_dep, self.get_parent_stages(rdd))

    def get_shuffle_map_stage(self, dep):
        with self._graph_lock:
            stage = self.shuffle_to_stage.get(dep.shuffle_id)
            if stage is None:
                stage = self.new_stage(dep.rdd, dep)
                self.shuffle_to_stage[dep.shuffle_id] = stage
                # an RDD that comes back from a pickle has no ctx and
                # runs no job, so this object is the shuffle's only
                # dependency a job can ever meet
                weakref.finalize(dep, self._unreachable.append,
                                 dep.shuffle_id).atexit = False
            return stage

    def _release_unreachable(self):
        """Forget every shuffle whose dependency died since the last
        call: its stage, its map-output locations and (the hook) the
        master's own state for it.  A shuffle's state lives exactly as
        long as its ShuffleDependency is reachable from the user's
        RDDs; called when a job starts and when one finishes."""
        pending = self._unreachable
        while pending:
            try:
                sid = pending.popleft()
            except IndexError:          # another driver thread got it
                return
            self.shuffle_to_stage.pop(sid, None)
            env.map_output_tracker.remove_outputs(sid)
            self._shuffle_unreachable(sid)

    def _shuffle_unreachable(self, sid):
        """Hook: nothing can read shuffle `sid` again (the tpu master
        frees its HBM store)."""

    def get_parent_stages(self, rdd):
        with self._graph_lock:
            return self._get_parent_stages_locked(rdd)

    def _get_parent_stages_locked(self, rdd):
        parents = []
        visited = set()

        def visit(r):
            if r.id in visited:
                return
            visited.add(r.id)
            for dep in r.dependencies:
                if isinstance(dep, ShuffleDependency):
                    stage = self.get_shuffle_map_stage(dep)
                    if stage not in parents:
                        parents.append(stage)
                else:
                    visit(dep.rdd)
        visit(rdd)
        return parents

    def get_missing_parent_stages(self, stage):
        return [p for p in stage.parents if not p.is_available]

    def _needed_shuffles(self, rdd, acc=None, visited=None,
                         transitive=False):
        """Shuffle ids reachable through NARROW deps — exactly what a
        task over `rdd` fetches, the multiprocess master's per-task
        map-output snapshot.  `transitive=True` additionally walks
        PAST shuffle boundaries: the whole lineage's shuffle ids, for
        per-job decode attribution under concurrent jobs (ISSUE 9) —
        that set must not ride every task message."""
        acc = acc if acc is not None else set()
        visited = visited if visited is not None else set()
        if rdd.id in visited:
            return acc
        visited.add(rdd.id)
        for dep in rdd.dependencies:
            if isinstance(dep, ShuffleDependency):
                acc.add(dep.shuffle_id)
                if not transitive:
                    continue
            self._needed_shuffles(dep.rdd, acc, visited, transitive)
        return acc

    # -- the job loop ----------------------------------------------------
    def run_job(self, final_rdd, func, partitions=None, allow_local=False):
        """Generator yielding per-partition results IN PARTITION ORDER
        (buffering completions that arrive early)."""
        if partitions is None:
            partitions = list(range(len(final_rdd.splits)))
        if not partitions:
            return
        import time as _time
        final_stage, record, local = self._begin_job(
            final_rdd, partitions, allow_local)
        job_t0 = _time.time()
        # allowLocal fast path (reference: runJob allowLocal) — single
        # partition, no shuffle parents: compute inline, no tasks.
        if local:
            try:
                yield func(final_rdd.iterator(
                    final_rdd.splits[partitions[0]]))
                record["finished"] = 1
                record["state"] = "done"
            except GeneratorExit:
                record["state"] = "partial"    # take/first stopped early
                raise
            except BaseException:
                record["state"] = "aborted"
                raise
            finally:
                self._finish_job(record, job_t0, local=True)
            return

        output_parts = list(partitions)
        part_index = {p: i for i, p in enumerate(output_parts)}
        finished = [False] * len(output_parts)
        results = [None] * len(output_parts)

        # job-scoped event queue: tasks submitted by THIS job report here,
        # so a generator abandoned mid-iteration (take/iterate) can never
        # leak its late completions into a subsequent job's loop
        events = queue.Queue()
        in_flight = [0]          # submitted tasks whose event hasn't arrived

        def report(task, status, payload):
            events.put((task, status, payload))

        waiting = set()         # stages blocked on parents
        running = set()         # stages with submitted tasks
        pending_tasks = {}      # stage -> set of partition ids not yet done
        failures = {}           # task partition retry counters per stage
        stage_failures = {}     # stage id -> lineage-recovery rounds
        #   (FetchFailed resubmits/recomputes), capped by
        #   conf.MAX_STAGE_FAILURES so a persistently failing shuffle
        #   source aborts with a chained error instead of looping
        progress = Progress(final_rdd.scope_name, len(output_parts))

        stage_of = {}

        def submit_stage(stage):
            stage_of[stage.id] = stage
            if stage in waiting or stage in running:
                return
            missing = self.get_missing_parent_stages(stage)
            if not missing:
                submit_missing_tasks(stage)
                running.add(stage)
            else:
                waiting.add(stage)
                for p in missing:
                    submit_stage(p)

        submitted_at = {}       # (stage_id, partition) -> last submit time

        def submit_missing_tasks(stage):
            tasks = []
            if stage.is_shuffle_map:
                self._maybe_choose_code(stage.shuffle_dep)
                for p in range(stage.num_partitions):
                    if stage.output_locs[p] is None:
                        tasks.append(ShuffleMapTask(
                            stage.id, stage.rdd, stage.shuffle_dep, p))
            else:
                for p in output_parts:
                    if not finished[part_index[p]]:
                        tasks.append(ResultTask(
                            stage.id, final_rdd, func, p, part_index[p]))
            pending_tasks.setdefault(stage, set()).update(
                t.partition for t in tasks)
            now = _time.time()
            for t in tasks:
                submitted_at[(stage.id, t.partition)] = now
            info = self._stage_info(record, stage.id)
            info.update({"rdd": type(stage.rdd).__name__,
                         "parts": stage.num_partitions,
                         "shuffle": stage.is_shuffle_map,
                         "parents": [p.id for p in stage.parents],
                         "started": now})
            # pane-plane attribution (ISSUE 10): windowed DStreams tag
            # the RDDs they build ({stream, role, pane} — pane-build /
            # tree-merge / late-patch / window-emit), so a stage's
            # cost lands on the pane-plane role that caused it in the
            # web UI and trace analysis
            stream_tag = getattr(stage.rdd, "_stream_tag", None)
            if stream_tag:
                info["stream"] = dict(stream_tag)
            logger.debug("submit stage %s with %d tasks", stage, len(tasks))
            in_flight[0] += len(tasks)
            if trace._PLANE is not None:
                # tasks carry the job id so worker-process task.run
                # spans parent correctly after the serialize trip
                for t in tasks:
                    t._trace_job = record["id"]
            self._run_tasks(stage, tasks, report, record)

        def spawn_duplicate(stage, p):
            """Speculative copy of a straggling task (first result wins)."""
            if stage.is_shuffle_map:
                t = ShuffleMapTask(stage.id, stage.rdd,
                                   stage.shuffle_dep, p)
            else:
                t = ResultTask(stage.id, final_rdd, func, p, part_index[p])
            in_flight[0] += 1
            record["speculated"] = record.get("speculated", 0) + 1
            logger.info("speculatively re-launching %r", t)
            if trace._PLANE is not None:
                t._trace_job = record["id"]
            self._run_tasks(stage, [t], report, record)

        # crash-consistent journal (ISSUE 20): write-ahead the job,
        # then seed any journaled stage completions whose outputs
        # survived a controller death — the submit below skips them
        from dpark_tpu import journal
        if journal._PLANE is not None:
            record["_jfp"] = journal.job_fingerprint(final_rdd,
                                                     output_parts)
            journal.append_job(record["_jfp"], final_rdd.scope_name)
            journal.seed_stages(self, final_stage, record,
                                record["_jfp"])

        submit_stage(final_stage)
        record["stages"] = len(stage_of)

        try:
            yield from self._event_loop(
                output_parts, finished, results, events, in_flight,
                waiting, running, pending_tasks, failures, progress,
                stage_of, submit_stage, submit_missing_tasks, record,
                report, submitted_at, spawn_duplicate, stage_failures)
        except GeneratorExit:
            # consumer stopped early (take/first/iterate) — by design
            record["state"] = "partial"
            raise
        finally:
            if record["state"] == "running":
                record["state"] = "done" if all(finished) else "aborted"
            self._finish_job(record, job_t0)
            # submit_stage calls itself through this frame's cell, a
            # cycle that holds final_rdd (submit_missing_tasks' cell):
            # break it, so a chain the caller drops dies by reference
            # count and not when the cyclic collector next runs
            submit_stage = None

    def _begin_job(self, final_rdd, partitions, allow_local):
        """The way into a job: the stage graph (new_stage walks the
        lineage) and the job's record (_new_job_record: the counter
        baselines, the history, _job_started).  Returns (final stage,
        record, whether the job runs inline: allowLocal, one partition,
        no shuffle parents).  With the trace plane on this is the
        `job.begin` span, which ends where `job` starts; the id is
        stamped on it, and on the `preflight` reading that
        DparkContext.runJob left on this thread, once it is minted."""
        plane = trace._PLANE
        if plane is not None:
            with trace.span("job.begin", "sched") as sp:
                out = self._open_job(final_rdd, partitions, allow_local)
                job = out[1]["id"]
                sp.args.update(job=job, stages=_graph_size(out[0]))
                pre = getattr(self._tls, "preflight", None)
                if pre is not None:
                    self._tls.preflight = None
                    from dpark_tpu.analysis import lint_mode
                    trace.emit("preflight", "sched", pre[0], pre[1],
                               job=job, mode=lint_mode())
                return out
        return self._open_job(final_rdd, partitions, allow_local)

    def _open_job(self, final_rdd, partitions, allow_local):
        final_stage = self.new_stage(final_rdd, None)
        local = bool(allow_local and len(partitions) == 1
                     and not final_stage.parents)
        record = self._new_job_record(final_rdd, len(partitions),
                                      stages=0 if local else 1)
        return final_stage, record, local

    def _run_tasks(self, stage, tasks, report, record):
        """_dispatch under the job's span context; with the trace plane
        on, one `stage.run` span a submission: `plan`, `join` and
        `stage.exec` lie inside it, and its self time is the
        scheduler's own work for the stage."""
        plane = trace._PLANE
        if plane is not None:
            with trace.ctx(job=record["id"], stage=stage.id), \
                    trace.span("stage.run", "sched", tasks=len(tasks),
                               shuffle=stage.is_shuffle_map):
                self._dispatch(stage, tasks, report, record)
        else:
            self._dispatch(stage, tasks, report, record)

    def _finish_job(self, record, t0, local=False):
        """The way out of a job: its seconds, then the finalizers.
        With the trace plane on, the `job` span (emitted with the
        unrounded duration; record["seconds"] keeps three decimals for
        the web UI) and, from where it ends to after _job_finished,
        the `job.finish` span."""
        import time as _time
        plane = trace._PLANE
        if plane is not None:
            with trace.span("job.finish", "sched", job=record["id"]) as sp:
                seconds = sp.t0 - t0
                self._finalize_job(record, t0, seconds, local)
        else:
            self._finalize_job(record, t0, _time.time() - t0, local)

    def _finalize_job(self, record, t0, seconds, local):
        record["seconds"] = round(seconds, 3)
        record.pop("_t_submit", None)
        if not local:
            jfp = record.pop("_jfp", None)
            if jfp is not None and record["state"] == "done":
                from dpark_tpu import journal
                journal.append_job_done(jfp)
        self._finalize_decodes(record)
        if not local:
            self._finalize_exchanges(record)
            self._finalize_adapt(record)
        self._trace_job_span(record, t0, seconds)
        self._finalize_health(record)
        self._job_finished(record)

    def _new_job_record(self, final_rdd, parts, stages=1):
        import time as _time
        with self._metrics_lock:
            self._next_job_id += 1
            job_id = self._next_job_id
        record = {"id": job_id, "scope": final_rdd.scope_name,
                  "parts": parts, "finished": 0, "stages": stages,
                  "seconds": 0.0, "state": "running", "stage_info": [],
                  # pre-flight lint findings (context.runJob stashes
                  # them on the final rdd) ride the job record so the
                  # web UI shows WHY a plan is suspect next to its
                  # per-stage timings
                  "lint": list(getattr(final_rdd, "_lint_findings",
                                       ()) or ())}
        # pane-plane job attribution (ISSUE 10): a job collecting a
        # windowed stream's emitted RDD carries that stream's tag
        stream_tag = getattr(final_rdd, "_stream_tag", None)
        if stream_tag:
            record["stream"] = dict(stream_tag)
        # coded-shuffle decode accounting (ISSUE 6): counters are
        # process-global, so each job snapshots a baseline at start
        # and takes the delta at finish (popped before the record
        # ships as JSON)
        from dpark_tpu import coding
        record["_decode_base"] = coding.counters_snapshot()
        # adaptive-execution accounting (ISSUE 7): the decision log is
        # process-global too — snapshot its position (and reset the
        # per-job de-dup epoch) so the decisions taken DURING this job
        # (steered or observe-mode would-be) ride this record as
        # record["adapt"], including choices repeated from a prior job
        from dpark_tpu import adapt
        try:
            record["_adapt_base"] = adapt.begin_job()
        except Exception:
            pass
        # worker-counter merge (ISSUE 8 satellite): with spool tracing
        # on, worker processes append cumulative fault/decode counters
        # to the trace spool — snapshot the merged view now so this
        # job's delta attributes only ITS decode activity
        if trace.mode() == "spool":
            try:
                record["_trace_decode_base"] = \
                    trace.merged_worker_counters()
            except Exception:
                pass
        # resident-service bookkeeping (ISSUE 9): tag the record with
        # the submitting client, stamp submit time (queue-wait and
        # first-wave latency measure from it), and pre-walk the
        # lineage's shuffle ids so decode attribution under CONCURRENT
        # jobs restricts to this job's own shuffles instead of the
        # overlapping process-global totals delta
        if self._service is not None:
            record["service"] = True
            record["_t_submit"] = _time.time()
            client = getattr(self._tls, "client", None)
            if client:
                record["client"] = client
            try:
                # a sorted list, not a set: /api/jobs may serialize
                # the record as JSON while the job is still running
                record["_sids"] = sorted(self._needed_shuffles(
                    final_rdd, transitive=True))
            except Exception:
                pass
        with self._metrics_lock:
            self.history.append(record)
            dropped = self.history[:-100]
            if dropped:
                self._archive_metrics(dropped)
            del self.history[:-100]
        self._current_record = record
        # resource attribution (ISSUE 15): register job -> tenant so
        # the ledger's accounts roll up per client (one `is None`
        # check when the plane is off; "local" on single-tenant
        # masters)
        from dpark_tpu import ledger
        if ledger._SINK is not None:
            ledger.note_job(record["id"], record.get("client"))
        self._job_started(record)
        return record

    def _job_started(self, record):
        """Hook: a job record was minted (the tpu master pins the
        job's HBM buckets and snapshots program-cache counters)."""
        self._release_unreachable()

    def _job_finished(self, record):
        """Hook: the job finalized (counters attributed, pins
        released)."""
        self._release_unreachable()

    def _finalize_health(self, record):
        """Health-plane job hook (ISSUE 14): per-tenant SLO accounting
        (resident service), flight-recorder dump on abort, throttled
        site-tail persistence into the adapt store.  One call per job;
        every branch inside is a cheap predicate and never raises."""
        from dpark_tpu import health
        health.job_finished(self, record)

    def _trace_job_span(self, record, t0, seconds):
        """Emit the job's span (trace plane, ISSUE 8) — the root of
        the per-job timeline tools/dtrace analyzes."""
        if trace._PLANE is None:
            return
        trace.emit("job", "sched", t0, seconds,
                   job=record["id"], scope=record.get("scope"),
                   state=record.get("state"),
                   stages=record.get("stages"),
                   # tenant identity rides the span so the OFFLINE
                   # ledger twin (dtrace --ledger) resolves accounts
                   # to tenants from a spool alone (ISSUE 15)
                   client=record.get("client") or "local")

    def _finalize_decodes(self, record):
        """Attribute coded-shuffle decode activity since the job
        started to this job record (ISSUE 6): the totals delta rides
        as ``record["decodes"]`` (repair = parity replaced a FAILED
        shard, straggler_win = parity merely beat a slow one,
        decode_failures = fewer than k survived and lineage had to
        pay), and per-shuffle deltas land on the PARENT stage whose
        outputs were decoded — the web UI's per-stage decode
        column."""
        from dpark_tpu import coding
        base = record.pop("_decode_base", None)
        sids = record.pop("_sids", None)
        if base is None:
            return
        snap = coding.counters_snapshot()
        base_per = base.get("per_shuffle", {})
        per_deltas = {}
        for sid, counts in snap.get("per_shuffle", {}).items():
            prev = base_per.get(sid, {})
            delta = {k: v - prev.get(k, 0) for k, v in counts.items()}
            if any(delta.values()):
                per_deltas[sid] = delta
        if sids is not None:
            # concurrent jobs on a resident service (ISSUE 9): the
            # process-global totals delta overlaps with every other
            # in-flight job — attribute only the per-shuffle deltas of
            # THIS job's own lineage, so records never cross-contaminate
            totals = {k: 0 for k in snap["totals"]}
            for sid in sids:
                for k, v in per_deltas.get(sid, {}).items():
                    totals[k] = totals.get(k, 0) + v
        else:
            base_totals = base.get("totals", {})
            totals = {k: v - base_totals.get(k, 0)
                      for k, v in snap["totals"].items()}
        if any(totals.values()) or coding.active():
            record["decodes"] = dict(totals, mode=coding.describe())
        for sid, delta in per_deltas.items():
            if sids is not None and sid not in sids:
                continue
            parent = self.shuffle_to_stage.get(sid)
            if parent is not None:
                info = self._stage_info(record, parent.id)
                d = info.setdefault("decodes", {})
                for k, v in delta.items():
                    d[k] = d.get(k, 0) + v
        self._merge_worker_decodes(record, sids)

    def _merge_worker_decodes(self, record, sids=None):
        """Fold WORKER-PROCESS decode deltas (spooled counter events,
        ISSUE 8 satellite) into this job's record: the multiprocess
        master's workers decode in their own processes, and before the
        trace spool their counters never reached the driver (the
        documented per-process caveat of PRs 6-7).  `sids` (service
        mode) restricts attribution to this job's own shuffles."""
        from dpark_tpu import coding
        wbase = record.pop("_trace_decode_base", None)
        if wbase is None:
            return
        try:
            snap = trace.merged_worker_counters()
        except Exception:
            return
        base_per = wbase.get("decodes_per_shuffle", {})
        per_deltas = {}
        for sid, counts in snap.get("decodes_per_shuffle",
                                    {}).items():
            prev = base_per.get(sid, {})
            delta = {k: v - prev.get(k, 0) for k, v in counts.items()}
            if any(delta.values()):
                per_deltas[sid] = delta
        if sids is not None:
            totals = {}
            for sid in sids:
                for k, v in per_deltas.get(sid, {}).items():
                    totals[k] = totals.get(k, 0) + v
        else:
            base_tot = wbase.get("decodes", {})
            totals = {k: v - base_tot.get(k, 0)
                      for k, v in snap.get("decodes", {}).items()}
        if any(totals.values()):
            d = record.setdefault("decodes",
                                  {"mode": coding.describe()})
            for k, v in totals.items():
                d[k] = d.get(k, 0) + v
            d["worker_processes"] = snap.get("processes", 0)
        for sid, delta in per_deltas.items():
            if sids is not None and sid not in sids:
                continue
            parent = self.shuffle_to_stage.get(sid)
            if parent is not None:
                info = self._stage_info(record, parent.id)
                d = info.setdefault("decodes", {})
                for k, v in delta.items():
                    d[k] = d.get(k, 0) + v

    def _finalize_adapt(self, record):
        """Attribute adaptive-execution decisions taken during this job
        to its record (ISSUE 7): ``record["adapt"]`` carries the mode
        plus the decision-log delta — steered choices (applied: true)
        and observe-mode would-be choices (applied: false), each with
        predicted (and, once measured, observed) ms.  Absent entirely
        with DPARK_ADAPT=off, so off-mode records stay bit-identical
        to the pre-PR shape."""
        base = record.pop("_adapt_base", None)
        if base is None:
            return
        try:
            from dpark_tpu import adapt
            if not adapt.enabled():
                return
            # concurrent jobs (ISSUE 9): the log interleaves decisions
            # from every in-flight job — restrict to the ones tagged
            # with THIS job's id (the service's slot threads tag them)
            job = record["id"] if record.get("service") else None
            decisions = adapt.decisions_since(base, job=job)
            record["adapt"] = {"mode": adapt.mode(),
                               "decisions": decisions}
        except Exception:
            pass

    # -- straggler-adaptive coded shuffle (ISSUE 19, decision pt 6) ------
    def _maybe_choose_code(self, dep):
        """Price a per-exchange shuffle code from the adapt store's
        per-peer fetch-tail sketches before the map stage writes its
        first bucket: an exchange whose peers historically straggle
        gets parity even with the global code off, a tight-tailed one
        drops to uncoded under a global rs(k,m).  The choice rides
        ``dep.code_spec`` to every task (writer AND reader register it
        process-locally), so mixed per-shuffle codes stay wire-safe
        through the self-describing container framing.  One flag check
        when DPARK_CODE_ADAPT is off."""
        if not conf.CODE_ADAPT:
            return
        if getattr(dep, "code_spec", None) is not None:
            return                      # resubmit: keep the first choice
        site = getattr(dep, "adapt_site", None)
        if not site:
            return
        from dpark_tpu import adapt, coding
        try:
            spec = adapt.choose_shuffle_code(site)
        except Exception:
            logger.exception("code choice failed for %s", site)
            return
        if spec is None:
            return                      # observe mode / no usable tails
        dep.code_spec = spec
        coding.set_shuffle_code(dep.shuffle_id, spec)

    def _finalize_exchanges(self, record):
        """Drain the per-exchange peer observations this process
        accumulated while the job fetched (ISSUE 19) into persistent
        adapt "xch" records keyed by the exchange's call site — the
        input the NEXT run's code policy prices from — and close the
        loop on any pending code decision (predicted vs observed fetch
        wall).  Worker processes of the multiprocess master accumulate
        in their own processes (the documented per-process caveat)."""
        from dpark_tpu import adapt
        if not adapt.enabled():
            return
        from dpark_tpu import shuffle as _shuffle
        try:
            obs = _shuffle.drain_exchange_observations()
        except Exception:
            return
        for sid, ent in obs.items():
            stage = self.shuffle_to_stage.get(sid)
            dep = stage.shuffle_dep if stage is not None else None
            site = getattr(dep, "adapt_site", None) if dep else None
            if not site:
                continue
            try:
                adapt.observe_exchange(site, ent.get("peers") or {},
                                       fetch_ms=ent.get("ms"))
            except Exception:
                pass

    # -- mid-job re-planning (ISSUE 19, decision pt 7) -------------------
    def _bucket_sizes(self, dep, stage):
        """Per-reduce-bucket byte sizes of a finished map stage,
        stat'd by the driver from the bucket files themselves — the
        skew probe's histogram.  None when any output is not a local
        file:// loc (bucket-server/tcp and hbm exchanges are excluded
        from re-planning: no cheap driver-side size probe)."""
        import os as _os
        n = dep.partitioner.num_partitions
        sizes = [0] * n
        for m, uri in enumerate(stage.output_locs):
            if not isinstance(uri, str) \
                    or not uri.startswith("file://"):
                return None
            d = _os.path.join(uri[len("file://"):], "shuffle",
                              str(dep.shuffle_id), str(m))
            for r in range(n):
                p = _os.path.join(d, str(r))
                try:
                    sizes[r] += _os.path.getsize(p)
                except OSError:
                    try:
                        sizes[r] += _os.path.getsize(p + ".shards")
                    except OSError:
                        return None
        return sizes

    def _replan_consumer(self, stage, dep, waiting):
        """The unique (waiting child stage, ShuffledRDD) pair that
        consumes `dep`, or (None, None) when the shape is not safely
        re-plannable: multiple children, multiple consumers, a
        consumer that is not a plain ShuffledRDD, or a CoGroupedRDD
        anywhere on the narrow walk (its narrow-vs-shuffle dep choice
        was fixed at graph build from partitioner EQUALITY — swapping
        the partitioner underneath it could desynchronize
        copartitioning)."""
        children = [c for c in waiting if stage in c.parents]
        if len(children) != 1:
            return None, None
        child = children[0]
        from dpark_tpu.rdd import CoGroupedRDD
        consumers = []
        hazard = [False]
        seen = set()

        def visit(r):
            if r.id in seen or hazard[0]:
                return
            seen.add(r.id)
            if isinstance(r, CoGroupedRDD):
                hazard[0] = True
                return
            for d in r.dependencies:
                if d is dep:
                    consumers.append(r)
                elif not isinstance(d, ShuffleDependency):
                    visit(d.rdd)
        visit(child.rdd)
        if hazard[0] or len(consumers) != 1:
            return None, None
        consumer = consumers[0]
        if getattr(consumer, "dep", None) is not dep:
            return None, None
        return child, consumer

    def _maybe_replan(self, stage, waiting, submit_stage, record):
        """Mid-job re-plan at the stage boundary (ISSUE 19 decision
        point 7): the map side just finished, its bucket sizes are
        REAL, and the reduce side has not launched — if one reduce
        bucket dominates the exchange (hash-collision skew the
        map-side combine could not dissolve), re-key the reduce side
        through a salted re-split of the already-written buckets.  No
        map task is recomputed: a ResplitReaderRDD stage re-buckets
        (map, reduce) pairs under SaltedHashPartitioner at the SAME
        width, and the waiting consumer is rewired onto it before it
        ever runs.  Observe mode logs the would-be decision and
        changes nothing.  One flag check when DPARK_REPLAN is off."""
        if not conf.REPLAN:
            return
        from dpark_tpu import adapt
        if not adapt.enabled():
            return
        dep = stage.shuffle_dep
        site = getattr(dep, "adapt_site", None)
        if not site:
            return
        from dpark_tpu.dependency import (
            Aggregator, HashPartitioner, SaltedHashPartitioner)
        if type(dep.partitioner) is not HashPartitioner:
            return                # already salted / range: leave alone
        n = dep.partitioner.num_partitions
        if n <= 1:
            return
        try:
            sizes = self._bucket_sizes(dep, stage)
        except Exception:
            return
        if not sizes:
            return
        total = sum(sizes)
        if total < conf.REPLAN_MIN_BYTES:
            return
        frac = max(sizes) / float(total)
        if frac < REPLAN_SKEW_FRAC:
            return
        child, consumer = self._replan_consumer(stage, dep, waiting)
        if child is None:
            return
        salt = 1
        steering = adapt.steering()
        try:
            reason = adapt.note_replan(site, n, salt, frac,
                                       applied=steering)
        except Exception:
            return
        if not steering:
            return                     # observe: decision logged only
        from dpark_tpu.rdd import ResplitReaderRDD, _identity
        mc = dep.aggregator.merge_combiners
        with self._graph_lock:
            reader = ResplitReaderRDD(dep)
            # readers yield (key, combiner) with each key at most once
            # per split (map-side dicts dedupe), so identity-create +
            # merge_combiners reproduces the original combine exactly;
            # map-id-major reader splits keep the merge order
            # bit-identical to the un-replanned fetch
            new_dep = ShuffleDependency(
                reader, Aggregator(_identity, mc, mc),
                SaltedHashPartitioner(n, salt))
            resplit_stage = self.get_shuffle_map_stage(new_dep)
            consumer.dep = new_dep
            consumer.dependencies = [new_dep]
            consumer.partitioner = new_dep.partitioner
            child.parents = self._get_parent_stages_locked(child.rdd)
        submit_stage(resplit_stage)
        record["replans"] = record.get("replans", 0) + 1
        record["stages"] = record.get("stages", 0) + 1
        info = self._stage_info(record, child.id)
        info["replan_reason"] = reason
        logger.info("re-planned shuffle %d -> %d (stage %d): %s",
                    dep.shuffle_id, new_dep.shuffle_id,
                    resplit_stage.id, reason)

    def _stage_info(self, record, stage_id):
        """The per-stage observability dict inside a job record
        (SURVEY.md 5.1: per-stage timings/path for the web UI)."""
        for info in record.get("stage_info", ()):
            if info["id"] == stage_id:
                return info
        info = {"id": stage_id, "kind": "object", "seconds": None}
        record.setdefault("stage_info", []).append(info)
        return info

    def note_stage(self, stage_id, **kw):
        """Executor/backends annotate the CURRENT job's stage record
        (e.g. kind=array, shuffle bytes) — best-effort, never raises."""
        record = getattr(self, "_current_record", None)
        if record is not None:
            self._stage_info(record, stage_id).update(kw)
        if "degrade_reason" in kw:
            # flight recorder (ISSUE 14): a runtime degrade is a
            # warning-and-above event — it lands in the always-armed
            # ring regardless of trace mode, and dumps a snapshot
            # when DPARK_FLIGHT_DIR is set.  Degrades are rare by
            # definition (each one already cost a retry or fallback).
            from dpark_tpu import health
            trace.flight("stage.degrade", "exec", stage=stage_id,
                         reason=str(kw["degrade_reason"])[:200])
            health.flight_dump("stage-degrade", scheduler=self)

    def _note_remote_fetch(self, stage_id, rx0):
        """Attribute bulk-channel bytes received while this stage's
        tasks ran (cross-controller shuffle fetches, ISSUE 12) to its
        stage record — the web UI's "remote fetch B" column.  Inline
        masters only: multiprocess workers fetch in their own
        processes (same per-process contract as the fault/decode
        counters).  The delta is over a PROCESS-WIDE counter, so with
        concurrent jobs on a resident service the stages that overlap
        in time each see the combined bytes — same documented contract
        as the per-job program_cache delta (ISSUE 9); fetches run on
        fetcher worker threads, so thread-local attribution cannot
        narrow it."""
        try:
            from dpark_tpu import bulkplane
            rx = bulkplane.total_received_bytes() - rx0
        except Exception:
            return
        if rx > 0:
            record = getattr(self, "_current_record", None)
            if record is not None:
                info = self._stage_info(record, stage_id)
                info["remote_fetch_bytes"] = \
                    info.get("remote_fetch_bytes", 0) + rx

    def fallback_reasons(self):
        """Every recorded WHY-the-array-path-was-left reason across the
        job history (the tpu master notes one per declined stage; other
        masters record none).  Bench artifacts ship this next to the
        per-phase table so a silent object-path regression is visible
        in CI."""
        out = []
        for rec in self.history:
            for st in rec.get("stage_info", ()):
                reason = st.get("fallback_reason")
                if reason and reason not in out:
                    out.append(reason)
        return out

    def degrade_reasons(self):
        """Every recorded runtime DEGRADATION reason across the job
        history (the tpu master notes one per stage that hit a device
        error / spill failure and recovered — halved wave budget,
        object-path fallback).  The runtime twin of
        fallback_reasons(); bench artifacts ship both."""
        out = []
        for rec in self.history:
            for st in rec.get("stage_info", ()):
                reason = st.get("degrade_reason")
                if reason and reason not in out:
                    out.append(reason)
        return out

    def _journal_stage(self, record, stage):
        """Write-ahead one COMPLETED shuffle-map stage (journal plane,
        ISSUE 20): fingerprint + writer shuffle id + output locations,
        so a restarted controller resumes past this stage instead of
        recomputing it."""
        jfp = record.get("_jfp")
        if jfp is not None:
            from dpark_tpu import journal
            journal.append_stage(jfp, stage)

    def recovery_summary(self):
        """Aggregate recovery accounting across the job history plus
        the chaos plane's per-site injection counters — the bench
        JSON's `faults`/`degrades` sections (ISSUE 5 satellite):
        proves in CI that injected faults actually fired and recovery
        actually ran."""
        from dpark_tpu import coding, faults
        out = {"resubmits": 0, "recomputes": 0, "retries": 0,
               "fetch_failed": 0, "speculated": 0, "replans": 0,
               "resumed_stages": 0}
        for rec in self.history:
            for k in list(out):
                out[k] += rec.get(k, 0)
        out["reasons"] = self.degrade_reasons()
        out["faults"] = faults.stats()
        # coded-shuffle view (ISSUE 6): repair / straggler_win /
        # decode_failures + the active mode.  decode_failures stays
        # DISTINCT from fetch_failed above — a failed decode names how
        # close parity came (shards_found/shards_needed ride the
        # FetchFailed), a plain fetch failure never had parity at all.
        out["decodes"] = coding.stats()
        # worker-counter merge (ISSUE 8 satellite): with spool tracing
        # on, worker processes append cumulative fault/decode counters
        # to the trace spool; fold them in so the multiprocess master's
        # summary finally covers what its workers observed
        if trace.mode() == "spool":
            try:
                workers = trace.merged_worker_counters()
            except Exception:
                workers = None
            if workers and workers.get("processes"):
                for site, st in workers["faults"].items():
                    ent = out["faults"].setdefault(
                        site, {"hits": 0, "fired": 0, "kind": "?"})
                    ent["hits"] = ent.get("hits", 0) + st["hits"]
                    ent["fired"] = ent.get("fired", 0) + st["fired"]
                for kind, v in workers["decodes"].items():
                    out["decodes"][kind] = \
                        out["decodes"].get(kind, 0) + v
                out["worker_processes"] = workers["processes"]
        # crash-consistency view (ISSUE 20): journal replay counters
        # and the peer-liveness lease registry, when armed
        from dpark_tpu import dcn, journal
        js = journal.stats()
        if js is not None:
            out["journal"] = js
        lv = dcn.liveness_stats()
        if lv is not None:
            out["liveness"] = lv
        return out

    @staticmethod
    def _new_metrics():
        return {"jobs": {}, "stages": {},
                "tasks": {"ok": 0, "fail": 0},
                "counters": {"retries": 0, "resubmits": 0,
                             "recomputes": 0, "fetch_failed": 0,
                             "speculated": 0, "replans": 0,
                             "resumed_stages": 0},
                "adapt_decisions": {"applied": 0, "logged": 0},
                "phases": {}}

    @staticmethod
    def _observe_phase(hists, phase, seconds):
        h = hists.get(phase)
        if h is None:
            h = hists[phase] = {
                "buckets": [0] * (len(PHASE_BUCKETS) + 1),
                "sum": 0.0, "count": 0}
        for i, le in enumerate(PHASE_BUCKETS):
            if seconds <= le:
                h["buckets"][i] += 1
                break
        else:
            h["buckets"][-1] += 1
        h["sum"] += seconds
        h["count"] += 1

    @classmethod
    def _fold_metrics_record(cls, out, rec):
        """Fold one job record into a metrics aggregate — defensively:
        a record mid-mutation contributes what it can, never throws.
        Records still RUNNING contribute nothing: their state flips
        and their counters/phase totals grow between scrapes, which
        would make counter-typed /metrics series decrease (Prometheus
        reads any decrease as a counter reset) — in-flight jobs are
        exposed separately as the dpark_jobs_running gauge."""
        try:
            state = str(rec.get("state", "unknown"))
            if state == "running":
                return
            out["jobs"][state] = out["jobs"].get(state, 0) + 1
            for k in out["counters"]:
                out["counters"][k] += int(rec.get(k, 0) or 0)
            ad = rec.get("adapt") or {}
            for d in list(ad.get("decisions") or ()):
                out["adapt_decisions"]["logged"] += 1
                if d.get("applied"):
                    out["adapt_decisions"]["applied"] += 1
            for st in list(rec.get("stage_info") or ()):
                kind = str(st.get("kind", "object"))
                out["stages"][kind] = out["stages"].get(kind, 0) + 1
                for t in list(st.get("tasks") or ()):
                    out["tasks"]["ok" if t.get("ok")
                                 else "fail"] += 1
                pipe = st.get("pipeline")
                if isinstance(pipe, dict):
                    for phase, key in (
                            ("ingest_tokenize", "ingest_ms"),
                            ("narrow", "compute_ms"),
                            ("exchange", "exchange_ms"),
                            ("spill", "spill_ms")):
                        ms = pipe.get(key)
                        if ms:
                            cls._observe_phase(out["phases"], phase,
                                               float(ms) / 1e3)
        except Exception:
            pass                    # record mid-mutation: best effort

    def _archive_metrics(self, records):
        """Fold records about to fall out of the 100-job history
        window into the persistent archive, so /metrics counters stay
        MONOTONIC (Prometheus counters must never decrease — a drop
        reads as a counter reset and rate() reports a huge spurious
        increase).  Called under the metrics lock; records this old
        are finalized."""
        for rec in records:
            self._fold_metrics_record(self._metrics_archive, rec)

    def metrics_snapshot(self):
        """Aggregate counters for the /metrics endpoint (ISSUE 8):
        the archived aggregate of trimmed history plus a defensive
        fold of the live window, copied under the scheduler lock — a
        scrape racing a mutating job record must return valid,
        monotonic numbers, never throw."""
        import copy
        with self._metrics_lock:
            records = list(self.history)
            out = copy.deepcopy(self._metrics_archive)
        for rec in records:
            self._fold_metrics_record(out, rec)
        try:
            out["jobs_running"] = sum(
                1 for rec in records
                if str(rec.get("state")) == "running")
        except Exception:
            out["jobs_running"] = 0
        ex = getattr(self, "executor", None)
        try:
            out["export_seconds"] = float(
                getattr(ex, "export_seconds", 0.0)) if ex else 0.0
        except Exception:
            out["export_seconds"] = 0.0
        # resident-service observability (ISSUE 9): compiled-program
        # cache counters and the admission-queue gauge ride /metrics
        try:
            out["program_cache"] = ex.program_cache_stats() \
                if ex is not None else None
        except Exception:
            out["program_cache"] = None
        svc = getattr(self, "_service", None)
        if svc is not None:
            try:
                out["service"] = svc.service_stats()
            except Exception:
                pass
        return out

    def phase_table(self):
        """Per-phase wall-time table of the DEEPEST streamed stage
        (ingest/tokenize, narrow compute, exchange, spill) plus the
        executor's host-bridge export total — the bench JSON's
        `phases` field.  None when no stage streamed."""
        pipe = self.pipeline_summary()
        if pipe is None:
            return None
        table = {
            "ingest_tokenize_ms": pipe.get("ingest_ms", 0.0),
            "narrow_ms": pipe.get("compute_ms", 0.0),
            "exchange_ms": pipe.get("exchange_ms", 0.0),
            "spill_ms": pipe.get("spill_ms", 0.0),
            "export_ms": 0.0,
        }
        ex = getattr(self, "executor", None)
        if ex is not None:
            table["export_ms"] = round(
                getattr(ex, "export_seconds", 0.0) * 1e3, 1)
        return table

    def pipeline_summary(self):
        """The overlapped-wave-pipeline snapshot of the DEEPEST streamed
        stage across the job history (most waves), per-wave detail
        dropped — the aggregate consumers (benchmarks/) report:
        ingest/compute/exchange/spill ms + device-idle fraction.
        None when no stage streamed."""
        best = None
        for rec in self.history:
            for st in rec.get("stage_info", ()):
                p = st.get("pipeline")
                if p and (best is None
                          or p.get("waves", 0) > best.get("waves", 0)):
                    best = p
        if best is None:
            return None
        return {k: v for k, v in best.items()
                if not k.startswith("per_wave")}

    def _finish_stage_info(self, record, stage_id):
        import time as _time
        info = self._stage_info(record, stage_id)
        if info.get("started") and info.get("seconds") is None:
            info["seconds"] = round(_time.time() - info["started"], 3)
            if trace._PLANE is not None:
                trace.emit("stage", "sched", info["started"],
                           info["seconds"], job=record["id"],
                           stage=stage_id, rdd=info.get("rdd"),
                           kind=info.get("kind"),
                           parents=list(info.get("parents") or ()))
        # streamed stages report per-wave pipeline timings live; once
        # the stage is done, keep only the tail so a thousand-wave run
        # doesn't bloat the job history (/api/jobs ships it as JSON)
        pipe = info.get("pipeline")
        if isinstance(pipe, dict):
            per_wave = pipe.get("per_wave")
            if per_wave and len(per_wave) > 16:
                pipe["per_wave"] = per_wave[-16:]
                pipe["per_wave_truncated"] = True

    def max_concurrency(self):
        """How many tasks can execute at once (None = unbounded/inline).
        Speculation only considers tasks that are actually RUNNING, which
        on a saturated pool means at most this many."""
        return None

    def _check_speculation(self, running, pending_tasks, durations,
                           submitted_at, speculated, spawn_duplicate):
        """Straggler re-launch (reference: dpark/job.py speculation)."""
        import time as _time
        now = _time.time()
        cap = self.max_concurrency()
        for stage in list(running):
            pend = pending_tasks.get(stage)
            done = durations.get(stage.id, [])
            if not pend or not done:
                continue
            if cap is not None and len(pend) > cap:
                # some pending tasks are still queue-waiting, not slow —
                # their submit-time age would trigger mass duplicates
                continue
            total = len(pend) + len(done)
            if len(done) / total < conf.SPECULATION_QUANTILE:
                continue
            med = sorted(done)[len(done) // 2]
            threshold = max(conf.SPECULATION_MULTIPLIER * med, 0.5)
            for p in list(pend):
                key = (stage.id, p)
                started = submitted_at.get(key)
                if (started is not None and key not in speculated
                        and now - started > threshold):
                    speculated.add(key)
                    spawn_duplicate(stage, p)

    def _event_loop(self, output_parts, finished, results, events,
                    in_flight, waiting, running, pending_tasks, failures,
                    progress, stage_of, submit_stage,
                    submit_missing_tasks, record, report, submitted_at,
                    spawn_duplicate, stage_failures=None):
        if stage_failures is None:
            stage_failures = {}
        import time as _time
        num_finished = 0
        next_to_yield = 0
        durations = {}          # stage_id -> completed task durations
        speculated = set()
        poll = 1.0 if conf.SPECULATION else conf.SCHEDULER_STALL_TIMEOUT
        while num_finished < len(output_parts):
            try:
                task, status, payload = events.get(timeout=poll)
            except queue.Empty:
                if in_flight[0] <= 0:
                    raise RuntimeError(
                        "scheduler deadlock: no tasks in flight and no "
                        "events (waiting=%r running=%r finished=%d/%d)"
                        % (waiting, running, num_finished,
                           len(output_parts)))
                if conf.SPECULATION:
                    self._check_speculation(
                        running, pending_tasks, durations, submitted_at,
                        speculated, spawn_duplicate)
                continue        # a long task is legitimately running
            in_flight[0] -= 1
            if "_t_submit" in record and "first_wave_ms" not in record:
                # resident-service latency metric (ISSUE 9): submit ->
                # first completed wave of work (includes queue wait and
                # any trace+compile the first stage paid — the number
                # the warm-submit A/B drives down)
                record["first_wave_ms"] = round(
                    (_time.time() - record["_t_submit"]) * 1e3, 1)
            stage = stage_of.get(task.stage_id)
            tkey = (task.stage_id, task.partition)
            started = submitted_at.pop(tkey, None)
            if started is not None and status == "success":
                durations.setdefault(task.stage_id, []).append(
                    _time.time() - started)
            if started is not None:
                # per-task drill-down for the web UI (SURVEY.md 5.1);
                # bounded so huge jobs don't bloat the history record
                tl = self._stage_info(record, task.stage_id) \
                    .setdefault("tasks", [])
                if len(tl) < 512:
                    # the host/executor that RAN the task when the
                    # master records one (locality-aware placement),
                    # else this process's host
                    tl.append({"p": task.partition,
                               "s": round(_time.time() - started, 3),
                               "host": getattr(task, "_ran_on",
                                               env.host),
                               "ok": status == "success"})
                if trace._PLANE is not None:
                    # driver-side task span (submit -> completion
                    # event), retroactive from the recorded times
                    trace.emit("task", "sched", started,
                               _time.time() - started,
                               job=record["id"], stage=task.stage_id,
                               task=task.partition, status=status,
                               host=getattr(task, "_ran_on",
                                            env.host))
            if status == "success":
                result, acc_updates, md_updates = payload
                self.host_manager.task_succeed_on(
                    getattr(task, "_ran_on", env.host))
                stats = (acc_updates or {}).pop(PROFILE_KEY, None)
                if stats is not None:
                    if self.profile is None:
                        from dpark_tpu.utils.profile import MergedProfile
                        self.profile = MergedProfile()
                    self.profile.add(stats)
                accumulator.merge_on_driver(acc_updates)
                if md_updates:
                    from dpark_tpu import mutable_dict
                    mutable_dict.merge_on_driver(md_updates)
                if isinstance(task, ResultTask):
                    pend = pending_tasks.get(stage)
                    if pend is not None:
                        pend.discard(task.partition)
                    idx = task.output_id
                    if not finished[idx]:
                        finished[idx] = True
                        results[idx] = result
                        num_finished += 1
                        record["finished"] = num_finished
                        if num_finished == len(output_parts):
                            self._finish_stage_info(record,
                                                    task.stage_id)
                        progress.tick()
                    while (next_to_yield < len(output_parts)
                           and finished[next_to_yield]):
                        yield results[next_to_yield]
                        results[next_to_yield] = None
                        next_to_yield += 1
                else:
                    stage.add_output_loc(task.partition, result)
                    pend = pending_tasks.get(stage)
                    if pend is not None:
                        pend.discard(task.partition)
                    if not stage.is_available and pend is not None \
                            and not pend:
                        # outputs were invalidated (FetchFailed on another
                        # map) while this stage was running: resubmit the
                        # holes, else the job deadlocks with no events left
                        submit_missing_tasks(stage)
                    if stage.is_available:
                        env.map_output_tracker.register_outputs(
                            stage.shuffle_dep.shuffle_id, stage.output_locs)
                        self._finish_stage_info(record, stage.id)
                        self._journal_stage(record, stage)
                        running.discard(stage)
                        # mid-job re-plan probe (ISSUE 19): if this
                        # map stage's bucket histogram shows one
                        # dominant reduce bucket, re-key the waiting
                        # reduce side through a salted re-split of the
                        # JUST-WRITTEN buckets before it launches
                        self._maybe_replan(stage, waiting,
                                           submit_stage, record)
                        # wake children whose parents are now all ready
                        for child in list(waiting):
                            if not self.get_missing_parent_stages(child):
                                waiting.discard(child)
                                submit_missing_tasks(child)
                                running.add(child)
            elif status == "fetch_failed":
                e = payload
                parent = self.shuffle_to_stage.get(e.shuffle_id)
                record["fetch_failed"] = record.get("fetch_failed",
                                                    0) + 1
                if parent is not None:
                    if e.map_id >= 0:
                        parent.output_locs[e.map_id] = None
                    if e.uri and (e.map_id < 0
                                  or str(e.uri).startswith("hbm://")):
                        # device-resident shuffles compute EVERY
                        # partition in one stage program and export
                        # through one uri: losing any hbm bucket means
                        # the whole store recomputes (a lone-map
                        # object-path recompute would silently cover
                        # only that map's rows)
                        parent.remove_outputs_by_uri(e.uri)
                    # publish the surviving outputs (only the lost maps
                    # are None) so in-flight reduces don't treat every
                    # healthy map as missing and trigger a full parent
                    # recompute (round-1 advisor fix)
                    env.map_output_tracker.register_outputs(
                        e.shuffle_id, list(parent.output_locs))
                if parent is not None and not parent.is_available:
                    logger.warning(
                        "fetch failed on %s; resubmitting parent %s",
                        stage, parent)
                    running.discard(stage)
                    waiting.add(stage)
                    # cap lineage-recovery ROUNDS per parent stage: a
                    # shuffle source that keeps failing must abort the
                    # job with the real error chained, not loop the
                    # DAG forever (ISSUE 5 satellite).  A burst of
                    # sibling FetchFaileds from one lost map counts as
                    # ONE round — only the event that initiates the
                    # resubmission increments (later siblings find the
                    # parent already re-running)
                    if parent not in running and parent not in waiting:
                        rounds = stage_failures.get(parent.id, 0) + 1
                        stage_failures[parent.id] = rounds
                        if rounds > conf.MAX_STAGE_FAILURES:
                            err = RuntimeError(
                                "stage %d failed %d lineage-recovery "
                                "rounds (conf.MAX_STAGE_FAILURES=%d); "
                                "aborting job — last fetch failure "
                                "chained below"
                                % (parent.id, rounds,
                                   conf.MAX_STAGE_FAILURES))
                            err.__cause__ = e
                            raise err
                        record["resubmits"] = record.get(
                            "resubmits", 0) + 1
                    submit_stage(parent)
                else:
                    # parent intact (task-local loss — e.g. a spill
                    # chunk failed its crc) or unknown shuffle: there
                    # is nothing for the parent to redo, so retry just
                    # THIS task under the ordinary per-task failure
                    # cap.  A stage resubmit here would enqueue zero
                    # parent tasks (deadlock) or duplicate every
                    # still-pending sibling per event.
                    logger.warning(
                        "fetch failed on %s (parent %s intact); "
                        "retrying the task", stage, parent)
                    if parent is not None:
                        record["recomputes"] = record.get(
                            "recomputes", 0) + 1
                    key = (task.stage_id, task.partition)
                    failures[key] = failures.get(key, 0) + 1
                    if failures[key] >= conf.MAX_TASK_FAILURES:
                        err = RuntimeError(
                            "task for partition %d of stage %d hit "
                            "FetchFailed %d times on shuffle %s with "
                            "intact parent outputs"
                            % (task.partition, task.stage_id,
                               failures[key], e.shuffle_id))
                        err.__cause__ = e
                        raise err
                    record["retries"] = record.get("retries", 0) + 1
                    retry = task.retry_copy()
                    in_flight[0] += 1
                    submitted_at[tkey] = _time.time()
                    if trace._PLANE is not None:
                        retry._trace_job = record["id"]
                    self._run_tasks(stage, [retry], report, record)
            else:       # failure
                # credit the EXECUTOR that ran the task (fleet
                # placement): blacklist ranking must see failures
                # against 'exec-N', not this process's hostname
                self.host_manager.task_failed_on(
                    getattr(task, "_ran_on", env.host))
                # losing duplicate of a partition another attempt already
                # completed: ignore (speculation/retry race), don't count
                if isinstance(task, ResultTask):
                    if finished[task.output_id]:
                        continue
                elif stage is not None \
                        and stage.output_locs[task.partition] is not None:
                    continue
                key = (task.stage_id, task.partition)
                failures[key] = failures.get(key, 0) + 1
                if failures[key] >= conf.MAX_TASK_FAILURES:
                    raise RuntimeError(
                        "task for partition %d of stage %d failed %d times; "
                        "last error:\n%s" % (task.partition, task.stage_id,
                                             failures[key], payload))
                # repr now: a handler that keeps its records (pytest's
                # capture, a MemoryHandler) must not keep the task,
                # and through it the job's RDD chain
                logger.warning("task %s failed (try %d): %s",
                               repr(task), failures[key],
                               str(payload)[:200])
                # a retry is a FRESH attempt with its own task id — no
                # shared-object mutation between attempts, so completion
                # attribution stays unambiguous when dispatch crosses
                # process/host boundaries
                record["retries"] = record.get("retries", 0) + 1
                retry = task.retry_copy()
                in_flight[0] += 1
                submitted_at[tkey] = _time.time()
                if trace._PLANE is not None:
                    retry._trace_job = record["id"]
                self._run_tasks(stage, [retry], report, record)

    # -- master-specific -------------------------------------------------
    def _dispatch(self, stage, tasks, report, record):
        """Run tasks now — or, with a resident job server attached
        (ISSUE 9), enqueue them into its fair dispatcher so stages
        from concurrent jobs interleave on the shared mesh.  One
        `is None` check when no service is attached."""
        svc = self._service
        if svc is None:
            self.submit_tasks(stage, tasks, report)
        else:
            svc.enqueue(self, record, stage, tasks, report)

    def submit_tasks(self, stage, tasks, report):
        """Run tasks and call report(task, status, payload) for each."""
        raise NotImplementedError

    def default_parallelism(self):
        return 2


PROFILE_KEY = "__profile__"


def _run_task_inline(task):
    if trace._PLANE is None:
        return _run_task_body(task)
    # the task.run span is the WORKER-side timeline unit: in a
    # multiprocess run it lands in that process's spool (its pid
    # distinguishes it in the merged Chrome trace); nested fetch/spill
    # spans inherit the job/stage/task fields from this context
    with trace.ctx(job=getattr(task, "_trace_job", None),
                   stage=task.stage_id, task=task.partition), \
            trace.span("task.run", "worker",
                       kind=type(task).__name__, tried=task.tried):
        return _run_task_body(task)


def _run_task_body(task):
    from dpark_tpu import mutable_dict
    accumulator.start_task()
    mutable_dict.clear_task_updates()
    try:
        if getattr(env, "profile", False):
            from dpark_tpu.utils.profile import profile_call
            result, stats = profile_call(task.run, task.tried)
        else:
            result, stats = task.run(task.tried), None
        updates = accumulator.finish_task()
        if stats is not None:
            updates[PROFILE_KEY] = stats
        md_updates = mutable_dict.collect_task_updates()
        return "success", (result, updates, md_updates)
    except FetchFailed as e:
        accumulator.finish_task()
        mutable_dict.clear_task_updates()
        return "fetch_failed", e
    except Exception:
        accumulator.finish_task()
        mutable_dict.clear_task_updates()
        return "failed", traceback.format_exc()


class LocalScheduler(DAGScheduler):
    """Single-threaded in-process master — the golden model every other
    backend is tested against (SURVEY.md section 4)."""

    def __init__(self, threads=1):
        super().__init__()

    def submit_tasks(self, stage, tasks, report):
        from dpark_tpu import bulkplane
        rx0 = bulkplane.total_received_bytes()
        for task in tasks:
            status, payload = _run_task_inline(task)
            report(task, status, payload)
        self._note_remote_fetch(stage.id, rx0)

    def default_parallelism(self):
        return 2


class InlineExecutor:
    """One named executor identity on this host, with its own workdir
    (the unit the locality scheduler places tasks on).  Tasks still run
    inline in-process — placement, not isolation, is what this models:
    the executor that ran a task is stamped on it (``task._ran_on``)
    and lands in the scheduler's per-task host records."""

    def __init__(self, host, workdir):
        import os as _os
        self.host = host
        self.workdir = workdir
        _os.makedirs(workdir, exist_ok=True)
        self.tasks_run = 0

    def run(self, task):
        task._ran_on = self.host
        self.tasks_run += 1
        return _run_task_inline(task)


class LocalFleetScheduler(DAGScheduler):
    """Several workdir-distinct InlineExecutors on one host with
    LOCALITY-AWARE placement (reference: dpark's Mesos offers honoring
    task.preferredLocations — SURVEY.md 2.1): a task whose
    preferred_locations() (chunkserver per-chunk hosts, cached-partition
    holders) name a fleet executor runs THERE; candidates rank through
    the shared TaskHostManager (blacklisted holders lose the
    preference); unhinted tasks round-robin.  A successful task on a
    should_cache RDD records its executor as the partition's holder, so
    later jobs over the cached RDD chase the data."""

    def __init__(self, executors=2, names=None):
        super().__init__()
        names = list(names) if names else [
            "exec-%d" % i for i in range(int(executors))]
        if not names:
            raise ValueError("fleet needs at least one executor")
        env.start()
        import os as _os
        self.executors = [
            InlineExecutor(n, _os.path.join(env.workdir, "fleet", n))
            for n in names]
        self._by_host = {e.host: e for e in self.executors}
        self._rr = 0
        self.cache_locs = {}     # (rdd_id, partition) -> executor host

    def _pick_executor(self, task):
        hints = []
        key = (task.rdd.id, task.partition)
        holder = self.cache_locs.get(key)
        if holder is not None:
            hints.append(holder)
        try:
            hints.extend(task.preferred_locations() or [])
        except Exception:
            pass
        local = [h for h in hints if h in self._by_host]
        if local:
            best = self.host_manager.offer_choice(local)
            if best is not None:
                return self._by_host[best]
        ex = self.executors[self._rr % len(self.executors)]
        self._rr += 1
        return ex

    def submit_tasks(self, stage, tasks, report):
        from dpark_tpu import bulkplane
        rx0 = bulkplane.total_received_bytes()
        for task in tasks:
            ex = self._pick_executor(task)
            status, payload = ex.run(task)
            if status == "success" \
                    and getattr(task.rdd, "should_cache", False):
                self.cache_locs[(task.rdd.id, task.partition)] = ex.host
            report(task, status, payload)
        self._note_remote_fetch(stage.id, rx0)

    def default_parallelism(self):
        return len(self.executors)


def _process_worker(task_bytes, snapshot, environ):
    """Runs in a forked pool worker; returns result bytes (our serializer,
    so arbitrary user values survive the trip back)."""
    from dpark_tpu.utils import memory as memutil
    env.start(is_master=False, environ=environ)
    env.is_master = False      # fork inherits the driver's started env
    env.profile = environ.get("DPARK_PROFILE") == "1"
    env.map_output_tracker.update(snapshot)
    try:
        task = serialize.loads(task_bytes)
    except Exception:
        return pickle.dumps(("failed", traceback.format_exc()), -1)
    limit = float(environ.get("DPARK_MEM_LIMIT") or 0)
    checker = None
    if limit and task.tried >= conf.MAX_TASK_FAILURES - 1:
        limit = 0.0        # final attempt runs unrestricted
    if limit:
        # escalate the budget on retries (reference: memory-kill + retry
        # with more memory, SURVEY.md 5.3), capped by MAX_TASK_MEMORY
        limit = min(limit * (1 << task.tried), conf.MAX_TASK_MEMORY)
        checker = memutil.MemoryChecker(limit).start()
        memutil.current_checker = checker
    try:
        status, payload = _run_task_inline(task)
    finally:
        if checker is not None:
            checker.stop()
            memutil.current_checker = None
        # cumulative fault/decode counters -> the trace spool (spool
        # mode only): the driver merges the latest event per process,
        # closing the per-process counter blindspot (ISSUE 8)
        trace.emit_process_counters()
    try:
        return serialize.dumps((status, payload))
    except Exception:
        if status == "success":
            return pickle.dumps(
                ("failed", "unserializable task result:\n" +
                 traceback.format_exc()), -1)
        return pickle.dumps(("failed", repr(payload)), -1)


class MultiProcessScheduler(DAGScheduler):
    """Process-pool master (reference: -m process).  Exercises the full
    serialize/ship/track path and is the CPU baseline for benchmarks.

    Workers fork from a FORKSERVER, not from the driver: the driver has
    usually initialized jax (multithreaded — forking it is the classic
    latent deadlock), while the forkserver process only ever imports
    modules and starts no backend threads, so forking it is safe and
    keeps per-task worker startup cheap.  Worker state therefore does
    NOT inherit driver memory: everything a task needs travels in
    task_bytes + the map-output snapshot + environ (broadcast derefs go
    through workdir files / TCP, same as a real remote worker)."""

    def __init__(self, threads=None):
        super().__init__()
        self.num_workers = threads or multiprocessing.cpu_count()
        self.pool = None

    def start(self):
        super().start()
        if self.pool is None:
            ctx = multiprocessing.get_context("forkserver")
            ctx.set_forkserver_preload(["dpark_tpu.schedule"])
            # suppress the worker bootstrap's __main__ re-import: our
            # serializer ships __main__-defined closures BY VALUE, so
            # workers never need the user's script — and re-importing
            # it breaks outright for <stdin>/-c programs and re-runs
            # script module bodies otherwise
            import sys
            main_mod = sys.modules.get("__main__")
            had_file = main_mod is not None \
                and hasattr(main_mod, "__file__")
            saved_file = getattr(main_mod, "__file__", None)
            # __spec__ must EXIST for the spawn prep (it reads the
            # attribute unconditionally) but None makes it skip the
            # module-name path; no __file__ skips the path path
            had_spec = main_mod is not None \
                and hasattr(main_mod, "__spec__")
            saved_spec = getattr(main_mod, "__spec__", None)
            if main_mod is not None:
                if had_file:
                    del main_mod.__file__
                main_mod.__spec__ = None
            try:
                self.pool = ctx.Pool(self.num_workers)
            finally:
                if main_mod is not None:
                    if had_file:
                        main_mod.__file__ = saved_file
                    if had_spec:
                        main_mod.__spec__ = saved_spec
                    else:
                        del main_mod.__spec__

    def stop(self):
        super().stop()
        if self.pool is not None:
            self.pool.terminate()
            self.pool.join()
            self.pool = None

    def submit_tasks(self, stage, tasks, report):
        if self.pool is None:
            self.start()
        environ = env.environ_for_worker()
        for task in tasks:
            # exact snapshot: parent stages are complete before this point
            snapshot = env.map_output_tracker.snapshot(
                self._needed_shuffles(task.rdd))
            task_bytes = serialize.dumps(task)

            def on_done(result_bytes, task=task):
                status, payload = serialize.loads(result_bytes)
                report(task, status, payload)

            def on_error(exc, task=task):
                report(task, "failed", repr(exc))

            self.pool.apply_async(
                _process_worker, (task_bytes, snapshot, environ),
                callback=on_done, error_callback=on_error)

    def default_parallelism(self):
        return self.num_workers

    def max_concurrency(self):
        return self.num_workers
