"""Resident executor service (ISSUE 9): one mesh, many concurrent jobs.

dpark's one-process-one-job heritage made every CLI run pay the full
trace+compile bill and hold the mesh exclusively.  This module splits
"mesh owner" from "job driver": a long-lived :class:`JobServer` owns
the ONE scheduler (and, for ``-m tpu``, the one JAXExecutor + device
mesh) for the life of the process, and N drivers multiplex their DAGs
onto it.  Concurrent jobs share the bounded compiled-program cache and
the HBM shuffle store (quota/LRU arbitration with disk spill — see
executor._evict_hbm), so a warm re-submission compiles NOTHING and a
second tenant never cold-starts the mesh.

Two transports:

* **in-process threads** — every :class:`DparkContext` created with
  ``DPARK_SERVICE=<master spec>`` (or master ``service[:spec]``)
  attaches a :class:`ClientScheduler` to the process-global server;
  each context's ``runJob`` drives its own job from its own thread.
* **remote** — :func:`serve` listens on the dcn framed-TCP channel and
  accepts pickled *job functions* (``fn(ctx) -> result``).  Shipping
  the driver FUNCTION rather than a built RDD graph sidesteps both
  the splits-stay-driver-side serialization contract and cross-client
  rdd/shuffle id collisions: the graph is built inside the server,
  in the server's id namespace.  :class:`ServiceClient` is the caller
  side.  Job payloads are unpickled BY DESIGN (a job is code); set
  DPARK_DCN_SECRET so only HMAC-authenticated peers can submit.

Scheduling: each ``submit_tasks`` call becomes one WORK ITEM (a "wave
slot") in the owning job's FIFO queue; ``conf.SERVICE_SLOTS`` slot
threads drain the queues WEIGHTED ROUND-ROBIN (a weight-2 job gets two
turns per cycle), so a long job cannot starve a short one.  Device
stages additionally serialize on the executor's mesh lock — the
fairness interleaving is between jobs' stages, and the overlap win is
one job's host/object-path work riding alongside another's device
stage.  Admission control bounds the blast radius: at most
``conf.SERVICE_MAX_JOBS`` jobs run concurrently, at most
``conf.SERVICE_QUEUE_MAX`` wait; past that, submission FAILS fast.

With ``DPARK_SERVICE`` unset nothing here is imported on the hot path
and every seam is one ``is None`` check (the faults.py contract).
"""

import base64
import itertools
import pickle
import threading
import time
import traceback
from collections import deque

from dpark_tpu import aotcache
from dpark_tpu import conf
from dpark_tpu import locks
from dpark_tpu import resultcache
from dpark_tpu.utils.log import get_logger

logger = get_logger("service")

_STOP = object()                 # slot-thread shutdown sentinel


class _Work:
    """One submit_tasks call from one job's driver — the unit the
    fair dispatcher interleaves."""
    __slots__ = ("sched", "record", "stage", "tasks", "report")

    def __init__(self, sched, record, stage, tasks, report):
        self.sched = sched
        self.record = record
        self.stage = stage
        self.tasks = tasks
        self.report = report


class _JobState:
    __slots__ = ("queue", "weight", "credits", "record")

    def __init__(self, weight, record):
        self.queue = deque()
        self.weight = max(1, int(weight or 1))
        self.credits = self.weight
        self.record = record


# attainment target backing the burn-rate math: a burn of 1.0 means
# violations are consuming the (1 - target) error budget exactly as
# fast as allowed; 2.0 means twice as fast (the classic multi-window
# burn alert; health.THRESHOLDS["slo_burn"] grades it) over the short
# and the long burn horizon in seconds
SLO_TARGET = 0.99
SLO_WINDOWS = (60.0, 600.0)


class _Tenant:
    """Per-tenant SLO accounting (ISSUE 14): tenants declare a per-job
    latency target (``ServiceClient(..., slo_ms=)`` /
    ``DPARK_SERVICE_SLO``); the server tracks lifetime attainment and
    a multi-window burn rate — how fast violations consume the
    ``1 - SLO_TARGET`` error budget (burn 1.0 = exactly as
    fast as allowed; 2.0 = twice as fast, the classic paging
    threshold).  The window deque is bounded by the longest burn
    horizon, so a resident server's memory stays flat."""
    __slots__ = ("slo_ms", "jobs", "violations", "window")

    def __init__(self, slo_ms):
        self.slo_ms = float(slo_ms)
        self.jobs = 0
        self.violations = 0
        self.window = deque()           # (ts, ok)

    def note(self, now, ok):
        self.jobs += 1
        if not ok:
            self.violations += 1
        self.window.append((now, ok))
        horizon = max(SLO_WINDOWS)
        while self.window and self.window[0][0] < now - horizon:
            self.window.popleft()

    def stats(self, now):
        budget = max(1e-9, 1.0 - SLO_TARGET)
        burn = {}
        for w in SLO_WINDOWS:
            recent = [ok for ts, ok in self.window if ts >= now - w]
            rate = (sum(1 for ok in recent if not ok) / len(recent)
                    if recent else 0.0)
            burn["%ds" % int(w)] = round(rate / budget, 3)
        return {"slo_ms": self.slo_ms, "jobs": self.jobs,
                "violations_total": self.violations,
                "attainment": round(1.0 - self.violations
                                    / self.jobs, 4)
                if self.jobs else 1.0,
                "burn": burn}


def _make_scheduler(spec):
    """The job server's INNER scheduler — the actual mesh owner.
    Accepts the same master grammar as DparkContext."""
    from dpark_tpu import schedule
    master, _, arg = str(spec or "local").partition(":")
    if master in ("", "local"):
        return schedule.LocalScheduler()
    if master in ("process", "multiprocess"):
        return schedule.MultiProcessScheduler(int(arg) if arg else None)
    if master == "fleet":
        return schedule.LocalFleetScheduler(int(arg) if arg else 2)
    if master == "tpu":
        from dpark_tpu.backend.tpu import TPUScheduler
        return TPUScheduler(int(arg) if arg else None)
    raise ValueError("unknown service master %r "
                     "(local/process/fleet/tpu)" % (spec,))


class JobServer:
    """Owns one scheduler (mesh + executor) and multiplexes many
    concurrent jobs onto it with weighted-round-robin fairness."""

    def __init__(self, master=None, slots=None, max_jobs=None,
                 queue_max=None):
        self.master = master or conf.DPARK_SERVICE or "local"
        self.slots = max(1, int(slots or conf.SERVICE_SLOTS))
        self.max_jobs = max(1, int(max_jobs or conf.SERVICE_MAX_JOBS))
        self.queue_max = int(conf.SERVICE_QUEUE_MAX
                             if queue_max is None else queue_max)
        self.scheduler = None
        self._threads = []
        self._cv = threading.Condition()
        self._jobs = {}              # job id -> _JobState
        self._rr = []                # job ids in round-robin order
        self._rr_pos = 0
        self._stopped = False
        self._started = False
        self._tls = threading.local()     # per-driver client/weight
        # admission control
        self._adm_cv = threading.Condition()
        self._active_jobs = 0
        self._waiting_jobs = 0
        # graceful degradation (ISSUE 20): draining refuses NEW jobs
        # while in-flight ones run to their wave boundaries
        self._draining = False
        self._lock = locks.named_lock("service.server")
        # per-tenant bulk-stream bytes (ISSUE 12; see note_bulk)
        self._bulk_bytes = {}
        # per-tenant SLO accounting (ISSUE 14; see note_job_done)
        self._tenants = {}
        # boot-warming summary (ISSUE 17; see _boot_warm)
        self._aot_warm = None
        # result-cache boot summary (ISSUE 18; see _boot_resultcache)
        self._rc_boot = None

    # -- lifecycle -------------------------------------------------------
    def start(self):
        with self._lock:
            if self._started:
                return self
            from dpark_tpu.env import env
            env.start(is_master=True)
            self.scheduler = _make_scheduler(self.master)
            self.scheduler.start()
            self.scheduler._service = self
            # instant-on serving (ISSUE 17): with the AOT plane
            # installed, pre-deserialize the hottest previously-seen
            # programs before the first submission arrives
            plane = aotcache._PLANE
            if plane is not None:
                self._boot_warm(plane)
            # shared computation (ISSUE 18): a disk-tier result cache
            # preloads its hottest entries so the first repeated
            # query after a restart serves with zero scan chunks
            rc = resultcache._PLANE
            if rc is not None:
                self._boot_resultcache(rc)
            self._stopped = False
            for i in range(self.slots):
                t = threading.Thread(target=self._slot_loop,
                                     name="dpark-service-slot-%d" % i,
                                     daemon=True)
                t.start()
                self._threads.append(t)
            self._started = True
            import atexit
            atexit.register(self.stop)
            logger.info("job server up: master=%s slots=%d "
                        "max_jobs=%d", self.master, self.slots,
                        self.max_jobs)
        return self

    def _boot_warm(self, plane):
        """Boot-warming pass (ISSUE 17): deserialize the hottest
        previously-seen programs — ranked by the adapt store's
        observed compile ms x hit count — into the AOT plane's
        preload map under the DPARK_AOT_WARM_BUDGET_MS deadline, so a
        restarted server's first submission starts from loaded
        executables.  The pass runs under the ``__boot__``
        pseudo-tenant span context: any work it triggers folds to the
        boot account, never a real tenant's, and the ledger's
        conservation ratio stays 1.0 (no mesh occupancy is drawn).
        Never raises — a defective cache dir means a cold start, not
        a dead server."""
        budget = float(getattr(conf, "AOT_WARM_BUDGET_MS", 0.0) or 0.0)
        if budget <= 0:
            return
        from dpark_tpu import trace
        try:
            with trace.ctx(job="__boot__"):
                summary = plane.warm(budget)
            self._aot_warm = summary
            logger.info(
                "aot boot warm: %d/%d entries in %.0f ms (budget "
                "%.0f ms)", summary["warmed"], summary["entries"],
                summary["ms"], summary["budget_ms"])
        except Exception as e:
            logger.debug("aot boot warm failed: %s", e)

    def _boot_resultcache(self, rc):
        """Result-cache boot pass (ISSUE 18): load the disk tier's
        index and preload the hottest entries (ranked by the adapt
        store's reuse profiles) into the memory tier.  Same contract
        as _boot_warm: runs as the ``__boot__`` pseudo-tenant, never
        raises — a defective cache dir means cold serving, not a dead
        server."""
        from dpark_tpu import trace
        try:
            with trace.ctx(job="__boot__"):
                summary = rc.boot()
            self._rc_boot = summary
            if summary.get("entries"):
                logger.info(
                    "result cache boot: %d/%d entries (%d bytes) in "
                    "%.0f ms", summary["preloaded"],
                    summary["entries"], summary["bytes"],
                    summary["ms"])
        except Exception as e:
            logger.debug("result cache boot failed: %s", e)

    def stop(self):
        with self._lock:
            if not self._started:
                return
            self._started = False
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        for t in self._threads:
            t.join(timeout=10)
        self._threads = []
        sched = self.scheduler
        if sched is not None:
            sched._service = None
            sched.stop()

    # -- submission (driver side) ---------------------------------------
    def declare_slo(self, client, slo_ms):
        """Register (or update) a tenant's per-job latency target in
        ms.  0/None clears nothing — an SLO once declared sticks for
        the life of the server (the accounting history must not reset
        because one submission omitted the knob)."""
        if not client or not slo_ms:
            return
        with self._cv:
            t = self._tenants.get(client)
            if t is None:
                self._tenants[client] = _Tenant(slo_ms)
            else:
                t.slo_ms = float(slo_ms)

    def note_job_done(self, record):
        """SLO accounting hook (called once per finished service job
        via health.job_finished): grade the job's submit-to-done
        latency against its tenant's declared target and attach the
        verdict to the record (the web UI's SLO column)."""
        client = record.get("client")
        if not client:
            return
        import time as _time
        now = _time.time()
        with self._cv:
            t = self._tenants.get(client)
            if t is None or not t.slo_ms:
                return
            # submit-to-done: record["seconds"] measures from record
            # mint (post-admission submit) through the last yielded
            # partition, so it already contains queue_wait_ms and
            # first_wave_ms — the evidence fields ride the record
            lat = float(record.get("seconds", 0.0) or 0.0) * 1e3
            ok = lat <= t.slo_ms
            t.note(now, ok)
            record["slo"] = {"slo_ms": t.slo_ms,
                             "latency_ms": round(lat, 1),
                             "ok": bool(ok)}

    def tenant_slo_stats(self):
        """{client: {slo_ms, jobs, violations_total, attainment,
        burn: {window: rate}}} for every tenant with a declared SLO
        — /metrics and /api/health read this."""
        import time as _time
        now = _time.time()
        with self._cv:
            return {c: t.stats(now)
                    for c, t in self._tenants.items()}

    def submit(self, rdd, func, partitions=None, allow_local=False,
               client=None, weight=None, slo_ms=None):
        """Generator over per-partition results, like run_job — but
        admission-controlled and driven through the fair dispatcher.
        The generator body runs on the CALLING thread: that thread IS
        the job's driver."""
        self.start()
        # NESTED submissions bypass admission: a driver thread that
        # already holds a slot (iterating one job's generator while
        # submitting another — e.g. a sortByKey bounds sample, or user
        # code collecting inside an iterate loop) must not block on
        # the cap it is itself holding — at saturation that is a
        # permanent deadlock, every slot waiting on itself
        depth = getattr(self._tls, "adm_depth", 0)
        if depth == 0:
            with self._adm_cv:
                if self._draining:
                    # nested submissions (depth > 0) still pass: an
                    # admitted job must be able to FINISH its own
                    # sortByKey samples etc. while the server drains
                    raise RuntimeError(
                        "service draining: admission stopped")
                if self.queue_max \
                        and self._waiting_jobs >= self.queue_max:
                    raise RuntimeError(
                        "service admission queue full (%d jobs "
                        "waiting, DPARK_SERVICE_QUEUE_MAX=%d)"
                        % (self._waiting_jobs, self.queue_max))
                self._waiting_jobs += 1
                try:
                    while self._active_jobs >= self.max_jobs:
                        self._adm_cv.wait()
                finally:
                    self._waiting_jobs -= 1
                self._active_jobs += 1
        self._tls.adm_depth = depth + 1
        try:
            sched = self.scheduler
            # run_job reads these thread-locals when minting the record
            sched._tls.client = client or getattr(
                self._tls, "client", None)
            # tenant SLO declaration (ISSUE 14): an explicit slo_ms
            # wins; otherwise the process default (DPARK_SERVICE_SLO)
            # applies to every tenant that declared nothing
            self.declare_slo(sched._tls.client,
                             slo_ms or conf.SERVICE_SLO_MS)
            self._tls.weight = weight or getattr(
                self._tls, "weight", None) or conf.SERVICE_WEIGHT
            yield from sched.run_job(rdd, func, partitions,
                                     allow_local)
        finally:
            self._tls.adm_depth = depth
            if depth == 0:
                with self._adm_cv:
                    self._active_jobs -= 1
                    self._adm_cv.notify()

    # -- graceful degradation (ISSUE 20) ---------------------------------
    def drain(self, timeout=30.0):
        """Stop admitting jobs, wait (bounded) for in-flight jobs to
        finish their wave-boundary work, then flush the crash journal
        so a subsequent exit loses nothing.  Idempotent; returns a
        summary the caller (or the remote `drain` endpoint) can log.
        Never raises — drain is the LAST thing a dying server does."""
        deadline = time.time() + max(0.0, float(timeout or 0.0))
        with self._adm_cv:
            already = self._draining
            self._draining = True
            while self._active_jobs > 0 and time.time() < deadline:
                self._adm_cv.wait(timeout=min(
                    1.0, max(0.01, deadline - time.time())))
            active = self._active_jobs
            waiting = self._waiting_jobs
        flushed = False
        try:
            from dpark_tpu import journal
            journal.flush()
            flushed = journal.active()
        except Exception as e:
            logger.warning("journal flush on drain failed: %s", e)
        summary = {"draining": True, "was_draining": already,
                   "drained": active == 0, "active_jobs": active,
                   "waiting_jobs": waiting,
                   "journal_flushed": flushed}
        logger.info("service drain: %s", summary)
        return summary

    def undrain(self):
        """Re-open admission after a drain (tests / operator rollback
        of a cancelled shutdown)."""
        with self._adm_cv:
            self._draining = False
            self._adm_cv.notify_all()

    # -- dispatcher ------------------------------------------------------
    def enqueue(self, sched, record, stage, tasks, report):
        """scheduler._dispatch hands every submit_tasks call here.
        Auto-registers the job (nested jobs — e.g. a sortByKey bounds
        sample submitted from inside an admitted job's driver — bypass
        admission: blocking them would deadlock their parent)."""
        jid = record["id"]
        with self._cv:
            state = self._jobs.get(jid)
            if state is None:
                state = self._jobs[jid] = _JobState(
                    getattr(self._tls, "weight", None)
                    or conf.SERVICE_WEIGHT, record)
                self._rr.append(jid)
            state.queue.append(_Work(sched, record, stage, tasks,
                                     report))
            self._cv.notify()

    def _next_work(self):
        """Weighted round-robin across jobs with queued work; blocks
        when idle.  Jobs burn one credit per turn; when every job with
        work is out of credits, a new cycle replenishes them."""
        with self._cv:
            while True:
                if self._stopped:
                    return _STOP
                # prune finished, drained jobs
                for jid in [j for j, s in self._jobs.items()
                            if not s.queue
                            and s.record.get("state") != "running"]:
                    del self._jobs[jid]
                    self._rr.remove(jid)
                busy = [j for j in self._rr if self._jobs[j].queue]
                if not busy:
                    self._cv.wait()
                    continue
                if all(self._jobs[j].credits <= 0 for j in busy):
                    for j in busy:
                        self._jobs[j].credits = self._jobs[j].weight
                n = len(self._rr)
                for off in range(n):
                    jid = self._rr[(self._rr_pos + off) % n]
                    state = self._jobs[jid]
                    if state.queue and state.credits > 0:
                        state.credits -= 1
                        self._rr_pos = (self._rr_pos + off + 1) % n
                        return state.queue.popleft()
                # busy jobs exist but none had credits: loop replenishes

    def _slot_loop(self):
        while True:
            item = self._next_work()
            if item is _STOP:
                return
            self._execute(item)
            # an idle slot must not hold its last stage's tasks, and
            # through them the job's RDD chain and dependencies
            del item

    def _execute(self, item):
        from dpark_tpu import adapt, trace
        sched, record = item.sched, item.record
        if "_t_submit" in record and "queue_wait_ms" not in record:
            # first stage execution of the job: everything before this
            # was queue wait (the per-job column in the web UI and the
            # bench `service` section)
            record["queue_wait_ms"] = round(
                (time.time() - record["_t_submit"]) * 1e3, 1)
        # attribute note_stage / store ownership / adapt decisions
        # taken on THIS thread to the right job
        sched._current_record = record
        adapt.set_current_job(record["id"])
        reported = set()

        def report(task, status, payload, _orig=item.report):
            reported.add(id(task))
            _orig(task, status, payload)

        try:
            with trace.ctx(job=record["id"], stage=item.stage.id):
                sched.submit_tasks(item.stage, item.tasks, report)
        except BaseException:
            # a crash here must surface to the JOB's event loop (its
            # driver owns retries/abort), never kill the slot thread.
            # Only tasks not already reported get the failure — a
            # double event would corrupt the driver's in-flight count.
            err = traceback.format_exc()
            logger.warning("stage execution failed in service slot:\n%s",
                           err)
            for task in item.tasks:
                if id(task) not in reported:
                    item.report(task, "failed", err)
        finally:
            adapt.set_current_job(None)
            sched._current_record = None

    # -- observability ---------------------------------------------------
    def note_bulk(self, client, nbytes):
        """Per-tenant bulk-stream accounting (ISSUE 12): bytes of job
        results streamed to each remote tenant over the bulk
        channel."""
        with self._cv:
            bulk = getattr(self, "_bulk_bytes", None)
            if bulk is None:
                bulk = self._bulk_bytes = {}
            bulk[client] = bulk.get(client, 0) + nbytes

    def service_stats(self):
        with self._cv:
            queued_items = sum(len(s.queue)
                               for s in self._jobs.values())
            bulk = dict(getattr(self, "_bulk_bytes", None) or {})
        with self._adm_cv:
            waiting = self._waiting_jobs
            active = self._active_jobs
        out = {"master": self.master, "slots": self.slots,
               "jobs_running": active, "jobs_queued": waiting,
               "work_items_queued": queued_items,
               "max_jobs": self.max_jobs, "bulk": bulk,
               "draining": self._draining,
               "tenants": self.tenant_slo_stats()}
        ex = getattr(self.scheduler, "executor", None)
        if ex is not None:
            out["program_cache"] = ex.program_cache_stats()
        if self._aot_warm is not None:
            out["aot_warm"] = dict(self._aot_warm)
        if self._rc_boot is not None:
            out["result_cache_boot"] = dict(self._rc_boot)
        rc = resultcache.stats()
        if rc is not None:
            out["result_cache"] = rc
        return out


# ---------------------------------------------------------------------------
# process-global server + the DparkContext seam
# ---------------------------------------------------------------------------

_SERVER = None
_SERVER_LOCK = locks.named_lock("service.global")
_client_ids = itertools.count(1)


def get_server(master=None):
    """The process-global JobServer (created on first use).  A master
    spec is honored only at creation; later callers share the
    existing mesh owner regardless of what they asked for."""
    global _SERVER
    with _SERVER_LOCK:
        if _SERVER is None:
            _SERVER = JobServer(master)
        elif master and _SERVER.master != master:
            logger.warning(
                "service already running with master=%s; ignoring "
                "requested %s", _SERVER.master, master)
        return _SERVER


def shutdown():
    """Stop and forget the process-global server (tests)."""
    global _SERVER
    with _SERVER_LOCK:
        srv, _SERVER = _SERVER, None
    if srv is not None:
        srv.stop()


class ClientScheduler:
    """What a DparkContext sees when attached to the service: the
    scheduler interface, with every job routed through the shared
    JobServer.  Unknown attributes (history, metrics_snapshot,
    executor, ...) delegate to the inner scheduler so the web UI and
    bench plumbing work unchanged."""

    is_service_client = True     # DparkContext.stop: leave env alive

    def __init__(self, server, client=None, weight=None, slo_ms=None,
                 share_results=None):
        self.server = server
        self.client = client or "client-%d" % next(_client_ids)
        self.weight = weight or conf.SERVICE_WEIGHT
        # per-tenant SLO (ISSUE 14): explicit target, else the
        # process default (DPARK_SERVICE_SLO); 0 = untracked
        self.slo_ms = slo_ms if slo_ms is not None \
            else conf.SERVICE_SLO_MS
        # cross-tenant result sharing (ISSUE 18): tenants share the
        # result cache by default; share_results=False opts this
        # tenant out of BOTH directions (no reads, no stores)
        if share_results is not None:
            resultcache.opt_out(self.client,
                                flag=not share_results)

    def start(self):
        self.server.start()

    def stop(self):
        # the server (and its mesh) outlives any one context
        pass

    def run_job(self, rdd, func, partitions=None, allow_local=False):
        return self.server.submit(rdd, func, partitions, allow_local,
                                  client=self.client,
                                  weight=self.weight,
                                  slo_ms=self.slo_ms)

    def default_parallelism(self):
        self.start()
        return self.server.scheduler.default_parallelism()

    def service_stats(self):
        return self.server.service_stats()

    def __getattr__(self, name):
        if name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.server.scheduler, name)


def client_scheduler(master=None, client=None):
    """The DPARK_SERVICE seam target: a per-context facade over the
    process-global server."""
    return ClientScheduler(get_server(master), client=client)


# ---------------------------------------------------------------------------
# remote transport: job FUNCTIONS over the dcn framed channel
# ---------------------------------------------------------------------------

def _context_for(server, client, slo_ms=None):
    """A DparkContext whose scheduler is a service client — what a
    remote job function receives as its `ctx`."""
    from dpark_tpu.context import DparkContext
    from dpark_tpu.env import env
    env.start(is_master=True)
    ctx = DparkContext("local")
    ctx.scheduler = ClientScheduler(server, client=client,
                                    slo_ms=slo_ms)
    ctx.started = True           # scheduler is live; skip start()
    # a remote fn calling ctx.stop() must not tear down the SERVER's
    # env/scheduler — the context is a per-request facade
    ctx.stop = lambda: None
    return ctx


def serve(addr="127.0.0.1:0", master=None, server=None):
    """Listen for remote job submissions on the dcn framed-TCP
    channel; returns the FramedServer (bind_address tells the port).

    Request grammar (JSON array like every dcn request):
      ("job", client, b64(serialize.dumps(fn))[, slo_ms])
          -> pickled fn(ctx); the optional 4th element declares the
          tenant's per-job latency SLO in ms (ISSUE 14) — a pre-SLO
          server simply never receives it (clients omit it when unset)
      ("job_bulk", client, b64(...)[, slo_ms])  -> same result, streamed over
          the chunk-framed bulk channel (ISSUE 12) — concurrent
          tenants' result streams multiplex through the shared
          per-peer windows, and per-tenant stream bytes land in
          service_stats()["bulk"]
      ("stats",)                                 -> pickled stats dict
      ("drain"[, timeout_s])                     -> pickled drain summary:
          stop admission, wait (bounded) for in-flight jobs, flush the
          crash journal (ISSUE 20 graceful degradation)
    """
    import os
    from dpark_tpu import dcn
    from dpark_tpu.utils import compress
    srv = server or get_server(master)
    srv.start()
    if not os.environ.get("DPARK_DCN_SECRET"):
        logger.warning(
            "serving WITHOUT DPARK_DCN_SECRET: any peer that can "
            "reach this port can submit arbitrary code")

    def run_job(client, payload, slo_ms=None):
        from dpark_tpu import serialize
        fn = serialize.loads(base64.b64decode(payload))
        ctx = _context_for(srv, "remote:%s" % client, slo_ms=slo_ms)
        try:
            return compress(pickle.dumps(fn(ctx), -1))
        finally:
            # the function's chains died with its frame: their stores
            # go now, not at some tenant's next job
            srv.scheduler._release_unreachable()

    def handle(req):
        kind = req[0]
        if kind == "job":
            client, payload = req[1], req[2]
            return run_job(client, payload,
                           req[3] if len(req) > 3 else None)
        if kind == "job_bulk":
            client, payload = req[1], req[2]
            blob = run_job(client, payload,
                           req[3] if len(req) > 3 else None)

            def note_sent(peer, nbytes, nchunks, _client=client):
                srv.note_bulk(_client, nbytes)
                # result streams count in the bulk plane's per-peer
                # sent counters too — /metrics must see ALL bulk
                # traffic, not just shuffle/broadcast payloads
                from dpark_tpu import bulkplane
                bulkplane._count_sent(peer, nbytes, nchunks)

            return dcn.BulkPayload({"kind": "blob"},
                                   dcn.chunked(blob),
                                   on_sent=note_sent)
        if kind == "stats":
            return compress(pickle.dumps(srv.service_stats(), -1))
        if kind == "drain":
            timeout = float(req[1]) if len(req) > 1 else 30.0
            return compress(pickle.dumps(srv.drain(timeout), -1))
        raise ValueError("unknown service request %r" % (kind,))

    host, _, port = str(addr).partition(":")
    framed = dcn.FramedServer(handle, host or "127.0.0.1",
                              int(port or 0), name="dpark-service")
    framed.start()
    logger.info("service listening on tcp://%s:%d"
                % framed.bind_address)
    return framed


class ServiceClient:
    """Caller side of the remote transport: ships a job FUNCTION to a
    served JobServer and returns its result.  The function runs as a
    driver thread inside the server — `fn(ctx)` builds its DAG there,
    in the server's id namespace."""

    def __init__(self, addr, client=None, timeout=600, slo_ms=None):
        addr = str(addr)
        if not addr.startswith("tcp://"):
            addr = "tcp://" + addr
        self.uri = addr
        self.client = client or "client-%d" % next(_client_ids)
        self.timeout = timeout
        # per-tenant SLO declaration (ISSUE 14): rides each job
        # request as an optional 4th element, so a pre-SLO server
        # never sees an unknown shape when the knob is unset
        self.slo_ms = slo_ms

    def run(self, fn):
        from dpark_tpu import conf, dcn, serialize
        from dpark_tpu.utils import decompress
        payload = base64.b64encode(serialize.dumps(fn)).decode("ascii")
        extra = (float(self.slo_ms),) if self.slo_ms else ()
        if conf.BULK_PLANE:
            # results stream back chunk-framed over the bulk channel
            # (ISSUE 12); a pre-bulk server answers "unknown service
            # request" and the plain single-frame path runs instead
            from dpark_tpu import bulkplane
            try:
                _, view = bulkplane.fetch(
                    self.uri,
                    ("job_bulk", self.client, payload) + extra,
                    timeout=self.timeout)
                return pickle.loads(decompress(bytes(view)))
            except bulkplane.BulkUnsupported:
                pass
        resp = dcn.fetch(self.uri,
                         ("job", self.client, payload) + extra,
                         timeout=self.timeout)
        return pickle.loads(decompress(resp))

    def stats(self):
        from dpark_tpu import dcn
        from dpark_tpu.utils import decompress
        resp = dcn.fetch(self.uri, ("stats",), timeout=self.timeout)
        return pickle.loads(decompress(resp))

    def drain(self, timeout_s=30.0):
        """Ask the server to stop admission, finish in-flight jobs and
        flush its crash journal; returns the server's drain summary."""
        from dpark_tpu import dcn
        from dpark_tpu.utils import decompress
        resp = dcn.fetch(self.uri, ("drain", float(timeout_s)),
                         timeout=self.timeout)
        return pickle.loads(decompress(resp))


def main(argv=None):
    """``python -m dpark_tpu.service --listen 127.0.0.1:7077 -m tpu``
    — a standalone resident mesh owner for remote clients."""
    import argparse
    p = argparse.ArgumentParser(
        prog="dpark_tpu.service",
        description="resident executor service (mesh owner)")
    p.add_argument("--listen", default="127.0.0.1:0",
                   metavar="HOST:PORT")
    p.add_argument("-m", "--master", default=None,
                   help="backing master spec (local, tpu[:N], ...)")
    args = p.parse_args(argv)
    framed = serve(args.listen, master=args.master)
    print("dpark_tpu service on tcp://%s:%d" % framed.bind_address,
          flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        framed.stop()
        shutdown()


if __name__ == "__main__":
    main()
