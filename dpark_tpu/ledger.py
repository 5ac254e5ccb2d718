"""Resource attribution plane (ISSUE 15 tentpole): who is consuming
the shared mesh, and what does each program cost?

The JobServer multiplexes N tenants onto one mesh (ISSUE 9) and the
health plane grades their latency (ISSUE 14), but nothing could answer
the ATTRIBUTION question — which tenant's which program burned the
device seconds, held the mesh lock, parked bytes in HBM.  This module
closes that gap the way health.py did: a :class:`LedgerSink` is a
second ``trace.TracePlane.record`` consumer (one ``is None`` check per
record when off; on/off job results are bit-identical — asserted
across the chaos matrix in tests/test_ledger.py) folding spans AS THEY
ARE EMITTED into bounded, merge-associative resource ACCOUNTS keyed by
(tenant, job, stage, program signature):

* **device wall ms** — ``stage.exec`` spans (the whole device stage,
  run under the mesh lock) plus per-wave detail from ``wave`` spans,
  both keyed by the adapt program signature.
* **compile ms** — measured ``compile.backend`` spans (a
  jax.monitoring listener the executor installs times the real XLA
  backend compile; the instant ``compile`` cache-miss events count
  alongside).
* **mesh-lock wait ms** — the new ``mesh.lock`` span the executor's
  :class:`~dpark_tpu.backend.tpu.executor._MeshLock` emits around
  every contended ``_mesh_lock`` acquisition.  Contention is the
  invisible cost of the resident service: a tenant that waits pays
  wall time no per-stage timer ever showed.
* **HBM byte-seconds** — ``hbm.store`` / ``hbm.release`` events from
  the executor's shuffle-store bookkeeping: bytes x residency seconds,
  accrued at release (spill-to-disk releases too, so eviction adjusts
  the account), with still-resident bytes reported as a live gauge.
* **shuffle / bulk / spill traffic** — fetch counts + wall from
  ``fetch.bucket``, bulk bytes from ``dcn.bulk.*`` / ``dcn.transfer``,
  spill bytes from ``spill.read`` / ``spill.write``.

Tenant resolution: accounts key internally by (job, stage, sig); the
scheduler registers job -> tenant at record mint (:func:`note_job`),
and the job span carries ``client`` so the OFFLINE twin
(``tools/dtrace --ledger``, :func:`fold_records`) resolves tenants
from a spool alone.  Everything is bounded: past
``conf.LEDGER_MAX_KEYS`` account keys, new keys fold into their job's
coarse account (stage/sig dropped) so totals stay honest.

Static **program cost profiles** ride alongside (the pricing prior
ROADMAP items 2/3 need before a program's first observed run): at
first dispatch of a freshly compiled stage program,
:func:`capture_program_cost` captures ``jitted.lower(args)``'s
``cost_analysis()`` (flops, bytes accessed — a host-side re-trace, no
extra XLA compile) and, under ``DPARK_LEDGER_COST=compile``, the
compiled ``memory_analysis()`` (measured arg/out/temp = peak-HBM
bytes), keyed by ``fuse.plan_adapt_signature`` and persisted to the
adapt store via ``adapt.record_program_cost``.

The **conservation check**: per-tenant attributed device-seconds must
reconcile with the measured mesh busy time (the mesh lock's depth-0
hold total) — :func:`conservation` computes the ratio,
``/api/health`` grades it with evidence.

Everything here is advisory: a fold failure logs at debug and never
breaks a job.  With ``DPARK_LEDGER=off`` the sink is None and the
plane costs one predicate per trace record.
"""

import threading
import time

from dpark_tpu import conf
from dpark_tpu import locks
from dpark_tpu.utils.log import get_logger

logger = get_logger("ledger")

MODES = ("off", "on")

_SINK = None                 # the `is None` check trace.record makes
_lock = locks.named_lock("ledger.install")   # guards install/clear

# fields every account carries, all additive (merge = field-wise sum,
# associative and commutative — asserted in tests).  *_ms/*_s are
# float sums, the rest int counters.
FIELDS = ("device_ms", "stages", "wave_ms", "waves", "dispatches",
          "compiles", "compile_ms", "lock_wait_ms", "lock_waits",
          "lock_hold_ms", "hbm_byte_s", "hbm_stored_bytes",
          "hbm_spills", "spill_bytes", "bulk_bytes", "fetches",
          "fetch_ms", "rc_byte_s", "rc_stored_bytes", "rc_hits",
          "rc_served_bytes")
_FLOAT_FIELDS = frozenset(f for f in FIELDS
                          if f.endswith("_ms") or f.endswith("_s"))

# the catch-all coarse signature accounts fold into past the key cap
OVERFLOW = "~"

# conservation grading: attributed per-tenant device-seconds must sum
# to at least this fraction of the measured mesh-busy time (the
# mesh-lock hold total) before /api/health grades attribution yellow —
# device time the ledger cannot name is untracked consumption
CONSERVE_YELLOW = 0.9


class Account:
    """One bounded resource account.  Folding is O(1) additions;
    merging is field-wise addition; memory is len(FIELDS) numbers no
    matter how many observations stream through."""

    __slots__ = FIELDS

    def __init__(self):
        for f in FIELDS:
            setattr(self, f, 0.0 if f in _FLOAT_FIELDS else 0)

    def merge(self, other):
        for f in FIELDS:
            setattr(self, f, getattr(self, f) + getattr(other, f))
        return self

    def to_dict(self):
        """JSON-safe digest (the wire/store format): only nonzero
        fields, floats rounded."""
        out = {}
        for f in FIELDS:
            v = getattr(self, f)
            if v:
                out[f] = round(v, 4) if f in _FLOAT_FIELDS else int(v)
        return out

    @classmethod
    def from_dict(cls, d):
        acct = cls()
        try:
            for f, v in (d or {}).items():
                if f in _FLOAT_FIELDS:
                    setattr(acct, f, float(v))
                elif f in Account.__slots__:
                    setattr(acct, f, int(v))
        except (TypeError, ValueError):
            pass
        return acct


def merge_account_digests(a, b):
    """Merge two account digests (the to_dict shape) — the
    cross-process sidecar merge and the offline-twin fold use it."""
    acct = Account.from_dict(a or {})
    acct.merge(Account.from_dict(b or {}))
    return acct.to_dict()


def _key_str(key):
    job, stage, sig = key
    return "%s|%s|%s" % ("-" if job is None else job,
                         "-" if stage is None else stage, sig or "-")


def parse_key(s):
    """Inverse of the account-key wire format ("job|stage|sig")."""
    job, stage, sig = str(s).split("|", 2)
    return (None if job == "-" else int(job),
            None if stage == "-" else int(stage),
            None if sig == "-" else sig)


class LedgerSink:
    """The in-process streaming aggregator.  fold() is called from
    TracePlane.record with every emitted record; everything is bounded
    (conf.LEDGER_MAX_KEYS accounts, live-store map the size of the HBM
    store) and guarded by one lock."""

    def __init__(self):
        self.lock = locks.named_lock("ledger.sink")
        self.accounts = {}       # (job, stage, sig) -> Account
        self.job_tenant = {}     # job id -> tenant/client name
        self._job_order = []
        # FINISHED jobs' accounts compact into a bounded per-(tenant,
        # sig) archive when their job span folds: live keys then stay
        # bounded by concurrency x stages x programs, a resident
        # server never exhausts the key cap into the unattributed
        # overflow, and per-tenant totals stay MONOTONIC (the archive
        # only ever grows) — the accounts surface a scrape reads is
        # live accounts + archive
        self.archive = {}        # (tenant, sig) -> Account
        self.retired = set()     # job ids whose accounts archived
        # live HBM stores: sid -> (bytes, t_registered, job, stage)
        self.hbm_live = {}
        # live result-cache entries: sid -> (bytes, t_registered,
        # storing tenant).  Tenant-keyed, not job-keyed: cache-served
        # queries run no job, so resultcache.* events carry the
        # tenant explicitly and byte-seconds settle straight into the
        # archive at release
        self.rc_live = {}
        self.folded = 0
        self.dropped_keys = 0
        # offline mesh view folded from mesh.lock spans (the live
        # endpoint prefers the executor's meter — see mesh_meter)
        self.mesh = {"busy_s": 0.0, "wait_s": 0.0,
                     "acquisitions": 0, "contended": 0}
        self._t_min = None
        self._t_max = None

    # -- accounts --------------------------------------------------------
    def _account(self, job, stage, sig):
        key = (job, stage, sig)
        acct = self.accounts.get(key)
        if acct is None:
            cap = int(getattr(conf, "LEDGER_MAX_KEYS", 512) or 0)
            if cap and len(self.accounts) >= cap:
                # overflow folds into the job's coarse account so
                # totals (and the conservation check) stay honest
                # past the key cap
                self.dropped_keys += 1
                key = (job, None, OVERFLOW)
                acct = self.accounts.get(key)
                if acct is None:
                    if len(self.accounts) >= cap + 16:
                        key = (None, None, OVERFLOW)
                        acct = self.accounts.get(key)
                        if acct is None:
                            acct = self.accounts[key] = Account()
                        return acct
                    acct = self.accounts[key] = Account()
                return acct
            acct = self.accounts[key] = Account()
        return acct

    def note_job(self, job, tenant):
        with self.lock:
            if job not in self.job_tenant:
                self._job_order.append(job)
                if len(self._job_order) > 4096:
                    # backstop for jobs that never folded a job span:
                    # archive their accounts BEFORE the tenant mapping
                    # goes (totals must move, not re-attribute), and
                    # SETTLE any still-resident HBM stores now — once
                    # the retired marker drops, a late release could
                    # otherwise resurrect a live account for a dead
                    # job under the wrong tenant
                    old = self._job_order.pop(0)
                    self._retire_locked(old)
                    old_tenant = self._tenant_of(old)
                    now = time.time()
                    for sid in [i for i, e in self.hbm_live.items()
                                if e[2] == old]:
                        nbytes, t0, _j, _st = self.hbm_live.pop(sid)
                        a = Account()
                        a.hbm_byte_s = nbytes * max(0.0, now - t0)
                        self._archive_locked(old_tenant, OVERFLOW, a)
                    self.job_tenant.pop(old, None)
                    self.retired.discard(old)
            self.job_tenant[job] = str(tenant or "local")

    def _archive_locked(self, tenant, sig, acct):
        cap = int(getattr(conf, "LEDGER_MAX_KEYS", 512) or 0)
        key = (tenant, sig or OVERFLOW)
        ent = self.archive.get(key)
        if ent is None:
            if cap and len(self.archive) >= cap:
                key = (tenant, OVERFLOW)
                ent = self.archive.get(key)
                if ent is None:
                    ent = self.archive[key] = Account()
            else:
                ent = self.archive[key] = Account()
        ent.merge(acct)

    def _retire_locked(self, job):
        """Compact one finished job's accounts into the per-(tenant,
        sig) archive.  The tenant mapping stays (late hbm releases
        and merged worker digests still resolve) until the job-order
        backstop prunes it."""
        if job is None or job in self.retired:
            return
        tenant = self._tenant_of(job)
        for key in [k for k in self.accounts if k[0] == job]:
            self._archive_locked(tenant, key[2],
                                 self.accounts.pop(key))
        self.retired.add(job)

    # -- folding ---------------------------------------------------------
    def fold(self, rec):
        name = rec.get("name", "")
        dur = float(rec.get("dur", 0.0) or 0.0)
        args = rec.get("args") or {}
        job = rec.get("job")
        stage = rec.get("stage")
        with self.lock:
            self.folded += 1
            ts = rec.get("ts")
            if ts:
                if self._t_min is None or ts < self._t_min:
                    self._t_min = ts
                end = ts + dur
                if self._t_max is None or end > self._t_max:
                    self._t_max = end
            if name == "stage.exec":
                a = self._account(job, stage, args.get("sig"))
                a.device_ms += dur * 1e3
                a.stages += 1
            elif name == "wave":
                a = self._account(job, stage, args.get("sig"))
                a.wave_ms += dur * 1e3
                a.waves += 1
            elif name == "dispatch":
                self._account(job, stage,
                              args.get("sig")).dispatches += 1
            elif name == "compile":
                self._account(job, stage,
                              args.get("sig")).compiles += 1
            elif name == "compile.backend":
                self._account(job, stage, args.get("sig")) \
                    .compile_ms += dur * 1e3
            elif name == "mesh.lock":
                hold = float(args.get("hold_s", 0.0) or 0.0)
                self.mesh["busy_s"] += hold
                self.mesh["acquisitions"] += 1
                a = self._account(job, stage, None)
                # the HOLD is the billable mesh occupancy: every
                # stage.exec / export / gather runs inside one, and
                # the span inherits the owning job from the thread
                # ctx — so per-tenant occupancy sums reconcile with
                # the meter's busy total (the conservation check)
                a.lock_hold_ms += hold * 1e3
                if dur > 0:
                    self.mesh["wait_s"] += dur
                    self.mesh["contended"] += 1
                    a.lock_wait_ms += dur * 1e3
                    a.lock_waits += 1
            elif name == "hbm.store":
                sid = args.get("sid")
                nbytes = int(args.get("bytes", 0) or 0)
                if sid is not None:
                    self.hbm_live[sid] = (nbytes, rec.get("ts")
                                          or time.time(), job, stage)
                a = self._account(job, stage, None)
                a.hbm_stored_bytes += nbytes
            elif name == "hbm.release":
                sid = args.get("sid")
                ent = self.hbm_live.pop(sid, None)
                if ent is not None:
                    nbytes, t0, sjob, sstage = ent
                    held = max(0.0, (rec.get("ts") or time.time())
                               - t0)
                    if sjob in self.retired:
                        # a store outliving its job (re-used shuffle
                        # outputs): accrue straight into the tenant's
                        # archive — never resurrect a live account
                        a = Account()
                        a.hbm_byte_s = nbytes * held
                        if args.get("reason") == "spill":
                            a.hbm_spills = 1
                        self._archive_locked(self._tenant_of(sjob),
                                             OVERFLOW, a)
                    else:
                        a = self._account(sjob, sstage, None)
                        a.hbm_byte_s += nbytes * held
                        if args.get("reason") == "spill":
                            a.hbm_spills += 1
            elif name == "resultcache.store":
                # shared result cache (ISSUE 18): residency bills to
                # the STORING tenant, carried in the event args (no
                # job exists when the planner stores or serves)
                sid = args.get("sid")
                tenant = str(args.get("tenant") or "local")
                nbytes = int(args.get("bytes", 0) or 0)
                if sid is not None:
                    self.rc_live[sid] = (nbytes, rec.get("ts")
                                         or time.time(), tenant)
                a = Account()
                a.rc_stored_bytes = nbytes
                self._archive_locked(tenant, "resultcache", a)
            elif name == "resultcache.release":
                ent = self.rc_live.pop(args.get("sid"), None)
                if ent is not None:
                    nbytes, t0, tenant = ent
                    held = max(0.0, (rec.get("ts") or time.time())
                               - t0)
                    a = Account()
                    a.rc_byte_s = nbytes * held
                    self._archive_locked(tenant, "resultcache", a)
            elif name == "resultcache.serve":
                # hits bill to the SERVED tenant: zero scan
                # device-seconds, just the hit count and served bytes
                a = Account()
                a.rc_hits = 1
                a.rc_served_bytes = int(args.get("bytes", 0) or 0)
                self._archive_locked(
                    str(args.get("tenant") or "local"),
                    "resultcache", a)
            elif name in ("spill.write", "spill.read"):
                self._account(job, stage, None).spill_bytes += \
                    int(args.get("bytes", 0) or 0)
            elif name in ("dcn.bulk.fetch", "dcn.bulk.serve",
                          "dcn.transfer"):
                self._account(job, stage, None).bulk_bytes += \
                    int(args.get("bytes", 0) or 0)
            elif name == "fetch.bucket":
                a = self._account(job, stage, None)
                a.fetches += 1
                a.fetch_ms += dur * 1e3
            elif name == "job":
                client = args.get("client")
                if client and job is not None:
                    # offline twin's tenant resolution (the job span
                    # is emitted at job END, after its stage spans)
                    self.job_tenant.setdefault(job, str(client))
                if job is not None:
                    # the job span only ever fires at finalize:
                    # compact its accounts into the archive so a
                    # resident server's live key set stays bounded by
                    # CONCURRENCY, not job history — identical in the
                    # live sink and the offline fold (both see this
                    # same record)
                    self._retire_locked(job)

    # -- reading back ----------------------------------------------------
    def _tenant_of(self, job):
        if job is None:
            return "unattributed"
        return self.job_tenant.get(job, "local")

    def account_digests(self):
        """{key_str: digest} under the lock — the wire/store shape the
        worker sidecar files and the offline twin merge."""
        with self.lock:
            return {_key_str(k): a.to_dict()
                    for k, a in self.accounts.items()}

    def snapshot(self, now=None):
        """Full digest view: accounts, per-job and per-tenant rollups,
        the folded mesh view, live HBM residency.  `now` pins the
        clock for the live byte-second gauge (the offline twin passes
        the spool's last timestamp so live and offline agree on
        everything the wall clock does not move)."""
        with self.lock:
            jobs = {}
            tenants = {}
            for (job, _stage, _sig), a in self.accounts.items():
                jobs.setdefault(job, Account()).merge(a)
            for job, a in jobs.items():
                tenants.setdefault(self._tenant_of(job),
                                   Account()).merge(a)
            for (tenant, _sig), a in self.archive.items():
                tenants.setdefault(tenant, Account()).merge(a)
            t_now = now if now is not None else time.time()
            live_bytes = sum(b for b, _, _, _ in
                             self.hbm_live.values())
            live_byte_s = sum(b * max(0.0, t_now - t0)
                              for b, t0, _, _ in
                              self.hbm_live.values())
            return {
                "accounts": {_key_str(k): a.to_dict()
                             for k, a in self.accounts.items()},
                "archive": {"%s|%s" % k: a.to_dict()
                            for k, a in self.archive.items()},
                "jobs": {str(j if j is not None else "-"):
                         a.to_dict() for j, a in jobs.items()},
                "tenants": {t: a.to_dict()
                            for t, a in tenants.items()},
                "job_tenant": {str(j): t for j, t in
                               self.job_tenant.items()},
                "mesh": dict(self.mesh),
                "hbm_live_bytes": int(live_bytes),
                "hbm_live_byte_s": round(live_byte_s, 4),
                "resultcache_live_bytes": int(sum(
                    b for b, _, _ in self.rc_live.values())),
                "resultcache_live_byte_s": round(sum(
                    b * max(0.0, t_now - t0)
                    for b, t0, _ in self.rc_live.values()), 4),
                "span_window_s": round(
                    (self._t_max - self._t_min), 6)
                if self._t_min is not None else 0.0,
                "folded": self.folded,
                "dropped_keys": self.dropped_keys,
            }


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def configure(mode=None):
    """Install (mode "on") or clear (mode "off") the process sink.
    None reads conf.DPARK_LEDGER.  Returns the sink or None.  The
    sink only ever sees records the TRACE plane emits — with
    DPARK_TRACE=off there is nothing to fold and the plane is inert
    either way."""
    global _SINK
    if mode is None:
        mode = str(getattr(conf, "DPARK_LEDGER", "on") or "on")
    mode = str(mode).lower()
    if mode not in MODES:
        raise ValueError("DPARK_LEDGER=%r (expected off|on)" % mode)
    with _lock:
        _SINK = LedgerSink() if mode == "on" else None
        return _SINK


def active():
    return _SINK is not None


def mode():
    return "on" if _SINK is not None else "off"


def sink():
    return _SINK


def note_job(job, tenant):
    """Scheduler hook: a job record was minted for `tenant` (the
    service client, or "local" on single-tenant masters).  One `is
    None` check when the plane is off."""
    s = _SINK
    if s is not None:
        s.note_job(job, tenant)


def snapshot():
    s = _SINK
    if s is None:
        return {"accounts": {}, "archive": {}, "jobs": {},
                "tenants": {}, "job_tenant": {}, "mesh": {},
                "hbm_live_bytes": 0, "hbm_live_byte_s": 0.0,
                "span_window_s": 0.0, "folded": 0, "dropped_keys": 0}
    return s.snapshot()


# ---------------------------------------------------------------------------
# offline twin: fold a record list (spool load) into a fresh sink
# ---------------------------------------------------------------------------

def fold_records(records):
    """Build a sink from already-collected trace records (the
    tools/dtrace --ledger path and the live-vs-offline consistency
    test).  Skips counter events' own rows but MERGES any worker
    ledger digests they carry, so the offline view matches the
    driver's merged live view.  Records fold in END-time order (ts +
    dur) — spans are EMITTED at completion, so this reproduces the
    live fold order: stage spans before their job span (whose ts is
    the job START), stores before their releases."""
    s = LedgerSink()
    worker = {}
    for rec in sorted(records, key=lambda r: (r.get("ts", 0.0)
                                              + r.get("dur", 0.0))):
        if rec.get("cat") == "counters":
            d = (rec.get("args") or {}).get("ledger")
            if d:
                worker[(rec.get("host"), rec.get("pid"))] = d
            continue
        try:
            s.fold(rec)
        except Exception:
            pass
    # worker sidecar digests: a worker's spans already folded above
    # when the span spool carried them, and adding its cumulative
    # digest on top would double-count — so digests only fill in
    # account keys the span fold never produced (a worker whose span
    # spool hit the byte cap still ships its sidecar).  Keys whose
    # job RETIRED skip too: their span-folded totals already live in
    # the archive under the tenant
    for digest in worker.values():
        for key_s, d in (digest or {}).items():
            try:
                key = parse_key(key_s)
            except (ValueError, TypeError):
                continue
            if key not in s.accounts and key[0] not in s.retired:
                s.accounts[key] = Account.from_dict(d)
    return s


def merged_account_digests(include_workers=True):
    """The driver's merged account view: the local sink's accounts
    plus (in spool mode) the latest worker-process ledger digests from
    the counters merge — multiproc workers' fetch/spill activity
    finally attributes to the jobs that caused it."""
    s = _SINK
    out = dict(s.account_digests()) if s is not None else {}
    if include_workers:
        try:
            from dpark_tpu import trace
            workers = trace.merged_worker_counters().get("ledger") \
                or {}
            for key_s, digest in workers.items():
                out[key_s] = merge_account_digests(out.get(key_s),
                                                   digest)
        except Exception:
            pass
    return out


def tenant_totals(include_workers=True):
    """{tenant: {device_seconds, lock_wait_seconds, hbm_byte_seconds,
    bulk_bytes, ...}} — the per-tenant /metrics rollup, merged across
    worker processes.  Monotonic: accounts only ever grow and
    byte-seconds accrue at release."""
    s = _SINK
    if s is None:
        return {}
    merged = merged_account_digests(include_workers)
    with s.lock:
        tenant_of = dict(s.job_tenant)
        archived = {k: a.to_dict() for k, a in s.archive.items()}
    out = {}
    for (tenant, _sig), d in archived.items():
        out.setdefault(tenant, Account()).merge(
            Account.from_dict(d))
    for key_s, d in merged.items():
        try:
            job, _stage, _sig = parse_key(key_s)
        except (ValueError, TypeError):
            continue
        tenant = "unattributed" if job is None \
            else tenant_of.get(job, "local")
        acct = out.setdefault(tenant, Account())
        acct.merge(Account.from_dict(d))
    return {t: _totals_shape(a) for t, a in out.items()}


def _totals_shape(a):
    """Account -> the per-tenant rollup shape /metrics and
    /api/ledger export (ONE definition — the offline twin ships the
    identical shape via tenant_totals_from_snapshot)."""
    return {
        # billable mesh occupancy: attributed lock-hold seconds when
        # a device master metered them, else the stage-execution wall
        # (host-only masters have no mesh lock but still run stages)
        "device_seconds": round(
            (a.lock_hold_ms or a.device_ms) / 1e3, 6),
        "stage_device_seconds": round(a.device_ms / 1e3, 6),
        "lock_wait_seconds": round(a.lock_wait_ms / 1e3, 6),
        "hbm_byte_seconds": round(a.hbm_byte_s, 4),
        "bulk_bytes": int(a.bulk_bytes),
        "spill_bytes": int(a.spill_bytes),
        "fetches": int(a.fetches),
        "compiles": int(a.compiles),
        "compile_ms": round(a.compile_ms, 3),
        "waves": int(a.waves),
        "resultcache_byte_seconds": round(a.rc_byte_s, 4),
        "resultcache_hits": int(a.rc_hits),
        "resultcache_served_bytes": int(a.rc_served_bytes),
    }


def tenant_totals_from_snapshot(snap):
    """The tenant_totals rollup shape computed from a snapshot's raw
    per-tenant accounts — tools/dtrace --ledger uses this so the
    offline twin's `tenants` field agrees field-for-field with the
    live /api/ledger."""
    return {t: _totals_shape(Account.from_dict(d))
            for t, d in (snap.get("tenants") or {}).items()}


# ---------------------------------------------------------------------------
# top-k evidence (ISSUE 15 satellite: /api/health names the consumer)
# ---------------------------------------------------------------------------

def top_programs(k=3, snap=None):
    """Top programs by attributed device-seconds: [(sig, device_s,
    tenant)] — the evidence a yellow executor grade attaches so the
    verdict names its likely consumer."""
    snap = snap or snapshot()
    per_sig = {}
    tenant_of = snap.get("job_tenant", {})
    for key_s, d in snap.get("accounts", {}).items():
        try:
            job, _stage, sig = parse_key(key_s)
        except (ValueError, TypeError):
            continue
        if not sig or sig == OVERFLOW:
            continue
        ms = float(d.get("device_ms", 0.0) or 0.0)
        if not ms:
            continue
        by_tenant = per_sig.setdefault(sig, {})
        tenant = "unattributed" if job is None \
            else tenant_of.get(str(job), "local")
        by_tenant[tenant] = by_tenant.get(tenant, 0.0) + ms
    for key_s, d in snap.get("archive", {}).items():
        # finished jobs' compacted accounts: tenant is the key.
        # rsplit, not split — tenant names are caller-supplied and
        # may contain "|"; the sig side never does
        tenant, _, sig = str(key_s).rpartition("|")
        if not sig or sig == OVERFLOW:
            continue
        ms = float(d.get("device_ms", 0.0) or 0.0)
        if not ms:
            continue
        by_tenant = per_sig.setdefault(sig, {})
        by_tenant[tenant] = by_tenant.get(tenant, 0.0) + ms
    rows = sorted(per_sig.items(),
                  key=lambda kv: -sum(kv[1].values()))[:k]
    # the named tenant is the DOMINANT consumer of the signature —
    # this is the evidence a yellow grade attaches, so it must not
    # depend on account iteration order
    return [{"sig": sig,
             "device_s": round(sum(by_tenant.values()) / 1e3, 4),
             "tenant": max(by_tenant, key=by_tenant.get)}
            for sig, by_tenant in rows]


def top_tenants(field="hbm_byte_seconds", k=3, totals=None):
    """Top tenants by an attributed field (default HBM byte-seconds)."""
    totals = totals if totals is not None else tenant_totals()
    rows = sorted(((t, d.get(field, 0)) for t, d in totals.items()),
                  key=lambda kv: -kv[1])[:k]
    return [{"tenant": t, field: v} for t, v in rows if v]


# ---------------------------------------------------------------------------
# conservation: attributed device-seconds vs measured mesh busy time
# ---------------------------------------------------------------------------

def mesh_meter(scheduler=None):
    """The live mesh occupancy counters: the executor's _MeshLock
    meter when a device scheduler is reachable, else the sink's folded
    mesh.lock view (the offline shape)."""
    try:
        ex = getattr(scheduler, "executor", None) \
            if scheduler is not None else None
        lock = getattr(ex, "_mesh_lock", None)
        if lock is not None and hasattr(lock, "meter"):
            return lock.meter()
    except Exception:
        pass
    s = _SINK
    if s is not None:
        with s.lock:
            out = dict(s.mesh)
            out["wall_s"] = round(s._t_max - s._t_min, 6) \
                if s._t_min is not None else 0.0
        return out
    return {"busy_s": 0.0, "wait_s": 0.0, "acquisitions": 0,
            "contended": 0, "wall_s": 0.0}


def conservation(scheduler=None, meter=None, snap=None):
    """JOB-attributed mesh occupancy vs measured mesh busy seconds.
    Attributed = the lock-hold seconds of accounts that name a job
    (the span inherits the owning job from the thread context — stage
    execution, export-bridge reads for a fetching job, device joins
    all bill correctly); busy = the _MeshLock meter's depth-0 hold
    total.  ratio < CONSERVE_YELLOW means more than
    (1 - ratio) of the mesh's busy time could not be billed to any
    tenant — untracked consumption the quota/preemption work cannot
    arbitrate.  ok is None when the mesh was never busy (nothing to
    conserve).  Stage-execution device-seconds ride as secondary
    evidence."""
    snap = snap or snapshot()
    if not snap.get("folded"):
        # the sink observed nothing (DPARK_TRACE=off, or tracing not
        # yet started): the always-on lock meter still accrued busy
        # time, but grading that as "unattributed consumption" would
        # flag every deliberately-untraced server — nothing to
        # conserve, not a violation.  The lifetime meter's busy rides
        # as evidence only.
        ev = meter or mesh_meter(scheduler)
        return {"attributed_device_s": 0.0, "stage_device_s": 0.0,
                "mesh_busy_s": round(float(ev.get("busy_s", 0.0)
                                           or 0.0), 6),
                "ratio": None,
                "floor": CONSERVE_YELLOW,
                "ok": None}
    if meter is None:
        # grade against the SINK's folded mesh view — the SAME window
        # as the attribution by construction.  The executor's
        # lifetime meter would falsely flag tracing enabled mid-life
        # (busy accrued while untraced can never be attributed);
        # tools/dtrace passes the spool's own meter.
        meter = snap.get("mesh") or {}
    attributed = 0.0
    stage_s = 0.0
    for key_s, d in snap.get("accounts", {}).items():
        stage_s += float(d.get("device_ms", 0.0) or 0.0) / 1e3
        try:
            job, _stage, _sig = parse_key(key_s)
        except (ValueError, TypeError):
            continue
        if job is not None:
            attributed += float(d.get("lock_hold_ms", 0.0)
                                or 0.0) / 1e3
    for d in snap.get("archive", {}).values():
        # archived accounts were job-attributed when they folded
        stage_s += float(d.get("device_ms", 0.0) or 0.0) / 1e3
        attributed += float(d.get("lock_hold_ms", 0.0) or 0.0) / 1e3
    busy = float(meter.get("busy_s", 0.0) or 0.0)
    floor = CONSERVE_YELLOW
    ratio = attributed / busy if busy > 0 else None
    return {"attributed_device_s": round(attributed, 6),
            "stage_device_s": round(stage_s, 6),
            "mesh_busy_s": round(busy, 6),
            "ratio": round(ratio, 4) if ratio is not None else None,
            "floor": floor,
            "ok": None if ratio is None else ratio >= floor}


def utilization(scheduler=None):
    """The mesh busy/idle/contended split for the web UI bar: busy =
    lock held, contended = time spent WAITING for the lock (demand the
    mesh could not serve), idle = the rest of the wall."""
    m = mesh_meter(scheduler)
    wall = max(float(m.get("wall_s", 0.0) or 0.0), 1e-9)
    busy = min(1.0, float(m.get("busy_s", 0.0)) / wall)
    contended = min(1.0 - busy,
                    float(m.get("wait_s", 0.0)) / wall)
    return {"busy_frac": round(busy, 4),
            "contended_frac": round(contended, 4),
            "idle_frac": round(max(0.0, 1.0 - busy - contended), 4),
            "meter": m}


# ---------------------------------------------------------------------------
# the /api/ledger payload (and the bench `ledger` section)
# ---------------------------------------------------------------------------

def api_ledger(scheduler=None):
    """Everything the web UI's tenant table + utilization bar need,
    built from defensive snapshots (a scrape racing a running job
    returns valid JSON, never an error)."""
    snap = snapshot()
    # one merged-totals pass per request: tenant_totals re-reads the
    # worker sidecar files, and the UI polls this endpoint every tick
    totals = tenant_totals()
    out = {
        "mode": mode(),
        "accounts": snap["accounts"],
        "archive": snap["archive"],
        "tenants": totals,
        "jobs": snap["jobs"],
        "job_tenant": snap["job_tenant"],
        "utilization": utilization(scheduler),
        "conservation": conservation(scheduler, snap=snap),
        "hbm_live_bytes": snap["hbm_live_bytes"],
        "hbm_live_byte_s": snap["hbm_live_byte_s"],
        "top_programs": top_programs(snap=snap),
        "top_tenants": top_tenants(totals=totals),
        "folded": snap["folded"],
        "dropped_keys": snap["dropped_keys"],
    }
    return out


def summary():
    """The `ledger` section for bench artifacts: mode + per-tenant
    rollup + conservation.  {"mode": "off", "tenants": {}} when the
    plane is off."""
    s = _SINK
    if s is None:
        return {"mode": "off", "tenants": {}, "accounts": 0}
    snap = s.snapshot()
    return {"mode": "on",
            "tenants": tenant_totals(),
            "accounts": len(snap["accounts"]) + len(snap["archive"]),
            "mesh": snap["mesh"],
            "conservation": conservation(snap=snap),
            "folded": snap["folded"]}


# ---------------------------------------------------------------------------
# static program cost profiles (the items-2/3 pricing prior)
# ---------------------------------------------------------------------------

_cost_seen = set()
_cost_lock = locks.named_lock("ledger.cost")


def _cost_key(sig):
    return "%s|%s" % (sig[0], sig[1])


def capture_program_cost(sig, jitted, args):
    """Capture one program's static cost profile at FIRST dispatch:
    ``jitted.lower(*args)`` (a host-side re-trace — no extra XLA
    compile) -> ``cost_analysis()`` flops / bytes accessed, plus under
    DPARK_LEDGER_COST=compile the compiled ``memory_analysis()``
    (measured arg/out/temp bytes = the peak-HBM prior).  Persisted to
    the adapt store keyed by the cross-process-stable plan signature,
    so a FRESH process prices a program before ever running it.
    Must be called BEFORE the jitted call when buffers are donated
    (lower only reads avals, never the buffers).  Never raises."""
    try:
        if _SINK is None or sig is None:
            return None
        # the streaming dispatch loop calls this per wave: the
        # already-captured fast path must be one set probe (racy read
        # is fine — the add below re-checks under the lock)
        key = _cost_key(sig)
        if key in _cost_seen:
            return None
        cost_mode = str(getattr(conf, "LEDGER_COST", "lower") or
                        "lower").lower()
        if cost_mode == "off":
            return None
        from dpark_tpu import adapt
        if not adapt.enabled():
            return None
        with _cost_lock:
            if key in _cost_seen:
                return None
            _cost_seen.add(key)
        if adapt.program_cost(key) is not None:
            return None              # an earlier process already paid
        lowered = jitted.lower(*args)
        ca = lowered.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        profile = {
            "flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)
                                    or 0.0),
            "arg_bytes": int(sum(int(getattr(a, "nbytes", 0) or 0)
                                 for a in args)),
        }
        if cost_mode == "compile":
            # the extra compile is PLANE overhead: suppress its
            # compile.backend span so the program's compile_ms
            # account never double-bills the tenant for it
            from dpark_tpu import trace
            trace.suppress_compile_spans(True)
            try:
                m = lowered.compile().memory_analysis()
            finally:
                trace.suppress_compile_spans(False)
            if m is not None:
                profile["out_bytes"] = int(
                    getattr(m, "output_size_in_bytes", 0) or 0)
                profile["temp_bytes"] = int(
                    getattr(m, "temp_size_in_bytes", 0) or 0)
                profile["peak_hbm_bytes"] = (
                    int(getattr(m, "argument_size_in_bytes", 0) or 0)
                    + profile["out_bytes"] + profile["temp_bytes"])
        adapt.record_program_cost(key, profile)
        from dpark_tpu import trace
        trace.event("ledger.cost", "ledger", sig=sig[0],
                    flops=profile["flops"])
        return profile
    except Exception as e:
        logger.debug("capture_program_cost failed: %s", e)
        return None


def reset_cost_capture():
    """Forget which signatures this process already profiled
    (tests)."""
    with _cost_lock:
        _cost_seen.clear()


def _init_from_conf():
    m = str(getattr(conf, "DPARK_LEDGER", "on") or "on").lower()
    if m == "on":
        configure("on")


_init_from_conf()
