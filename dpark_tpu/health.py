"""Online health plane (ISSUE 14 tentpole): streaming telemetry
sketches, subsystem grading, and the flight recorder.

The PR 8 trace plane measures per-fetch latency, decode outcomes, and
phase timings, but nothing consumed them ONLINE: spans died in the
spool, /metrics exported totals without tails, and the JobServer had
no notion of whether a tenant's latency was healthy.  This module
closes that gap without re-parsing anything: a :class:`HealthSink`
subscribes to ``trace.TracePlane.record`` (one ``is None`` check when
off — the established faults/trace contract) and folds spans AS THEY
ARE EMITTED into compact, bounded, merge-associative sketches:

* **latency tails** — log2-bucketed histograms with p50/p95/p99
  estimates keyed by SITE:
  ``fetch.bucket:<peer>`` (reduce-side bucket fetches per serving
  peer), ``dcn.transfer:<peer>`` / ``dcn.bulk.fetch:<peer>`` /
  ``dcn.bulk.serve:<peer>`` (host bridge and bulk data plane per
  peer), ``wave:<sig>`` / ``stage.exec`` (device execution per
  program signature), ``spill.write`` / ``spill.read``,
  ``executor.compile:<sig>`` / ``dispatch:<sig>`` (count-only —
  instant events).
* **rates** — decode outcomes, fetch failures, bulk-stream failures,
  degrade/abort events, compile counts.
* **pressure** — cumulative spill bytes read/written (HBM pressure is
  read live off the executor by :func:`api_health`; it is a gauge of
  NOW, not a foldable stream).

Three consumers ride the sink:

1. **site stats -> adapt store** (ROADMAP item 5's named handoff):
   :func:`persist_site_tails` appends per-site digest DELTAS to the
   crc-framed adapt store (``adapt.record_site_tail``), so a fresh
   process — and eventually the straggler-adaptive coder — reads the
   observed per-site tail distribution back (``adapt.site_tails()``).
   Worker processes fold their sketches into the cross-process
   cross-process counters merge (one atomically-rewritten
   ``health-<host>-<pid>.jsonl`` beside the counters file — see
   trace._write_process_health), so driver-side tails include
   multiproc fetches — the same merge that closed the fault/decode
   counter blindspot in PR 8.
2. **per-tenant SLO accounting** — service.py tracks attainment and
   multi-window burn; :func:`api_health` attaches the graded verdict.
3. **the flight recorder** — warning-and-above events land in an
   always-armed bounded ring (``trace._FLIGHT``) even with
   ``DPARK_TRACE=off``; on job abort, stage degrade, or SIGUSR2,
   :func:`flight_dump` writes a crc-framed snapshot (ring contents +
   health sketches + recovery summary + adapt decisions) under
   ``DPARK_FLIGHT_DIR`` for post-mortem via ``tools/dtrace --flight``.

Everything here is advisory: a fold/persist/dump failure logs at
debug and never breaks a job.  With ``DPARK_HEALTH=off`` the sink is
None and the whole plane costs one predicate per trace record.
"""

import json
import math
import os
import socket
import threading
import time

from dpark_tpu import conf
from dpark_tpu import locks
from dpark_tpu.utils.log import get_logger

logger = get_logger("health")

MODES = ("off", "on")

# log2 bucket layout shared by every sketch: bucket 0 holds durations
# <= _B0 seconds (0.1 ms), bucket i holds (_B0 * 2^(i-1), _B0 * 2^i].
# 36 buckets reach ~= 55 minutes; anything longer clips into the last
# bucket.  The layout is FIXED (not configurable) so digests written
# by one process/version merge bit-identically with another's.
_B0 = 1e-4
NBUCKETS = 36

_SINK = None                 # the `is None` check trace.record makes
_lock = locks.named_lock("health.install")   # guards install/clear


class Sketch:
    """One bounded log-bucketed latency histogram.  Folding is O(1),
    merging is bucket-wise addition (associative and commutative —
    asserted in tests), and the memory is NBUCKETS ints regardless of
    how many observations stream through."""

    __slots__ = ("buckets", "n", "sum")

    def __init__(self):
        self.buckets = [0] * NBUCKETS
        self.n = 0
        self.sum = 0.0

    @staticmethod
    def bucket_of(seconds):
        if seconds <= _B0:
            return 0
        return min(NBUCKETS - 1,
                   1 + int(math.log2(seconds / _B0)))

    @staticmethod
    def bucket_edge(i):
        """Upper edge of bucket i in seconds."""
        return _B0 * (2 ** i) if i else _B0

    def add(self, seconds):
        self.buckets[self.bucket_of(max(0.0, float(seconds)))] += 1
        self.n += 1
        self.sum += max(0.0, float(seconds))

    def merge(self, other):
        for i, v in enumerate(other.buckets):
            self.buckets[i] += v
        self.n += other.n
        self.sum += other.sum
        return self

    def quantile(self, q):
        """Estimated q-quantile in seconds (None when empty): find the
        bucket holding the q-th observation and interpolate
        geometrically inside it (log-uniform assumption — the honest
        middle of a log bucket)."""
        if not self.n:
            return None
        target = q * self.n
        acc = 0
        for i, v in enumerate(self.buckets):
            acc += v
            if acc >= target:
                hi = self.bucket_edge(i)
                if i == 0:
                    return hi
                lo = self.bucket_edge(i - 1)
                # position of the target inside this bucket
                frac = 1.0 - (acc - target) / max(1, v)
                return lo * ((hi / lo) ** max(0.0, min(1.0, frac)))
        return self.bucket_edge(NBUCKETS - 1)

    def to_dict(self):
        """Sparse, JSON-safe digest (the wire/store format)."""
        return {"b": {str(i): v for i, v in enumerate(self.buckets)
                      if v},
                "n": self.n, "s": round(self.sum, 6)}

    @classmethod
    def from_dict(cls, d):
        sk = cls()
        try:
            for i, v in (d.get("b") or {}).items():
                i = int(i)
                if 0 <= i < NBUCKETS:
                    sk.buckets[i] = int(v)
            sk.n = int(d.get("n", sum(sk.buckets)))
            sk.sum = float(d.get("s", 0.0))
        except (TypeError, ValueError):
            pass
        return sk

    def summary(self):
        """{"n", "p50_ms", "p95_ms", "p99_ms", "mean_ms"} — the
        human/bench view.  Count-only sketches (instant events, sum
        0) report just "n"."""
        out = {"n": self.n}
        if self.n and self.sum > 0:
            for name, q in (("p50_ms", 0.50), ("p95_ms", 0.95),
                            ("p99_ms", 0.99)):
                out[name] = round(self.quantile(q) * 1e3, 3)
            out["mean_ms"] = round(self.sum / self.n * 1e3, 3)
        return out


def merge_digests(a, b):
    """Merge two digest dicts (the to_dict shape) — used by the
    cross-process counter merge and the adapt-store fold."""
    sk = Sketch.from_dict(a or {})
    sk.merge(Sketch.from_dict(b or {}))
    return sk.to_dict()


# ---------------------------------------------------------------------------
# the sink: site routing for trace records
# ---------------------------------------------------------------------------

def _peer_of(args):
    """Best-effort peer identity from span args: an explicit `peer`,
    else the host of a `uri`."""
    peer = args.get("peer")
    if peer:
        return str(peer)
    uri = args.get("uri")
    if not uri:
        return None
    u = str(uri)
    for scheme in ("tcp://", "http://", "file://", "hbm://"):
        if u.startswith(scheme):
            u = u[len(scheme):]
            break
    return u.split("/", 1)[0].rsplit(":", 1)[0] or "local"


# per-(job, stage) fetch sketches kept; the oldest evicts
STAGE_SKETCHES = 256


class HealthSink:
    """The in-process streaming aggregator.  fold() is called from
    TracePlane.record with every emitted record; everything is bounded
    (conf.HEALTH_MAX_SITES site sketches, STAGE_SKETCHES per-stage
    fetch sketches) and guarded by one lock."""

    def __init__(self):
        self.lock = locks.named_lock("health.sink")
        self.sites = {}          # site -> Sketch (bounded)
        self.rates = {}          # event name -> count
        self.gauges = {"spill_bytes_written": 0,
                       "spill_bytes_read": 0}
        # per-(job, stage) fetch-latency sketches for the web UI's
        # stage fetch-p99 column (bounded: oldest evicts)
        self.stage_fetch = {}
        self._stage_order = []
        self.folded = 0
        self.dropped_sites = 0
        # deltas already persisted to the adapt store, per site
        self._persisted = {}
        self._last_persist = 0.0

    # -- folding ---------------------------------------------------------
    def _site_sketch(self, site):
        sk = self.sites.get(site)
        if sk is None:
            cap = int(getattr(conf, "HEALTH_MAX_SITES", 256) or 0)
            if cap and len(self.sites) >= cap:
                # overflow folds into the base site (before the ":"),
                # so totals stay honest even past the key cap
                self.dropped_sites += 1
                base = site.split(":", 1)[0]
                sk = self.sites.get(base)
                if sk is None and len(self.sites) < cap + 16:
                    sk = self.sites[base] = Sketch()
                return sk
            sk = self.sites[site] = Sketch()
        return sk

    def fold(self, rec):
        name = rec.get("name", "")
        dur = float(rec.get("dur", 0.0) or 0.0)
        args = rec.get("args") or {}
        with self.lock:
            self.folded += 1
            if name == "fetch.bucket":
                site = "fetch.bucket:%s" % (_peer_of(args) or "local")
                sk = self._site_sketch(site)
                if sk is not None:
                    sk.add(dur)
                if "error" in args:
                    self.rates["fetch.error"] = \
                        self.rates.get("fetch.error", 0) + 1
                key = (rec.get("job"), rec.get("stage"))
                if key != (None, None):
                    ssk = self.stage_fetch.get(key)
                    if ssk is None:
                        if len(self._stage_order) >= STAGE_SKETCHES:
                            old = self._stage_order.pop(0)
                            self.stage_fetch.pop(old, None)
                        ssk = self.stage_fetch[key] = Sketch()
                        self._stage_order.append(key)
                    ssk.add(dur)
            elif name in ("dcn.transfer", "dcn.bulk.fetch",
                          "dcn.bulk.serve"):
                site = "%s:%s" % (name, _peer_of(args) or "local")
                sk = self._site_sketch(site)
                if sk is not None:
                    sk.add(dur)
                if "error" in args:
                    self.rates["dcn.error"] = \
                        self.rates.get("dcn.error", 0) + 1
            elif name == "wave":
                site = "wave:%s" % (args.get("sig") or "?")
                sk = self._site_sketch(site)
                if sk is not None:
                    sk.add(dur)
            elif name == "stage.exec":
                sk = self._site_sketch("stage.exec")
                if sk is not None:
                    sk.add(dur)
            elif name in ("compile", "dispatch"):
                # instant events: count-only sketches keyed by the
                # program signature (latency lives in wave/stage.exec)
                site = "executor.%s:%s" % (
                    name, args.get("sig") or args.get("program")
                    or "?")
                sk = self._site_sketch(site)
                if sk is not None:
                    sk.n += 1
                self.rates[name] = self.rates.get(name, 0) + 1
            elif name in ("spill.write", "spill.read"):
                sk = self._site_sketch(name)
                if sk is not None:
                    sk.add(dur)
                gk = "spill_bytes_written" if name == "spill.write" \
                    else "spill_bytes_read"
                self.gauges[gk] += int(args.get("bytes", 0) or 0)
            elif name.startswith("decode."):
                self.rates[name] = self.rates.get(name, 0) + 1
            elif name in ("fetch.failed", "dcn.bulk.failed",
                          "stage.degrade", "job.abort"):
                self.rates[name] = self.rates.get(name, 0) + 1
            elif name == "job":
                state = args.get("state")
                if state:
                    self.rates["job.%s" % state] = \
                        self.rates.get("job.%s" % state, 0) + 1

    # -- reading back ----------------------------------------------------
    def snapshot(self):
        """Full digest view (the wire/store shapes) under the lock."""
        with self.lock:
            return {
                "sites": {s: sk.to_dict()
                          for s, sk in self.sites.items()},
                "rates": dict(self.rates),
                "gauges": dict(self.gauges),
                "stage_fetch": {"%s:%s" % k: sk.to_dict()
                                for k, sk in self.stage_fetch.items()},
                "folded": self.folded,
                "dropped_sites": self.dropped_sites,
            }

    def site_digests(self):
        with self.lock:
            return {s: sk.to_dict() for s, sk in self.sites.items()}


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def configure(mode=None):
    """Install (mode "on") or clear (mode "off") the process sink.
    None reads conf.DPARK_HEALTH.  Returns the sink or None.  The
    sink only ever sees records the TRACE plane emits — with
    DPARK_TRACE=off there is nothing to fold and the plane is inert
    either way."""
    global _SINK
    if mode is None:
        mode = str(getattr(conf, "DPARK_HEALTH", "on") or "on")
    mode = str(mode).lower()
    if mode not in MODES:
        raise ValueError("DPARK_HEALTH=%r (expected off|on)" % mode)
    with _lock:
        _SINK = HealthSink() if mode == "on" else None
        return _SINK


def active():
    return _SINK is not None


def mode():
    return "on" if _SINK is not None else "off"


def sink():
    return _SINK


def snapshot():
    s = _SINK
    return s.snapshot() if s is not None else {
        "sites": {}, "rates": {}, "gauges": {}, "stage_fetch": {},
        "folded": 0, "dropped_sites": 0}


# ---------------------------------------------------------------------------
# offline twin: fold a record list (spool load) into a fresh sink
# ---------------------------------------------------------------------------

def fold_records(records):
    """Build a registry from already-collected trace records (the
    tools/dtrace --health path and the live-vs-offline consistency
    test).  Skips counter events' own record rows but MERGES any
    worker health digests they carry, so the offline view matches the
    driver's merged live view."""
    s = HealthSink()
    worker = {}
    for rec in records:
        if rec.get("cat") == "counters":
            h = (rec.get("args") or {}).get("health")
            if h:
                # cumulative per process: newest per (host, pid) wins
                worker[(rec.get("host"), rec.get("pid"))] = h
            continue
        try:
            s.fold(rec)
        except Exception:
            pass
    # NOTE: worker spool files already hold the worker's own spans, so
    # folding them above covers what the digests summarize; the
    # digests only fill in when a worker's SPAN spool was capped but
    # its counters file (never capped) still shipped the sketch.  Take
    # the per-site MAX of fold-vs-digest counts so neither source
    # double-counts the other.
    for digest in worker.values():
        for site, d in (digest or {}).items():
            have = s.sites.get(site)
            cand = Sketch.from_dict(d)
            if have is None or cand.n > have.n:
                s.sites[site] = cand
    return s


def summarize_sites(site_digests):
    """{site: digest} -> {site: summary} sorted by site."""
    out = {}
    for site in sorted(site_digests):
        out[site] = Sketch.from_dict(site_digests[site]).summary()
    return out


def merged_site_digests(include_workers=True):
    """The driver's merged per-site view: the local sink's sketches
    plus (in spool mode) the latest worker-process health digests
    from the counters merge — multiproc fetch tails finally surface
    on the driver."""
    s = _SINK
    out = dict(s.site_digests()) if s is not None else {}
    if include_workers:
        try:
            from dpark_tpu import trace
            workers = trace.merged_worker_counters().get("health") \
                or {}
            for site, digest in workers.items():
                out[site] = merge_digests(out.get(site), digest)
        except Exception:
            pass
    return out


def summary():
    """The `health` section for bench artifacts: mode + per-site tail
    summaries + event rates.  {"mode": "off", "sites": {}} when the
    plane is off."""
    s = _SINK
    if s is None:
        return {"mode": "off", "sites": {}, "rates": {}}
    snap = s.snapshot()
    return {"mode": "on",
            "sites": summarize_sites(merged_site_digests()),
            "rates": snap["rates"],
            "gauges": snap["gauges"],
            "folded": snap["folded"]}


# ---------------------------------------------------------------------------
# consumer 1: per-site tails -> the adapt store (ROADMAP item 5)
# ---------------------------------------------------------------------------

# minimum seconds between site-tail persists into the adapt store
# (persist_site_tails runs at job finish; a streaming job finishing
# one tick-job per second must not append per tick).  Deltas are
# persisted, so the throttle trades freshness, not truth.
PERSIST_MIN_S = 30.0


def persist_site_tails(force=False):
    """Append each site's UNPERSISTED observations to the adapt store
    as a digest delta (the store folds deltas by bucket addition, so
    repeated persists never double-count).  Throttled to once per
    PERSIST_MIN_S unless forced.  Returns the number of
    sites written."""
    s = _SINK
    if s is None:
        return 0
    try:
        from dpark_tpu import adapt
        if not adapt.enabled():
            return 0
        now = time.time()
        with s.lock:
            if not force and now - s._last_persist < PERSIST_MIN_S:
                return 0
            s._last_persist = now
        # the MERGED view: local sketches plus worker-process digests
        # from the counters merge — on a multiprocess master the
        # driver itself fetches nothing, and the whole point of the
        # handoff is the WORKERS' observed tails
        merged = merged_site_digests()
        deltas = {}
        with s.lock:
            for site, digest in merged.items():
                sk = Sketch.from_dict(digest)
                if not sk.sum:
                    continue             # count-only: no tail to store
                prev = s._persisted.get(site)
                if prev is None:
                    prev = s._persisted[site] = ([0] * NBUCKETS, 0.0)
                delta = Sketch()
                for i, v in enumerate(sk.buckets):
                    delta.buckets[i] = v - prev[0][i]
                delta.n = sum(delta.buckets)
                if delta.n <= 0:
                    continue
                # the sum delta rides too: summary() gates percentile
                # output on sum > 0, so a stored tail must read back
                # as a REAL latency sketch, not a count-only one
                delta.sum = max(0.0, sk.sum - prev[1])
                deltas[site] = delta.to_dict()
                s._persisted[site] = (list(sk.buckets), sk.sum)
        for site, digest in deltas.items():
            adapt.record_site_tail(site, digest)
        return len(deltas)
    except Exception as e:
        logger.debug("persist_site_tails failed: %s", e)
        return 0


# ---------------------------------------------------------------------------
# grading: the /api/health verdicts (reused offline by dtrace --health)
# ---------------------------------------------------------------------------

# grading thresholds (yellow, red) — evidence ships with every verdict
# so an operator sees the number AND the bar it crossed
THRESHOLDS = {
    "fetch_p99_ms": (250.0, 1000.0),
    "dcn_p99_ms": (500.0, 2000.0),
    "wave_p99_ms": (5000.0, 30000.0),
    "spill_p99_ms": (500.0, 5000.0),
    "error_rate": (0.01, 0.10),
    # per-tenant SLO burn (service.SLO_TARGET's error budget)
    "slo_burn": (1.0, 2.0),
}


def _grade_of(value, yellow, red):
    if value is None:
        return "green"
    if value >= red:
        return "red"
    if value >= yellow:
        return "yellow"
    return "green"


def _worst(*grades):
    for g in ("red", "yellow"):
        if g in grades:
            return g
    return "green"


def grade(site_digests, rates, tenants=None, counters=None,
          ledger_data=None):
    """Grade each subsystem green/yellow/red WITH the evidence (tail
    ms, rates, thresholds) attached.  Pure function of its inputs so
    the offline twin (tools/dtrace --health) and the live endpoint
    compute identical verdicts from identical data.

    `ledger_data` (ISSUE 15): {"top_programs", "top_tenants",
    "conservation"} from the resource attribution plane — top-k
    consumers attach as evidence so a yellow verdict NAMES its likely
    consumer, and the conservation check grades as its own
    `attribution` subsystem."""
    rates = rates or {}
    counters = counters or {}
    sites = summarize_sites(site_digests or {})
    out = {}

    def tail(prefix, field="p99_ms"):
        vals = [(s, d[field]) for s, d in sites.items()
                if s.startswith(prefix) and field in d]
        if not vals:
            return None, None
        return max(vals, key=lambda kv: kv[1])

    # shuffle fetch: worst per-peer p99 + failure rate over fetches
    fy, fr = THRESHOLDS["fetch_p99_ms"]
    site, p99 = tail("fetch.bucket")
    fetches = sum(d["n"] for s, d in sites.items()
                  if s.startswith("fetch.bucket"))
    # an exhausted fetch shows up BOTH as an error-carrying span
    # (fetch.error) and a flight event (fetch.failed) — take the max,
    # not the sum, so one failure isn't graded twice
    fails = max(rates.get("fetch.error", 0),
                rates.get("fetch.failed", 0))
    fail_rate = fails / fetches if fetches else 0.0
    ey, er = THRESHOLDS["error_rate"]
    out["shuffle_fetch"] = {
        "grade": _worst(_grade_of(p99, fy, fr),
                        _grade_of(fail_rate if fetches else None,
                                  ey, er)),
        "evidence": {"worst_site": site, "p99_ms": p99,
                     "fetches": fetches, "failures": fails,
                     "failure_rate": round(fail_rate, 4),
                     "thresholds": {"p99_ms": [fy, fr],
                                    "failure_rate": [ey, er]}}}
    # dcn / bulk plane
    site, p99 = tail("dcn.")
    dcn_fails = rates.get("dcn.error", 0) \
        + rates.get("dcn.bulk.failed", 0)
    dcn_n = sum(d["n"] for s, d in sites.items()
                if s.startswith("dcn."))
    dcn_rate = dcn_fails / dcn_n if dcn_n else 0.0
    dy, dr = THRESHOLDS["dcn_p99_ms"]
    out["dcn"] = {
        "grade": _worst(_grade_of(p99, dy, dr),
                        _grade_of(dcn_rate if dcn_n else None,
                                  ey, er)),
        "evidence": {"worst_site": site, "p99_ms": p99,
                     "transfers": dcn_n, "failures": dcn_fails,
                     "failure_rate": round(dcn_rate, 4),
                     "thresholds": {"p99_ms": [dy, dr],
                                    "failure_rate": [ey, er]}}}
    # coding: decode failures vs decode activity
    repairs = rates.get("decode.repair", 0) \
        + rates.get("decode.straggler_win", 0)
    dfails = rates.get("decode.decode_failures", 0)
    decodes = repairs + dfails
    drate = dfails / decodes if decodes else 0.0
    out["coding"] = {
        "grade": _grade_of(drate if decodes else None, ey, er),
        "evidence": {"repairs": repairs, "decode_failures": dfails,
                     "failure_rate": round(drate, 4),
                     "thresholds": {"failure_rate": [ey, er]}}}
    # executor: wave tail + degrade events
    site, p99 = tail("wave:")
    wy, wr = THRESHOLDS["wave_p99_ms"]
    degrades = rates.get("stage.degrade", 0)
    out["executor"] = {
        "grade": _worst(_grade_of(p99, wy, wr),
                        "yellow" if degrades else "green"),
        "evidence": {"worst_wave_sig": site, "wave_p99_ms": p99,
                     "compiles": rates.get("compile", 0),
                     "degrades": degrades,
                     "thresholds": {"wave_p99_ms": [wy, wr]}}}
    if ledger_data and ledger_data.get("top_programs"):
        # a yellow/red executor verdict should NAME its likely
        # consumer (ISSUE 15 satellite): the heaviest programs by
        # attributed device-seconds ride the evidence
        out["executor"]["evidence"]["top_programs"] = \
            ledger_data["top_programs"]
    # spill I/O
    site, p99 = tail("spill.")
    sy, sr = THRESHOLDS["spill_p99_ms"]
    out["spill"] = {
        "grade": _grade_of(p99, sy, sr),
        "evidence": {"worst_site": site, "p99_ms": p99,
                     "thresholds": {"p99_ms": [sy, sr]}}}
    # scheduler: recovery counters + aborts.  One aborted job emits
    # BOTH a job span with state=aborted and a job.abort flight event
    # — max, not sum, so the evidence reports the true count
    aborts = max(rates.get("job.abort", 0),
                 rates.get("job.aborted", 0))
    resubmits = int(counters.get("resubmits", 0) or 0)
    out["scheduler"] = {
        "grade": _worst("red" if aborts else "green",
                        "yellow" if resubmits else "green"),
        "evidence": {"aborts": aborts, "resubmits": resubmits,
                     "retries": int(counters.get("retries", 0) or 0),
                     "fetch_failed": int(counters.get("fetch_failed",
                                                      0) or 0)}}
    # crash-consistent control plane (ISSUE 20): journal + peer-lease
    # evidence.  A refused journal file is red — completed work exists
    # on disk that this process cannot replay (schema newer than it
    # understands).  Lease expiries / suspect peers / skipped frames
    # are yellow: recovery WORKED, but a peer died or a frame tore and
    # an operator should know.
    jstats = lease = None
    try:
        from dpark_tpu import journal as _journal
        jstats = _journal.stats()
    except Exception:
        jstats = None
    try:
        from dpark_tpu import dcn as _dcn
        lease = _dcn.liveness_stats()
    except Exception:
        lease = None
    if jstats is not None or lease is not None:
        jc = (jstats or {}).get("counters") or {}
        lc = (lease or {}).get("counters") or {}
        if int(jc.get("refused_files", 0) or 0):
            g = "red"
        elif int(jc.get("skipped_frames", 0) or 0) \
                or int(lc.get("lease_expiries", 0) or 0) \
                or (lease or {}).get("suspect"):
            g = "yellow"
        else:
            g = "green"
        out["recovery"] = {
            "grade": g,
            "evidence": {
                "journal": jstats, "liveness": lease,
                "resumed_stages": int(counters.get("resumed_stages",
                                                   0) or 0)}}
    # per-tenant SLO (only when a service with declared SLOs is live)
    if tenants:
        by, br = THRESHOLDS["slo_burn"]
        worst = "green"
        for t in tenants.values():
            burn = max((t.get("burn") or {}).values() or [0.0])
            worst = _worst(worst, _grade_of(burn, by, br))
        out["service_slo"] = {
            "grade": worst,
            "evidence": {"tenants": tenants,
                         "thresholds": {"burn": [by, br]}}}
        if ledger_data and ledger_data.get("top_tenants"):
            # who is consuming the shared mesh (ISSUE 15): the
            # heaviest tenants by HBM byte-seconds ride the SLO
            # evidence so a burning tenant's verdict names the
            # neighbor crowding it
            out["service_slo"]["evidence"]["top_tenants"] = \
                ledger_data["top_tenants"]
    if ledger_data and ledger_data.get("conservation") is not None:
        # the conservation check (ISSUE 15 acceptance): attributed
        # device-seconds must reconcile with measured mesh busy time
        # — a shortfall means untracked consumption the quota/
        # preemption work (ROADMAP item 3) could not bill
        cons = ledger_data["conservation"]
        ok = cons.get("ok")
        ev = dict(cons)
        if ledger_data.get("top_programs"):
            ev["top_programs"] = ledger_data["top_programs"]
        if ledger_data.get("top_tenants"):
            ev["top_tenants"] = ledger_data["top_tenants"]
        out["attribution"] = {
            "grade": "green" if ok in (True, None) else "yellow",
            "evidence": ev}
    return out


def api_health(scheduler=None):
    """The /api/health payload: merged site summaries, rates, graded
    subsystems with evidence, per-tenant SLO stats, per-stage fetch
    p99s, and live pressure gauges — built from defensive snapshots
    (a scrape racing a running job returns valid JSON, never an
    error)."""
    s = _SINK
    snap = snapshot()
    digests = merged_site_digests()
    counters = {}
    tenants = None
    try:
        if scheduler is not None \
                and hasattr(scheduler, "metrics_snapshot"):
            counters = scheduler.metrics_snapshot().get("counters",
                                                        {}) or {}
    except Exception:
        counters = {}
    try:
        svc = getattr(scheduler, "_service", None) \
            if scheduler is not None else None
        if svc is None and scheduler is not None:
            # a ClientScheduler facade: reach through to the server
            svc = getattr(getattr(scheduler, "server", None),
                          "scheduler", None)
            svc = getattr(svc, "_service", None) \
                if svc is not None else None
        if svc is not None:
            tenants = svc.tenant_slo_stats() or None
    except Exception:
        tenants = None
    ledger_data = None
    try:
        from dpark_tpu import ledger
        if ledger.active():
            # one snapshot + one merged-totals pass per scrape (the
            # UI polls this endpoint; tenant_totals re-reads the
            # worker sidecar files)
            lsnap = ledger.snapshot()
            ltotals = ledger.tenant_totals()
            ledger_data = {
                "top_programs": ledger.top_programs(snap=lsnap),
                "top_tenants": ledger.top_tenants(totals=ltotals),
                "conservation": ledger.conservation(scheduler,
                                                    snap=lsnap),
            }
    except Exception:
        ledger_data = None
    out = {
        "mode": mode(),
        "sites": summarize_sites(digests),
        "rates": snap.get("rates", {}),
        "gauges": dict(snap.get("gauges", {})),
        "subsystems": grade(digests, snap.get("rates"), tenants,
                            counters, ledger_data=ledger_data),
        "stage_fetch": {},
        "folded": snap.get("folded", 0),
    }
    if tenants is not None:
        out["tenants"] = tenants
    try:
        # straggler-adaptive coded shuffle (ISSUE 19): per-peer decode
        # outcomes next to the coding grade, and the chosen-(k,m)
        # history as executor evidence — the operator's answer to
        # "which peer made the policy escalate, and to what".
        # Evidence only; grades are unchanged.
        from dpark_tpu import coding
        per_peer = coding.stats().get("per_peer") or {}
        if per_peer and "coding" in out["subsystems"]:
            out["subsystems"]["coding"]["evidence"]["by_peer"] = \
                per_peer
        choices = coding.code_history()
        if choices and "executor" in out["subsystems"]:
            out["subsystems"]["executor"]["evidence"][
                "code_choices"] = choices
    except Exception:
        pass
    try:
        # shared-computation result-cache counters (ISSUE 18)
        from dpark_tpu import resultcache
        rc = resultcache.stats()
        if rc is not None:
            out["result_cache"] = rc
    except Exception:
        pass
    try:
        # crash-journal counters (ISSUE 20) for the UI topline
        from dpark_tpu import journal
        js = journal.stats()
        if js is not None:
            out["journal"] = js
    except Exception:
        pass
    try:
        # peer-lease liveness (ISSUE 20): suspect set + expiry counts
        from dpark_tpu import dcn
        lv = dcn.liveness_stats()
        if lv is not None:
            out["liveness"] = lv
    except Exception:
        pass
    if s is not None:
        with s.lock:
            out["stage_fetch"] = {
                "%s:%s" % k: sk.summary()
                for k, sk in s.stage_fetch.items()}
    try:
        ex = getattr(scheduler, "executor", None) \
            if scheduler is not None else None
        if ex is not None:
            out["gauges"]["hbm_store_bytes"] = \
                int(getattr(ex, "_store_bytes", 0))
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# consumer 3: the flight recorder
# ---------------------------------------------------------------------------

_flight_lock = locks.named_lock("health.flight")
_flight_dumps = 0
_sigusr2_installed = False


def flight_dir():
    return getattr(conf, "DPARK_FLIGHT_DIR", "") or ""


def flight_dump(reason, scheduler=None, record=None):
    """Write one crc-framed post-mortem snapshot under
    DPARK_FLIGHT_DIR: header, the always-armed warning ring plus the
    trace ring tail, the health sketches, the scheduler's recovery
    summary, and the adapt decision log.  Returns the path, or None
    when the dir is unset / the per-process dump cap is hit / the
    write fails (best-effort, never raises)."""
    global _flight_dumps
    d = flight_dir()
    if not d:
        return None
    try:
        from dpark_tpu import trace
        from dpark_tpu.utils import frame_jsonl
        with _flight_lock:
            cap = int(getattr(conf, "FLIGHT_MAX_DUMPS", 16) or 0)
            if cap and _flight_dumps >= cap:
                return None
            _flight_dumps += 1
            seq = _flight_dumps
        os.makedirs(d, exist_ok=True)
        host = socket.gethostname()
        pid = os.getpid()
        path = os.path.join(d, "flight-%s-%d-%d.jsonl"
                            % (host, pid, seq))
        recs = [{"kind": "flight.header", "reason": str(reason),
                 "ts": round(time.time(), 6), "host": host,
                 "pid": pid, "run": trace.run_id()}]
        ring = trace.flight_snapshot()
        seen = {id(r) for r in ring}
        # the trace ring's tail rides along when a plane is up — the
        # immediate context around the warning events
        for r in trace.snapshot()[-256:]:
            if id(r) not in seen:
                ring.append(r)
        ring.sort(key=lambda r: r.get("ts", 0.0))
        recs.extend({"kind": "flight.event", "rec": r} for r in ring)
        recs.append({"kind": "flight.health", "snapshot": snapshot()})
        try:
            from dpark_tpu import ledger
            if ledger.active():
                # resource attribution rides the post-mortem (ISSUE
                # 15): who held the mesh when things went wrong
                recs.append({"kind": "flight.ledger",
                             "snapshot": ledger.snapshot()})
        except Exception:
            pass
        if record is not None:
            try:
                recs.append({"kind": "flight.job",
                             "record": json.loads(json.dumps(
                                 record, default=str))})
            except Exception:
                pass
        try:
            if scheduler is not None \
                    and hasattr(scheduler, "recovery_summary"):
                recs.append({"kind": "flight.recovery",
                             "summary": scheduler.recovery_summary()})
        except Exception:
            pass
        try:
            from dpark_tpu import adapt
            recs.append({"kind": "flight.adapt",
                         "summary": adapt.summary()})
        except Exception:
            pass
        blob = b""
        for rec in recs:
            try:
                blob += frame_jsonl(rec)
            except Exception:
                continue             # one unserializable row, not all
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
        logger.warning("flight recorder dumped %d records -> %s "
                       "(reason: %s)", len(recs), path, reason)
        return path
    except Exception as e:
        logger.debug("flight dump failed: %s", e)
        return None


def load_flight(path):
    """Parse one flight dump back into its records (corrupt lines
    skip — the shared crc-framed contract).  Returns the record
    list."""
    from dpark_tpu.utils import unframe_jsonl
    with open(path, "rb") as f:
        raw = f.read()
    return unframe_jsonl(raw)[0]


def install_sigusr2():
    """Arm SIGUSR2 -> flight_dump("sigusr2") (main thread only; a
    no-op anywhere signals cannot be installed).  Called lazily the
    first time a scheduler finishes a job with DPARK_FLIGHT_DIR
    set — `kill -USR2 <pid>` then snapshots a LIVE process."""
    global _sigusr2_installed
    if _sigusr2_installed or not flight_dir():
        return False
    try:
        import signal

        def _on_usr2(signum, frame):
            flight_dump("sigusr2")

        signal.signal(signal.SIGUSR2, _on_usr2)
        _sigusr2_installed = True
        return True
    except (ValueError, OSError, AttributeError):
        # not the main thread / platform without SIGUSR2
        return False


# ---------------------------------------------------------------------------
# scheduler hooks (one call per job; cheap checks first)
# ---------------------------------------------------------------------------

def job_finished(scheduler, record):
    """Called from the scheduler's run_job finalizer: SLO accounting
    for service jobs, flight dump on abort, throttled site-tail
    persistence, SIGUSR2 arming.  Best-effort — never raises into
    the job path."""
    try:
        svc = getattr(scheduler, "_service", None)
        if svc is not None:
            try:
                svc.note_job_done(record)
            except Exception as e:
                logger.debug("slo accounting failed: %s", e)
        if record.get("state") == "aborted":
            from dpark_tpu import trace
            trace.flight("job.abort", "sched", job=record.get("id"),
                         scope=record.get("scope"),
                         seconds=record.get("seconds"))
            flight_dump("job-abort:%s" % record.get("id"),
                        scheduler=scheduler, record=record)
        if flight_dir():
            install_sigusr2()
        if _SINK is not None:
            persist_site_tails()
    except Exception as e:
        logger.debug("health job_finished hook failed: %s", e)


def _init_from_conf():
    m = str(getattr(conf, "DPARK_HEALTH", "on") or "on").lower()
    if m == "on":
        configure("on")


_init_from_conf()
