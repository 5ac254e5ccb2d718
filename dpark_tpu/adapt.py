"""Adaptive execution (ISSUE 7): a persistent feedback store + cost
model that closes the loop from recorded stats to plan choices.

PRs 1-6 built rich telemetry — per-stage phase tables, pipeline idle
fractions, fallback/degrade reasons, decode counters — but every plan
decision was static at trace time, so each job re-discovered the same
budgets and re-paid the same mispredictions.  This module persists
per-(program, shape class) observations ACROSS jobs and feeds four
decision points ("Partial Partial Aggregates" is the theory anchor for
pricing aggregation choices by observed cost):

  1. wave budget     conf.stream_chunk_rows seeds from the last-known
                     -good budget of the (row-width) class — recorded
                     by the OOM degradation ladder — instead of
                     re-deriving HBM/16 and re-walking the halving
                     ladder every job.
  2. device vs host  the tpu scheduler prices the array path against
                     the object path from OBSERVED per-program ms and
                     declines the device when the host is recorded
                     cheaper (`adapt_reason` per stage, the cost-model
                     sibling of fallback_reason/degrade_reason).
  3. partition count a dominant key group (from the bucket histograms
                     SegMapOp already computes) widens the reduce side
                     of the next run of that program.
  4. map-side combine the groupByKey aggregate rewrite is priced from
                     the observed combine ratio (distinct keys /
                     rows): a ratio near 1 means pre-aggregation buys
                     nothing, so the rewrite is declined and the
                     device SegAggOp serves the chain — the PR-1
                     linter's `group-agg` advisory as an actual
                     optimizer choice.

Modes (conf.DPARK_ADAPT):
  off      no reads, no writes, zero hot-path cost beyond a flag check
  observe  record observations (and log would-be choices, applied:
           false) but NEVER steer — bit-identical to off; the CI-safe
           default
  on       record AND steer

Store: JSON-lines under conf.DPARK_ADAPT_DIR (one ``stats.jsonl``).
Each line is framed ``<crc32 hex> <json>`` with the same checksum the
spill runs use (shuffle.spill_crc), appended with a single O_APPEND
write so concurrent processes interleave whole lines; corrupt or
truncated lines are skipped at load (never an error).  Reset by
deleting the directory (``rm -rf $DPARK_ADAPT_DIR``) or via
``adapt.configure(...)`` / ``adapt.reset_store()``.

Every public entry point is guarded: adaptation must never break a
job, so failures log at debug and fall back to the static behavior.
"""

import json
import os
import threading

from dpark_tpu import conf
from dpark_tpu.utils.log import get_logger

logger = get_logger("adapt")

MODES = ("off", "observe", "on")

STORE_FILE = "stats.jsonl"

# decisions kept in the process-global log (older entries age out; the
# absolute position survives trimming so per-job deltas stay correct)
_LOG_CAP = 512
# exponential-moving-average weight for ms / ratio observations
_EMA = 0.5

_lock = threading.RLock()
_mode = None                  # resolved mode, or None = read conf lazily
_dir = None                   # resolved store dir, or None = read conf
_loaded = False
_agg = {"wave_budget": {}, "stage": {}, "skew": {}, "combine": {},
        "pane": {}, "site": {}, "reuse": {}, "xch": {},
        "replan": {}}
_counters = {"store_hits": 0, "store_misses": 0, "steered": 0,
             "recorded": 0, "skipped_lines": 0}
_decisions = []
_decisions_base = 0           # absolute position of _decisions[0]
_logged = set()               # (point, key, choice) de-dup for the log
_pending = {}                 # stage key -> decision awaiting observed ms
# per-thread job attribution (ISSUE 9): the resident job server's
# slot threads set the job id they are executing for, so a decision
# taken during a CONCURRENT stage lands in the right job's record
_job_tls = threading.local()


def set_current_job(job):
    """Tag decisions taken on THIS thread with a job id (None clears).
    Only the resident service sets this; single-job schedulers leave
    decisions untagged, and decisions_since(pos) returns them all —
    the pre-service behavior, bit for bit."""
    _job_tls.job = job


def _current_job():
    return getattr(_job_tls, "job", None)


# ---------------------------------------------------------------------------
# configuration / lifecycle
# ---------------------------------------------------------------------------

def mode():
    """The resolved mode (validates conf.DPARK_ADAPT on first read)."""
    global _mode
    if _mode is None:
        m = str(getattr(conf, "DPARK_ADAPT", "observe")).lower()
        if m not in MODES:
            raise ValueError(
                "DPARK_ADAPT=%r (expected off|observe|on)" % m)
        _mode = m
    return _mode


def enabled():
    """True when observations should be recorded (observe or on)."""
    return mode() != "off"


def steering():
    """True only when recorded stats may CHANGE plan choices."""
    return mode() == "on"


def store_dir():
    global _dir
    if _dir is None:
        _dir = getattr(conf, "DPARK_ADAPT_DIR", None) or os.path.join(
            conf.DPARK_WORK_DIR, "adapt")
    return _dir


def configure(mode=None, store_dir=None):
    """Re-point the adaptive plane (tests/benchmarks): resets ALL
    in-memory state (aggregates, counters, decision log) and resolves
    mode/dir from the arguments, falling back to conf for whichever is
    None.  The on-disk store is untouched — use reset_store() to wipe
    it."""
    global _mode, _dir, _loaded, _decisions_base
    with _lock:
        _mode = None
        _dir = None
        _loaded = False
        for d in _agg.values():
            d.clear()
        for k in _counters:
            _counters[k] = 0
        _decisions.clear()
        _decisions_base = 0
        _logged.clear()
        _pending.clear()
        if mode is not None:
            if str(mode).lower() not in MODES:
                raise ValueError(
                    "adapt mode %r (expected off|observe|on)" % mode)
            _mode = str(mode).lower()
        if store_dir is not None:
            _dir = str(store_dir)


def reset_store():
    """Delete the on-disk store (the documented reset) and the
    in-memory aggregates, keeping the configured mode/dir."""
    with _lock:
        path = _store_path()
        try:
            os.unlink(path)
        except OSError:
            pass
        global _loaded
        _loaded = False
        for d in _agg.values():
            d.clear()


# ---------------------------------------------------------------------------
# the store: crc-framed JSON lines, process-safe append
# ---------------------------------------------------------------------------

def _crc(blob):
    from dpark_tpu.shuffle import spill_crc
    return spill_crc(blob)


def _store_path():
    return os.path.join(store_dir(), STORE_FILE)


def _ensure_loaded():
    """Load the store file into the in-memory aggregates once per
    process (records apply in file order = chronological order)."""
    global _loaded
    if _loaded:
        return
    with _lock:
        if _loaded:
            return
        _loaded = True               # even when the file is absent
        path = _store_path()
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return
        from dpark_tpu.utils import unframe_jsonl
        recs, skipped = unframe_jsonl(raw)
        _counters["skipped_lines"] += skipped
        for rec in recs:
            try:
                _apply(rec)
            except Exception:
                # foreign / malformed record: skip, never fail
                _counters["skipped_lines"] += 1
        cap = int(getattr(conf, "ADAPT_STORE_MAX_BYTES", 0) or 0)
        if cap and len(raw) > cap:
            _compact_locked(path)


def _compact_locked(path):
    """Rewrite the store as its folded aggregates — one line per key —
    so the append-only file stays bounded (conf.ADAPT_STORE_MAX_BYTES).
    Best-effort tmp+rename: lines another process appends during the
    rewrite are lost, which is acceptable for advisory statistics (the
    EMA sample counts also reset to the compacted snapshot)."""
    recs = []
    for key, ent in _agg["wave_budget"].items():
        for slot, ok in (("good", True), ("bad", False)):
            if ent.get(slot):
                recs.append({"k": "wb", "key": key,
                             "budget": int(ent[slot]), "ok": ok,
                             "src": "compact"})
    for key, ent in _agg["stage"].items():
        for p in ("device", "host"):
            if ent.get(p + "_ms") is not None:
                recs.append({"k": "stage", "key": key, "path": p,
                             "ms": round(ent[p + "_ms"], 2)})
        for _ in range(min(int(ent.get("device_errors", 0)), 3)):
            recs.append({"k": "stage", "key": key, "path": "device",
                         "error": True})
    for key, ent in _agg["skew"].items():
        recs.append(dict(ent, k="skew", key=key))
    for key, ent in _agg["combine"].items():
        if ent.get("ratio") is not None:
            recs.append({"k": "combine", "key": key,
                         "rows_in": 1000000,
                         "rows_out": int(ent["ratio"] * 1000000)})
    for key, ent in _agg["pane"].items():
        for mode in ("tree", "flat", "inv"):
            if ent.get(mode + "_ms") is not None:
                recs.append({"k": "pane", "key": key, "mode": mode,
                             "ms": round(ent[mode + "_ms"], 2),
                             "w": int(ent.get("w", 0))})
    for key, ent in _agg["site"].items():
        recs.append({"k": "site", "key": key, "digest": dict(ent)})
    for key, ent in _agg["reuse"].items():
        recs.append(dict(ent, k="reuse", key=key))
    for key, ent in _agg["xch"].items():
        rec = {"k": "xch", "key": key,
               "peers": {p: dict(c)
                         for p, c in ent.get("peers", {}).items()}}
        if ent.get("fetch_ms") is not None:
            rec["fetch_ms"] = round(float(ent["fetch_ms"]), 2)
        recs.append(rec)
    for key, ent in _agg["replan"].items():
        recs.append(dict(ent, k="replan", key=key))
    try:
        from dpark_tpu.utils import frame_jsonl
        tmp = path + ".compact.%d" % os.getpid()
        with open(tmp, "wb") as f:
            f.write(b"".join(frame_jsonl(rec) for rec in recs))
        os.replace(tmp, path)
        logger.debug("adapt store compacted to %d records", len(recs))
    except Exception as e:
        logger.debug("adapt store compaction failed: %s", e)


def _append(rec):
    """Persist one observation: update the in-memory aggregates and
    append one crc-framed line with a single O_APPEND write (whole
    lines interleave safely across processes)."""
    _ensure_loaded()
    with _lock:
        _apply(rec)
        _counters["recorded"] += 1
        try:
            from dpark_tpu.utils import frame_jsonl
            line = frame_jsonl(rec)
            os.makedirs(store_dir(), exist_ok=True)
            fd = os.open(_store_path(),
                         os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
            try:
                os.write(fd, line)
            finally:
                os.close(fd)
        except Exception as e:
            logger.debug("adapt store append failed: %s", e)


def _apply(rec):
    """Fold one record into the in-memory aggregates."""
    kind = rec.get("k")
    key = rec.get("key")
    if not key:
        return
    if kind == "wb":
        ent = _agg["wave_budget"].setdefault(
            key, {"good": None, "bad": None})
        budget = int(rec.get("budget", 0))
        if budget > 0:
            ent["good" if rec.get("ok") else "bad"] = budget
    elif kind == "stage":
        ent = _agg["stage"].setdefault(
            key, {"device_ms": None, "host_ms": None,
                  "device_n": 0, "host_n": 0, "device_errors": 0})
        path = rec.get("path")
        if rec.get("error"):
            ent["device_errors"] += 1
        elif path in ("device", "host"):
            ms = float(rec.get("ms", 0.0))
            cur = ent[path + "_ms"]
            ent[path + "_ms"] = ms if cur is None \
                else cur * (1 - _EMA) + ms * _EMA
            ent[path + "_n"] += 1
    elif kind == "skew":
        _agg["skew"][key] = {
            "rows": int(rec.get("rows", 0)),
            "groups": int(rec.get("groups", 0)),
            "max_group": int(rec.get("max_group", 0)),
            "parts": int(rec.get("parts", 0))}
    elif kind == "combine":
        rows_in = max(1, int(rec.get("rows_in", 1)))
        ratio = min(1.0, int(rec.get("rows_out", 0)) / rows_in)
        ent = _agg["combine"].setdefault(key, {"ratio": None, "n": 0})
        cur = ent["ratio"]
        ent["ratio"] = ratio if cur is None \
            else cur * (1 - _EMA) + ratio * _EMA
        ent["n"] += 1
    elif kind == "site":
        # per-site latency-tail digest delta (health plane, ISSUE 14):
        # the log-bucketed sketch shape health.Sketch.to_dict writes —
        # folding is bucket-wise addition, so deltas from any number
        # of processes/persists accumulate into one honest histogram
        # (the ROADMAP item 5 handoff: straggler-adaptive coding will
        # price (k, m) per exchange from these)
        from dpark_tpu import health
        _agg["site"][key] = health.merge_digests(
            _agg["site"].get(key), rec.get("digest"))
    elif kind == "reuse":
        # result-cache hit-rate profile (ISSUE 18), keyed by the
        # cache entry key: pure running counts (compaction folds them
        # into one line, so reload stays honest).  The disk tier's
        # boot preload ranks entries by these hits.
        ent = _agg["reuse"].setdefault(
            key, {"hits": 0, "misses": 0, "partials": 0})
        for k in ("hits", "misses", "partials"):
            ent[k] = int(ent.get(k, 0)) + int(rec.get(k, 0) or 0)
    elif kind == "xch":
        # per-exchange peer profile (ISSUE 19): which peers served one
        # shuffle call site, with per-peer fetch counts and decode
        # outcomes accumulated across runs — the straggler-adaptive
        # code policy joins these peers against the "site" tail
        # sketches to price (k, m) for the NEXT run of this exchange
        ent = _agg["xch"].setdefault(key, {"peers": {}, "n": 0})
        for p, counts in (rec.get("peers") or {}).items():
            pc = ent["peers"].setdefault(str(p), {})
            for ck, cv in (counts or {}).items():
                try:
                    pc[ck] = int(pc.get(ck, 0)) + int(cv)
                except (TypeError, ValueError):
                    pass
        ent["n"] = int(ent.get("n", 0)) + 1
        if rec.get("fetch_ms") is not None:
            ms = float(rec["fetch_ms"])
            cur = ent.get("fetch_ms")
            ent["fetch_ms"] = ms if cur is None \
                else cur * (1 - _EMA) + ms * _EMA
    elif kind == "replan":
        # mid-job re-plan outcome (ISSUE 19): the salted re-split the
        # scheduler performed (or, in observe mode, would have) for a
        # shuffle call site — latest-wins, consumed by suggest_salt()
        # so the next run of the shape salts at PLAN time instead of
        # paying the mid-job re-split again
        _agg["replan"][key] = {
            "parts": int(rec.get("parts", 0)),
            "salt": int(rec.get("salt", 0)),
            "frac": float(rec.get("frac", 0.0))}
    elif kind == "pane":
        # per-(stream signature) windowed-emit tick cost by pane
        # strategy ("tree" | "flat" | "inv"): the split-point pricing
        # substrate (ISSUE 10)
        mode = rec.get("mode")
        if mode not in ("tree", "flat", "inv", "pane"):
            return
        ent = _agg["pane"].setdefault(key, {"w": 0})
        slot = mode + "_ms"
        ms = float(rec.get("ms", 0.0))
        cur = ent.get(slot)
        ent[slot] = ms if cur is None \
            else cur * (1 - _EMA) + ms * _EMA
        ent["w"] = int(rec.get("w", ent.get("w", 0)))


# ---------------------------------------------------------------------------
# decision log (rides job records as record["adapt"] and the bench JSON)
# ---------------------------------------------------------------------------

def _decide(point, key, choice, reason, predicted_ms=None,
            applied=True):
    """Log one (de-duplicated) decision; returns the dict so callers
    can later attach the observed outcome."""
    job = _current_job()
    with _lock:
        # the job id is part of the de-dup identity: two concurrent
        # jobs taking the same choice must EACH log it (each record
        # filters the log by its own id — ISSUE 9)
        dedup = (point, str(key), str(choice), bool(applied), job)
        if dedup in _logged:
            for d in reversed(_decisions):
                if (d["point"], str(d["key"]), str(d["choice"]),
                        d["applied"], d.get("job")) == dedup:
                    return d
            # aged out of the log: fall through and re-log
        _logged.add(dedup)
        d = {"point": point, "key": str(key), "choice": choice,
             "reason": reason, "applied": bool(applied)}
        if job is not None:
            d["job"] = job
        if predicted_ms is not None:
            d["predicted_ms"] = round(float(predicted_ms), 2)
        from dpark_tpu import trace
        if trace._PLANE is not None:
            # trace-plane twin (ISSUE 8): cost-model choices land on
            # the timeline next to the stages they steered
            trace.event("adapt.decision", "adapt", point=point,
                        choice=str(choice), applied=bool(applied))
        _decisions.append(d)
        if applied:
            _counters["steered"] += 1
        global _decisions_base
        if len(_decisions) > _LOG_CAP:
            drop = len(_decisions) - _LOG_CAP
            del _decisions[:drop]
            _decisions_base += drop
        return d


def log_position():
    with _lock:
        return _decisions_base + len(_decisions)


def begin_job():
    """Mark a job boundary: returns the current log position AND
    resets the decision de-dup epoch, so a job that takes the same
    steered choice as its predecessor still logs it (its
    record["adapt"] delta and the `steered` counter would otherwise
    silently undercount repeat steering).  Within one job the de-dup
    stands — a streamed stage consulting the store once per wave logs
    one decision, not hundreds.

    Only UNTAGGED entries clear: job-TAGGED de-dup tuples (resident
    service, ISSUE 9) already scope per job via the id in the tuple,
    and clearing them here would wipe a CONCURRENT job's epoch — its
    streamed stage would then re-log the same decision every wave.
    Tagged entries for long-gone jobs are pruned by rebuilding from
    the capped decision log once the set outgrows it."""
    with _lock:
        stale = {d for d in _logged if d[-1] is None}
        _logged.difference_update(stale)
        if len(_logged) > 4 * _LOG_CAP:
            live = {(d["point"], str(d["key"]), str(d["choice"]),
                     d["applied"], d.get("job")) for d in _decisions}
            _logged.intersection_update(live)
        return _decisions_base + len(_decisions)


def decisions_since(pos, job=None):
    """Decisions logged at or after `pos`.  With `job` set (resident
    service, ISSUE 9), only decisions tagged with that job id return —
    untagged decisions (made outside any slot thread) stay visible to
    every job, matching the single-job behavior."""
    with _lock:
        start = max(0, int(pos) - _decisions_base)
        out = [dict(d) for d in _decisions[start:]]
    if job is not None:
        out = [d for d in out if d.get("job") in (None, job)]
    return out


def summary():
    """The `adapt` section for bench artifacts / job records: mode,
    store location, hit/steer counters, persisted site-tail keys,
    recent decisions with predicted-vs-observed ms."""
    if enabled():
        _ensure_loaded()        # a fresh process reports STORED sites
    with _lock:
        return {"mode": mode(), "store": _store_path(),
                "store_hits": _counters["store_hits"],
                "store_misses": _counters["store_misses"],
                "steered": _counters["steered"],
                "recorded": _counters["recorded"],
                # per-site latency-tail keys the health plane has
                # persisted (ISSUE 14): the item-5 handoff's proof a
                # fresh process sees what earlier ones observed
                "sites": sorted(_agg["site"]),
                "decisions": [dict(d) for d in _decisions[-32:]]}


# ---------------------------------------------------------------------------
# stable cross-process identity for plan program keys
# ---------------------------------------------------------------------------

def stable_key(obj):
    """Hash an arbitrary program-key structure to a short id that is
    STABLE ACROSS PROCESSES: code objects hash by bytecode + consts
    (fuse.fn_key carries live code objects whose repr embeds a memory
    address), functions by their code, bytes by digest; the generic
    fallback strips ``at 0x...`` addresses from reprs."""
    import hashlib
    return hashlib.sha1(
        _stable_repr(obj).encode("utf-8", "replace")).hexdigest()[:16]


def _stable_repr(o, depth=0):
    import hashlib
    import re
    import types
    if depth > 12:
        return "..."
    if isinstance(o, types.CodeType):
        return "code(%s,%s,%s)" % (
            o.co_name, hashlib.sha1(o.co_code).hexdigest()[:12],
            _stable_repr(o.co_consts, depth + 1))
    if isinstance(o, types.FunctionType):
        return "fn(%s)" % _stable_repr(o.__code__, depth + 1)
    if isinstance(o, (bytes, bytearray)):
        return "b(%s)" % hashlib.sha1(bytes(o)).hexdigest()[:12]
    if isinstance(o, (tuple, list)):
        return "(%s)" % ",".join(_stable_repr(x, depth + 1) for x in o)
    if isinstance(o, dict):
        return "{%s}" % ",".join(
            "%s:%s" % (_stable_repr(k, depth + 1),
                       _stable_repr(v, depth + 1))
            for k, v in sorted(o.items(), key=lambda kv: repr(kv[0])))
    if isinstance(o, (str, int, float, bool)) or o is None:
        return repr(o)
    return re.sub(r" at 0x[0-9a-f]+", "", repr(o))


# ---------------------------------------------------------------------------
# decision point 1: wave budget (conf.stream_chunk_rows)
# ---------------------------------------------------------------------------

def _wb_key(row_bytes):
    return "rb%d" % int(row_bytes)


def record_wave_budget(row_bytes, budget, ok, source="stream"):
    """Persist the outcome of running (or failing) a wave budget for a
    row-width class.  Known-good budgets seed the next run; a failing
    budget makes the next run start BELOW the rung that OOM'd.
    Identical consecutive outcomes are not re-appended."""
    try:
        if not enabled() or not budget:
            return
        _ensure_loaded()
        key = _wb_key(row_bytes)
        with _lock:
            ent = _agg["wave_budget"].get(key)
            slot = "good" if ok else "bad"
            if ent is not None and ent.get(slot) == int(budget):
                return
        _append({"k": "wb", "key": key, "budget": int(budget),
                 "ok": bool(ok), "src": source})
    except Exception as e:
        logger.debug("record_wave_budget failed: %s", e)


def steer_wave_budget(base, row_bytes):
    """The effective auto wave budget: the store's last-known-good
    budget for this row-width class when it is SMALLER than the
    freshly derived base (a learned budget larger than base never
    applies — base is already the memory-derived ceiling).  With only
    a failing budget on record, start at half that rung.  Never
    steers outside DPARK_ADAPT=on."""
    try:
        if not steering():
            return base
        _ensure_loaded()
        key = _wb_key(row_bytes)
        with _lock:
            ent = _agg["wave_budget"].get(key)
        if ent is None:
            _counters["store_misses"] += 1
            return base
        _counters["store_hits"] += 1
        good, bad = ent.get("good"), ent.get("bad")
        cand = good if good else (max(64, bad // 2) if bad else None)
        if cand is None or cand >= base:
            return base
        _decide("wave_budget", key, cand,
                "seeded wave budget %d rows/device from the store "
                "(last known good for %s; derived base %d)"
                % (cand, key, base))
        return int(cand)
    except Exception as e:
        logger.debug("steer_wave_budget failed: %s", e)
        return base


def wave_budget_row_widths():
    """Row-width classes (ints, bytes/row) with stored budgets — the
    adapt-stale-hint lint rule compares these against the plan's
    actual columnar row width."""
    try:
        if not enabled():
            return set()
        _ensure_loaded()
        with _lock:
            return {int(k[2:]) for k in _agg["wave_budget"]
                    if k.startswith("rb")}
    except Exception:
        return set()


# ---------------------------------------------------------------------------
# decision point 2: device vs object path by predicted cost
# ---------------------------------------------------------------------------

def _stage_key(sig):
    return "%s|%s" % (sig[0], sig[1])


# the object path must beat the device path by this factor of observed
# ms before the cost model declines the array path (ties keep the
# device: its compile cost amortizes across runs)
PATH_MARGIN = 0.8


def choose_path(sig):
    """Cost-model path choice for an analyzable stage: given the plan
    signature (program id, shape class) from fuse.plan_adapt_signature,
    return a decision dict ({"choice": "object"|"device", "reason",
    "predicted_ms"}) when BOTH paths have recorded ms for this program
    class, else None (no history -> static behavior: the array path).
    The host must beat the device by PATH_MARGIN to win —
    ties keep the device (its compile cost amortizes).  Observe mode
    logs the would-be choice (applied: false) and returns None."""
    try:
        if sig is None or not enabled():
            return None
        _ensure_loaded()
        key = _stage_key(sig)
        with _lock:
            ent = _agg["stage"].get(key)
        if ent is None:
            _counters["store_misses"] += 1
            return None
        d_ms, h_ms = ent.get("device_ms"), ent.get("host_ms")
        if d_ms is None or h_ms is None:
            _counters["store_misses"] += 1
            return None
        _counters["store_hits"] += 1
        if h_ms < d_ms * PATH_MARGIN:
            choice, predicted = "object", h_ms
            reason = ("cost model: object path predicted cheaper "
                      "(host ~%.1fms vs device ~%.1fms observed for "
                      "this program class)" % (h_ms, d_ms))
        else:
            choice, predicted = "device", d_ms
            reason = ("cost model: array path confirmed (device "
                      "~%.1fms vs host ~%.1fms observed)"
                      % (d_ms, h_ms))
        if not steering():
            _decide("path", key, choice, reason, predicted_ms=predicted,
                    applied=False)
            return None
        d = _decide("path", key, choice, reason, predicted_ms=predicted)
        with _lock:
            _pending[key] = d
        return dict(d)
    except Exception as e:
        logger.debug("choose_path failed: %s", e)
        return None


def observe_path(sig, path, ms=None, error=False):
    """Record an observed stage run (path = "device" | "host", wall
    ms) for the plan signature, and complete any pending path decision
    with the observed outcome."""
    try:
        if sig is None or not enabled():
            return
        key = _stage_key(sig)
        rec = {"k": "stage", "key": key, "path": path}
        if error:
            rec["error"] = True
        else:
            rec["ms"] = round(float(ms), 2)
        _append(rec)
        with _lock:
            d = _pending.pop(key, None)
            if d is not None and not error:
                d["observed_ms"] = round(float(ms), 2)
    except Exception as e:
        logger.debug("observe_path failed: %s", e)


def stage_history():
    """Copy of the per-program stage aggregates (tests / debugging)."""
    _ensure_loaded()
    with _lock:
        return {k: dict(v) for k, v in _agg["stage"].items()}


# ---------------------------------------------------------------------------
# decision point 3: partition count re-planned on observed skew
# ---------------------------------------------------------------------------

def record_skew(site, rows, groups, max_group, parts):
    """Persist a bucket-histogram observation for a grouping site (the
    segment layout SegMapOp computes anyway): total rows, group count,
    the largest group's approximate size, and the reduce width it ran
    at."""
    try:
        if not enabled() or not site or not rows:
            return
        _append({"k": "skew", "key": str(site), "rows": int(rows),
                 "groups": int(groups), "max_group": int(max_group),
                 "parts": int(parts)})
    except Exception as e:
        logger.debug("record_skew failed: %s", e)


# dominant-group fraction (max group rows / total rows) above which an
# observed histogram counts as skewed, and the widening factor applied
# to the DEFAULT reduce width on the next run of that program
SKEW_FRAC = 0.5
SKEW_WIDEN = 2


def suggest_partitions(site, default_n):
    """Reduce-side width for a combineByKey/groupByKey whose caller
    took the DEFAULT parallelism: when the last recorded histogram for
    this call site shows one dominant key group (max_group/rows >=
    SKEW_FRAC), widen by SKEW_WIDEN so the
    non-dominant keys spread thinner around the hot partition.
    Explicit user numSplits are never overridden (callers only consult
    this on the default path)."""
    try:
        if not enabled() or not site:
            return default_n
        _ensure_loaded()
        with _lock:
            ent = _agg["skew"].get(str(site))
        if ent is None or not ent.get("rows"):
            return default_n
        frac = ent["max_group"] / max(1, ent["rows"])
        if frac < SKEW_FRAC:
            return default_n
        _counters["store_hits"] += 1
        widened = max(default_n + 1, default_n * SKEW_WIDEN)
        reason = ("observed skew at %s: dominant group ~%d of %d rows "
                  "(%.0f%%) — widening the reduce side %d -> %d"
                  % (site, ent["max_group"], ent["rows"], frac * 100,
                     default_n, widened))
        if not steering():
            _decide("partitions", site, widened, reason, applied=False)
            return default_n
        _decide("partitions", site, widened, reason)
        return widened
    except Exception as e:
        logger.debug("suggest_partitions failed: %s", e)
        return default_n


# ---------------------------------------------------------------------------
# decision point 4: map-side combine priced from the combine ratio
# ---------------------------------------------------------------------------

def record_combine_ratio(site, rows_in, rows_out):
    """Persist an observed combine ratio (rows after map-side combine,
    or distinct groups, over input rows) for a grouping/combining call
    site."""
    try:
        if not enabled() or not site or not rows_in:
            return
        _append({"k": "combine", "key": str(site),
                 "rows_in": int(rows_in), "rows_out": int(rows_out)})
    except Exception as e:
        logger.debug("record_combine_ratio failed: %s", e)


# observed combine ratio (distinct keys / rows) above which map-side
# pre-aggregation is priced OFF (nearly every key distinct: the
# combine pass costs a sort and saves no exchange bytes)
COMBINE_MAX_RATIO = 0.6


def map_side_combine(site, kind):
    """Should the groupByKey aggregate rewrite apply map-side combine
    for this site?  True (the static default) without history; False
    when the OBSERVED combine ratio says pre-aggregation barely
    shrinks the exchange (ratio > COMBINE_MAX_RATIO —
    nearly every key is distinct, so the combine pass costs a sort and
    saves no wire bytes).  Observe mode logs the would-be choice and
    keeps the static default."""
    try:
        if not enabled() or not site:
            return True
        _ensure_loaded()
        with _lock:
            ent = _agg["combine"].get(str(site))
        if ent is None or ent.get("ratio") is None:
            return True
        ratio = ent["ratio"]
        limit = COMBINE_MAX_RATIO
        if ratio <= limit:
            return True
        _counters["store_hits"] += 1
        reason = ("observed combine ratio %.2f > %.2f at %s: map-side "
                  "combine for %s priced off (exchange the raw rows; "
                  "the device segment path serves the aggregate)"
                  % (ratio, limit, site, kind))
        if not steering():
            _decide("map_combine", site, "off", reason, applied=False)
            return True
        _decide("map_combine", site, "off", reason)
        return False
    except Exception as e:
        logger.debug("map_side_combine failed: %s", e)
        return True


# ---------------------------------------------------------------------------
# decision point 5: pane-tree split points from observed pane costs
# ---------------------------------------------------------------------------

def record_pane_cost(site, mode, ms, panes):
    """Persist one observed per-tick windowed-emit wall (ms) for a
    pane stream signature under a pane strategy ("tree" = dyadic merge
    tree, "flat" = union all panes, "inv" = invertible O(1) update).
    Streams sample this ONCE per stream (median of post-warmup ticks),
    so the store sees one line per (stream shape, mode) per run."""
    try:
        if not enabled() or not site:
            return
        _append({"k": "pane", "key": str(site), "mode": str(mode),
                 "ms": round(float(ms), 2), "w": int(panes)})
    except Exception as e:
        logger.debug("record_pane_cost failed: %s", e)


def steer_pane_mode(site, panes, static_tree):
    """Split-point choice for a non-invertible pane window ("Partial
    Partial Aggregates": pick the decomposition by COST, not by
    shape): `static_tree` is the conf.STREAM_PANE_TREE_MIN default;
    with DPARK_ADAPT=on and BOTH strategies' per-tick costs on record
    for this stream signature, the observed-cheaper one wins (logged
    as a `pane_split` decision).  Observe mode logs the would-be
    choice and keeps the static default."""
    try:
        if not site or not enabled():
            return static_tree
        _ensure_loaded()
        with _lock:
            ent = _agg["pane"].get(str(site))
        if ent is None:
            _counters["store_misses"] += 1
            return static_tree
        tree_ms, flat_ms = ent.get("tree_ms"), ent.get("flat_ms")
        if tree_ms is None or flat_ms is None:
            _counters["store_misses"] += 1
            return static_tree
        _counters["store_hits"] += 1
        use_tree = tree_ms <= flat_ms
        reason = ("observed pane costs for w=%d: tree ~%.1fms vs flat "
                  "~%.1fms per tick — %s merge"
                  % (panes, tree_ms, flat_ms,
                     "dyadic-tree" if use_tree else "flat"))
        if not steering():
            if use_tree != static_tree:
                _decide("pane_split", site,
                        "tree" if use_tree else "flat", reason,
                        applied=False)
            return static_tree
        _decide("pane_split", site, "tree" if use_tree else "flat",
                reason, applied=(use_tree != static_tree))
        return use_tree
    except Exception as e:
        logger.debug("steer_pane_mode failed: %s", e)
        return static_tree


def pane_history():
    """Copy of the per-stream pane cost aggregates (tests / debug)."""
    _ensure_loaded()
    with _lock:
        return {k: dict(v) for k, v in _agg["pane"].items()}


# ---------------------------------------------------------------------------
# per-site latency tails (health plane, ISSUE 14 — the item-5 handoff)
# ---------------------------------------------------------------------------

def record_site_tail(site, digest):
    """Persist one per-site latency-sketch DELTA (the health plane's
    log-bucketed histogram shape).  The store folds deltas by bucket
    addition, so repeated persists from any process accumulate into
    one distribution per site — the observed straggler/tail data
    ROADMAP item 5's adaptive coder reads back."""
    try:
        if not enabled() or not site or not digest:
            return
        _append({"k": "site", "key": str(site),
                 "digest": dict(digest)})
    except Exception as e:
        logger.debug("record_site_tail failed: %s", e)


def record_reuse(key, hits=0, misses=0, partials=0):
    """Persist one result-cache probe outcome (shared-computation
    plane, ISSUE 18) keyed by the cache entry key: the hit-rate
    profile the disk tier's boot preload ranks entries by."""
    try:
        if not enabled() or not key:
            return
        if not (hits or misses or partials):
            return
        _append({"k": "reuse", "key": str(key), "hits": int(hits),
                 "misses": int(misses), "partials": int(partials)})
    except Exception as e:
        logger.debug("record_reuse failed: %s", e)


def reuse_profiles():
    """{cache key: {hits, misses, partials}} — every persisted
    result-cache hit-rate profile.  A fresh process calling this
    ranks entries it never served."""
    try:
        if not enabled():
            return {}
        _ensure_loaded()
        with _lock:
            return {k: dict(v) for k, v in _agg["reuse"].items()}
    except Exception:
        return {}


def site_tails():
    """{site: digest} — every persisted per-site latency sketch
    (folded across all recorded deltas).  A fresh process calling
    this reads back what earlier processes observed."""
    try:
        if not enabled():
            return {}
        _ensure_loaded()
        with _lock:
            return {k: dict(v) for k, v in _agg["site"].items()}
    except Exception:
        return {}


# ---------------------------------------------------------------------------
# decision point 6: per-exchange (k, m) from recorded peer tails
# (ISSUE 19 tentpole 1 — the ROADMAP item-4 consumer of the site tails)
# ---------------------------------------------------------------------------

def choose_shuffle_code(site, static_spec=None):
    """Price the erasure code for one exchange (identified by its
    shuffle call site) from the store: the peers recorded serving it
    ("xch" records), their fetch-tail sketches ("site" records, keyed
    fetch.bucket:<peer>), and their accumulated decode outcomes.
    Returns the chosen spec string when the policy should steer this
    run, else None (no history / policy off / observe mode — the
    static DPARK_SHUFFLE_CODE stands).  Every actionable choice logs
    as decision point "code"; a steered one stays pending until
    observe_exchange() attaches the observed fetch wall."""
    try:
        from dpark_tpu import coding
        if not enabled() or not site \
                or not getattr(conf, "CODE_ADAPT", False):
            return None
        _ensure_loaded()
        with _lock:
            ent = _agg["xch"].get(str(site))
        if ent is None or not ent.get("peers"):
            _counters["store_misses"] += 1
            return None
        peers = sorted(ent["peers"])
        all_tails = site_tails()
        tails = {p: all_tails.get("fetch.bucket:%s" % p)
                 for p in peers}
        spec, reason, predicted = coding.choose_code(
            peers, tails, ent["peers"], static_spec)
        if spec is None:
            _counters["store_misses"] += 1
            return None
        _counters["store_hits"] += 1
        if not steering():
            _decide("code", site, spec, reason,
                    predicted_ms=predicted, applied=False)
            coding.record_choice(str(site), spec, reason, False,
                                 predicted)
            return None
        d = _decide("code", site, spec, reason,
                    predicted_ms=predicted)
        coding.record_choice(str(site), spec, reason, True, predicted)
        with _lock:
            _pending["code|%s" % site] = d
        return spec
    except Exception as e:
        logger.debug("choose_shuffle_code failed: %s", e)
        return None


def observe_exchange(site, peers, fetch_ms=None):
    """Persist which peers served one exchange this run — `peers` is
    {peer: {"fetches"/"repair"/"straggler_win"/"decode_failures": n}}
    — and complete a pending code decision with the observed fetch
    wall, so the policy is graded by its own telemetry (predicted vs
    observed ms on the job record)."""
    try:
        if not enabled() or not site or not peers:
            return
        rec_peers = {}
        for p, counts in peers.items():
            cc = {k: int(v) for k, v in (counts or {}).items()
                  if isinstance(v, (int, float)) and v}
            if cc:
                rec_peers[str(p)] = cc
        if not rec_peers:
            return
        rec = {"k": "xch", "key": str(site), "peers": rec_peers}
        if fetch_ms is not None:
            rec["fetch_ms"] = round(float(fetch_ms), 2)
        _append(rec)
        with _lock:
            d = _pending.pop("code|%s" % site, None)
        if d is not None and fetch_ms is not None:
            d["observed_ms"] = round(float(fetch_ms), 2)
    except Exception as e:
        logger.debug("observe_exchange failed: %s", e)


def exchange_profiles():
    """{site: {"peers": {peer: counts}, "n", "fetch_ms"}} — every
    persisted per-exchange peer profile (tests / debugging)."""
    try:
        if not enabled():
            return {}
        _ensure_loaded()
        with _lock:
            return {k: {"peers": {p: dict(c)
                                  for p, c in v.get("peers",
                                                    {}).items()},
                        "n": v.get("n", 0),
                        "fetch_ms": v.get("fetch_ms")}
                    for k, v in _agg["xch"].items()}
    except Exception:
        return {}


# ---------------------------------------------------------------------------
# decision point 7: mid-job re-plan of a skewed reduce side
# ---------------------------------------------------------------------------

def note_replan(site, parts, salt, frac, applied):
    """Log the mid-job re-plan decision (decision point 7) taken — or,
    in observe mode, declined — by the scheduler at a stage boundary,
    and persist the replan record so the NEXT run of this call site
    salts its partitioner at plan time (suggest_salt) instead of
    paying the re-split again.  Returns the reason string the
    scheduler records as the consumer stage's `replan_reason`."""
    reason = ("map-side bucket histogram at %s: dominant bucket "
              "%.0f%% of exchange bytes across width %d — re-keying "
              "the reduce side through a salted re-split (salt=%d), "
              "no map task recomputed" % (site, frac * 100, parts,
                                          salt))
    try:
        if not enabled() or not site:
            return reason
        _decide("replan", site, "resplit(salt=%d)" % int(salt),
                reason, applied=bool(applied))
        _append({"k": "replan", "key": str(site), "parts": int(parts),
                 "salt": int(salt), "frac": round(float(frac), 4)})
    except Exception as e:
        logger.debug("note_replan failed: %s", e)
    return reason


def suggest_salt(site):
    """Plan-time twin of the mid-job re-plan: a recorded re-plan for
    this call site returns its salt so combineByKey builds the salted
    partitioner up front — the map side then writes balanced buckets
    and the mid-job probe finds nothing to re-split (the "skip
    already-replanned shapes" contract).  0 = no salt / not steering."""
    try:
        if not enabled() or not site \
                or not getattr(conf, "REPLAN", False):
            return 0
        _ensure_loaded()
        with _lock:
            ent = _agg["replan"].get(str(site))
        if not ent or not ent.get("salt"):
            return 0
        _counters["store_hits"] += 1
        reason = ("recorded re-plan at %s (dominant bucket %.0f%% of "
                  "exchange bytes): salting the partitioner at plan "
                  "time" % (site, ent.get("frac", 0.0) * 100))
        if not steering():
            _decide("replan", site, "salt=%d" % ent["salt"], reason,
                    applied=False)
            return 0
        _decide("replan", site, "salt=%d" % ent["salt"], reason)
        return int(ent["salt"])
    except Exception as e:
        logger.debug("suggest_salt failed: %s", e)
        return 0


def replan_profiles():
    """{site: {"parts", "salt", "frac"}} — every persisted re-plan
    record (tests / debugging)."""
    try:
        if not enabled():
            return {}
        _ensure_loaded()
        with _lock:
            return {k: dict(v) for k, v in _agg["replan"].items()}
    except Exception:
        return {}
