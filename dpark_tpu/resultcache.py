"""Shared-computation plane: the cross-tenant sub-plan result cache
(ISSUE 18 tentpole; ROADMAP item 5's first two legs).

At millions-of-users scale the same dashboards hit the same tables:
two tenants running the identical ``ctx.sql`` group-by each paid a
full scan + device exchange, even though the plan-signature machinery
(adapt.stable_key) can already prove the work identical and the
tabular v2 stats footer gives a cheap per-chunk source fingerprint.
PR 17 made restarts skip the COMPILE; this plane makes repeated
queries skip the WORK.

Keying.  An entry's identity is ``stable_key(("rc", format,
plan_signature(root), dtypes, fingerprints))``:

  * ``query.logical.plan_signature`` — the canonical subtree shape
    INCLUDING every expression text (``sketch()`` prints only
    ``name:func`` for aggregates, so ``sum(b)`` and ``sum(c)`` would
    collide on it);
  * the scan segments' resolved dtypes (the same promotion the adapt
    pricing key uses);
  * one ``tabular.source_fingerprint`` per part file: v2 files digest
    the footer statistics (rewriting any chunk drifts the digest
    without reading a data byte), v1 files fall back to
    (path, mtime_ns, size) — mutation ALWAYS means a miss, never a
    stale serve.

Serving.  The planner probes at plan time (``planner._rule_reuse``):

  * a FULL hit presets the planned query's row cache — no scan, no
    device exchange, no scheduler job; the logical root is replaced by
    a ``CachedResult`` leaf so ``explain()`` shows what did not run;
  * a PARTIAL-AGGREGATE hit (same group-by keys + mergeable combiner
    — sum/count/min/max, no avg/UDA — over the same source, where the
    cached entry's filter box is CONTAINED in the new query's and the
    difference is one single-interval residual on one int column)
    rewrites the plan to merge the cached aggregate rows with a
    residual scan over only the uncovered interval — the pane
    MergeTree's share-the-overlap idea lifted out of dstream/panes
    into a query-plane service ("Partial Partial Aggregates",
    PAPERS.md).

Storage.  Host-memory tier with size-budgeted LRU eviction
(``DPARK_RESULT_CACHE_BUDGET`` bytes); ``disk`` mode adds a
crc-framed on-disk tier that survives restarts — entry files are
tmp+rename with a crc-framed header and a
crc/length-checked pickled payload, the index is O_APPEND whole-line
jsonl (the adapt-store idioms), and ANY defect means "miss and
recompute", never an error.

Tenancy.  One JobServer's tenants share the cache by default — a hit
is a hit no matter who stored it.  ``opt_out(tenant)`` removes a
tenant from BOTH directions (reads and writes); the ledger bills an
entry's byte-seconds of residency to the tenant that stored it and
counts hits/served-bytes against the tenant that was served (zero
scan device-seconds — the conservation check still holds because a
served query never touches the mesh).

Modes (``DPARK_RESULT_CACHE`` / conf.RESULT_CACHE):

  off   no plane installed.  The seams cost exactly one module-global
        load + ``is None`` check — the same off-mode contract as the
        faults/trace/health/ledger/lockcheck planes,
        machine-checked by the ``plane-contract`` dlint rule.
  mem   host-memory LRU only.
  disk  mem + write-through to the on-disk tier; a restarting server
        boots its hottest entries back (ranked by the adapt store's
        reuse profiles) and serves its first repeated query with zero
        scan chunks.
"""

import os
import pickle
import threading
import time

from dpark_tpu import conf, locks
from dpark_tpu.utils import atomic_file, frame_jsonl, unframe_jsonl
from dpark_tpu.utils.log import get_logger

logger = get_logger("resultcache")

__all__ = ["MODES", "ResultCachePlane", "configure", "active",
           "plane", "stats", "probe", "offer", "merge_group_rows",
           "opt_out", "tenant"]

MODES = ("off", "mem", "disk")

# entry-format generation: bump on any layout/keying change so old
# dirs (and old-format index lines) skip instead of mis-serving
FORMAT = "dpark-rc-1"

INDEX_FILE = "index.jsonl"

COUNTERS = ("hits", "partial_hits", "misses", "stores",
            "store_errors", "oversize", "evictions", "disk_loads",
            "disk_stores", "load_errors", "version_skips",
            "preloaded", "opt_outs")

# partial merges admit only combiners whose FINAL value is also the
# mergeable accumulator (avg's final is s/c — not re-mergeable)
MERGEABLE = ("sum", "count", "min", "max")

_PLANE = None
_tls = threading.local()


def _crc(data):
    from dpark_tpu.shuffle import spill_crc
    return spill_crc(data)


class tenant:
    """Context manager overriding the tenant the calling thread's
    probes/offers attribute to (tests and embedded callers without a
    ClientScheduler)."""

    def __init__(self, name):
        self.name = str(name)

    def __enter__(self):
        self._prev = getattr(_tls, "tenant", None)
        _tls.tenant = self.name
        return self

    def __exit__(self, *exc):
        _tls.tenant = self._prev
        return False


def merge_group_rows(cached, fresh, nk, kinds):
    """Merge two disjoint-source group-aggregate row sets (rows are
    (key..., val...) tuples of one schema): sum/count add, min/max
    fold, keys present on one side only pass through.  Output is
    sorted by key so the merged path is deterministic."""
    acc = {}
    for row in cached:
        acc[row[:nk]] = list(row[nk:])
    for row in fresh:
        key = row[:nk]
        vals = acc.get(key)
        if vals is None:
            acc[key] = list(row[nk:])
            continue
        for i, kind in enumerate(kinds):
            v = row[nk + i]
            if kind in ("sum", "count"):
                vals[i] = vals[i] + v
            elif kind == "min":
                vals[i] = v if v < vals[i] else vals[i]
            else:                   # max
                vals[i] = v if v > vals[i] else vals[i]
    return [k + tuple(v) for k, v in sorted(acc.items())]


def _interval_contains(outer, inner):
    """Closed-interval containment with None = unbounded."""
    lo1, hi1 = outer
    lo2, hi2 = inner
    if lo1 is not None and (lo2 is None or lo2 < lo1):
        return False
    if hi1 is not None and (hi2 is None or hi2 > hi1):
        return False
    return True


def _residual_intervals(new, cand):
    """The (up to two) closed int intervals of ``new - cand`` given
    ``cand`` contained in ``new``."""
    lo1, hi1 = new
    lo2, hi2 = cand
    out = []
    if lo2 is not None and (lo1 is None or lo1 <= lo2 - 1):
        out.append((lo1, lo2 - 1))
    if hi2 is not None and (hi1 is None or hi1 >= hi2 + 1):
        out.append((hi2 + 1, hi1))
    return out


def _range_pred_text(col, rng):
    lo, hi = rng
    parts = []
    if lo is not None:
        parts.append("%s >= %d" % (col, lo))
    if hi is not None:
        parts.append("%s <= %d" % (col, hi))
    return " and ".join(parts)


class ResultCachePlane:
    """One JobServer's shared sub-plan result cache."""

    def __init__(self, mode, cache_dir, budget_bytes):
        self.mode = mode
        self.dir = cache_dir
        self.budget = max(1, int(budget_bytes))
        self._mu = locks.named_lock("resultcache.store")
        self._counters = {k: 0 for k in COUNTERS}
        self._mem = {}           # key -> entry (insertion order = LRU)
        self._bytes = 0
        self._partials = {}      # group_sig -> {key, ...}
        self._opt_out = set()
        if mode == "disk":
            try:
                os.makedirs(cache_dir, exist_ok=True)
            except OSError as e:
                logger.debug("result cache dir %s: %s", cache_dir, e)

    # -- bookkeeping -----------------------------------------------------
    def _bump(self, name, n=1):
        with self._mu:
            self._counters[name] += n

    def set_opt_out(self, tenant_name, flag=True):
        with self._mu:
            if flag:
                self._opt_out.add(str(tenant_name))
            else:
                self._opt_out.discard(str(tenant_name))

    def _tenant_of(self, pq):
        t = getattr(_tls, "tenant", None)
        if t:
            return str(t)
        sched = getattr(getattr(pq, "ctx", None), "scheduler", None)
        return str(getattr(sched, "client", None) or "local")

    def stats(self):
        with self._mu:
            out = dict(self._counters)
            out["mode"] = self.mode
            out["entries"] = len(self._mem)
            out["bytes"] = int(self._bytes)
            out["budget_bytes"] = int(self.budget)
        return out

    # -- keying ----------------------------------------------------------
    def _key_of(self, pq):
        """(key, group_sig, ranges, meta) of a plannable query, or
        None when the plan is uncacheable (non-tabular source, UDA
        aggregates, unsignable expressions).  group_sig/ranges/meta
        are None unless the plan is partial-merge ELIGIBLE."""
        from dpark_tpu import adapt, tabular
        from dpark_tpu.query import logical
        if pq.mode not in ("scan", "group", "join", "join_group"):
            return None
        try:
            sig = logical.plan_signature(pq.root)
        except Exception:
            return None
        fps = []
        for seg in pq.segs:
            src = seg.scan.source
            if not isinstance(src, tabular.TabularRDD):
                return None     # in-memory sources mutate invisibly
            fps.append(tuple(tabular.source_fingerprint(p)
                             for p in src.files))
        for node in logical.iter_plan(pq.root):
            if isinstance(node, logical.GroupAgg):
                for a in node.aggs:
                    if a[1] == "uda" or a[3] is not None:
                        return None     # UDA identity is not stable
        dtypes = tuple(sorted((k, str(v)) for s in pq.segs
                              for k, v in s.dtypes.items()))
        key = adapt.stable_key(("rc", FORMAT, sig, dtypes,
                                tuple(fps)))
        group_sig = ranges = meta = None
        part = self._partial_shape(pq)
        if part is not None:
            ranges, g_node = part
            scan = pq.segs[0].scan
            gsig = ("rc-part", FORMAT,
                    tuple((n, ce.expr) for n, ce in g_node.keys),
                    tuple((a[0], a[1],
                           a[2].expr if a[2] is not None else None)
                          for a in g_node.aggs),
                    ("Scan", scan.table_name, tuple(scan.fields)),
                    dtypes, tuple(fps))
            group_sig = adapt.stable_key(gsig)
            g = pq._group
            meta = {"ranges": {c: list(r) for c, r in ranges.items()},
                    "nk": int(g["nk"]), "kinds": list(g["kinds"]),
                    "fields": list(g["key_names"])
                    + list(g["agg_names"])}
        return key, group_sig, ranges, meta

    def _partial_shape(self, pq):
        """(filter ranges, GroupAgg node) when the plan is
        partial-merge eligible, else None: a single-segment group over
        Filter-only scan ops, general-reduce lowering, mergeable
        combiners, no egest, and every predicate fully captured as an
        int-column range box."""
        from dpark_tpu.query.logical import Filter
        if pq.mode != "group" or pq.egest_ops or len(pq.segs) != 1:
            return None
        g = pq._group
        # any lowering whose FINAL rows are still mergeable
        # accumulators qualifies (avg finalizes s/c, UDAs are opaque
        # — both excluded below via kinds)
        if g is None or g["lower"] not in ("classified", "reduce"):
            return None
        if not g["kinds"] or any(k not in MERGEABLE
                                 for k in g["kinds"]):
            return None
        sh = getattr(pq, "_shape", None) or {}
        ops = sh.get("scan_ops", ())
        if any(not isinstance(op, Filter) for op in ops):
            return None
        preds = [p for op in ops for p in op.preds]
        ranges = self._full_ranges(preds, pq.segs[0])
        if ranges is None:
            return None
        return ranges, sh["group"]

    @staticmethod
    def _full_ranges(preds, seg):
        """{col: (lo, hi)} ONLY when every predicate is a conjunction
        of ``int_col <cmp> int_literal`` compares — the ranges then
        EXACTLY describe the filter region (unlike planner
        _skip_bounds, which is a conservative superset), so interval
        arithmetic on them is sound.  None on any uncaptured piece."""
        import ast
        dtypes = getattr(seg, "src_dtypes", None) or seg.dtypes or {}
        out = {}

        def add(col, lo, hi):
            plo, phi = out.get(col, (None, None))
            if lo is not None:
                plo = lo if plo is None else max(plo, lo)
            if hi is not None:
                phi = hi if phi is None else min(phi, hi)
            out[col] = (plo, phi)

        def visit(node):
            if isinstance(node, ast.BoolOp) \
                    and isinstance(node.op, ast.And):
                return all(visit(v) for v in node.values)
            if not isinstance(node, ast.Compare) \
                    or len(node.ops) != 1:
                return False
            left, op, right = (node.left, node.ops[0],
                               node.comparators[0])
            flip = False
            if isinstance(left, ast.Name) \
                    and isinstance(right, ast.Constant):
                name, const = left.id, right.value
            elif isinstance(right, ast.Name) \
                    and isinstance(left, ast.Constant):
                name, const = right.id, left.value
                flip = True
            else:
                return False
            if isinstance(const, bool) \
                    or not isinstance(const, int):
                return False
            try:
                import numpy as np
                if np.dtype(dtypes.get(name, object)).kind != "i":
                    return False
            except TypeError:
                return False
            opname = type(op).__name__
            if flip:
                opname = {"Lt": "Gt", "LtE": "GtE", "Gt": "Lt",
                          "GtE": "LtE"}.get(opname, opname)
            if opname == "Eq":
                add(name, const, const)
            elif opname == "Gt":
                add(name, const + 1, None)
            elif opname == "GtE":
                add(name, const, None)
            elif opname == "Lt":
                add(name, None, const - 1)
            elif opname == "LtE":
                add(name, None, const)
            else:
                return False
            return True

        for p in preds:
            body = p.tree.body if p.tree is not None else None
            if body is None or not visit(body):
                return None
        return out

    # -- memory tier -----------------------------------------------------
    def get(self, key):
        """Entry for ``key`` or None: memory first (LRU touch), then
        the disk tier in disk mode (a disk hit re-enters memory)."""
        with self._mu:
            ent = self._mem.get(key)
            if ent is not None:
                # LRU touch: re-insert at the MRU end
                del self._mem[key]
                self._mem[key] = ent
                return ent
        if self.mode != "disk":
            return None
        ent = self._load_entry(key)
        if ent is None:
            return None
        self._bump("disk_loads")
        self._insert(key, ent, write_disk=False)
        return ent

    def _insert(self, key, ent, write_disk):
        evicted = []
        with self._mu:
            old = self._mem.pop(key, None)
            if old is not None:
                self._bytes -= old["nbytes"]
            self._mem[key] = ent
            self._bytes += ent["nbytes"]
            if ent.get("group_sig"):
                self._partials.setdefault(ent["group_sig"],
                                          set()).add(key)
            while self._bytes > self.budget and len(self._mem) > 1:
                k, e = next(iter(self._mem.items()))
                if k == key:
                    break
                del self._mem[k]
                self._bytes -= e["nbytes"]
                self._counters["evictions"] += 1
                evicted.append((k, e))
        # events emit OUTSIDE the plane mutex (resultcache.store
        # orders before trace.plane in locks.DOCUMENTED_ORDER, but
        # not holding it across the sink fold is cheaper and safer)
        from dpark_tpu import trace
        for k, e in evicted:
            # in disk mode the entry file survives eviction — only
            # the memory-tier residency (the billed byte-seconds)
            # ends here
            trace.event("resultcache.release", "resultcache", sid=k,
                        bytes=e["nbytes"], reason="evict",
                        tenant=e.get("tenant"))

    def offer(self, pq, rows):
        """Store one finished query's result rows under the offer the
        probe recorded at plan time.  Size-gated; never raises."""
        off = getattr(pq, "_cache_offer", None)
        if off is None:
            return False
        pq._cache_offer = None
        try:
            key = off["key"]
            fields = list(pq._out_fields or [])
            meta = off.get("meta")
            blob = pickle.dumps((fields, list(rows), meta),
                                protocol=pickle.HIGHEST_PROTOCOL)
            nbytes = len(blob)
            if nbytes > self.budget:
                self._bump("oversize")
                return False
            ent = {"rows": list(rows), "fields": fields,
                   "nbytes": nbytes, "meta": meta,
                   "group_sig": off.get("group_sig"),
                   "tenant": off.get("tenant")}
            self._insert(key, ent, write_disk=True)
            self._bump("stores")
            from dpark_tpu import trace
            trace.event("resultcache.store", "resultcache", sid=key,
                        bytes=nbytes, tenant=off.get("tenant"))
            if self.mode == "disk":
                self._store_entry(key, blob, ent)
            return True
        except Exception as e:
            logger.debug("result cache offer failed: %s", e)
            self._bump("store_errors")
            return False

    # -- disk tier -------------------------------------------------------
    def _entry_path(self, key):
        return os.path.join(self.dir, key + ".rc")

    def _store_entry(self, key, blob, ent):
        try:
            header = {"fmt": FORMAT, "k": key,
                      "nbytes": len(blob),
                      "group_sig": ent.get("group_sig"),
                      "tenant": ent.get("tenant"),
                      "created": round(time.time(), 3)}
            with atomic_file(self._entry_path(key)) as f:
                f.write(frame_jsonl(header))
                f.write(b"%08x %08x\n" % (_crc(blob), len(blob)))
                f.write(blob)
            self._append_index({"k": key, "fmt": FORMAT,
                                "nbytes": len(blob),
                                "group_sig": ent.get("group_sig"),
                                "meta": ent.get("meta")})
            self._bump("disk_stores")
        except Exception as e:
            logger.debug("result cache disk store failed for %s: %s",
                         key, e)
            self._bump("store_errors")

    def _append_index(self, rec):
        """One crc-framed line, one O_APPEND write: concurrent
        replicas interleave whole lines (the adapt-store idiom)."""
        line = frame_jsonl(rec)
        fd = os.open(os.path.join(self.dir, INDEX_FILE),
                     os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
        try:
            os.write(fd, line)
        finally:
            os.close(fd)

    def index(self):
        """{key: latest index record}, current-format lines only.
        Torn/corrupt lines skip; duplicate keys fold latest-wins."""
        try:
            with open(os.path.join(self.dir, INDEX_FILE), "rb") as f:
                raw = f.read()
        except OSError:
            return {}
        recs, _ = unframe_jsonl(raw)
        out = {}
        for r in recs:
            k = r.get("k")
            if not k:
                continue
            if r.get("fmt") != FORMAT:
                continue
            out[str(k)] = r
        return out

    def _load_entry(self, key):
        """Read one entry file; None on ANY defect — missing file,
        torn header, format drift, payload crc or length mismatch,
        unpicklable blob.  Corruption means recompute, never an
        error (the adapt-store contract)."""
        try:
            with open(self._entry_path(key), "rb") as f:
                raw = f.read()
        except OSError:
            return None
        try:
            head, _, rest = raw.partition(b"\n")
            recs, skipped = unframe_jsonl(head + b"\n")
            if skipped or not recs:
                raise ValueError("corrupt header")
            header = recs[0]
            if header.get("fmt") != FORMAT:
                self._bump("version_skips")
                return None
            crcline, _, blob = rest.partition(b"\n")
            crc_hex, _, len_hex = crcline.partition(b" ")
            if len(blob) != int(len_hex, 16):
                raise ValueError("truncated payload")
            if int(crc_hex, 16) != _crc(blob):
                raise ValueError("payload crc mismatch")
            fields, rows, meta = pickle.loads(blob)
            gs = header.get("group_sig")
            if meta is not None and isinstance(meta.get("ranges"),
                                               dict):
                meta["ranges"] = {c: tuple(r) for c, r in
                                  meta["ranges"].items()}
            return {"rows": [tuple(r) for r in rows],
                    "fields": list(fields), "nbytes": len(blob),
                    "meta": meta, "group_sig": gs,
                    "tenant": header.get("tenant")}
        except Exception as e:
            logger.debug("result cache entry %s unusable: %s",
                         key, e)
            self._bump("load_errors")
            return None

    def boot(self, budget_bytes=None):
        """Disk-mode boot: load the index and preload the hottest
        entries (ranked by the adapt store's reuse profiles) into the
        memory tier up to a byte budget, so a restarted server's
        first repeated query serves from memory.  Returns a summary
        for service_stats; never raises past the caller's guard."""
        t0 = time.time()
        if self.mode != "disk":
            return {"entries": 0, "preloaded": 0, "bytes": 0,
                    "ms": 0.0}
        idx = self.index()
        try:
            from dpark_tpu import adapt
            profiles = adapt.reuse_profiles()
        except Exception:
            profiles = {}

        def _score(rec):
            prof = profiles.get(str(rec.get("k"))) or {}
            return (float(prof.get("hits", 0) or 0),
                    -float(rec.get("nbytes", 0) or 0))

        cap = min(self.budget,
                  int(budget_bytes or self.budget)) // 2
        loaded = 0
        nbytes = 0
        for rec in sorted(idx.values(), key=_score, reverse=True):
            key = str(rec.get("k"))
            if nbytes + int(rec.get("nbytes", 0) or 0) > cap:
                continue
            ent = self._load_entry(key)
            if ent is None:
                continue
            self._insert(key, ent, write_disk=False)
            self._bump("preloaded")
            loaded += 1
            nbytes += ent["nbytes"]
        return {"entries": len(idx), "preloaded": loaded,
                "bytes": int(nbytes),
                "ms": round((time.time() - t0) * 1e3, 1)}

    # -- probing ---------------------------------------------------------
    def probe(self, pq):
        """Plan-time cache consult: returns "hit", "partial", or None
        (miss/ineligible).  On a miss the offer for this key is left
        on the planned query so its first execution stores back."""
        tname = self._tenant_of(pq)
        with self._mu:
            opted_out = tname in self._opt_out
        if opted_out:
            self._bump("opt_outs")
            return None
        keyinfo = self._key_of(pq)
        if keyinfo is None:
            return None
        key, group_sig, ranges, meta = keyinfo
        ent = self.get(key)
        if ent is not None:
            self._serve_full(pq, key, ent, tname, tier="full")
            return "hit"
        got = None
        if group_sig is not None:
            got = self._probe_partial(pq, key, group_sig, ranges,
                                      tname)
        pq._cache_offer = {"key": key, "group_sig": group_sig,
                           "meta": meta, "tenant": tname}
        if got is None:
            self._bump("misses")
            self._reuse_note(key, misses=1)
        return got

    def _serve_full(self, pq, key, ent, tname, tier):
        from dpark_tpu import trace
        from dpark_tpu.query.logical import CachedResult
        replaced = pq.root.describe()
        pq._rows_cache = list(ent["rows"])
        pq._out_fields = list(ent["fields"])
        pq.root = CachedResult(list(ent["fields"]), replaced,
                               key[:12])
        pq.decide("result-cache", "plan", "cache",
                  "%s hit: %d rows served from the shared result "
                  "cache (stored by tenant %r); no scan, no device "
                  "exchange" % (tier, len(ent["rows"]),
                                ent.get("tenant")))
        self._bump("hits")
        self._reuse_note(key, hits=1)
        trace.event("resultcache.serve", "resultcache", sid=key,
                    bytes=ent["nbytes"], tier=tier, tenant=tname)

    def _probe_partial(self, pq, key, group_sig, new_ranges, tname):
        """Candidate walk: same group signature, contained filter box,
        single-interval residual on exactly one column."""
        with self._mu:
            cand_keys = list(self._partials.get(group_sig, ()))
        if self.mode == "disk" and not cand_keys:
            cand_keys = [k for k, r in self.index().items()
                         if r.get("group_sig") == group_sig]
        for key2 in cand_keys:
            if key2 == key:
                continue
            ent = self.get(key2)
            if ent is None or ent.get("meta") is None:
                continue
            meta = ent["meta"]
            cand_ranges = {c: tuple(r) for c, r in
                           (meta.get("ranges") or {}).items()}
            plan = self._residual_plan(pq, new_ranges, cand_ranges)
            if plan is None:
                continue
            if plan == "equal":
                # range-equivalent filters with different texts
                # ("t >= 100000" vs "t > 99999"): the cached rows ARE
                # the answer
                self._serve_full(pq, key2, ent, tname,
                                 tier="equivalent")
                return "hit"
            from dpark_tpu import trace
            pq._partial = {"rows": list(ent["rows"]),
                           "nk": int(meta["nk"]),
                           "kinds": tuple(meta["kinds"]),
                           "fields": list(ent["fields"]),
                           "residual": plan, "key": key2}
            pq.decide("result-cache", "plan", "cache",
                      "partial-aggregate hit: cached rows for ranges "
                      "%s merge with a residual scan of %s"
                      % (dict(sorted(cand_ranges.items())),
                         plan.children[0].describe()))
            self._bump("partial_hits")
            self._reuse_note(key2, partials=1)
            trace.event("resultcache.serve", "resultcache", sid=key2,
                        bytes=ent["nbytes"], tier="partial",
                        tenant=tname)
            return "partial"
        return None

    def _residual_plan(self, pq, new_ranges, cand_ranges):
        """A fresh GroupAgg(Filter(Scan)) logical tree covering
        exactly ``new - cand``, "equal" when the regions coincide, or
        None when the difference is not one single-interval column."""
        from dpark_tpu.query import exprs as E
        from dpark_tpu.query.logical import Filter, GroupAgg, Scan
        cols = set(new_ranges) | set(cand_ranges)
        diff_col = None
        residual = None
        for c in sorted(cols):
            n = new_ranges.get(c, (None, None))
            k = cand_ranges.get(c, (None, None))
            if n == k:
                continue
            if not _interval_contains(n, k):
                return None     # cached is not narrower: no merge
            if diff_col is not None:
                return None     # two differing columns: not a box
            ivs = _residual_intervals(n, k)
            if len(ivs) > 1:
                return None     # split residual needs two scans
            diff_col = c
            residual = ivs[0] if ivs else None
        if diff_col is None or residual is None:
            return "equal" if diff_col is None else None
        old_scan = pq.segs[0].scan
        scan = Scan(old_scan.source, list(old_scan.fields),
                    old_scan.table_name)
        texts = [_range_pred_text(diff_col, residual)]
        for c in sorted(cols):
            if c == diff_col:
                continue
            t = _range_pred_text(c, new_ranges.get(c, (None, None)))
            if t:
                texts.append(t)
        preds = [E.compile_expr(t, list(scan.fields))
                 for t in texts if t]
        g = pq._shape["group"]
        return GroupAgg(Filter(scan, preds), list(g.keys),
                        list(g.aggs))

    def _reuse_note(self, key, hits=0, misses=0, partials=0):
        try:
            from dpark_tpu import adapt
            adapt.record_reuse(key, hits=hits, misses=misses,
                               partials=partials)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# module seams (plane-contract shapes, registered in
# analysis/concurrency.py PLANE_SEAMS)
# ---------------------------------------------------------------------------

def probe(pq):
    """Plan-time cache consult for one planned query: "hit",
    "partial", or None.  One global load + ``is None`` check when the
    plane is off."""
    plane = _PLANE
    if plane is None:
        return None
    return plane.probe(pq)


def offer(pq, rows):
    """Run-time store-back of a finished query whose probe recorded
    an offer.  One global load + ``is None`` check when off."""
    plane = _PLANE
    if plane is None:
        return False
    return plane.offer(pq, rows)


def stats():
    """Hot counters + mode/occupancy for /metrics and /api/health;
    None when the plane is off."""
    plane = _PLANE
    if plane is None:
        return None
    return plane.stats()


def opt_out(tenant_name, flag=True):
    """Remove (or re-admit) one tenant from cross-tenant sharing —
    both directions: an opted-out tenant neither reads nor stores."""
    plane = _PLANE
    if plane is None:
        return False
    plane.set_opt_out(tenant_name, flag)
    return True


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def configure(mode=None, cache_dir=None, budget_bytes=None):
    """Install (mem/disk) or clear (off) the process plane.  None
    reads conf.RESULT_CACHE.  Returns the installed plane or None."""
    global _PLANE
    if mode is None:
        mode = str(getattr(conf, "RESULT_CACHE", "off") or "off")
    mode = str(mode).strip().lower()
    if mode in ("", "0", "none", "disable", "disabled"):
        mode = "off"
    if mode not in MODES:
        raise ValueError("DPARK_RESULT_CACHE=%r (expected "
                         "off|mem|disk)" % mode)
    if mode == "off":
        _PLANE = None
        return None
    _PLANE = ResultCachePlane(
        mode, cache_dir or conf.RESULT_CACHE_DIR,
        budget_bytes or getattr(conf, "RESULT_CACHE_BUDGET", 0)
        or (64 << 20))
    return _PLANE


def active():
    return _PLANE is not None


def plane():
    return _PLANE


def _init_from_conf():
    m = str(getattr(conf, "RESULT_CACHE", "off") or "off")
    if m not in ("off", ""):
        configure(m)


_init_from_conf()
