"""Named-lock registry + runtime lock-order sanitizer (ISSUE 16).

The resident JobServer multiplexes many tenants onto one mesh behind a
web of locks (the metered mesh lock, the scheduler's graph/metrics
locks, service slot queues, the ledger/health sink locks).  Every
deadlock to date was found by luck at runtime: the PR 3 export-bucket
collective wedge and the PR 9 mesh->shard_build inversion each cost a
debugging session that a cycle detector would have flagged from one
clean run.  This module is that detector — the dynamic half of the
concurrency sanitizer plane (the static half lives in
``dpark_tpu.analysis.concurrency``).

Modes (``DPARK_LOCKCHECK`` / conf.DPARK_LOCKCHECK):

  off     no sanitizer installed.  Every named lock costs exactly one
          module-global load + ``is None`` check per acquisition on
          top of the raw ``threading`` primitive — the same off-mode
          contract as the faults/trace/health/ledger planes, and
          machine-checked by the ``plane-contract`` dlint rule.
  record  per-thread acquisition order is recorded and merged into a
          process-wide edge graph; :func:`cycles` reports every cycle
          OBSERVED ACROSS THE WHOLE RUN even when no deadlock fired
          (two threads that each survived their inverted acquisitions
          still drew the edges).  CI arms this across the full test
          suite, so a future PR that inverts an order fails fast.
  strict  like record, but the acquisition that CLOSES a cycle (or
          re-acquires a non-reentrant lock the same thread already
          holds) raises :class:`LockOrderError` naming the cycle
          before the lock is taken — the deadlock becomes a stack
          trace instead of a wedge.

Lock identity is the NAME, not the instance: every ``named_lock`` and
every :class:`~dpark_tpu.backend.tpu.executor._MeshLock` acquisition
under one name merges into the same node of the order graph, so a
cycle between e.g. ``executor.mesh`` and ``executor.shard_build`` is
reported no matter which executor instance drew it.

The documented global order lives in :data:`DOCUMENTED_ORDER`; the
first entry pair records the rule PR 9 fixed (``executor.mesh`` before
``executor.shard_build``, never inverted).  ``report()`` grades the
observed edges against it.
"""

import sys
import threading

from dpark_tpu import conf

MODES = ("off", "record", "strict")

_SANITIZER = None            # the `is None` check every acquisition makes
_install_mu = threading.Lock()

# The documented global lock order: a lock earlier in this tuple may be
# held while acquiring a later one, NEVER the reverse.  Locks absent
# from the tuple are unordered (the sanitizer still catches their
# cycles; it just can't grade them against documentation).  Keep the
# README "Concurrency sanitizer" section in sync.
DOCUMENTED_ORDER = (
    "service.server",        # JobServer lifecycle (start/stop)
    "schedule.graph",        # DAG registration
    "schedule.metrics",      # per-stage metric folds
    "executor.mesh",         # THE mesh lock: every device dispatch
    "executor.shard_build",  # PR 9 rule: mesh -> shard_build only
    "executor.program_cache",
    "shuffle.shard_pool",
    "dcn.serves",
    "resultcache.store",     # shared result cache LRU/counters: the
    #                          planner probes it before any job
    #                          exists and offers under a finished
    #                          query; its trace events emit AFTER
    #                          release, so it must order before
    #                          trace.plane and never nest under it
    "trace.plane",           # span ring/spool (spans emit under mesh)
    "health.sink",
    "ledger.sink",
)
_ORDER_INDEX = {n: i for i, n in enumerate(DOCUMENTED_ORDER)}


class LockOrderError(RuntimeError):
    """Strict mode: this acquisition would close a lock-order cycle
    (or self-deadlock a non-reentrant lock).  ``.cycle`` carries the
    named path, e.g. ``["executor.mesh", "executor.shard_build",
    "executor.mesh"]``."""

    def __init__(self, message, cycle=()):
        super().__init__(message)
        self.cycle = list(cycle)


class Sanitizer:
    """Process-wide acquisition-order recorder.

    Per-thread state is a held-lock stack (thread-local: no lock
    needed); the global edge graph merges under one internal mutex
    which is deliberately a RAW ``threading.Lock`` — the sanitizer
    must never observe itself."""

    def __init__(self, strict=False):
        self.strict = strict
        self._mu = threading.Lock()
        self._tls = threading.local()
        self.locks = {}          # name -> {"count", "reentrant"}
        self.edges = {}          # (held, acquired) -> {"count", "site"}
        self.findings = []       # self-deadlock shapes seen in record mode
        self.acquisitions = 0

    # -- per-thread stack ------------------------------------------------
    def _held(self):
        tls = self._tls
        held = getattr(tls, "held", None)
        if held is None:
            held = tls.held = []           # acquisition-ordered names
            tls.counts = {}                # name -> depth
        return held

    # -- notes -----------------------------------------------------------
    def acquiring(self, name, reentrant=True):
        """Called BEFORE the underlying acquire so a strict-mode cycle
        (or self-deadlock) raises instead of wedging."""
        held = self._held()
        counts = self._tls.counts
        depth = counts.get(name, 0)
        if depth:
            if not reentrant:
                msg = ("self-deadlock: thread %r re-acquires "
                       "non-reentrant lock %r it already holds"
                       % (threading.current_thread().name, name))
                with self._mu:
                    self.findings.append(
                        {"kind": "self-deadlock", "lock": name,
                         "detail": msg})
                if self.strict:
                    raise LockOrderError(msg, [name, name])
            counts[name] = depth + 1
            return
        new = [(h, name) for h in held if h != name]
        site = None
        cycle = None
        with self._mu:
            self.acquisitions += 1
            ent = self.locks.get(name)
            if ent is None:
                self.locks[name] = {"count": 1, "reentrant": reentrant}
            else:
                ent["count"] += 1
            for e in new:
                eent = self.edges.get(e)
                if eent is not None:
                    eent["count"] += 1
                    continue
                if site is None:
                    site = _caller_site()
                self.edges[e] = {"count": 1, "site": site}
                if cycle is None:
                    path = self._path(e[1], e[0])
                    if path is not None:
                        cycle = [e[0]] + path
        counts[name] = 1
        held.append(name)
        if cycle is not None:
            msg = ("lock-order cycle closed by acquiring %r while "
                   "holding %r: %s (first drawn at %s)"
                   % (name, cycle[0], " -> ".join(cycle), site))
            with self._mu:
                self.findings.append(
                    {"kind": "lock-order-cycle", "lock": name,
                     "cycle": cycle, "detail": msg})
            if self.strict:
                raise LockOrderError(msg, cycle)

    def released(self, name):
        tls = self._tls
        counts = getattr(tls, "counts", None)
        if not counts:
            return                       # armed mid-hold: tolerate
        depth = counts.get(name, 0)
        if depth > 1:
            counts[name] = depth - 1
            return
        if depth == 1:
            del counts[name]
            try:
                tls.held.remove(name)
            except ValueError:
                pass

    def abandon(self, name):
        """Un-note an acquisition whose underlying acquire failed."""
        self.released(name)

    # -- graph queries (all under _mu) -----------------------------------
    def _succ(self):
        succ = {}
        for (a, b) in self.edges:
            succ.setdefault(a, []).append(b)
        return succ

    def _path(self, src, dst):
        """Shortest edge path src -> ... -> dst, or None.  Caller holds
        _mu."""
        if src == dst:
            return [src]
        succ = self._succ()
        seen = {src}
        frontier = [[src]]
        while frontier:
            nxt = []
            for path in frontier:
                for b in succ.get(path[-1], ()):
                    if b in seen:
                        continue
                    if b == dst:
                        return path + [b]
                    seen.add(b)
                    nxt.append(path + [b])
            frontier = nxt
        return None

    def cycles(self):
        """Every distinct cycle in the observed order graph, each as a
        named path closing on its first element.  Empty list = no
        inversion was ever observed."""
        with self._mu:
            succ = self._succ()
            nodes = sorted(set(succ)
                           | {b for bs in succ.values() for b in bs})
            sccs = _tarjan(nodes, succ)
            out = []
            for scc in sccs:
                group = set(scc)
                if len(scc) == 1:
                    n = scc[0]
                    if n not in succ.get(n, ()):
                        continue
                    out.append([n, n])
                    continue
                # one representative cycle: walk within the SCC from
                # its smallest node back to itself
                start = min(scc)
                path = self._scc_cycle(start, group, succ)
                if path:
                    out.append(path)
            return out

    @staticmethod
    def _scc_cycle(start, group, succ):
        seen = {start}
        frontier = [[start]]
        while frontier:
            nxt = []
            for path in frontier:
                for b in succ.get(path[-1], ()):
                    if b == start:
                        return path + [start]
                    if b in group and b not in seen:
                        seen.add(b)
                        nxt.append(path + [b])
            frontier = nxt
        return None

    def order_violations(self):
        """Observed edges that contradict DOCUMENTED_ORDER (held a
        later lock while acquiring an earlier one)."""
        out = []
        with self._mu:
            for (a, b), ent in sorted(self.edges.items()):
                ia, ib = _ORDER_INDEX.get(a), _ORDER_INDEX.get(b)
                if ia is not None and ib is not None and ia > ib:
                    out.append({"held": a, "acquired": b,
                                "count": ent["count"],
                                "site": ent["site"]})
        return out

    def report(self):
        cyc = self.cycles()
        with self._mu:
            edges = [{"from": a, "to": b, "count": e["count"],
                      "site": e["site"]}
                     for (a, b), e in sorted(self.edges.items())]
            locks = {n: dict(v) for n, v in sorted(self.locks.items())}
            findings = list(self.findings)
            acq = self.acquisitions
        return {"mode": "strict" if self.strict else "record",
                "acquisitions": acq, "locks": locks, "edges": edges,
                "cycles": cyc, "findings": findings,
                "order_violations": self.order_violations()}


def _tarjan(nodes, succ):
    """Strongly connected components (iterative Tarjan)."""
    index = {}
    low = {}
    onstack = set()
    stack = []
    sccs = []
    counter = [0]
    for root in nodes:
        if root in index:
            continue
        work = [(root, iter(succ.get(root, ())))]
        index[root] = low[root] = counter[0]
        counter[0] += 1
        stack.append(root)
        onstack.add(root)
        while work:
            node, it = work[-1]
            advanced = False
            for child in it:
                if child not in index:
                    index[child] = low[child] = counter[0]
                    counter[0] += 1
                    stack.append(child)
                    onstack.add(child)
                    work.append((child, iter(succ.get(child, ()))))
                    advanced = True
                    break
                if child in onstack:
                    low[node] = min(low[node], index[child])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    n = stack.pop()
                    onstack.discard(n)
                    scc.append(n)
                    if n == node:
                        break
                sccs.append(sorted(scc))
    return sccs


def _caller_site():
    """file:line of the acquisition site (first frame outside this
    module) — computed only when an edge is FIRST drawn."""
    f = sys._getframe(1)
    here = __file__
    while f is not None and f.f_code.co_filename == here:
        f = f.f_back
    if f is None:
        return "?"
    import os
    return "%s:%d" % (os.path.basename(f.f_code.co_filename),
                      f.f_lineno)


class _NamedLock:
    """A ``threading.Lock``/``RLock`` wrapped with a stable name.  With
    the sanitizer off (``_SANITIZER is None``) an acquisition is the
    raw primitive plus exactly one global load + ``is None`` check —
    the plane off-mode contract."""

    __slots__ = ("_lock", "name", "reentrant")

    def __init__(self, name, reentrant=False):
        self._lock = threading.RLock() if reentrant else threading.Lock()
        self.name = name
        self.reentrant = reentrant

    def __enter__(self):
        san = _SANITIZER
        if san is not None:
            san.acquiring(self.name, self.reentrant)
            try:
                self._lock.acquire()
            except BaseException:
                san.abandon(self.name)
                raise
            return self
        self._lock.acquire()
        return self

    def __exit__(self, *exc):
        san = _SANITIZER
        if san is not None:
            san.released(self.name)
        self._lock.release()
        return False

    def acquire(self, blocking=True, timeout=-1):
        san = _SANITIZER
        if san is None:
            return self._lock.acquire(blocking, timeout)
        if blocking:
            san.acquiring(self.name, self.reentrant)
            try:
                got = self._lock.acquire(blocking, timeout)
            except BaseException:
                san.abandon(self.name)
                raise
            if not got:
                san.abandon(self.name)
            return got
        got = self._lock.acquire(False)
        if got:
            # can't wedge: note post-acquire (edges are identical)
            san.acquiring(self.name, self.reentrant)
        return got

    def release(self):
        san = _SANITIZER
        if san is not None:
            san.released(self.name)
        self._lock.release()

    def locked(self):
        return self._lock.locked()

    def __repr__(self):
        return "<_NamedLock %s%s>" % (self.name,
                                      " (reentrant)" if self.reentrant
                                      else "")


def named_lock(name, reentrant=False):
    """A registry lock: behaves exactly like ``threading.Lock()`` (or
    ``RLock()``) with the sanitizer off; with it on, every acquisition
    records into the process-wide order graph under ``name``."""
    return _NamedLock(name, reentrant)


# ---------------------------------------------------------------------------
# notes for externally-managed locks (the metered _MeshLock keeps its
# own RLock; it calls these around its depth-0 acquisitions)
# ---------------------------------------------------------------------------

def note_acquire(name, reentrant=True):
    san = _SANITIZER
    if san is not None:
        san.acquiring(name, reentrant)


def note_release(name):
    san = _SANITIZER
    if san is not None:
        san.released(name)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def configure(mode=None):
    """Install (record/strict) or clear (off) the process sanitizer.
    None reads conf.DPARK_LOCKCHECK.  Returns the Sanitizer or None."""
    global _SANITIZER
    if mode is None:
        mode = str(getattr(conf, "DPARK_LOCKCHECK", "off") or "off")
    mode = str(mode).strip().lower()
    if mode in ("", "0", "none", "disable", "disabled"):
        mode = "off"
    if mode not in MODES:
        raise ValueError("DPARK_LOCKCHECK=%r (expected "
                         "off|record|strict)" % mode)
    with _install_mu:
        _SANITIZER = (None if mode == "off"
                      else Sanitizer(strict=(mode == "strict")))
        return _SANITIZER


def active():
    return _SANITIZER is not None


def mode():
    san = _SANITIZER
    if san is None:
        return "off"
    return "strict" if san.strict else "record"


def sanitizer():
    return _SANITIZER


def cycles():
    san = _SANITIZER
    return san.cycles() if san is not None else []


def report():
    san = _SANITIZER
    return san.report() if san is not None else {"mode": "off"}


def render_report(rep=None):
    """Human-readable cycle report (the README documents how to read
    it): every observed edge with its first site, then each cycle as a
    named path, then documented-order violations."""
    rep = rep or report()
    lines = ["lockcheck mode=%s acquisitions=%d locks=%d"
             % (rep.get("mode"), rep.get("acquisitions", 0),
                len(rep.get("locks", {})))]
    for e in rep.get("edges", ()):
        lines.append("  edge %-24s -> %-24s x%-5d first at %s"
                     % (e["from"], e["to"], e["count"], e["site"]))
    for c in rep.get("cycles", ()):
        lines.append("  CYCLE %s" % " -> ".join(c))
    for v in rep.get("order_violations", ()):
        lines.append("  ORDER VIOLATION held %s while acquiring %s "
                     "(documented order says the reverse; first at %s)"
                     % (v["held"], v["acquired"], v["site"]))
    for f in rep.get("findings", ()):
        lines.append("  FINDING %s: %s" % (f["kind"], f["detail"]))
    return "\n".join(lines)


class scoped:
    """Context manager installing a FRESH sanitizer and restoring the
    previous one on exit — unit tests draw deliberate cycles without
    polluting the suite-wide recorder CI grades at session end."""

    def __init__(self, mode="record"):
        self._mode = mode

    def __enter__(self):
        global _SANITIZER
        with _install_mu:
            self._prev = _SANITIZER
            _SANITIZER = (None if self._mode == "off"
                          else Sanitizer(strict=(self._mode == "strict")))
            return _SANITIZER

    def __exit__(self, *exc):
        global _SANITIZER
        with _install_mu:
            _SANITIZER = self._prev
        return False


def _init_from_conf():
    m = str(getattr(conf, "DPARK_LOCKCHECK", "off") or "off")
    if m not in ("off", ""):
        configure(m)


_init_from_conf()
