"""Static concurrency sanitizer rules (ISSUE 16) — the static half of
the lockcheck plane (the runtime half is ``dpark_tpu.locks``).

One pass over the package AST inventories every lock definition and
acquisition site, builds the static lock-order graph (lexical ``with``
nesting plus a transitive closure over same-module calls), and reports:

  lock-order-cycle     two code paths acquire the same pair of locks
                       in opposite orders — the PR 3 / PR 9 deadlock
                       shape, flagged without running anything.
  blocking-under-lock  a call that can block indefinitely (socket
                       recv/connect, ``open``, zero-arg ``queue.get``/
                       ``Condition.wait``, subprocess waits,
                       ``time.sleep``) is reachable while holding the
                       MESH lock — every tenant's device work queues
                       behind it.
  unbounded-wait       ``.get()`` / ``.wait()`` / ``.join()`` with no
                       timeout anywhere in the package: a lost peer or
                       dead worker thread parks the caller forever
                       instead of surfacing a recoverable failure.
  thread-leak          a non-daemon ``threading.Thread`` with no
                       visible ``join`` path — interpreter exit hangs
                       on it.
  plane-contract       each observability plane's documented off-mode
                       seam (one attribute load + ``is None`` check on
                       the hot path, no allocation) is verified by
                       shape, not by review — the machine check behind
                       the ``<=1.03x overhead when off`` bar.

Lock identity is canonical: a lock minted by ``locks.named_lock("x")``
is node ``x`` (matching the DYNAMIC sanitizer's graph), a raw
``threading.Lock()`` bound to an attribute is ``<module>.<Class>.<attr>``,
and ``_MeshLock()`` is ``executor.mesh``.  Aliases
(``self._export_lock = self._mesh_lock``) resolve to their target.
"""

import ast
import os
import re

from dpark_tpu.analysis.report import Report

MESH_LOCKS = frozenset(["executor.mesh"])

# blocking-call classifier: dotted-tail -> human name.  Zero-arg .get/
# .wait/.join are classified separately (arg shape disambiguates them
# from dict.get / str.join).
_SOCKET_METHODS = {"recv", "recvfrom", "recv_into", "recvmsg",
                   "accept", "connect", "sendall"}
_SUBPROCESS_FNS = {"check_call", "check_output", "communicate"}

_LOCKISH = re.compile(r"lock", re.I)


class _FnInfo:
    __slots__ = ("qual", "acquires", "edges", "calls", "blocking")

    def __init__(self, qual):
        self.qual = qual
        self.acquires = []      # (lockname, lineno)
        self.edges = []         # (held, acquired, lineno) lexical
        self.calls = []         # (callee_qual, lineno, held tuple)
        self.blocking = []      # (kind, lineno, held tuple)


class _ModuleInfo:
    __slots__ = ("path", "rel", "mod", "lockdefs", "fns", "funcs",
                 "daemonized", "joined", "thread_sites")

    def __init__(self, path, rel, mod):
        self.path = path
        self.rel = rel
        self.mod = mod
        self.lockdefs = {}      # (class or "", attr) -> canonical name
        self.fns = {}           # qual ("mod.Class.meth") -> _FnInfo
        self.funcs = set()      # defined function quals
        self.daemonized = set() # names with .daemon = True / setDaemon
        self.joined = set()     # names with a .join( call
        self.thread_sites = [] # (lineno, target name or None, has_daemon)


def _dotted(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_lock_factory(call):
    """Canonical suffix for a lock-minting call, or None.
    Returns ("raw", None) for threading.Lock/RLock, ("named", name)
    for locks.named_lock("name"), ("mesh", None) for _MeshLock()."""
    if not isinstance(call, ast.Call):
        return None
    dotted = _dotted(call.func) or ""
    tail = dotted.split(".")[-1]
    if tail in ("Lock", "RLock") and (
            dotted.startswith("threading.") or dotted in ("Lock",
                                                          "RLock")):
        return ("raw", None)
    if tail == "named_lock":
        if call.args and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, str):
            return ("named", call.args[0].value)
        return ("named", None)
    if tail == "_MeshLock":
        return ("mesh", None)
    return None


class _DefCollector(ast.NodeVisitor):
    """Pass 1: lock definitions (module and class scope, including
    aliases), thread daemon/join evidence, function inventory."""

    def __init__(self, mi):
        self.mi = mi
        self._class = ""
        self._fn_depth = 0
        self._raw = []          # (scope, attr, value-expr) for aliases

    def visit_ClassDef(self, node):
        saved, self._class = self._class, node.name
        self.generic_visit(node)
        self._class = saved

    def visit_FunctionDef(self, node):
        qual = self._qual(node.name)
        self.mi.funcs.add(qual)
        self._fn_depth += 1
        self.generic_visit(node)
        self._fn_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def _qual(self, name):
        return ("%s.%s.%s" % (self.mi.mod, self._class, name)
                if self._class else "%s.%s" % (self.mi.mod, name))

    def visit_Assign(self, node):
        for t in node.targets:
            scope = attr = None
            if isinstance(t, ast.Name):
                if not self._fn_depth:
                    # module scope, or a class-body attribute (reached
                    # as self.<name> from methods)
                    scope, attr = self._class, t.id
                else:
                    attr = t.id     # function-local: threads only
            elif isinstance(t, ast.Attribute) \
                    and isinstance(t.value, ast.Name) \
                    and t.value.id == "self":
                scope, attr = self._class, t.attr
                if t.attr == "daemon" \
                        and isinstance(node.value, ast.Constant) \
                        and node.value.value:
                    continue
            elif isinstance(t, ast.Attribute) and t.attr == "daemon" \
                    and isinstance(node.value, ast.Constant) \
                    and node.value.value \
                    and isinstance(t.value, ast.Name):
                # t.daemon = True on a local thread object
                self.mi.daemonized.add(t.value.id)
                continue
            else:
                continue
            if scope is not None:
                kind = _is_lock_factory(node.value)
                if kind is not None:
                    fam, name = kind
                    if fam == "named" and name:
                        canon = name
                    elif fam == "mesh":
                        canon = "executor.mesh"
                    else:
                        canon = ("%s.%s.%s"
                                 % (self.mi.mod, scope, attr)
                                 if scope else
                                 "%s.%s" % (self.mi.mod, attr))
                    self.mi.lockdefs[(scope, attr)] = canon
                else:
                    self._raw.append((scope, attr, node.value))
            # thread assignment bookkeeping (any scope)
            if isinstance(node.value, ast.Call):
                d = _dotted(node.value.func) or ""
                if d.split(".")[-1] == "Thread" \
                        and d.startswith("threading"):
                    has_daemon = any(
                        kw.arg == "daemon"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value
                        for kw in node.value.keywords)
                    self.mi.thread_sites.append(
                        (node.value.lineno, attr, has_daemon))
        self.generic_visit(node)

    def visit_Call(self, node):
        d = _dotted(node.func) or ""
        tail = d.split(".")[-1]
        if tail == "join" and isinstance(node.func, ast.Attribute):
            base = node.func.value
            name = base.attr if isinstance(base, ast.Attribute) \
                else (base.id if isinstance(base, ast.Name) else None)
            if name:
                self.mi.joined.add(name)
        elif tail == "setDaemon" \
                and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value:
            self.mi.daemonized.add(node.func.value.id)
        elif tail == "Thread" and d.startswith("threading"):
            # bare threading.Thread(...).start() with no assignment
            has_daemon = any(kw.arg == "daemon"
                             and isinstance(kw.value, ast.Constant)
                             and kw.value.value
                             for kw in node.keywords)
            self.mi.thread_sites.append((node.lineno, None, has_daemon))
        self.generic_visit(node)

    def resolve_aliases(self):
        """self.X = self.Y / X = Y where the RHS is a known lock: two
        rounds close simple forward chains."""
        for _ in range(2):
            for scope, attr, value in self._raw:
                canon = self._lock_of(value, scope)
                if canon is not None:
                    self.mi.lockdefs.setdefault((scope, attr), canon)

    def _lock_of(self, expr, scope):
        if isinstance(expr, ast.Name):
            return self.mi.lockdefs.get(("", expr.id))
        if isinstance(expr, ast.Attribute) \
                and isinstance(expr.value, ast.Name) \
                and expr.value.id == "self":
            return self.mi.lockdefs.get((scope, expr.attr))
        return None


class _FnWalker:
    """Pass 2: walk one function body tracking the held-lock stack,
    recording acquisitions, lexical order edges, calls (with held
    context), and blocking calls."""

    def __init__(self, mi, cls, fn_node):
        self.mi = mi
        self.cls = cls
        qual = ("%s.%s.%s" % (mi.mod, cls, fn_node.name) if cls
                else "%s.%s" % (mi.mod, fn_node.name))
        self.fi = _FnInfo(qual)
        self.held = []

    def run(self, fn_node):
        for stmt in fn_node.body:
            self._stmt(stmt)
        return self.fi

    # -- resolution ------------------------------------------------------
    def _resolve_lock(self, expr):
        if isinstance(expr, ast.Name):
            return self.mi.lockdefs.get(("", expr.id))
        if isinstance(expr, ast.Attribute) \
                and isinstance(expr.value, ast.Name) \
                and expr.value.id == "self":
            canon = self.mi.lockdefs.get((self.cls, expr.attr))
            if canon is not None:
                return canon
            if _LOCKISH.search(expr.attr):
                # lockish attribute with no visible definition (set by
                # a collaborator): still a node, scoped to the class
                return ("%s.%s.%s" % (self.mi.mod, self.cls, expr.attr)
                        if self.cls else
                        "%s.%s" % (self.mi.mod, expr.attr))
        return None

    def _resolve_callee(self, call):
        fn = call.func
        if isinstance(fn, ast.Name):
            qual = "%s.%s" % (self.mi.mod, fn.id)
            return qual if qual in self.mi.funcs else None
        if isinstance(fn, ast.Attribute) \
                and isinstance(fn.value, ast.Name) \
                and fn.value.id == "self" and self.cls:
            qual = "%s.%s.%s" % (self.mi.mod, self.cls, fn.attr)
            return qual if qual in self.mi.funcs else None
        return None

    # -- walk ------------------------------------------------------------
    def _stmt(self, node):
        if isinstance(node, ast.With):
            locks = []
            for item in node.items:
                canon = self._resolve_lock(item.context_expr)
                self._expr(item.context_expr)
                if canon is None:
                    continue
                self.fi.acquires.append((canon, node.lineno))
                for h in self.held:
                    if h != canon:
                        self.fi.edges.append((h, canon, node.lineno))
                self.held.append(canon)
                locks.append(canon)
            for stmt in node.body:
                self._stmt(stmt)
            for canon in reversed(locks):
                self.held.pop()
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def: walked with an EMPTY held stack — it runs
            # later, not here (closures that demonstrably run inline
            # are beyond a static pass; the dynamic sanitizer covers
            # them)
            saved, self.held = self.held, []
            for stmt in node.body:
                self._stmt(stmt)
            self.held = saved
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.stmt):
                self._stmt(child)
            else:
                self._expr(child)

    def _expr(self, node):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self._call(sub)

    def _call(self, node):
        held = tuple(self.held)
        callee = self._resolve_callee(node)
        if callee is not None:
            self.fi.calls.append((callee, node.lineno, held))
        kind = _blocking_kind(node)
        if kind is not None:
            self.fi.blocking.append((kind, node.lineno, held))


def _blocking_kind(call):
    """Human name of a potentially-unbounded blocking call, or None."""
    fn = call.func
    dotted = _dotted(fn) or ""
    tail = dotted.split(".")[-1] if dotted else (
        fn.attr if isinstance(fn, ast.Attribute) else "")
    nargs = len(call.args)
    kwargs = {kw.arg for kw in call.keywords}
    if isinstance(fn, ast.Name) and fn.id == "open":
        return "open()"
    if tail in _SOCKET_METHODS:
        return "socket .%s()" % tail
    if tail in _SUBPROCESS_FNS or (
            dotted.startswith("subprocess.") and tail in ("run",
                                                          "call")):
        return "subprocess %s()" % tail
    if dotted == "time.sleep":
        return "time.sleep()"
    if not isinstance(fn, ast.Attribute):
        return None
    # zero-arg shapes: dict.get/str.join always take positional args,
    # so an argless .get()/.join()/.wait() is the queue/thread/
    # condition form.  A timeout= keyword (or block+timeout) bounds it.
    if tail == "get" and nargs == 0 and "timeout" not in kwargs \
            and kwargs <= {"block"}:
        return "queue .get() without timeout"
    if tail == "wait" and nargs == 0 and "timeout" not in kwargs \
            and not kwargs:
        return ".wait() without timeout"
    if tail == "join" and nargs == 0 and "timeout" not in kwargs \
            and not kwargs \
            and not isinstance(fn.value, ast.Constant):
        return ".join() without timeout"
    return None


# ---------------------------------------------------------------------------
# plane-contract verification
# ---------------------------------------------------------------------------

# Every observability plane's hot-path seam: (file, function qualname,
# plane-global expression).  The rule verifies each is EXACTLY the
# documented off-mode shape — one load of the global, immediately
# guarded by a pure `is None` / `is not None` test, with nothing
# allocated or called on the off path.  A seam that cannot be found
# fails too: manifest drift must be loud.
PLANE_SEAMS = (
    ("faults.py", "hit", "_PLANE"),
    ("trace.py", "span", "_PLANE"),
    ("trace.py", "event", "_PLANE"),
    ("trace.py", "emit", "_PLANE"),
    ("trace.py", "ctx", "_PLANE"),
    ("trace.py", "TracePlane.record", "_health._SINK"),
    ("trace.py", "TracePlane.record", "_ledger._SINK"),
    ("locks.py", "_NamedLock.__enter__", "_SANITIZER"),
    ("locks.py", "_NamedLock.__exit__", "_SANITIZER"),
    ("locks.py", "note_acquire", "_SANITIZER"),
    ("locks.py", "note_release", "_SANITIZER"),
    ("aotcache.py", "set_current_sig", "_PLANE"),
    ("aotcache.py", "stats", "_PLANE"),
    ("resultcache.py", "probe", "_PLANE"),
    ("resultcache.py", "offer", "_PLANE"),
    ("resultcache.py", "stats", "_PLANE"),
    ("backend/tpu/executor.py", "_ProgramCache.__setitem__",
     "aotcache._PLANE"),
    # the host-side spans of the array path (plan, launch, eager,
    # readback, egest, ingest, hbm.spill): off, each site is this one
    # check
    ("backend/tpu/__init__.py", "TPUScheduler._analyze", "trace._PLANE"),
    ("backend/tpu/executor.py", "JAXExecutor._launch", "trace._PLANE"),
    ("backend/tpu/executor.py", "JAXExecutor._source_outs",
     "trace._PLANE"),
    ("backend/tpu/executor.py", "JAXExecutor._spill_shuffle_to_disk",
     "trace._PLANE"),
    ("backend/tpu/executor.py", "JAXExecutor._check_cached_keys",
     "trace._PLANE"),
    ("backend/tpu/layout.py", "host_read", "trace._PLANE"),
    ("backend/tpu/layout.py", "egest", "trace._PLANE"),
    # the driver's way in and out of a job (preflight, job.begin,
    # stage.run, job.finish, store.release)
    ("context.py", "DparkContext.runJob", "trace._PLANE"),
    ("schedule.py", "DAGScheduler._begin_job", "trace._PLANE"),
    ("schedule.py", "DAGScheduler._run_tasks", "trace._PLANE"),
    ("schedule.py", "DAGScheduler._finish_job", "trace._PLANE"),
    ("backend/tpu/__init__.py", "TPUScheduler._drain_unreachable",
     "trace._PLANE"),
    # the two children of stage.run (adapt.path, result.rows)
    ("backend/tpu/__init__.py", "TPUScheduler._adapt_span",
     "trace._PLANE"),
    ("backend/tpu/__init__.py", "TPUScheduler._run_array_stage",
     "trace._PLANE"),
    # the query plane's two spans (query.plan, measured here and
    # emitted by PlannedQuery._job under the job's id; query.finish)
    ("query/planner.py", "plan_query", "trace._PLANE"),
    ("query/planner.py", "PlannedQuery._run", "trace._PLANE"),
)


def _match_global(node, dotted):
    if "." in dotted:
        head, _, tail = dotted.partition(".")
        return (isinstance(node, ast.Attribute) and node.attr == tail
                and isinstance(node.value, ast.Name)
                and node.value.id == head)
    return isinstance(node, ast.Name) and node.id == dotted \
        and isinstance(node.ctx, ast.Load)


def _find_fn(tree, qualname):
    cls, _, meth = qualname.rpartition(".")
    if cls:
        for node in tree.body:
            if isinstance(node, ast.ClassDef) and node.name == cls:
                for sub in node.body:
                    if isinstance(sub, ast.FunctionDef) \
                            and sub.name == meth:
                        return sub
        return None
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == qualname:
            return node
    return None


def _stmt_lists(fn_node):
    yield fn_node.body
    for node in ast.walk(fn_node):
        for field in ("body", "orelse", "finalbody"):
            block = getattr(node, field, None)
            if isinstance(block, list) and node is not fn_node \
                    and block and isinstance(block[0], ast.stmt):
                yield block


def _is_simple(expr):
    """No allocation/calls: Constant, Name, or a plain attribute."""
    if expr is None:
        return True
    return isinstance(expr, (ast.Constant, ast.Name, ast.Attribute))


def _guard_test(test, local):
    """(form, ok): test must be `<local> is None` / `is not None`."""
    if not (isinstance(test, ast.Compare) and len(test.ops) == 1
            and isinstance(test.left, ast.Name)
            and test.left.id == local
            and isinstance(test.comparators[0], ast.Constant)
            and test.comparators[0].value is None):
        return None
    if isinstance(test.ops[0], ast.Is):
        return "is-none"
    if isinstance(test.ops[0], ast.IsNot):
        return "is-not-none"
    return None


def check_plane_seam(tree, qualname, dotted):
    """None when the seam is exactly the documented shape, else a
    (lineno, problem) tuple.

    The contract is about the OFF path: the FIRST load of the plane
    global must be a pure ``is None`` guard whose off branch does no
    work, and every later load must be reachable only when the plane
    is on (after an is-None guard that returned, or inside an
    is-not-None body)."""
    fn = _find_fn(tree, qualname)
    if fn is None:
        return (1, "hot-path function %r not found (manifest drift?)"
                % qualname)
    loads = sorted((n for n in ast.walk(fn)
                    if _match_global(n, dotted)),
                   key=lambda n: (n.lineno, n.col_offset))
    if not loads:
        return (fn.lineno, "no load of %s on the hot path (manifest "
                "drift?)" % dotted)
    load = loads[0]
    rest = loads[1:]
    for block in _stmt_lists(fn):
        for i, stmt in enumerate(block):
            # form (b): `if GLOBAL is None: return <simple>`
            if isinstance(stmt, ast.If) \
                    and isinstance(stmt.test, ast.Compare) \
                    and len(stmt.test.ops) == 1 \
                    and isinstance(stmt.test.ops[0], ast.Is) \
                    and stmt.test.left is load:
                comp = stmt.test.comparators[0]
                if not (isinstance(comp, ast.Constant)
                        and comp.value is None):
                    return (stmt.lineno, "guard compares %s against a "
                            "non-None value" % dotted)
                if not (stmt.body
                        and isinstance(stmt.body[0], ast.Return)
                        and _is_simple(stmt.body[0].value)):
                    return (stmt.lineno, "off path is not a plain "
                            "return (allocation on the off path)")
                # later loads run only after the guard returned: on-path
                for n in rest:
                    if n.lineno <= stmt.lineno:
                        return (n.lineno, "extra load of %s before "
                                "the off-mode guard" % dotted)
                return None
            # form (a): `x = GLOBAL` + adjacent guard on x
            if isinstance(stmt, ast.Assign) and stmt.value is load:
                if rest:
                    return (rest[0].lineno, "%s loaded again after "
                            "being bound to a local — use the local"
                            % dotted)
                if len(stmt.targets) != 1 \
                        or not isinstance(stmt.targets[0], ast.Name):
                    return (stmt.lineno, "plane global must bind to "
                            "one plain local")
                local = stmt.targets[0].id
                if i + 1 >= len(block) \
                        or not isinstance(block[i + 1], ast.If):
                    return (stmt.lineno, "load of %s is not "
                            "immediately guarded" % dotted)
                guard = block[i + 1]
                form = _guard_test(guard.test, local)
                if form is None:
                    return (guard.lineno, "guard is not a pure "
                            "`%s is None` test" % local)
                if form == "is-none":
                    if guard.orelse:
                        return (guard.lineno, "is-None guard carries "
                                "an else branch")
                    if not (guard.body
                            and isinstance(guard.body[0], ast.Return)
                            and _is_simple(guard.body[0].value)):
                        return (guard.lineno, "off path is not a "
                                "plain return")
                    return None
                # is-not-none: every other use of the local must live
                # inside this guard (the off path falls through doing
                # nothing)
                inside = {id(n) for n in ast.walk(guard)}
                for n in ast.walk(fn):
                    if isinstance(n, ast.Name) and n.id == local \
                            and n is not stmt.targets[0] \
                            and n is not guard.test.left \
                            and id(n) not in inside:
                        return (n.lineno, "local %r escapes its "
                                "is-not-None guard" % local)
                return None
    return (load.lineno, "load of %s is neither bound to a guarded "
            "local nor tested directly" % dotted)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

class ConcurrencyPass:
    def __init__(self, root=None, mesh_locks=MESH_LOCKS):
        self.root = root
        self.mesh_locks = frozenset(mesh_locks)
        self.modules = []
        self._parse_errors = []

    def add_source(self, path, text=None):
        if text is None:
            with open(path, "r", encoding="utf-8",
                      errors="replace") as f:
                text = f.read()
        try:
            tree = ast.parse(text, filename=path)
        except SyntaxError:
            return                  # closure_rules already reports it
        rel = os.path.relpath(path, self.root).replace(os.sep, "/") \
            if self.root else path
        mod = os.path.splitext(os.path.basename(path))[0]
        mi = _ModuleInfo(path, rel, mod)
        coll = _DefCollector(mi)
        coll.visit(tree)
        coll.resolve_aliases()
        self._walk_functions(mi, tree)
        self.modules.append(mi)

    @staticmethod
    def _walk_functions(mi, tree):
        def walk(nodes, cls):
            for node in nodes:
                if isinstance(node, ast.ClassDef):
                    walk(node.body, node.name)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    fi = _FnWalker(mi, cls, node).run(node)
                    mi.fns[fi.qual] = fi
        walk(tree.body, "")

    # -- closures --------------------------------------------------------
    def _closures(self):
        """Transitive (locks, blocking-kinds) acquired/reached by each
        function through same-module calls — bounded fixpoint."""
        own_locks, own_block, callees, fn_mod = {}, {}, {}, {}
        for mi in self.modules:
            for qual, fi in mi.fns.items():
                own_locks[qual] = {l for l, _ in fi.acquires}
                own_block[qual] = {k for k, _, _ in fi.blocking}
                callees[qual] = {c for c, _, _ in fi.calls}
                fn_mod[qual] = mi
        clo_locks = {q: set(s) for q, s in own_locks.items()}
        clo_block = {q: set(s) for q, s in own_block.items()}
        for _ in range(16):
            changed = False
            for q, cs in callees.items():
                for c in cs:
                    if c in clo_locks:
                        before = len(clo_locks[q]) + len(clo_block[q])
                        clo_locks[q] |= clo_locks[c]
                        clo_block[q] |= clo_block[c]
                        if len(clo_locks[q]) + len(clo_block[q]) \
                                != before:
                            changed = True
            if not changed:
                break
        return clo_locks, clo_block

    def finish(self, report=None):
        report = report if report is not None else Report()
        clo_locks, clo_block = self._closures()

        # -- global lock-order graph ------------------------------------
        edges = {}              # (a, b) -> site

        def add_edge(a, b, site):
            if a != b and (a, b) not in edges:
                edges[(a, b)] = site

        for mi in self.modules:
            for fi in mi.fns.values():
                for a, b, lineno in fi.edges:
                    add_edge(a, b, "%s:%d" % (mi.rel, lineno))
                for callee, lineno, held in fi.calls:
                    if not held:
                        continue
                    for l in clo_locks.get(callee, ()):
                        if l in held:
                            # a lock the caller already holds is a
                            # reentrant re-acquire in the callee (the
                            # mesh RLock under _export_bucket), not a
                            # fresh ordering edge
                            continue
                        for h in held:
                            add_edge(h, l,
                                     "%s:%d" % (mi.rel, lineno))

        succ = {}
        for (a, b) in edges:
            succ.setdefault(a, []).append(b)
        from dpark_tpu.locks import _tarjan
        nodes = sorted(set(succ)
                       | {b for bs in succ.values() for b in bs})
        for scc in _tarjan(nodes, succ):
            group = set(scc)
            cyc = None
            if len(scc) > 1:
                cyc = _scc_path(min(scc), group, succ)
            elif scc[0] in succ.get(scc[0], ()):
                cyc = [scc[0], scc[0]]
            if not cyc:
                continue
            sites = [edges.get((cyc[i], cyc[i + 1]), "?")
                     for i in range(len(cyc) - 1)]
            report.add(
                "lock-order-cycle", "error",
                "%s cycle(%s)" % (sites[0], ",".join(sorted(group))),
                "static lock-order cycle: %s (edge sites: %s) — two "
                "threads interleaving these paths deadlock"
                % (" -> ".join(cyc), ", ".join(sites)),
                "pick one global order (see locks.DOCUMENTED_ORDER) "
                "and release the earlier lock before taking the later "
                "one on every path")

        # -- blocking-under-lock / unbounded-wait / thread-leak ---------
        for mi in self.modules:
            for fi in mi.fns.values():
                for kind, lineno, held in fi.blocking:
                    site = "%s:%d" % (mi.rel, lineno)
                    if any(h in self.mesh_locks for h in held):
                        report.add(
                            "blocking-under-lock", "warn", site,
                            "%s while holding the mesh lock: every "
                            "tenant's device dispatch queues behind "
                            "this call" % kind,
                            "move the blocking operation outside the "
                            "lock, or bound it with a timeout")
                    if "without timeout" in kind:
                        report.add(
                            "unbounded-wait", "warn", site,
                            "%s: a dead peer or worker parks this "
                            "thread forever instead of surfacing a "
                            "recoverable failure" % kind,
                            "pass timeout= and translate expiry into "
                            "the caller's failure path (FetchFailed, "
                            "retry, or abort)")
                for callee, lineno, held in fi.calls:
                    if not any(h in self.mesh_locks for h in held):
                        continue
                    kinds = clo_block.get(callee, ())
                    if kinds:
                        report.add(
                            "blocking-under-lock", "warn",
                            "%s:%d" % (mi.rel, lineno),
                            "call to %s() under the mesh lock reaches "
                            "a blocking operation (%s)"
                            % (callee, ", ".join(sorted(kinds))),
                            "hoist the blocking work out of the "
                            "locked region")
            named_lines = {l for l, t, _ in mi.thread_sites
                           if t is not None}
            for lineno, target, has_daemon in mi.thread_sites:
                if has_daemon:
                    continue
                if target is None and lineno in named_lines:
                    continue    # same call seen via its assignment

                if target is not None and (target in mi.daemonized
                                           or target in mi.joined):
                    continue
                report.add(
                    "thread-leak", "warn", "%s:%d" % (mi.rel, lineno),
                    "non-daemon thread%s has no visible join path: "
                    "interpreter exit hangs on it"
                    % ("" if target is None else " %r" % target),
                    "pass daemon=True, or join it on the shutdown "
                    "path")

        # -- plane contracts --------------------------------------------
        self._check_planes(report)
        return report

    def _check_planes(self, report):
        pkg = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        cache = {}
        for relfile, qualname, dotted in PLANE_SEAMS:
            path = os.path.join(pkg, relfile)
            tree = cache.get(path)
            if tree is None:
                try:
                    with open(path, "r", encoding="utf-8") as f:
                        tree = ast.parse(f.read(), filename=path)
                except (OSError, SyntaxError) as e:
                    report.add("plane-contract", "error",
                               "dpark_tpu/%s %s" % (relfile, qualname),
                               "plane module unreadable: %s" % e)
                    continue
                cache[path] = tree
            bad = check_plane_seam(tree, qualname, dotted)
            if bad is not None:
                lineno, problem = bad
                report.add(
                    "plane-contract", "error",
                    "dpark_tpu/%s:%d %s[%s]" % (relfile, lineno,
                                                qualname, dotted),
                    "off-mode seam violated: %s" % problem,
                    "the hot path must be exactly one load of the "
                    "plane global guarded by a pure `is None` check "
                    "with nothing allocated when off — the <=1.03x "
                    "overhead bar depends on it")


def _scc_path(start, group, succ):
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


def lint_concurrency(paths, report=None, root=None):
    """Run the concurrency rule families over `paths` (files); the
    plane-contract manifest is always checked against the installed
    package regardless of `paths`.  Returns the Report."""
    report = report if report is not None else Report()
    p = ConcurrencyPass(root=root)
    for path in paths:
        p.add_source(path)
    p.finish(report)
    return report
