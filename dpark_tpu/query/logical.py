"""Logical plan nodes of the columnar query plane.

TableRDD.select/where/groupBy/join/sort and the SQL ``execute()`` front
end both lower into this tree; the physical planner
(dpark_tpu/query/planner.py) walks it with rewrite rules and compiles
each node onto the device machinery.

The nodes deliberately speak the SAME traversal protocol as the RDD
lineage DAG (`dependencies` entries carrying `.rdd`), so the PR 1 lint
rule engine's walk — analysis.plan_rules.iter_lineage — iterates a
logical plan unchanged.  That is what makes every planner rule a
lintable explanation: rules see the exact artifact the linter can walk.
"""


class _Dep:
    """Edge shim: the lint walk reads `dep.rdd`."""

    __slots__ = ("rdd",)
    is_shuffle = False

    def __init__(self, child):
        self.rdd = child


class Node:
    """Base logical node.  `fields` is the node's output schema (column
    names in order); `children` its inputs."""

    children = ()

    def __init__(self, fields):
        self.fields = list(fields)

    @property
    def dependencies(self):
        return [_Dep(c) for c in self.children]

    @property
    def scope_name(self):
        return type(self).__name__.lower()

    def describe(self):
        return type(self).__name__

    def sketch(self, indent=0):
        out = ["%s%s" % ("  " * indent, self.describe())]
        for c in self.children:
            out.extend(c.sketch(indent + 1))
        return out


def iter_plan(root):
    """Walk every node reachable from `root` exactly once — literally
    the lint engine's lineage walk over the logical tree."""
    from dpark_tpu.analysis.plan_rules import iter_lineage
    return iter_lineage(root)


class Scan(Node):
    """Leaf: a columnar source.  `source` is a TabularRDD (file scan),
    a driver-resident RDD with columnarizable slices
    (ParallelCollection), or a cached RDD resident on the device
    (`device` holds its stats).  The planner's pushdown rules fill `wanted`
    (column pruning), `pushed` (vectorized predicates evaluated over
    column batches before any row exists), and `ranges` (chunk-skip
    {col: (lo, hi)} intervals for the footer-stats pruning)."""

    def __init__(self, source, fields, table_name="table"):
        super().__init__(fields)
        self.source = source
        self.table_name = table_name
        self.wanted = None          # planner: subset of fields to read
        self.pushed = []            # planner: [(ColumnExpr, vec_fn)]
        self.ranges = None          # planner: {col: (lo, hi)}
        self.derived = []           # planner: [(name, ColumnExpr)]
        # a cached RDD whose partitions are columns resident on the
        # device: its load-time stats (TableRDD._resident), else None
        self.device = None

    def describe(self):
        cols = sorted(self.wanted) if self.wanted is not None \
            else "*"
        extra = ""
        if self.pushed:
            extra += " pushed=%d" % len(self.pushed)
        if self.ranges:
            extra += " chunk-skip=%s" % sorted(self.ranges)
        return "Scan(%s cols=%s%s)" % (self.table_name, cols, extra)


class Project(Node):
    """exprs: [(out_name, ColumnExpr)] over the child's fields."""

    def __init__(self, child, exprs):
        super().__init__([n for n, _ in exprs])
        self.children = (child,)
        self.exprs = exprs

    def describe(self):
        return "Project(%s)" % ", ".join(n for n, _ in self.exprs)


class Filter(Node):
    """preds: [ColumnExpr], conjunctive."""

    def __init__(self, child, preds):
        super().__init__(child.fields)
        self.children = (child,)
        self.preds = preds

    def describe(self):
        return "Filter(%s)" % " and ".join(p.expr for p in self.preds)


class GroupAgg(Node):
    """keys: [(out_name, ColumnExpr)]; aggs: [(out_name, func,
    ColumnExpr|None, uda_fn|None)] with func in sum/count/min/max/avg
    or "uda" (a traceable per-group function over the single argument
    column)."""

    def __init__(self, child, keys, aggs):
        super().__init__([n for n, _ in keys] + [a[0] for a in aggs])
        self.children = (child,)
        self.keys = keys
        self.aggs = aggs

    def describe(self):
        return "GroupAgg(keys=%s aggs=%s)" % (
            [n for n, _ in self.keys],
            ["%s:%s" % (a[0], a[1]) for a in self.aggs])


class Join(Node):
    """Equi-join on one column name present in both inputs; output
    schema mirrors TableRDD.join ([on] + left-rest + right-rest with
    uniquified names)."""

    def __init__(self, left, right, on, fields):
        super().__init__(fields)
        self.children = (left, right)
        self.on = on

    def describe(self):
        return "Join(on=%s)" % self.on


class Sort(Node):
    """keys: [ColumnExpr]; applied at egest (result rows are
    driver-resident by then — the coordinator gather-sort)."""

    def __init__(self, child, keys, reverse=False):
        super().__init__(child.fields)
        self.children = (child,)
        self.keys = keys
        self.reverse = reverse

    def describe(self):
        return "Sort(%s%s)" % (", ".join(k.expr for k in self.keys),
                               " desc" if self.reverse else "")


class CachedResult(Node):
    """Leaf standing in for a subtree the result-cache plane served:
    `explain()` shows exactly what was NOT executed.  `replaced` keeps
    the original subtree's one-line describe for the sketch."""

    def __init__(self, fields, replaced, key):
        super().__init__(fields)
        self.replaced = replaced
        self.key = key

    def describe(self):
        return "CachedResult(%s key=%s)" % (self.replaced, self.key)


def plan_signature(node):
    """Canonical, process-stable signature of a logical subtree.

    Unlike `sketch()`/`describe()` this includes every expression TEXT
    (GroupAgg.describe prints only `name:func`, so sum(b) and sum(c)
    would collide on the sketch) — the result-cache plane and the
    `repeated-subplan` lint rule key on this.  Source CONTENT is
    deliberately absent: the cache composes this with a per-file
    fingerprint (tabular.source_fingerprint); the lint rule wants
    shape-equality within one plan.  Raises on expression objects that
    lack `.expr` — callers treat that subtree as unsignable."""
    t = type(node).__name__
    if isinstance(node, Scan):
        return ("Scan", node.table_name, tuple(node.fields))
    if isinstance(node, Project):
        return ("Project",
                tuple((n, ce.expr) for n, ce in node.exprs),
                plan_signature(node.children[0]))
    if isinstance(node, Filter):
        return ("Filter", tuple(sorted(p.expr for p in node.preds)),
                plan_signature(node.children[0]))
    if isinstance(node, GroupAgg):
        return ("GroupAgg",
                tuple((n, ce.expr) for n, ce in node.keys),
                tuple((a[0], a[1],
                       a[2].expr if a[2] is not None else None,
                       # UDAs carry opaque callables: their identity is
                       # not content-stable, so mark them unsignable-ish
                       # by name only (cache callers reject UDA plans)
                       getattr(a[3], "__name__", None)
                       if a[3] is not None else None)
                      for a in node.aggs),
                plan_signature(node.children[0]))
    if isinstance(node, Join):
        return ("Join", node.on, tuple(node.fields),
                plan_signature(node.children[0]),
                plan_signature(node.children[1]))
    if isinstance(node, Sort):
        return ("Sort", tuple(k.expr for k in node.keys),
                bool(node.reverse),
                plan_signature(node.children[0]))
    if isinstance(node, CachedResult):
        return ("CachedResult", node.key)
    return (t,) + tuple(plan_signature(c) for c in node.children)
