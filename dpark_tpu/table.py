"""Table DSL: schema'd RDDs of named rows with expression select/where,
grouped aggregation, sort and joins.

Reference parity: dpark/table.py (SURVEY.md section 2.3) — a TableRDD wraps
an RDD of namedtuple rows; string expressions are compiled with eval
against the row's fields; groupBy supports sum/count/avg/min/max and
approximate distinct count (HyperLogLog, dpark/hyperloglog.py analog in
dpark_tpu/hyperloglog.py).  Exact method shapes follow this framework's
conventions; the surface (select/where/groupBy/sort/top/join/collect) is
the reference's.

Columnar query plane (ISSUE 13): every DSL call ALSO lowers into a
logical plan (dpark_tpu/query/) when its source is a columnar scan
(tabular part files, parallelize slices, or a cached RDD whose columns
are resident on the device) and its expressions parse.
Actions (collect/take/count/top) then ask the rule-driven physical
planner to compile the plan onto the device path — pruned vectorized
scans, device exchanges for group-by/join, egest-side result finishing
— and fall back to the eager host RDD chain below (which is always
built, lazily, alongside) whenever any operator declines; the decline
reasons ride `_query_fallbacks` for the `table-host-fallback` lint
rule and the planner's decision log.  `DPARK_QUERY=0` pins every
action to the host chain (the pre-plan behavior, and the bench A/B's
baseline side).
"""

import functools
import re
import time
from collections import namedtuple

from dpark_tpu.utils.log import get_logger

logger = get_logger("table")

_AGG_RE = re.compile(
    r"^\s*(count|sum|avg|min|max|adcount|first|group_concat)\s*"
    r"\(\s*(.*?)\s*\)\s*$", re.I)
_AS_RE = re.compile(r"^(.*?)\s+as\s+(\w+)\s*$", re.I)


def _compile_expr(expr, fields):
    """Compile a string expression over row fields into row -> value.

    SECURITY NOTE: expression strings are CODE, at the same trust level
    as a lambda passed to .map() — the restricted-builtins dict below
    blocks accidents, not adversaries (attribute traversal escapes any
    eval sandbox).  Never feed untrusted input to ctx.sql / where /
    select; this matches the reference, whose table layer also evals
    user expressions (dpark/table.py [L])."""
    code = compile(expr, "<table:%s>" % expr, "eval")

    def run(row):
        env = dict(zip(fields, row))
        return eval(code, {"__builtins__": _SAFE_BUILTINS}, env)
    run.expr = expr
    return run


_SAFE_BUILTINS = {
    "abs": abs, "min": min, "max": max, "len": len, "round": round,
    "int": int, "float": float, "str": str, "bool": bool, "sum": sum,
    "True": True, "False": False, "None": None,
}


def _branchless_min(a, b):
    """min that the device tracer can see through: `a if a <= b else b`
    forces a concrete bool, so group-by min/max would demote the whole
    aggregate shuffle to the host object path (VERDICT r3 #8 — the
    Table DSL must inherit the core's device speed).  Host objects
    (strings, dates) keep exact Python comparison semantics."""
    try:
        import jax
        if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
            import jax.numpy as jnp
            # jnp.where(a <= b, a, b), NOT jnp.minimum: minimum
            # propagates NaN where the host comparison returns b —
            # device and host float min must agree on NaN rows
            # (ADVICE r4)
            return jnp.where(a <= b, a, b)
    except ImportError:
        pass
    return a if a <= b else b


def _branchless_max(a, b):
    try:
        import jax
        if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
            import jax.numpy as jnp
            # mirror the host's `a if a >= b else b` NaN behavior
            # (ADVICE r4; see _branchless_min)
            return jnp.where(a >= b, a, b)
    except ImportError:
        pass
    return a if a >= b else b


def _branchless_div(a, b):
    """avg's finalize without a concrete-bool branch (device rows only
    exist for observed keys, so the count is never 0 there; the host
    path keeps the divide-by-zero -> None convention)."""
    try:
        import jax
        if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
            return a / b
    except ImportError:
        pass
    return a / b if b else None


class _Agg:
    """One aggregate column: (create, merge, combine, finalize)."""

    def __init__(self, func, arg_fn, name):
        self.func = func
        self.arg_fn = arg_fn
        self.name = name

    def create(self, row):
        f = self.func
        if f == "count":
            if self.arg_fn is None:
                return 1
            return 0 if self.arg_fn(row) is None else 1
        v = self.arg_fn(row)
        if f == "sum":
            return v
        if f == "avg":
            return (v, 1)
        if f in ("min", "max", "first"):
            return v
        if f == "adcount":
            from dpark_tpu.hyperloglog import HyperLogLog
            h = HyperLogLog()
            h.add(v)
            return h
        if f == "group_concat":
            return [v]
        raise ValueError("unknown aggregate %r" % f)

    def merge(self, acc, row):
        return self.combine(acc, self.create(row))

    def combine(self, a, b):
        f = self.func
        if f in ("count", "sum"):
            return a + b
        if f == "avg":
            return (a[0] + b[0], a[1] + b[1])
        if f == "min":
            return _branchless_min(a, b)
        if f == "max":
            return _branchless_max(a, b)
        if f == "first":
            return a
        if f == "adcount":
            a.update(b)
            return a
        if f == "group_concat":
            a.extend(b)
            return a
        raise ValueError(f)

    def finalize(self, acc):
        f = self.func
        if f == "avg":
            return _branchless_div(acc[0], acc[1])
        if f == "adcount":
            return len(acc)
        if f == "group_concat":
            return ",".join(str(x) for x in acc)
        return acc


def _parse_column(col, fields, index):
    """'expr as name' | 'agg(expr)' | 'name' -> (name, fn_or_agg)."""
    name = None
    m = _AS_RE.match(col)
    if m:
        col, name = m.group(1), m.group(2)
    m = _AGG_RE.match(col)
    if m:
        func, arg = m.group(1).lower(), m.group(2)
        arg_fn = None
        if arg and arg != "*":
            arg_fn = _compile_expr(arg, fields)
        agg_name = name or ("%s_%s" % (func, arg.replace("*", "all")
                                       .replace("(", "").replace(")", "")
                                       .strip() or "all"))
        agg_name = re.sub(r"\W+", "_", agg_name).strip("_") or \
            ("agg%d" % index)
        return agg_name, _Agg(func, arg_fn, agg_name)
    if col in fields:
        return name or col, _compile_expr(col, fields)
    return (name or ("col%d" % index)), _compile_expr(col, fields)


def _pin_exact(rdd, reason):
    """The planner could not prove a product or a sum of this table's
    aggregation inside int64, where the device wraps: every combine of
    the row chain (`_AggCombine`) is pinned to the object path, which
    folds exact Python ints.  The reason is each stage's
    fallback_reason (fuse.analyze_stage reads `host_reason`)."""
    seen, todo = set(), [rdd]
    while todo:
        r = todo.pop()
        if r.id in seen:
            continue
        seen.add(r.id)
        for dep in r.dependencies:
            agg = getattr(dep, "aggregator", None)
            if isinstance(getattr(agg, "merge_combiners", None),
                          _AggCombine):
                dep.host_reason = ("the query plane declined (%s): the "
                                   "aggregation folds Python ints on the "
                                   "host" % reason)
            todo.append(dep.rdd)


class _UDA:
    """User-defined aggregate marker for groupBy: a traceable
    per-group function over one argument column's value list.  On the
    host path the values fold as a Python list; on the device plan the
    same function rides the SegMapOp segmented apply (admission:
    traceable + padding-invariant, see fuse.classify_seg_map)."""

    def __init__(self, expr, fn, name=None):
        self.expr = expr
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "uda")


def uda(expr, fn, name=None):
    """A groupBy aggregate column computed by `fn(values_list)` over
    the per-group values of `expr` — e.g.
    ``t.groupBy("k", uda("v", lambda vs: sum(x * x for x in vs),
    "sumsq"))``."""
    return _UDA(expr, fn, name)


class TableRDD:
    def __init__(self, rdd, fields, name="table", plan=None,
                 plan_fallbacks=None):
        if isinstance(fields, str):
            fields = [f.strip() for f in fields.replace(",", " ").split()]
        self.rdd = rdd
        self.fields = list(fields)
        self.name = name
        self._row_type = namedtuple("Row", self.fields, rename=True)
        self._plan_fallbacks = list(plan_fallbacks or ())
        self.plan = plan if plan is not None else self._scan_plan()
        self._planned_q = False     # False = not planned yet
        self._reuse = True          # result-cache probe allowed

    # -- query-plane lowering -------------------------------------------
    def _scan_plan(self):
        """A Scan node when this table's source is columnar (tabular
        part files / driver-resident parallelize slices / a cached RDD
        whose partitions are columns resident on the device), else
        None — the host chain then serves every action."""
        try:
            from dpark_tpu.query.logical import Scan
            from dpark_tpu.rdd import ParallelCollection
            from dpark_tpu.tabular import TabularRDD
            resident = self._resident()
            if resident is not None:
                scan = Scan(self.rdd, self.fields, self.name)
                scan.device = resident
                return scan
            if isinstance(self.rdd, TabularRDD):
                if list(self.fields) == list(self.rdd.wanted):
                    return Scan(self.rdd, self.fields, self.name)
                self._note_fallback(
                    "scan", "table fields rename the tabular columns")
            elif isinstance(self.rdd, ParallelCollection) \
                    and self.rdd._slices is not None:
                return Scan(self.rdd, self.fields, self.name)
        except Exception as e:
            logger.debug("no scan plan: %s", e)
        return None

    def _resident(self):
        """What the executor knows of this table's RDD as columns
        resident on the device (JAXExecutor.resident_table: row count,
        a dtype and a value range a column, read once and kept with
        the cached batch), or None: no array executor, the RDD not in
        its result cache, or records that are not flat rows of as many
        columns as the table has fields."""
        executor = getattr(getattr(self.rdd.ctx, "scheduler", None),
                           "executor", None)
        describe = getattr(executor, "resident_table", None)
        found = describe(self.rdd.id) if describe is not None else None
        if found is None or len(found["columns"]) != len(self.fields):
            return None
        return found

    def _note_fallback(self, op, reason):
        self._plan_fallbacks.append({"op": op, "reason": reason})

    def _qexprs(self, texts):
        """Compile expression texts for the logical plan.  Returns
        (exprs, None) or (None, reason) — the caller threads the
        reason into the DERIVED table's fallback provenance (mutating
        self here would stamp one query's decline onto every sibling
        query built from the same base table)."""
        from dpark_tpu.query.exprs import compile_expr
        out = []
        for t in texts:
            ce = compile_expr(t, self.fields)
            if ce.parse_error:
                return None, ce.parse_error
            out.append(ce)
        return out, None

    def _derive(self, rdd, fields, plan, op=None, reason=None):
        """A downstream TableRDD carrying plan + fallback provenance."""
        fb = list(self._plan_fallbacks)
        if plan is None and reason is not None:
            fb.append({"op": op or "plan", "reason": reason})
        return TableRDD(rdd, fields, self.name, plan=plan,
                        plan_fallbacks=fb)

    def _planned(self):
        """The PlannedQuery serving this table's actions, or None (host
        chain).  Planned once; the physical RDD pipeline and scan
        results are reused across repeated actions — like any cached
        RDD lineage."""
        if self._planned_q is not False:
            return self._planned_q
        self._planned_q = None
        from dpark_tpu import conf
        if not getattr(conf, "QUERY_PLAN", True) or self.plan is None:
            if self.plan is None and self._plan_fallbacks:
                self.rdd._query_fallbacks = list(self._plan_fallbacks)
            return None
        try:
            from dpark_tpu.query.planner import plan_query
            pq = plan_query(self.plan, self.rdd.ctx,
                            reuse=self._reuse)
        except Exception as e:
            # no silent host path: the reason rides the lineage like
            # any decline (plan_query catches its own rules' errors;
            # what arrives here is an import or a planner bug)
            self._note_fallback(
                "plan", "query planning failed: %s: %s"
                % (type(e).__name__, (str(e).splitlines() or [""])[0]))
            self.rdd._query_fallbacks = list(self._plan_fallbacks)
            return None
        if pq.ok:
            self._planned_q = pq
        else:
            # host path serves the query; the planner's reasons ride
            # the lineage for the table-host-fallback lint rule (the
            # pre-flight twin of the runtime fallback_reason)
            self.rdd._query_fallbacks = (list(self._plan_fallbacks)
                                         + list(pq.fallbacks))
            self._host_sig = pq.adapt_sig
            for f in pq.fallbacks:
                if "int64" in f["reason"] or "overflow" in f["reason"]:
                    _pin_exact(self.rdd, f["reason"])
                    break
        return self._planned_q

    def _host_observe(self, t0):
        """Feed the cost model the host side's observed wall ms when a
        priced/declined plan ran the object path (adapt decision
        point 2 at query granularity)."""
        sig = getattr(self, "_host_sig", None)
        if sig is None:
            return
        try:
            from dpark_tpu import adapt
            adapt.observe_path(sig, "host", (time.time() - t0) * 1e3)
        except Exception:
            pass

    def shared(self, flag=True):
        """Per-QUERY result-cache opt-out: ``t.shared(False).collect()``
        neither probes nor stores into the shared-computation plane
        (resultcache.py) for this table's actions.  Tenant-wide
        opt-out lives on the JobServer (``resultcache.opt_out``);
        this is the query-granularity escape hatch.  Call it LAST —
        derived tables (select/where/...) start back at the
        default."""
        self._reuse = bool(flag)
        self._planned_q = False     # re-plan under the new setting
        return self

    def explain(self):
        """The logical plan + every planner rule decision (device or
        host, with reasons) — '' when no plan lowered."""
        pq = self._planned()
        if pq is not None:
            return pq.explain()
        lines = ["plan: host object path"]
        for f in self._plan_fallbacks:
            lines.append("  [%s] %s" % (f["op"], f["reason"]))
        return "\n".join(lines)

    # -- basic relational ops -------------------------------------------
    def select(self, *cols):
        cols = _split_cols(cols)
        parsed = [_parse_column(c, self.fields, i)
                  for i, c in enumerate(cols)]
        if any(isinstance(fn, _Agg) for _, fn in parsed):
            return self._aggregate_all(parsed)
        names = [n for n, _ in parsed]
        fns = [fn for _, fn in parsed]
        out = self.rdd.map(_SelectFn(fns))
        plan = None
        err = None
        if self.plan is not None:
            from dpark_tpu.query.logical import Project
            ces, err = self._qexprs([fn.expr for fn in fns])
            if ces is not None:
                plan = Project(self.plan, list(zip(names, ces)))
        return self._derive(out, names, plan, op="select", reason=err)

    def where(self, *conditions):
        texts = _split_cols(conditions)
        conds = [_compile_expr(c, self.fields) for c in texts]
        out = self.rdd.filter(_WhereFn(conds))
        plan = None
        err = None
        if self.plan is not None:
            from dpark_tpu.query.logical import Filter
            ces, err = self._qexprs(texts)
            if ces is not None:
                plan = Filter(self.plan, ces)
        return self._derive(out, self.fields, plan, op="where",
                            reason=err)

    filter = where

    def groupBy(self, keys, *aggs, **named_aggs):
        key_cols = _split_cols((keys,) if isinstance(keys, str) else keys)
        key_fns = [_compile_expr(k, self.fields) for k in key_cols]
        udas = [a for a in aggs if isinstance(a, _UDA)]
        if udas:
            return self._group_uda(key_cols, key_fns, aggs, named_aggs)
        parsed = [_parse_column(a, self.fields, i)
                  for i, a in enumerate(_split_cols(aggs))]
        for name, expr in sorted(named_aggs.items()):
            n, fn = _parse_column(expr, self.fields, 0)
            parsed.append((name, fn))
        for n, fn in parsed:
            if not isinstance(fn, _Agg):
                raise ValueError("groupBy columns must be aggregates: %r"
                                 % n)
        aggs_only = [fn for _, fn in parsed]
        keyed = self.rdd.map(_PairKeyFn(key_fns))
        combined = keyed.combineByKey(
            _AggCreate(aggs_only), _AggMerge(aggs_only),
            _AggCombine(aggs_only))
        out = combined.map(_AggFinalize(aggs_only, len(key_cols)))
        names = [re.sub(r"\W+", "_", k).strip("_") or ("k%d" % i)
                 for i, k in enumerate(key_cols)]
        names += [n for n, _ in parsed]
        plan, err = self._group_plan(key_cols, names[:len(key_cols)],
                                     parsed)
        return self._derive(out, names, plan, op="group-agg",
                            reason=err)

    def _group_plan(self, key_cols, key_names, parsed):
        """(GroupAgg node, None) or (None, decline reason)."""
        if self.plan is None:
            return None, None
        from dpark_tpu.query.logical import GroupAgg
        kces, err = self._qexprs(key_cols)
        if kces is None:
            return None, err
        agg_specs = []
        for name, agg in parsed:
            arg_ce = None
            if agg.arg_fn is not None:
                ces, err = self._qexprs([agg.arg_fn.expr])
                if ces is None:
                    return None, err
                arg_ce = ces[0]
            elif agg.func != "count":
                return None, ("aggregate %s(*) needs an argument "
                              "column for the device plan" % agg.func)
            agg_specs.append((name, agg.func, arg_ce, None))
        return GroupAgg(self.plan, list(zip(key_names, kces)),
                        agg_specs), None

    def _group_uda(self, key_cols, key_fns, aggs, named_aggs):
        """groupBy with a user-defined aggregate: the per-group value
        list of ONE argument column folds through fn(values) — host
        via groupByKey().mapValues, device via the SegMapOp segmented
        apply over the same graph."""
        if named_aggs or len(aggs) != 1:
            raise ValueError("a uda() must be the only groupBy "
                             "aggregate")
        (u,) = aggs
        arg_fn = _compile_expr(u.expr, self.fields)
        keyed = self.rdd.map(_UDAPairFn(key_fns, arg_fn))
        out = keyed.groupByKey().mapValues(u.fn) \
            .map(_UDAFlatten(len(key_cols)))
        names = [re.sub(r"\W+", "_", k).strip("_") or ("k%d" % i)
                 for i, k in enumerate(key_cols)]
        names += [u.name]
        plan = None
        err = None
        if self.plan is not None:
            from dpark_tpu.query.logical import GroupAgg
            kces, e1 = self._qexprs(key_cols)
            aces, e2 = self._qexprs([u.expr])
            err = e1 or e2
            if kces is not None and aces is not None:
                plan = GroupAgg(
                    self.plan,
                    list(zip(names[:len(key_cols)], kces)),
                    [(u.name, "uda", aces[0], u.fn)])
        return self._derive(out, names, plan, op="group-agg",
                            reason=err)

    def _aggregate_all(self, parsed):
        aggs = [fn for _, fn in parsed]
        for n, fn in parsed:
            if not isinstance(fn, _Agg):
                raise ValueError("mixing aggregates with plain columns "
                                 "requires groupBy")
        zero = None
        create, combine = _AggCreate(aggs), _AggCombine(aggs)
        parts = [p for p in self.rdd.ctx.runJob(
            self.rdd, _AggPartition(aggs)) if p is not None]
        if parts:
            acc = parts[0]
            for p in parts[1:]:
                acc = combine(acc, p)
            row = tuple(a.finalize(v) for a, v in zip(aggs, acc))
        else:
            row = tuple(None for _ in aggs)
        out = self.rdd.ctx.parallelize([row], 1)
        return TableRDD(out, [n for n, _ in parsed], self.name)

    def sort(self, key, reverse=False, numSplits=None):
        """Sort by one or several key expressions; `reverse` is one
        flag for every key or a list of one flag a key."""
        texts = _split_cols((key,) if isinstance(key, str) else key)
        fns = [_compile_expr(k, self.fields) for k in texts]
        flags = list(reverse) if isinstance(reverse, (list, tuple)) \
            else [reverse] * len(fns)
        if len(set(flags)) > 1:
            # mixed directions: the descending keys compare reversed
            key_fn, flip = _SortKeyFn(fns, flags), False
        else:
            key_fn, flip = _GroupKeyFn(fns), bool(flags[0])
        out = self.rdd.sort(key=key_fn, reverse=flip, numSplits=numSplits)
        plan = None
        err = None
        if self.plan is not None:
            from dpark_tpu.query.logical import Sort
            ces, err = self._qexprs(texts)
            if ces is not None:
                plan = Sort(self.plan, ces, reverse=reverse)
        return self._derive(out, self.fields, plan, op="sort",
                            reason=err)

    def top(self, n=10, key=None, reverse=False):
        if key is None:
            key_fn = None
        else:
            fns = [_compile_expr(k, self.fields)
                   for k in _split_cols((key,) if isinstance(key, str)
                                        else key)]
            key_fn = _GroupKeyFn(fns)
        rows = self._plan_rows()
        if rows is not None:
            import heapq
            pick = heapq.nsmallest if reverse else heapq.nlargest
            return [self._row_type(*r) for r in pick(n, rows, key_fn)]
        t0 = time.time()
        out = [self._row_type(*r)
               for r in self.rdd.top(n, key=key_fn, reverse=reverse)]
        self._host_observe(t0)
        return out

    def join(self, other, on, numSplits=None):
        """Inner equi-join: `on` is a column name of both tables, or a
        pair (column of this table, column of `other`) whose names may
        differ, as TPC-H's c_custkey = o_custkey.  The output is the
        key (under both names where they differ), this table's other
        columns, then `other`'s (a name this table has already gets
        `other`'s name as a prefix).  Joins chain: the left input of a
        join may itself be a join."""
        lon, ron = (on, on) if isinstance(on, str) else tuple(on)
        if lon not in self.fields or ron not in other.fields:
            raise ValueError("join columns %r and %r must be plain "
                             "fields of the left and the right table"
                             % (lon, ron))
        li, ri = self.fields.index(lon), other.fields.index(ron)
        lf = _compile_expr(lon, self.fields)
        rf = _compile_expr(ron, other.fields)
        left = self.rdd.map(_JoinKeyFn(lf))
        right = other.rdd.map(_JoinKeyFn(rf))
        joined = left.join(right, numSplits)
        keys = [lon] if lon == ron else [lon, ron]
        out = joined.map(_JoinMerge(li, ri, len(keys)))
        fields = (keys + [f for f in self.fields if f != lon]
                  + [f if f not in self.fields else other.name + "_" + f
                     for f in other.fields if f != ron])
        # ensure uniqueness, tracking which source column each output
        # name came from (the plan's join column map)
        srcs = ([("on", k) for k in keys]
                + [("l", f) for f in self.fields if f != lon]
                + [("r", f) for f in other.fields if f != ron])
        seen, uniq = set(), []
        for f in fields:
            while f in seen:
                f = f + "_"
            seen.add(f)
            uniq.append(f)
        plan = None
        if self.plan is not None and other.plan is not None:
            from dpark_tpu.query.logical import Join
            colmap = [(out_name, side, src) for out_name, (side, src)
                      in zip(uniq, srcs)]
            plan = Join(self.plan, other.plan, lon, uniq, right_on=ron)
            plan.colmap = colmap
            return self._derive(out, uniq, plan)
        reason = None
        if self.plan is not None and other.plan is None:
            reason = ("join input %r has no columnar plan"
                      % other.name)
        return self._derive(out, uniq, None, op="join", reason=reason)

    # -- actions ---------------------------------------------------------
    def _plan_call(self, method, *args):
        """(result, served) via the physical plan; (None, False) means
        the host path serves.  EVERY plan action funnels through here
        so a run-time plan failure (mixed-type column, missing file)
        records its reason on the lineage for the table-host-fallback
        lint rule regardless of which action tripped it."""
        pq = self._planned()
        if pq is None:
            return None, False
        try:
            return getattr(pq, method)(*args), True
        except Exception as e:
            # the host chain is always correct — serve from it and
            # record why
            logger.warning("query plan failed at run time (%s); "
                           "host path", e)
            self._note_fallback("run", "plan execution failed: %s"
                                % str(e)[:160])
            self._planned_q = None
            self.rdd._query_fallbacks = list(self._plan_fallbacks)
            return None, False

    def _plan_rows(self):
        """Rows via the physical plan, or None (host path serves)."""
        rows, served = self._plan_call("rows")
        return rows if served else None

    def collect(self):
        rows = self._plan_rows()
        if rows is not None:
            return [self._row_type(*r) for r in rows]
        t0 = time.time()
        out = [self._row_type(*r) if isinstance(r, tuple)
               else self._row_type(r) for r in self.rdd.collect()]
        self._host_observe(t0)
        return out

    def take(self, n):
        rows = self._plan_rows()
        if rows is not None:
            return [self._row_type(*r) for r in rows[:n]]
        return [self._row_type(*r) for r in self.rdd.take(n)]

    def count(self):
        got, served = self._plan_call("count")
        if served:
            return got
        t0 = time.time()
        out = self.rdd.count()
        self._host_observe(t0)
        return out

    def save(self, path):
        return self.rdd.saveAsCSVFile(path)

    def indexBy(self, key):
        fn = _compile_expr(key, self.fields)
        return self.rdd.map(_JoinKeyFn(fn))

    def __repr__(self):
        return "<TableRDD %s(%s)>" % (self.name, ", ".join(self.fields))


_SQL_RE = re.compile(
    r"^\s*select\s+(?P<cols>.+?)\s+from\s+(?P<table>\w+)"
    r"(?P<joins>(?:\s+join\s+\w+\s+on\s+.+?)*)"
    r"(?:\s+where\s+(?P<where>.+?))?"
    r"(?:\s+group\s+by\s+(?P<group>.+?))?"
    r"(?:\s+having\s+(?P<having>.+?))?"
    r"(?:\s+order\s+by\s+(?P<order>.+?))?"
    r"(?:\s+limit\s+(?P<limit>\d+))?\s*$",
    re.I | re.S)

_JOIN_RE = re.compile(r"\s+join\s+(\w+)\s+on\s+", re.I)

# ON col | ON a.x = b.y (each side optionally qualified by its table)
_JOIN_ON_RE = re.compile(
    r"^\s*(?:(\w+)\s*\.\s*)?(\w+)\s*"
    r"(?:=\s*(?:(\w+)\s*\.\s*)?(\w+)\s*)?$")

_ORDER_DIR_RE = re.compile(r"^(.*?)\s+(asc|desc)$", re.I | re.S)

# an aggregate CALL embedded in a larger expression (one paren-nesting
# level in the argument, e.g. avg(abs(x)))
_AGG_CALL_RE = re.compile(
    r"\b(count|sum|avg|min|max|adcount|first|group_concat)\s*"
    r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)", re.I)


def _sub_aggs(expr, add_agg):
    """Replace every aggregate call in `expr` with the column name
    `add_agg(call_text)` returns.  Enables aggregate EXPRESSIONS in
    SELECT and HAVING (``sum(v) / count(*) as r``, ``having count(*)
    > 3``): the calls compute in the grouped aggregation, the
    surrounding expression evaluates over the aggregated row.
    Returns (rewritten_expr, any_found)."""
    found = []

    def repl(m):
        found.append(True)
        return add_agg(m.group(0))

    return _AGG_CALL_RE.sub(repl, expr), bool(found)


def _mask_literals(sql):
    """Same-length copy of `sql` with quoted-string contents blanked, so
    clause keywords inside literals don't split the query.  Handles
    BOTH escape spellings inside a literal: backslash (``'don\\'t'``)
    and the SQL doubled quote (``'don''t'``) — a doubled quote
    continues the literal instead of closing and reopening it, so an
    expression like ``item == 'don''t, group by'`` masks as ONE
    literal and its embedded clause keywords/commas never split the
    query."""
    out = list(sql)
    i = 0
    while i < len(out):
        q = out[i]
        if q in "'\"":
            i += 1
            while i < len(out):
                if out[i] == "\\" and i + 1 < len(out):
                    out[i] = "x"
                    out[i + 1] = "x"    # escaped char incl. quote
                    i += 2
                    continue
                if out[i] == q:
                    if i + 1 < len(out) and out[i + 1] == q:
                        out[i] = "x"    # SQL '' escape: still inside
                        out[i + 1] = "x"
                        i += 2
                        continue
                    break
                out[i] = "x"
                i += 1
        i += 1
    return "".join(out)


def _sql_quote_escapes(text):
    """SQL doubled-quote escapes translated to Python backslash form,
    so an extracted clause like ``item == 'don''t'`` compiles with
    eval to the string ``don't`` instead of the implicit concatenation
    ``dont``.  Backslash escapes pass through untouched."""
    out = []
    i = 0
    n = len(text)
    while i < n:
        ch = text[i]
        out.append(ch)
        i += 1
        if ch not in "'\"":
            continue
        q = ch
        while i < n:
            c2 = text[i]
            if c2 == "\\" and i + 1 < n:
                out.append(c2)
                out.append(text[i + 1])
                i += 2
                continue
            if c2 == q:
                if i + 1 < n and text[i + 1] == q:
                    out.append("\\")
                    out.append(q)
                    i += 2
                    continue
                out.append(q)
                i += 1
                break
            out.append(c2)
            i += 1
    return "".join(out)


def _join_keys(on, left, right, rname):
    """(left column, right column) of a JOIN's ON text: ``col``, or
    ``a.x = b.y`` in either order, a side qualified by its table's name
    or known by which table has the column."""
    jm = _JOIN_ON_RE.match(on)
    if not jm:
        raise ValueError("unsupported JOIN ON: %r" % on)
    qa, a, qb, b = jm.groups()
    if b is None:
        return a, a
    if qa == rname or qb not in (None, rname) or (
            qa is None and qb is None and a not in left.fields
            and b in left.fields):
        a, b = b, a
    if a not in left.fields or b not in right.fields:
        raise ValueError("JOIN %s ON %r: %r is not a column of the left "
                         "input or %r not one of %r" % (rname, on, a, b,
                                                        rname))
    return a, b


def _order_keys(order):
    """ORDER BY text -> [(expression, descending?)] a key."""
    out = []
    for item in _split_cols((order,)):
        dm = _ORDER_DIR_RE.match(item)
        out.append((dm.group(1).strip(), dm.group(2).lower() == "desc")
                   if dm else (item.strip(), False))
    return out


def execute(sql, tables):
    """Minimal SQL-ish front over TableRDD (reference: dpark table's
    `execute` [SURVEY.md 2.3, low-confidence item]).  Supports
    SELECT cols FROM t [JOIN t2 ON on2 [JOIN t3 ON on3 ...]]
    [WHERE expr] [GROUP BY keys] [HAVING expr]
    [ORDER BY k1 [ASC|DESC], k2 [ASC|DESC] ...] [LIMIT n]; column
    expressions and aggregates use the DSL's syntax.  SELECT and HAVING
    may use aggregate EXPRESSIONS (``sum(v) / count(*)``).  Each JOIN
    ... ON lowers to TableRDD.join (the device-riding equi-join) over
    the join before it, a left-deep chain, and takes ``col`` or ``a.x
    = b.y``, whose names may differ (TPC-H's ``c_custkey =
    o_custkey``).  Each ORDER BY key has its own direction.

    `tables`: dict name -> TableRDD.  Returns a TableRDD, or a row list
    when LIMIT is given.
    """
    masked = _mask_literals(sql)
    m = _SQL_RE.match(masked)
    if not m:
        raise ValueError("unsupported SQL: %r" % sql)

    def part(name):
        span = m.span(name)
        if span == (-1, -1):
            return None
        # clause text is extracted from the ORIGINAL sql (the masked
        # copy only guides the split); SQL '' escapes inside string
        # literals translate to Python form before any eval/compile
        return _sql_quote_escapes(sql[span[0]:span[1]])

    t = tables.get(m.group("table"))
    if t is None:
        raise ValueError("unknown table %r" % m.group("table"))
    lo, hi = m.span("joins")
    found = list(_JOIN_RE.finditer(masked, lo, hi))
    for k, jm in enumerate(found):
        other = tables.get(jm.group(1))
        if other is None:
            raise ValueError("unknown table %r" % jm.group(1))
        end = found[k + 1].start() if k + 1 < len(found) else hi
        t = t.join(other, _join_keys(sql[jm.end():end].strip(), t, other,
                                     jm.group(1)))
    if part("where"):
        t = t.where(part("where"))

    order = _order_keys(part("order")) if part("order") else []
    cols = part("cols").strip()

    if part("having") and not part("group"):
        raise ValueError("HAVING requires GROUP BY")
    if part("group"):
        group_keys = _split_cols((part("group"),))
        sel = _split_cols((cols,))
        aggs, out_exprs, out_names = [], [], []
        key_names = [re.sub(r"\W+", "_", k).strip("_") or ("k%d" % i)
                     for i, k in enumerate(group_keys)]

        def add_agg(text):
            # helper column for one aggregate call (leading underscores
            # would be stripped by _parse_column's sanitizer); dodge
            # user columns of the same name
            name = "agg%d" % len(aggs)
            while name in t.fields or name in key_names:
                name += "x"
            aggs.append("%s as %s" % (text, name))
            return name

        for c in sel:
            am = _AS_RE.match(c)
            expr, alias = (am.group(1), am.group(2)) if am \
                else (c, None)
            # _AGG_RE alone would also "match" compound expressions
            # (its lazy arg + end anchor spans `sum(a) * 2 + count(*)`)
            # — a BARE call is a fullmatch of the balanced call regex
            if _AGG_CALL_RE.fullmatch(expr.strip()):
                name = alias or _parse_column(c, t.fields, 0)[0]
                out_exprs.append("%s as %s" % (add_agg(expr), name))
                out_names.append(name)
            elif expr.strip() in group_keys:
                kn = key_names[group_keys.index(expr.strip())]
                name = alias or kn
                out_exprs.append("%s as %s" % (kn, name))
                out_names.append(name)
            else:
                new, found = _sub_aggs(expr, add_agg)
                if not found:
                    raise ValueError(
                        "non-aggregate select column %r is not a "
                        "group key" % c)
                name = alias or ("col%d" % len(out_exprs))
                out_exprs.append("%s as %s" % (new, name))
                out_names.append(name)
        hav = None
        if part("having"):
            hav, _ = _sub_aggs(part("having"), add_agg)
        t = t.groupBy(group_keys, *aggs)
        if hav is not None:
            t = t.where(hav)
        if order and _sort_first(order, t.fields, out_names):
            # ORDER BY grouped columns (one that the SELECT list drops
            # or renames among them): sort on the aggregated table
            # before projecting
            t = _sort_by(t, order)
            order = []
        t = t.select(*out_exprs)
        if order:
            t = _sort_by(t, order)
    else:
        # ORDER BY may reference either the source columns or a projected
        # output name: sort on whichever side actually holds it
        if order and cols != "*":
            projected = [
                _parse_column(c, t.fields, i)[0]
                for i, c in enumerate(_split_cols((cols,)))]
            if _sort_first(order, t.fields, projected):
                t = _sort_by(t, order)
                order = []
        if cols != "*":
            t = t.select(cols)
        if order:
            t = _sort_by(t, order)
    if m.group("limit"):
        return t.take(int(m.group("limit")))
    return t


def _sort_by(t, order):
    return t.sort([k for k, _ in order], reverse=[d for _, d in order])


def _sort_first(order, before, after):
    """Does the ORDER BY sort the table before the SELECT's projection?
    One key: unless it is an output name.  Several: where every key is
    a column before the projection, or some key is not an output."""
    if len(order) == 1:
        return order[0][0] not in after
    return all(k in before for k, _ in order) \
        or not all(k in after for k, _ in order)


def _split_cols(cols):
    out = []
    for c in cols:
        if isinstance(c, (list, tuple)):
            out.extend(_split_cols(c))
        elif isinstance(c, _UDA):
            out.append(c)
        else:
            # split on top-level commas — not inside parens and not
            # inside string literals (a comma embedded in 'a, b' or a
            # ''-escaped literal must not split the expression)
            depth, cur, q = 0, "", None
            i = 0
            while i < len(c):
                ch = c[i]
                if q is not None:
                    cur += ch
                    if ch == "\\" and i + 1 < len(c):
                        cur += c[i + 1]
                        i += 2
                        continue
                    if ch == q:
                        if i + 1 < len(c) and c[i + 1] == q:
                            cur += c[i + 1]     # '' escape
                            i += 2
                            continue
                        q = None
                    i += 1
                    continue
                if ch in "'\"":
                    q = ch
                elif ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                if ch == "," and depth == 0:
                    out.append(cur.strip())
                    cur = ""
                else:
                    cur += ch
                i += 1
            if cur.strip():
                out.append(cur.strip())
    return out


class _UDAPairFn:
    def __init__(self, key_fns, arg_fn):
        self.key_fns = key_fns
        self.arg_fn = arg_fn

    def __call__(self, row):
        if len(self.key_fns) == 1:
            return (self.key_fns[0](row), self.arg_fn(row))
        return (tuple(fn(row) for fn in self.key_fns),
                self.arg_fn(row))


class _UDAFlatten:
    def __init__(self, n_keys):
        self.n_keys = n_keys

    def __call__(self, kv):
        k, v = kv
        keys = k if isinstance(k, tuple) and self.n_keys > 1 else (k,)
        return tuple(keys) + (v,)


class _SelectFn:
    def __init__(self, fns):
        self.fns = fns

    def __call__(self, row):
        return tuple(fn(row) for fn in self.fns)


class _WhereFn:
    def __init__(self, conds):
        self.conds = conds

    def __call__(self, row):
        return all(c(row) for c in self.conds)


class _GroupKeyFn:
    def __init__(self, fns):
        self.fns = fns

    def __call__(self, row):
        if len(self.fns) == 1:
            return self.fns[0](row)
        return tuple(fn(row) for fn in self.fns)


class _PairKeyFn(_GroupKeyFn):
    def __call__(self, row):
        return (super().__call__(row), row)


class _JoinKeyFn(_GroupKeyFn):
    def __init__(self, fn):
        self.fn = fn

    def __call__(self, row):
        return (self.fn(row), row)


class _JoinMerge:
    """(k, (left row, right row)) -> the key `nkeys` times (once a
    name), then each row without its key column."""

    def __init__(self, li, ri, nkeys=1):
        self.li = li
        self.ri = ri
        self.nkeys = nkeys

    def __call__(self, kv):
        k, (l, r) = kv
        l = tuple(x for i, x in enumerate(l) if i != self.li)
        r = tuple(x for i, x in enumerate(r) if i != self.ri)
        return (k,) * self.nkeys + l + r


@functools.total_ordering
class _Desc:
    """A sort key's value compared in reverse: a key of a descending
    direction in a mixed-direction sort (_SortKeyFn)."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __eq__(self, other):
        return self.v == other.v

    def __lt__(self, other):
        return other.v < self.v

    def __hash__(self):
        return hash(self.v)

    def __getstate__(self):
        return self.v

    def __setstate__(self, v):
        self.v = v


class _SortKeyFn:
    """A row's sort key over several keys of their own directions."""

    def __init__(self, fns, reverse):
        self.fns = fns
        self.reverse = tuple(reverse)

    def __call__(self, row):
        return tuple(_Desc(fn(row)) if r else fn(row)
                     for fn, r in zip(self.fns, self.reverse))


class _AggCreate:
    def __init__(self, aggs):
        self.aggs = aggs

    def __call__(self, row):
        return tuple(a.create(row) for a in self.aggs)


class _AggMerge:
    def __init__(self, aggs):
        self.aggs = aggs

    def __call__(self, acc, row):
        return tuple(a.merge(v, row) for a, v in zip(self.aggs, acc))


class _AggCombine:
    def __init__(self, aggs):
        self.aggs = aggs

    def __call__(self, a, b):
        return tuple(g.combine(x, y) for g, x, y in zip(self.aggs, a, b))


class _AggFinalize:
    def __init__(self, aggs, n_keys):
        self.aggs = aggs
        self.n_keys = n_keys

    def __call__(self, kv):
        k, acc = kv
        keys = k if isinstance(k, tuple) and self.n_keys > 1 else (k,)
        return tuple(keys) + tuple(
            a.finalize(v) for a, v in zip(self.aggs, acc))


class _AggPartition:
    def __init__(self, aggs):
        self.aggs = aggs

    def __call__(self, it):
        acc = None
        merge = _AggMerge(self.aggs)
        create = _AggCreate(self.aggs)
        for row in it:
            acc = create(row) if acc is None else merge(acc, row)
        return acc
