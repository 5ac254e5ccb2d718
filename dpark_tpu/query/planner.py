"""Rule-driven physical planner of the columnar query plane.

`plan_query` walks a logical plan (dpark_tpu/query/logical.py) with a
fixed sequence of rewrite rules — shape normalization, column pruning,
predicate pushdown (vectorized filters + chunk-skip ranges), string
dictionary encoding, group-agg and join lowering, and adaptive path
pricing — and compiles the admitted pipeline onto the shipped device
machinery.  Where a SCAN runs depends on what its source is, and on
nothing else (no conf entry, no argument):

  * tabular part files and driver-resident parallelize slices scan on
    the DRIVER, as a columnar pipeline in numpy: only `wanted` columns
    are read (or columnarized from the slices), whole chunks skip via
    the v2 footer's min/max stats, and filter predicates / derived
    columns evaluate as vectorized array programs over column batches
    (`_ScanSeg.run`); the result is handed to the device by
    `parallelize` for the group-by or the join;
  * a cached RDD whose partitions are columns RESIDENT ON THE DEVICE
    (`TableRDD._resident`: it is in the executor's result cache and its
    records are flat rows) scans THERE: the same admitted programs
    become the `filter` and `map` of one narrow stage over the cached
    RDD (`PlannedQuery._device_chain`), traced by fuse.py like any user
    lambda, and feed the group-by's shuffle write directly: no numpy
    pass on the driver, no parallelize, no ingest.  What the rules
    need of such a table (dtypes, [min, max] a column, rows) was read
    once, when the table was made (`JAXExecutor.resident_table`).
    Group-by and plain scans lower; a join over such a table declines.

After the scan:

  * GROUP-AGG lowers onto the device exchange: multi-aggregate queries
    ride a reduceByKey whose accumulator merge traces (the PR 3
    tuple-key combine path) and whose accumulator holds each distinct
    sum / count / min / max ONCE (`_share_leaves`: avg(x) is sum(x)
    over the count); single provable aggregates ride
    groupByKey().mapValues(sum/min/max/len) (SegAggOp / the combiner
    rewrite — adapt decision point 4 prices which), and traceable UDAs
    ride the SegMapOp segmented apply (PR 4);
  * equi-JOINs lower onto the PR 3 device join source;
  * string group/join keys (and string passthrough columns crossing
    the device) ride TokenDict-encoded int64 ids, decoded at egest; a
    fixed-width byte-string (S<w>) key column of a resident table rides
    as its int64 words, alone or beside other key columns, and is
    rebuilt as bytes at egest (`_key_of`, `_key_columns`);
  * result finishing (HAVING, post-aggregate projections, ORDER BY,
    LIMIT) runs at EGEST on the driver with exact host eval semantics
    — result rows are one-per-group / driver-resident by then.

Every rule records its choice with a reason; host choices surface as
`fallbacks` which the `table-host-fallback` lint rule reports
pre-flight and the runtime records per stage.  Admission is exact:
anything the rules cannot PROVE equivalent to the host row path
declines with a reason, and the host object path serves the query.
With the trace plane on, `plan_query` is the `query.plan` span and the
egest-side finishing the `query.finish` span (dpark_tpu/trace.py).
"""

import time

import numpy as np

from dpark_tpu.query import exprs as E
from dpark_tpu.query.logical import (Filter, GroupAgg, Join, Project,
                                     Scan, Sort)
from dpark_tpu.utils.log import get_logger

logger = get_logger("query.planner")

_I64_MAX = 2 ** 63 - 1

DEVICE_AGGS = ("sum", "count", "avg", "min", "max")

# classified per-group consumers for the single-aggregate lowering:
# builtins the shipped monoid classifier proves exactly, so the chain
# rides SegAggOp or the map-side-combine rewrite (adapt decision 4
# prices which)
_CLASSIFIED = {"sum": sum, "min": min, "max": max, "count": len}


# ---------------------------------------------------------------------------
# stable device-function factories
# ---------------------------------------------------------------------------
# Closures over HASHABLE parameters: fuse.fn_key hashes (code, cell
# values), so two plans of the same query compile to the SAME program
# key and the executor's program cache serves warm runs across plan
# rebuilds.

def _word_ints(value, width):
    """A host `bytes` value of an S<width> column as the signed 64-bit
    big-endian words the device holds it in (layout.pack_bytes)."""
    raw = value.ljust(8 * -(-width // 8), b"\0")
    return tuple(int.from_bytes(raw[i:i + 8], "big", signed=True)
                 for i in range(0, len(raw), 8))


def _key_nwords(widths):
    """Key words of a group's key columns: a number is one, an S<w>
    byte string one a started 8 bytes."""
    return sum(-(-w // 8) if w else 1 for w in widths)


def _key_of(rec, widths):
    """The group key of a flat (k..., a...) row.  `widths` holds, a key
    column, 0 for a number or w for an S<w> byte string, whose bytes
    ride as int64 key words, big-endian as layout.pack_bytes lays them
    (a layout.ByteStr inside a stage program, `bytes` in a host row):
    a key is ONE int or a flat tuple of ints, which is what the
    exchange hashes, orders and compares."""
    parts = []
    for k, w in zip(rec, widths):
        if not w:
            parts.append(k)
        elif isinstance(k, bytes):
            parts.extend(_word_ints(k, w))
        else:
            parts.extend(k.words)
    return parts[0] if len(parts) == 1 else tuple(parts)


def _key_columns(key, widths, decode):
    """The inverse at egest: a collected key back to one value a key
    column, `bytes` for a byte string (numpy strips an S column's
    trailing NULs at tolist()) and decode(column, value) for the rest."""
    words = list(key) if isinstance(key, tuple) else [key]
    out = []
    for i, w in enumerate(widths):
        take = -(-w // 8) if w else 1
        part, words = words[:take], words[take:]
        if not w:
            out.append(decode(i, part[0]))
            continue
        raw = b"".join(int(x).to_bytes(8, "big", signed=True)
                       for x in part)
        out.append(raw[:w].rstrip(b"\0"))
    return tuple(out)


def _make_pair(widths):
    """Flat (k1..kn, v) row -> (key, v)."""
    nk = len(widths)

    def f(rec):
        return (_key_of(rec, widths), rec[nk])
    return f


def _make_create(widths, leaves):
    """Flat (k..., a...) row -> (key, accumulator): one leaf for each
    of `leaves`, (op, index of its argument column or None): the
    argument for sum / min / max, int64 1 for count.  Aggregates share
    leaves (_admit_aggs): avg(x) is sum(x) over THE count."""
    nk = len(widths)

    def f(rec):
        vals = rec[nk:]
        return (_key_of(rec, widths),
                tuple(np.int64(1) if arg is None else vals[arg]
                      for _op, arg in leaves))
    return f


def _make_merge(ops):
    """Accumulator merge, a leaf an op of add / min / max, branchless
    so the device exchange traces it (min/max via the table layer's
    jnp.where forms)."""
    def f(a, b):
        from dpark_tpu.table import _branchless_max, _branchless_min
        out = []
        for op, x, y in zip(ops, a, b):
            if op == "add":
                out.append(x + y)
            elif op == "min":
                out.append(_branchless_min(x, y))
            else:
                out.append(_branchless_max(x, y))
        return tuple(out)
    return f


def _make_dev_filter(names, progs):
    """Record predicate of a table resident on the device: the admitted
    programs of a Filter over the record's columns by name."""
    def f(rec):
        env = dict(zip(names, rec))
        out = E.evaluate(progs[0], env)
        for p in progs[1:]:
            out = out & E.evaluate(p, env)
        return out
    return f


def _make_dev_project(names, items):
    """Record projection of a table resident on the device: an output
    column is the name of an input column or (program, kind)."""
    def f(rec):
        env = dict(zip(names, rec))
        out = []
        for item in items:
            if isinstance(item, str):
                out.append(env[item])
                continue
            v = E.evaluate(item[0], env)
            if item[1] == "f":
                # what a float column is on the device
                v = np.float32(v) if isinstance(v, (int, float)) \
                    else v.astype(np.float32)
            out.append(v)
        return tuple(out)
    return f


def _make_join_side(nvals):
    """Flat (k, v1..vn) row -> (k, (v1..vn)) for the join exchange."""
    def f(rec):
        return (rec[0], tuple(rec[1:1 + nvals]))
    return f


def _make_join_flat(nl, nr):
    """Joined (k, ((l...), (r...))) -> flat (k, l..., r...)."""
    def f(kv):
        k, (lv, rv) = kv
        return (k,) + tuple(lv) + tuple(rv)
    return f


def _make_group_over(key_idxs, leaves):
    """Flat joined row -> (key, accumulator), keys picked by index and
    every leaf's argument by its index in the row."""
    def f(rec):
        if len(key_idxs) == 1:
            key = rec[key_idxs[0]]
        else:
            key = tuple(rec[i] for i in key_idxs)
        return (key, tuple(np.int64(1) if arg is None else rec[arg]
                           for _op, arg in leaves))
    return f


def _make_pick(idxs):
    """Flat row -> sub-row by indices (projection after a join)."""
    def f(rec):
        return tuple(rec[i] for i in idxs)
    return f


# ---------------------------------------------------------------------------
# plan-time helpers
# ---------------------------------------------------------------------------

def _share_leaves(kinds, args):
    """The accumulator of a group's aggregates as shared leaves:
    (leaves, refs).  A leaf is (sum | count | min | max, its argument
    or None), each distinct one once; an aggregate refers to its leaf,
    avg to (its sum, the count): eight aggregates of TPC-H Q1 are six
    leaves, and what the exchange carries is leaves."""
    leaves, refs = [], []

    def leaf(op, arg):
        if (op, arg) not in leaves:
            leaves.append((op, arg))
        return leaves.index((op, arg))

    for kind, arg in zip(kinds, args):
        if kind == "count":
            refs.append((leaf("count", None),))
        elif kind == "avg":
            refs.append((leaf("sum", arg), leaf("count", None)))
        else:
            refs.append((leaf(kind, arg),))
    return tuple(leaves), tuple(refs)


def _merge_ops(leaves):
    return tuple("add" if op in ("sum", "count") else op
                 for op, _ in leaves)


def _std_dtype(dt):
    """The scan's standardized dtype: the host row path materializes
    Python ints/floats (ndarray.tolist()), so the columnar twin
    computes in int64/float64 regardless of the stored width."""
    dt = np.dtype(dt)
    if dt.kind == "i":
        return np.dtype(np.int64)
    if dt.kind == "f":
        return np.dtype(np.float64)
    return dt


def _std_col(arr):
    a = np.asarray(arr) if not isinstance(arr, list) \
        else np.array(arr, dtype=object)
    dt = _std_dtype(a.dtype) if a.dtype.kind in "if" else a.dtype
    if a.dtype != dt:
        a = a.astype(dt)
    return a


def _is_bare_name(colexpr):
    import ast
    t = colexpr.tree
    return (t is not None and isinstance(t.body, ast.Name)
            and t.body.id in colexpr.columns)


def _skip_bounds(pred, source_cols, col_dtypes=None):
    """{col: (lo, hi)} chunk-skip ranges a simple predicate implies
    over RAW source columns: conjunctions of ``col <cmp> literal``
    (either operand order).  Conservative — anything else contributes
    nothing.  The strict-inequality tightening (``> c`` -> lo = c+1)
    applies ONLY to integer COLUMNS: an int literal compared against a
    float column must keep the untightened bound (a chunk whose max is
    10.5 still matches ``f > 10``)."""
    import ast
    out = {}
    col_dtypes = col_dtypes or {}

    def visit(node):
        if isinstance(node, ast.BoolOp) and isinstance(node.op, ast.And):
            for v in node.values:
                visit(v)
            return
        if not isinstance(node, ast.Compare) or len(node.ops) != 1:
            return
        left, op, right = node.left, node.ops[0], node.comparators[0]
        name = const = None
        flip = False
        if isinstance(left, ast.Name) and isinstance(right, ast.Constant):
            name, const = left.id, right.value
        elif isinstance(right, ast.Name) and isinstance(left,
                                                        ast.Constant):
            name, const = right.id, left.value
            flip = True
        if name not in source_cols or isinstance(const, bool) \
                or not isinstance(const, (int, float)):
            return
        opname = type(op).__name__
        if flip:
            opname = {"Lt": "Gt", "LtE": "GtE", "Gt": "Lt",
                      "GtE": "LtE"}.get(opname, opname)
        is_int = (isinstance(const, int)
                  and np.dtype(col_dtypes.get(name, object)).kind
                  == "i")
        lo = hi = None
        if opname == "Eq":
            lo = hi = const
        elif opname == "Gt":
            lo = const + 1 if is_int else const
        elif opname == "GtE":
            lo = const
        elif opname == "Lt":
            hi = const - 1 if is_int else const
        elif opname == "LtE":
            hi = const
        else:
            return
        plo, phi = out.get(name, (None, None))
        if lo is not None:
            plo = lo if plo is None else max(plo, lo)
        if hi is not None:
            phi = hi if phi is None else min(phi, hi)
        out[name] = (plo, phi)

    body = pred.tree.body if pred.tree is not None else None
    if body is not None:
        visit(body)
    return out


def _normalize(val):
    """np scalars -> exact Python scalars (recursively through acc
    tuples) so egest rows match the host row path's Python values."""
    if isinstance(val, tuple):
        return tuple(_normalize(v) for v in val)
    if isinstance(val, np.generic):
        return val.item()
    if isinstance(val, np.ndarray) and val.ndim == 0:
        return val.item()
    return val


class _Decline(Exception):
    def __init__(self, op, reason):
        super().__init__(reason)
        self.op = op
        self.reason = reason


# ---------------------------------------------------------------------------
# scan segments
# ---------------------------------------------------------------------------

class _ScanSeg:
    """One scan-side pipeline: which columns to read, the chunk-skip
    ranges, and the admitted vectorized steps (leaf-to-top order)."""

    def __init__(self, scan):
        self.scan = scan
        self.wanted = None          # ordered source columns to read
        self.skip_ranges = None     # {col: (lo, hi)} for read_chunks
        self.steps = []             # ("filter", [fn]) | ("project", [...])
        self.out = []               # final env field names, ordered
        self.dtypes = {}            # final env dtypes
        self.bounds = {}            # final env int bounds
        self._env = None            # run-time cache
        # the source is a table resident on the device: its load-time
        # stats (JAXExecutor.resident_table), else None.  The steps
        # then become the filter and map of a stage program
        # (PlannedQuery._device_chain); run() is for the other sources
        self.device = scan.device

    # -- plan-time -------------------------------------------------------
    def source_meta(self):
        """(dtypes, ranges, nrows) of the raw source columns,
        standardized — footer stats for tabular files (no data read),
        the columnarized slices for in-memory sources."""
        from dpark_tpu.tabular import TabularRDD, read_header
        src = self.scan.source
        if self.device is not None:
            # read once, when the table was made: nothing is read here
            dtypes, ranges = {}, {}
            for name, (dt, rng) in zip(self.scan.fields,
                                       self.device["columns"]):
                dtypes[name] = dt if dt.kind == "S" else _std_dtype(dt)
                if dt.kind == "i":
                    ranges[name] = rng
            return dtypes, ranges, self.device["rows"]
        if isinstance(src, TabularRDD):
            ranges, rows = {}, 0
            seen_stats = {}
            seen_kinds = {}         # name -> set of 'i'/'f'/'o'
            for path in src.files:
                header = read_header(path)
                for chunk in header["chunks"]:
                    rows += chunk["rows"]
                    for name, meta in zip(header["fields"],
                                          chunk["columns"]):
                        if name not in self.scan.fields:
                            continue
                        if meta["kind"] == "object":
                            seen_kinds.setdefault(name, set()).add("o")
                            continue
                        seen_kinds.setdefault(name, set()).add(
                            _std_dtype(meta["dtype"]).kind)
                        if "min" in meta:
                            lo, hi = seen_stats.get(name, (None, None))
                            lo = meta["min"] if lo is None \
                                else min(lo, meta["min"])
                            hi = meta["max"] if hi is None \
                                else max(hi, meta["max"])
                            seen_stats[name] = (lo, hi)
                        else:
                            seen_stats.setdefault(name, (None, None))
            # chunk dtypes PROMOTE across the file set: any object
            # chunk makes the column object, any float chunk makes a
            # numeric column float64 (run() re-casts int chunks up, so
            # the admitted float semantics hold for every row) —
            # taking the first chunk's dtype would admit int no-wrap
            # proofs over truncated stats
            dtypes = {}
            for name in self.scan.fields:
                kinds = seen_kinds.get(name, {"o"})
                if "o" in kinds:
                    dtypes[name] = np.dtype(object)
                elif "f" in kinds:
                    dtypes[name] = np.dtype(np.float64)
                else:
                    dtypes[name] = np.dtype(np.int64)
            for name, (lo, hi) in seen_stats.items():
                if lo is not None and dtypes[name].kind == "i":
                    ranges[name] = (int(lo), int(hi))
            return dtypes, ranges, rows
        cols = self._columnarize()
        dtypes = {n: c.dtype for n, c in cols.items()}
        return dtypes, E.int_ranges(cols), \
            len(next(iter(cols.values()))) if cols else 0

    def _columnarize(self):
        """In-memory source -> {field: standardized array} (cached —
        the data is driver-resident either way)."""
        if getattr(self, "_raw_cols", None) is not None:
            return self._raw_cols
        src = self.scan.source
        slices = getattr(src, "_slices", None)
        if slices is None:
            raise _Decline("scan", "source slices not driver-resident")
        fields = self.scan.fields
        from dpark_tpu.rdd import _ColumnarSlice
        if slices and all(isinstance(s, _ColumnarSlice) for s in slices):
            cols = [np.concatenate([np.asarray(s.columns[i])
                                    for s in slices])
                    for i in range(len(fields))]
        else:
            rows = [r for s in slices for r in s]
            if rows and not isinstance(rows[0], tuple):
                rows = [(r,) for r in rows]
            cols = []
            for i in range(len(fields)):
                vals = [r[i] for r in rows]
                kinds = {type(v) for v in vals}
                if kinds <= {int} and kinds:
                    try:
                        cols.append(np.array(vals, np.int64))
                        continue
                    except OverflowError:
                        raise _Decline(
                            "scan", "int column %r exceeds int64"
                            % fields[i])
                if kinds <= {int, float} and kinds:
                    cols.append(np.array(vals, np.float64))
                    continue
                cols.append(np.array(vals, dtype=object))
            if not rows:
                cols = [np.array([], dtype=object)
                        for _ in fields]
        self._raw_cols = {n: _std_col(c)
                          for n, c in zip(fields, cols)}
        return self._raw_cols

    # -- run-time --------------------------------------------------------
    def _note_host_rows(self, n):
        """Rows this scan evaluated on the driver, reported to the array
        executor's counter `scan_rows_host` where the master has one."""
        note = getattr(getattr(self.scan.source.ctx.scheduler, "executor",
                               None), "note_host_scan", None)
        if note is not None:
            note(n)

    def run(self, stats=None):
        """Execute the pipeline -> {field: array} (cached: repeated
        actions on one planned query re-use the scanned columns)."""
        if self._env is not None:
            return self._env
        from dpark_tpu.tabular import TabularRDD, read_chunks
        src = self.scan.source
        if isinstance(src, TabularRDD):
            parts = {name: [] for name in self.out}
            for path in src.files:
                for nrows, cols in read_chunks(
                        path, self.wanted, self.skip_ranges,
                        stats=stats):
                    self._note_host_rows(nrows)
                    env = {}
                    for nm, c in cols.items():
                        a = _std_col(c)
                        want = getattr(self, "src_dtypes", {}).get(nm)
                        # mixed-chunk promotion: an int chunk of a
                        # float-resolved column casts up so the
                        # admitted semantics hold for every row
                        if want is not None and want.kind == "f" \
                                and a.dtype.kind == "i":
                            a = a.astype(want)
                        env[nm] = a
                    env, n = self._apply(env, nrows)
                    for name in self.out:
                        parts[name].append(env[name])
            env = {}
            for name in self.out:
                chunks = parts[name]
                if not chunks:
                    env[name] = np.array(
                        [], dtype=self.dtypes.get(name, object))
                elif len(chunks) == 1:
                    env[name] = chunks[0]
                else:
                    env[name] = np.concatenate(chunks)
        else:
            raw = self._columnarize()
            n = len(next(iter(raw.values()))) if raw else 0
            self._note_host_rows(n)
            env = {k: raw[k] for k in (self.wanted or raw)}
            if stats is not None:
                stats.setdefault("columns_read", set()).update(env)
                stats["chunks_total"] = stats.get("chunks_total", 0) + 1
            env, n = self._apply(env, n)
            env = {name: env[name] for name in self.out}
        self._env = env
        return env

    def _apply(self, env, n):
        for kind, items in self.steps:
            if kind == "filter":
                mask = None
                for ve in items:
                    m = ve.fn(env)
                    mask = m if mask is None else mask & m
                env = {k: v[mask] for k, v in env.items()}
                n = int(mask.sum())
            else:
                out = {}
                for name, spec in items:
                    if spec[0] == "pass":
                        out[name] = env[spec[1]]
                    else:
                        r = spec[1].fn(env)
                        if np.ndim(r) == 0:
                            r = np.full(n, r)
                        out[name] = r
                env = out
        return env, n


# ---------------------------------------------------------------------------
# the planned query
# ---------------------------------------------------------------------------

class PlannedQuery:
    """A lowered query: scan segments + the device RDD pipeline + the
    egest program, with every rule decision recorded."""

    def __init__(self, root, ctx):
        self.root = root
        self.ctx = ctx
        self.ok = False
        self.decisions = []
        self.fallbacks = []
        self.scan_stats = {}
        self.adapt_sig = None
        self.mode = None            # scan | group | join | join_group
        self.segs = []
        self.egest_ops = []         # leaf-to-top (code, kind, meta)
        self.decoders = {}          # out field -> TokenDict
        self._rdd = None
        self._rows_cache = None
        self._group = None
        self._join = None
        self._out_fields = None
        self._partial = None        # result-cache partial-merge recipe
        self._cache_offer = None    # result-cache store-back ticket
        # (ts, dur, args) of plan_query with the trace plane on, until
        # _job emits it under the job's id
        self._plan_reading = None

    # -- bookkeeping -----------------------------------------------------
    def decide(self, rule, op, choice, reason):
        self.decisions.append({"rule": rule, "op": op,
                               "choice": choice, "reason": reason})
        if choice == "host":
            self.fallbacks.append({"op": op, "reason": reason})

    def explain(self):
        lines = ["plan (%s):" % (self.mode or "declined")]
        lines += ["  " + ln for ln in self.root.sketch(1)]
        lines.append("decisions:")
        for d in self.decisions:
            lines.append("  [%s] %s -> %s: %s"
                         % (d["rule"], d["op"], d["choice"],
                            d["reason"]))
        return "\n".join(lines)

    # -- actions ---------------------------------------------------------
    def rows(self):
        if self._rows_cache is None:
            rows = None
            if self._partial is not None:
                rows = self._merge_partial(self._partial)
            if rows is None:
                rows = self._run()
            self._rows_cache = rows
            if self._cache_offer is not None:
                try:
                    from dpark_tpu import resultcache
                    resultcache.offer(self, rows)
                except Exception as e:
                    logger.debug("result cache offer: %s", e)
        return self._rows_cache

    def collect(self):
        return self.rows()

    def take(self, n):
        return self.rows()[:n]

    def count(self):
        has_filter = any(op[0] == "filter" for op in self.egest_ops)
        if self._rows_cache is not None or has_filter \
                or self._partial is not None:
            return len(self.rows())
        if self.mode == "scan" and self.segs[0].device is None:
            env = self.segs[0].run(self.scan_stats)
            return len(next(iter(env.values()))) if env else 0
        return self._job(lambda rdd: rdd.count())[0]

    # -- execution -------------------------------------------------------
    def _job(self, action):
        """One action over the lowered RDD: the query's job.  Returns
        (what the action returned, the id of the job this thread opened
        for it, or None).  With the trace plane on, the reading
        plan_query left on this query is emitted here, as the
        `query.plan` span of that job: a plan that leads to no job
        leaves no span."""
        rdd = self._build_rdd()
        # the calling thread's own record (DAGScheduler._tls), not the
        # history's last, which under concurrent clients is anybody's
        tls = getattr(self.ctx.scheduler, "_tls", None)
        before = getattr(tls, "record", None)
        out = action(rdd)
        record = getattr(tls, "record", None)
        job = record["id"] if record is not None \
            and record is not before else None
        reading, self._plan_reading = self._plan_reading, None
        if reading is not None and job is not None:
            from dpark_tpu import trace
            trace.emit("query.plan", "query", reading[0], reading[1],
                       job=job, **reading[2])
        return out, job

    def _finish(self, raw):
        """The egest side of a query: the collected rows decoded and
        finalized (_shape_rows), then HAVING / projections / ORDER BY
        (_egest).  _run puts it under the `query.finish` span."""
        if self.mode == "scan":
            rows, fields = [tuple(r) for r in raw], self.segs[0].out
        else:
            rows, fields = self._shape_rows(raw)
        return self._egest(rows, fields)

    def _run(self):
        from dpark_tpu import trace
        t0 = time.time()
        if self.mode == "scan" and self.segs[0].device is None:
            env = self.segs[0].run(self.scan_stats)
            names = self.segs[0].out
            rows = list(zip(*(env[n].tolist()
                              if isinstance(env[n], np.ndarray)
                              and env[n].dtype != object
                              else list(env[n]) for n in names))) \
                if names else []
            rows = self._egest(rows, names)
        else:
            raw, job = self._job(lambda rdd: rdd.collect())
            plane = trace._PLANE
            if plane is not None:
                with trace.span("query.finish", "query", job=job) as sp:
                    rows = self._finish(raw)
                    sp.args["rows"] = len(rows)
            else:
                rows = self._finish(raw)
        self._observe("device", (time.time() - t0) * 1e3)
        return rows

    def _merge_partial(self, part):
        """Serve a partial-aggregate cache hit: run the residual plan
        the probe built (covering exactly the source region the cached
        entry does not), then fold the two disjoint aggregate row sets
        with the mergeable combiners.  Any failure returns None and
        the caller falls back to the full uncached run — the merge
        path is an optimization, never a correctness dependency."""
        try:
            from dpark_tpu import resultcache, trace
            t0 = time.time()
            rpq = plan_query(part["residual"], self.ctx, reuse=False)
            if not rpq.ok:
                return None
            res = rpq.rows()
            for k, v in rpq.scan_stats.items():
                if isinstance(v, set):
                    self.scan_stats.setdefault(k, set()).update(v)
                else:
                    self.scan_stats[k] = self.scan_stats.get(k, 0) + v
            rows = resultcache.merge_group_rows(
                part["rows"], res, part["nk"], part["kinds"])
            rows = self._egest(rows, list(part["fields"]))
            trace.event("resultcache.merge", "resultcache",
                        sid=part["key"], cached=len(part["rows"]),
                        residual=len(res),
                        ms=round((time.time() - t0) * 1e3, 2))
            return rows
        except Exception as e:
            logger.debug("partial-aggregate merge fell back: %s", e)
            return None

    def _observe(self, path, ms):
        try:
            from dpark_tpu import adapt
            if self.adapt_sig is not None and adapt.enabled():
                adapt.observe_path(self.adapt_sig, path, ms)
        except Exception:
            pass

    def _build_rdd(self):
        if self._rdd is not None:
            return self._rdd
        from dpark_tpu.rdd import Columns
        ctx = self.ctx
        npart = max(1, ctx.default_parallelism)
        if self.mode == "scan":
            r = self._device_chain(self.segs[0])
        elif self.mode == "group":
            seg = self.segs[0]
            g = self._group
            if seg.device is not None:
                base = self._device_chain(seg)
            else:
                env = seg.run(self.scan_stats)
                # decoders key by the OUTPUT field name (what
                # _shape_rows decodes), not the internal __k*/__a*
                # pipeline names
                dec_names = list(g["key_names"]) + [None] * (
                    len(g["cols"]) - g["nk"])
                cols = [self._encoded(env[c], dn or c)
                        for c, dn in zip(g["cols"], dec_names)]
                if len(cols) == g["nk"]:
                    # count-only query: no aggregate argument columns
                    # — records still need a value leaf (the count
                    # ignores its content)
                    cols.append(np.ones(len(cols[0]) if cols else 0,
                                        np.int64))
                base = ctx.parallelize(Columns(*cols), npart)
            if g["lower"] == "classified":
                r = base.map(_make_pair(g["widths"])) \
                    .groupByKey(npart) \
                    .mapValues(_CLASSIFIED[g["kinds"][0]])
            elif g["lower"] == "uda":
                r = base.map(_make_pair(g["widths"])) \
                    .groupByKey(npart).mapValues(g["uda"])
            else:
                r = base.map(_make_create(g["widths"], g["leaves"])) \
                    .reduceByKey(_make_merge(_merge_ops(g["leaves"])),
                                 npart)
        else:                       # join / join_group
            j = self._join
            sides = []
            for si, seg in enumerate(self.segs):
                env = seg.run(self.scan_stats)
                names = j["side_cols"][si]
                dec_names = j["side_dec"][si]
                n = len(next(iter(env.values()))) if env else 0
                cols = []
                for c, dn in zip(names, dec_names):
                    if c is None:       # key-only side: dummy value
                        cols.append(np.zeros(n, np.int64))
                        continue
                    cols.append(self._encoded(
                        env[c], dn or c, j["enc"].get((si, c))))
                rdd = ctx.parallelize(Columns(*cols), npart)
                sides.append(rdd.map(_make_join_side(len(names) - 1)))
            joined = sides[0].join(sides[1], npart)
            nl = len(j["side_cols"][0]) - 1
            nr = len(j["side_cols"][1]) - 1
            flat = joined.map(_make_join_flat(nl, nr))
            if self.mode == "join_group":
                g = self._group
                flat = flat.map(_make_group_over(
                    tuple(g["key_idxs"]), g["leaves"]))
                r = flat.reduceByKey(
                    _make_merge(_merge_ops(g["leaves"])), npart)
            else:
                r = flat.map(_make_pick(tuple(j["out_idxs"])))
        self._rdd = r
        return r

    def _device_chain(self, seg):
        """The scan pipeline of a table resident on the device as the
        narrow chain of ONE stage over the cached RDD: a pick of the
        columns the query reads, then every admitted Filter as a
        `filter` and every Project as a `map` of the record, which
        fuse.py traces like any user lambda.  The functions close over
        names and programs alone (E.evaluate), so a plan made anew of
        the same text is the same stage program."""
        rdd = seg.scan.source
        names = tuple(seg.scan.fields)
        wanted = tuple(seg.wanted or names)
        if wanted != names:
            rdd = rdd.map(_make_pick(tuple(names.index(c)
                                           for c in wanted)))
            names = wanted
        for kind, items in seg.steps:
            if kind == "filter":
                rdd = rdd.filter(_make_dev_filter(
                    names, tuple(ve.prog for ve in items)))
                continue
            rdd = rdd.map(_make_dev_project(names, tuple(
                spec[1] if spec[0] == "pass"
                else (spec[1].prog, spec[1].kind)
                for _, spec in items)))
            names = tuple(n for n, _ in items)
        return rdd

    def _encoded(self, col, name, dict_=None):
        """Dictionary-encode an object column for the device path (or
        pass a numeric column through).  `dict_` shares one TokenDict
        across the two sides of a join.  Only GENUINE str values
        encode — a bool/None/mixed object column raises, which the
        table action catches as a recorded host fallback (encoding
        them would silently turn True into the string 'True' at
        egest)."""
        if col.dtype != object and col.dtype.kind not in "US":
            return col
        from dpark_tpu.native import TokenDict
        td = dict_ if dict_ is not None else TokenDict()
        if len(col):
            # np.unique on a mixed-type object column raises on the
            # sort compare — also a (caught) host fallback
            uniq, inv = np.unique(col, return_inverse=True)
            for u in uniq.tolist():
                if type(u) is not str:
                    raise TypeError(
                        "non-string value %r in dictionary-encoded "
                        "column %r (host path serves it)" % (u, name))
            ids = np.array([td.put(u) for u in uniq.tolist()],
                           np.int64)
            out = ids[inv]
        else:
            out = np.array([], np.int64)
        self.decoders.setdefault(name, td)
        return out

    def _decode(self, name, val):
        td = self.decoders.get(name)
        if td is None:
            return val
        return td.decode(int(val))

    def _shape_rows(self, raw):
        """Collected device rows -> flat output tuples of the pre-egest
        schema, finalized (avg division etc.) and decoded, with exact
        Python scalars."""
        out = []
        if self.mode in ("group", "join_group"):
            g = self._group
            key_names = g["key_names"]
            widths = g["widths"]
            for k, acc in raw:
                keys = _key_columns(
                    k, widths, lambda i, v: self._decode(
                        key_names[i], _normalize(v)))
                if g["lower"] in ("classified", "uda"):
                    out.append(keys + (_normalize(acc),))
                    continue
                acc = _normalize(tuple(acc))
                vals = []
                for kind, ref in zip(g["kinds"], g["refs"]):
                    if kind == "avg":
                        s, c = acc[ref[0]], acc[ref[1]]
                        vals.append(s / c if c else None)
                    else:
                        vals.append(acc[ref[0]])
                out.append(keys + tuple(vals))
            return out, list(g["key_names"]) + list(g["agg_names"])
        # join (no group): rows are already flat in out_idx order
        j = self._join
        fields = j["out_fields"]
        for rec in raw:
            rec = tuple(_normalize(v) for v in rec)
            rec = tuple(self._decode(fields[i], v)
                        for i, v in enumerate(rec))
            out.append(rec)
        return out, fields

    def _egest(self, rows, fields):
        """Result finishing with exact host eval semantics: HAVING
        filters, post-aggregate projections, ORDER BY — one row per
        group by now, driver-resident."""
        from dpark_tpu.table import _SAFE_BUILTINS
        for kind, meta in self.egest_ops:
            if kind == "filter":
                keep = []
                for row in rows:
                    env = dict(zip(fields, row))
                    if all(eval(code, {"__builtins__": _SAFE_BUILTINS},
                                dict(env)) for code in meta):
                        keep.append(row)
                rows = keep
            elif kind == "project":
                names = [n for n, _ in meta]
                new = []
                for row in rows:
                    env = dict(zip(fields, row))
                    new.append(tuple(
                        eval(code, {"__builtins__": _SAFE_BUILTINS},
                             dict(env)) for _, code in meta))
                rows = new
                fields = names
            else:                   # sort
                codes, reverse = meta
                def key(row, codes=codes, fields=fields):
                    env = dict(zip(fields, row))
                    ks = [eval(c, {"__builtins__": _SAFE_BUILTINS},
                               dict(env)) for c in codes]
                    return ks[0] if len(ks) == 1 else tuple(ks)
                rows = sorted(rows, key=key, reverse=reverse)
        self._out_fields = fields
        return rows


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

def plan_query(root, ctx, reuse=True):
    """Plan a logical tree onto the device path.  Returns a
    PlannedQuery; `.ok` False means the host object path should serve
    the query (with `.fallbacks` carrying the reasons).  `reuse=False`
    skips the result-cache probe (residual plans must not re-probe)."""
    pq = PlannedQuery(root, ctx)
    from dpark_tpu import trace
    plane = trace._PLANE
    if plane is not None:
        # no job exists yet: the reading stays with the query until the
        # job it leads to is open (PlannedQuery._job)
        t0 = time.time()
        _plan(pq, reuse)
        sources = {"device" if seg.device is not None else "files"
                   if hasattr(seg.scan.source, "files") else "driver"
                   for seg in pq.segs}
        pq._plan_reading = (t0, time.time() - t0, {
            "mode": pq.mode, "source": "+".join(sorted(sources)),
            "rules": len(pq.decisions)})
        return pq
    return _plan(pq, reuse)


def _plan(pq, reuse):
    try:
        _rule_shape(pq)
        _rule_resident(pq)
        _rule_prune(pq)
        _rule_scan_pipelines(pq)
        if pq.mode in ("join", "join_group"):
            _rule_lower_join(pq)
        if pq.mode in ("group", "join_group"):
            _rule_lower_group(pq)
        compile_egest(pq)
        _rule_price(pq)
        if reuse:
            _rule_reuse(pq)
        pq.ok = True
    except _Decline as d:
        pq.decide("planner", d.op, "host", d.reason)
        pq.ok = False
    except Exception as e:          # planner bugs must not kill queries
        logger.debug("query planning failed: %s", e)
        pq.decide("planner", "plan", "host",
                  "planner error: %s" % str(e)[:160])
        pq.ok = False
    return pq


def _rule_resident(pq):
    """A Scan over a table resident on the device plans from what was
    read when the table was made; the batch itself has to be there
    still (the executor drops cached results under HBM pressure)."""
    for seg in pq.segs:
        if seg.device is None:
            continue
        executor = getattr(pq.ctx.scheduler, "executor", None)
        if executor is None \
                or seg.scan.source.id not in executor.result_cache_ids():
            raise _Decline("scan", "table %r is no longer resident on "
                           "the device" % seg.scan.table_name)
        pq.decide("scan-resident", "scan", "device",
                  "table %r is resident on the device (%d rows): its "
                  "filters and projections are the narrow stage's"
                  % (seg.scan.table_name, seg.device["rows"]))


def _linearize(node):
    ops = []
    while isinstance(node, (Project, Filter, Sort)):
        ops.append(node)
        node = node.children[0]
    return ops, node


def _rule_shape(pq):
    """Normalize the tree into (egest ops, core, scan pipelines);
    decline shapes outside the supported grammar."""
    ops1, core = _linearize(pq.root)
    if isinstance(core, Scan):
        pq.mode = "scan"
        pq.segs = [_ScanSeg(core)]
        # Sorts cannot vectorize into the columnar pipe; they (and
        # everything ABOVE them — order matters) finish at egest
        pipe, egest = [], []
        for op in reversed(ops1):          # leaf-to-top
            if egest or isinstance(op, Sort):
                egest.append(op)
            else:
                pipe.append(op)
        pq._shape = {"scan_ops": list(reversed(pipe)), "egest": egest}
        return
    if isinstance(core, GroupAgg):
        ops2, inner = _linearize(core.children[0])
        if any(isinstance(o, Sort) for o in ops2):
            raise _Decline("sort", "sort below a group-by has no "
                           "effect on grouped output; host path")
        if isinstance(inner, Scan):
            pq.mode = "group"
            pq.segs = [_ScanSeg(inner)]
            pq._shape = {"scan_ops": ops2, "egest": list(reversed(ops1)),
                         "group": core}
            return
        if isinstance(inner, Join):
            pq.mode = "join_group"
            pq._shape = {"egest": list(reversed(ops1)), "group": core,
                         "join": inner, "join_ops": ops2}
            _shape_join(pq, inner)
            return
        raise _Decline("plan", "unsupported plan below group-by")
    if isinstance(core, Join):
        pq.mode = "join"
        pq._shape = {"egest": list(reversed(ops1)), "join": core,
                     "join_ops": []}
        _shape_join(pq, core)
        return
    raise _Decline("plan", "unsupported plan shape (%s)"
                   % type(core).__name__)


def _shape_join(pq, join):
    sides = []
    for child in join.children:
        ops, leaf = _linearize(child)
        if not isinstance(leaf, Scan):
            raise _Decline("join", "join input is not a scan chain")
        if any(isinstance(o, Sort) for o in ops):
            raise _Decline("sort", "sort below a join stays on host")
        if leaf.device is not None:
            raise _Decline("join", "a join over a table resident on "
                           "the device is not lowered yet (table %r)"
                           % leaf.table_name)
        sides.append((ops, leaf))
    pq.segs = [_ScanSeg(leaf) for _, leaf in sides]
    pq._shape["side_ops"] = [ops for ops, _ in sides]


def _refs_of(ops, needed):
    """Columns a scan must produce so `ops` (leaf-to-top application
    order is reversed(ops)) can compute `needed` output names."""
    need = set(needed)
    for op in ops:                  # ops are top-down: walk downward
        if isinstance(op, Project):
            nxt = set()
            for name, ce in op.exprs:
                if name in need or not need:
                    nxt |= ce.columns
            need = nxt
        elif isinstance(op, Filter):
            for p in op.preds:
                need |= p.columns
        elif isinstance(op, Sort):
            for k in op.keys:
                need |= k.columns
    return need


def _rule_prune(pq):
    """Column pruning: each scan reads only the columns the query
    references."""
    sh = pq._shape
    if pq.mode == "scan":
        needed = set(pq.root.fields)
        for op in sh["egest"]:
            if isinstance(op, (Filter,)):
                for p in op.preds:
                    needed |= p.columns
            elif isinstance(op, Sort):
                for k in op.keys:
                    needed |= k.columns
            elif isinstance(op, Project):
                for _, ce in op.exprs:
                    needed |= ce.columns
        wanted = _refs_of(sh["scan_ops"],
                          needed & set(_pipe_out_fields(pq)))
        scan = pq.segs[0].scan
        if not sh["scan_ops"]:
            wanted = needed & set(scan.fields)
        pq.segs[0].wanted = [c for c in scan.fields if c in wanted] \
            or list(scan.fields[:1])
        pq.decide("prune-columns", "scan", "device",
                  "scan reads %s of %d columns"
                  % (pq.segs[0].wanted, len(scan.fields)))
        return
    if pq.mode == "group":
        g = sh["group"]
        needed = set()
        for _, ce in g.keys:
            needed |= ce.columns
        for (_name, _fn, arg, _uda) in g.aggs:
            if arg is not None:
                needed |= arg.columns
        wanted = _refs_of(sh["scan_ops"], needed)
        scan = pq.segs[0].scan
        pq.segs[0].wanted = [c for c in scan.fields if c in wanted] \
            or list(scan.fields[:1])
        pq.decide("prune-columns", "scan", "device",
                  "scan reads %s of %d columns"
                  % (pq.segs[0].wanted, len(scan.fields)))
        return
    # join modes: need the on-column + every referenced output column,
    # mapped back through the join's column map to each side
    join = sh["join"]
    needed_out = set()
    if pq.mode == "join_group":
        g = sh["group"]
        for _, ce in g.keys:
            needed_out |= ce.columns
        for (_name, _fn, arg, _uda) in g.aggs:
            if arg is not None:
                needed_out |= arg.columns
    else:
        needed_out = set(join.fields)
        for op in sh["egest"]:
            if isinstance(op, Filter):
                for p in op.preds:
                    needed_out |= p.columns
            elif isinstance(op, Sort):
                for k in op.keys:
                    needed_out |= k.columns
            elif isinstance(op, Project):
                for _, ce in op.exprs:
                    needed_out |= ce.columns
    for op in sh["join_ops"]:
        if isinstance(op, Filter):
            for p in op.preds:
                needed_out |= p.columns
        else:
            raise _Decline(
                "join", "non-filter operator between join and "
                "group-by stays on host")
    side_needed = [set(), set()]
    for out_name, side, src in join.colmap:
        if side == "on":
            continue
        if out_name in needed_out:
            side_needed[0 if side == "l" else 1].add(src)
    for si, (ops) in enumerate(sh["side_ops"]):
        scan = pq.segs[si].scan
        wanted = _refs_of(ops, side_needed[si] | {join.on})
        wanted |= {join.on}
        pq.segs[si].wanted = [c for c in scan.fields if c in wanted]
        pq.decide("prune-columns", "scan[%d]" % si, "device",
                  "scan reads %s of %d columns"
                  % (pq.segs[si].wanted, len(scan.fields)))
    pq._side_needed = side_needed


def _pipe_out_fields(pq):
    """Field names the scan pipeline ends with (after its projects)."""
    ops = pq._shape["scan_ops"]
    for op in ops:                  # topmost project wins
        if isinstance(op, Project):
            return [n for n, _ in op.exprs]
    return pq.segs[0].scan.fields


def _build_pipeline(pq, seg, ops, label):
    """Admit a scan-side op chain as vectorized steps; fills
    seg.steps/out/dtypes/bounds.  Declines with the exact reason."""
    dtypes, ranges, nrows = seg.source_meta()
    seg.nrows = nrows
    seg.src_dtypes = dict(dtypes)   # run() casts chunks up to these
    device = seg.device is not None
    env = {}                        # name -> (dtype, bounds, src | None)
    for c in (seg.wanted or seg.scan.fields):
        env[c] = (dtypes.get(c, np.dtype(object)), ranges.get(c), c)
    first_filters = True
    skip = {}
    for op in reversed(ops):        # leaf-to-top application order
        if isinstance(op, Filter):
            fns = []
            for p in op.preds:
                ve, reason = E.vectorize(
                    p, {k: v[0] for k, v in env.items()},
                    {k: v[1] for k, v in env.items() if v[1]},
                    boolean=True, device=device)
                if ve is None:
                    raise _Decline(
                        "filter", "predicate %r stays on the host: %s"
                        % (p.expr, reason))
                fns.append(ve)
                if first_filters and not device:
                    for col, rng in _skip_bounds(
                            p, set(seg.wanted or ()),
                            {k: v[0] for k, v in env.items()}).items():
                        src = env.get(col, (None, None, None))[2]
                        if src is not None:
                            plo, phi = skip.get(src, (None, None))
                            lo, hi = rng
                            if lo is not None:
                                plo = lo if plo is None \
                                    else max(plo, lo)
                            if hi is not None:
                                phi = hi if phi is None \
                                    else min(phi, hi)
                            skip[src] = (plo, phi)
            seg.steps.append(("filter", fns))
        elif isinstance(op, Project):
            first_filters = False
            items = []
            nxt = {}
            for name, ce in op.exprs:
                if ce.columns and not ce.columns <= set(env):
                    # an output nothing above reads, over a column the
                    # pruning therefore left out (_refs_of): dropped
                    # here too; a later reader of it still declines
                    continue
                if _is_bare_name(ce):
                    src = ce.tree.body.id
                    if src not in env:
                        raise _Decline("project",
                                       "unknown column %r" % src)
                    items.append((name, ("pass", src)))
                    nxt[name] = env[src]
                    continue
                ve, reason = E.vectorize(
                    ce, {k: v[0] for k, v in env.items()},
                    {k: v[1] for k, v in env.items() if v[1]},
                    device=device)
                if ve is not None and device and ve.kind == "f" \
                        and pq.mode == "scan":
                    ve, reason = None, (
                        "float arithmetic over a table resident on the "
                        "device is float32 and the rows are the answer")
                if ve is None:
                    raise _Decline(
                        "project", "expression %r stays on the host: "
                        "%s" % (ce.expr, reason))
                items.append((name, ("vec", ve)))
                nxt[name] = (np.dtype(np.int64) if ve.kind == "i"
                             else np.dtype(np.float64), ve.bounds,
                             None)
            seg.steps.append(("project", items))
            env = nxt
        else:
            raise _Decline("sort", "sort inside a scan pipeline")
    if skip:
        seg.skip_ranges = skip
        pq.decide("pushdown-predicate", label, "device",
                  "chunk-skip ranges %s" % {
                      k: v for k, v in sorted(skip.items())})
    nfilters = sum(1 for k, _ in seg.steps if k == "filter")
    if nfilters:
        pq.decide("pushdown-predicate", label, "device",
                  "%d predicate(s) evaluate as vectorized array "
                  "programs before any row materializes" % nfilters)
    seg.env_meta = env
    seg.out = list(env)
    seg.dtypes = {k: v[0] for k, v in env.items()}
    seg.bounds = {k: v[1] for k, v in env.items() if v[1]}
    return env


def _rule_scan_pipelines(pq):
    sh = pq._shape
    if pq.mode in ("scan", "group"):
        _build_pipeline(pq, pq.segs[0], sh["scan_ops"], "scan")
    else:
        for si, ops in enumerate(sh["side_ops"]):
            _build_pipeline(pq, pq.segs[si], ops, "scan[%d]" % si)


def _key_decline(name, dt, device=False):
    if dt.kind == "f":
        return ("float group/join key %r: device hash routing needs "
                "int keys (floats ride range/sortByKey only)" % name)
    if dt.kind == "S" and device:
        return None     # its words are int key columns (_key_of)
    if dt.kind not in "i" and dt != np.dtype(object):
        return "unsupported key dtype %s for %r" % (dt, name)
    return None


def _key_words_ok(seg, bounds, widths, key_srcs):
    """A resident table's key columns as int key words (_key_of): how
    many there are, and that no valid row's word is the exchange's
    padding sentinel, proved from the load-time ranges as ingest proves
    it of a host batch.  Returns a decline reason or None."""
    from dpark_tpu import conf
    if _key_nwords(widths) > int(getattr(conf, "MAX_KEY_LEAVES", 4)):
        return ("the group key is %d key words (a byte string is one "
                "a started 8 bytes), over conf.MAX_KEY_LEAVES=%d"
                % (_key_nwords(widths), conf.MAX_KEY_LEAVES))
    stats = dict(zip(seg.scan.fields, seg.device["columns"]))
    for ranges, src in zip(bounds, key_srcs):
        # a derived int key has its expression's bounds; a bare column
        # (a number's range, a byte string's a word) its load-time ones
        if ranges is None and src in stats:
            ranges = stats[src][1]
        for rng in ranges if isinstance(ranges, list) else [ranges]:
            if rng is None or rng[1] >= _I64_MAX:
                return ("a group key column may hold the device's "
                        "padding sentinel (no range below 2**63 - 1)")
    return None


def _rule_lower_group(pq):
    """Lower GroupAgg onto the device exchange: key shapes, aggregate
    kinds, int-sum overflow proofs, UDA admission."""
    from dpark_tpu import conf
    g = pq._shape["group"]
    seg = pq.segs[0] if pq.mode == "group" else None
    nrows = max(1, max(getattr(s, "nrows", 1) or 1 for s in pq.segs))
    # -- keys ------------------------------------------------------------
    key_cols, key_names, encode = [], [], []
    if len(g.keys) > int(getattr(conf, "MAX_KEY_LEAVES", 4)):
        raise _Decline("group-agg", "%d group keys exceed "
                       "conf.MAX_KEY_LEAVES=%d" % (
                           len(g.keys), conf.MAX_KEY_LEAVES))
    if pq.mode == "group":
        env = seg.env_meta
        device = seg.device is not None
        extra = []                  # derived key/arg project items
        widths, key_srcs = [], []
        for name, ce in g.keys:
            cname = "__k%d" % len(key_cols)
            dt, reason = _group_col(pq, seg, env, ce, cname, extra)
            if reason is not None:
                raise _Decline("group-agg", "group key %r: %s"
                               % (ce.expr, reason))
            bad = _key_decline(ce.expr, dt, device)
            if bad:
                if dt == np.dtype(object):
                    encode.append(cname)
                else:
                    raise _Decline("group-agg", bad)
            elif dt == np.dtype(object):
                encode.append(cname)
            key_cols.append(cname)
            key_names.append(name)
            widths.append(dt.itemsize if dt.kind == "S" else 0)
            # the SOURCE column behind a bare name, through any
            # passing projections (the load-time stats are by it)
            key_srcs.append(env[ce.tree.body.id][2]
                            if _is_bare_name(ce) else None)
        widths = tuple(widths)
        if device:
            bad = _key_words_ok(
                seg, [env.get(c, (None, None))[1] for c in key_cols],
                widths, key_srcs)
            if bad:
                raise _Decline("group-agg", bad)

        def _extra_pop(cname):
            extra[:] = [(n, s) for n, s in extra if n != cname]
            env.pop(cname, None)

        kinds, agg_args, arg_cols, agg_names, uda = _admit_aggs(
            pq, g, nrows, lambda ce, nm:
            _group_col(pq, seg, env, ce, nm, extra), _extra_pop)
        if extra:
            # the derived key/arg project REPLACES the pipeline's
            # output env: from here on the exchange sees only the
            # __k*/__a* columns
            seg.steps.append(("project", list(extra)))
            seg.out = [n for n, _ in extra]
        leaves, refs = _share_leaves(kinds, agg_args)
        pq._group = {
            "cols": key_cols + arg_cols, "nk": len(key_cols),
            "kinds": tuple(kinds), "key_names": key_names,
            "agg_names": agg_names, "encode": encode,
            "widths": widths, "leaves": leaves, "refs": refs,
            # a resident table always combines on the map side: the
            # narrow stage's shuffle write is the aggregation
            "lower": ("uda" if uda is not None else
                      "classified" if _classified_ok(kinds)
                      and not device else "reduce"),
            "uda": uda}
        if encode:
            pq.decide("encode-strings", "group-agg", "device",
                      "string group key(s) %s ride dictionary-encoded "
                      "(TokenDict int64 ids, decoded at egest)"
                      % [key_names[key_cols.index(c)] for c in encode])
        if any(widths):
            pq.decide("key-words", "group-agg", "device",
                      "byte-string group key(s) %s ride as int64 key "
                      "words (%d in all), rebuilt as bytes at egest"
                      % ([n for n, w in zip(key_names, widths) if w],
                         _key_nwords(widths)))
        pq.decide("lower-group-agg", "group-agg", "device",
                  "lowered as %s over the %s-key exchange (aggs: %s; "
                  "%d accumulator leaves)"
                  % (pq._group["lower"],
                     "tuple" if _key_nwords(widths) > 1 else "scalar",
                     ",".join(kinds) if kinds else "uda",
                     len(leaves)))
        return
    # -- join_group: keys/args picked from the flat joined row ----------
    j = pq._join
    idx_of = j["idx_of"]
    dtypes = j["out_dtypes"]
    key_idxs = []
    key_names = []
    for name, ce in g.keys:
        if not _is_bare_name(ce) or ce.tree.body.id not in idx_of:
            raise _Decline(
                "group-agg", "group key %r over a join must be a "
                "plain joined column" % ce.expr)
        src = ce.tree.body.id
        dt = dtypes[src]
        bad = _key_decline(src, dt)
        if bad and dt != np.dtype(object):
            raise _Decline("group-agg", bad)
        key_idxs.append(idx_of[src])
        key_names.append(name)
    kinds, agg_args, agg_names = [], [], []
    for (name, fn, arg, uda) in g.aggs:
        if uda is not None:
            raise _Decline("group-agg",
                           "UDA over a join stays on host")
        if fn not in DEVICE_AGGS:
            raise _Decline("group-agg", "non-device aggregate %r "
                           "(device aggregates: %s)"
                           % (fn, "/".join(DEVICE_AGGS)))
        if fn == "count" and arg is not None and _is_bare_name(arg) \
                and dtypes.get(arg.tree.body.id) == np.dtype(object):
            raise _Decline(
                "group-agg", "count(%s) over an object column counts "
                "non-null on the host" % arg.expr)
        if fn != "count":
            if arg is None or not _is_bare_name(arg) \
                    or arg.tree.body.id not in idx_of:
                raise _Decline(
                    "group-agg", "aggregate argument %r over a join "
                    "must be a plain joined column"
                    % (arg.expr if arg else None))
            src = arg.tree.body.id
            if dtypes[src] == np.dtype(object):
                raise _Decline("group-agg",
                               "string aggregate column %r" % src)
        kinds.append(fn)
        agg_args.append(None if fn == "count" else idx_of[src])
        agg_names.append(name)
    leaves, refs = _share_leaves(kinds, agg_args)
    pq._group = {"nk": len(key_idxs), "kinds": tuple(kinds),
                 "key_idxs": key_idxs, "leaves": leaves, "refs": refs,
                 "widths": (0,) * len(key_idxs),
                 "key_names": key_names, "agg_names": agg_names,
                 "lower": "reduce", "uda": None}
    pq.decide("lower-group-agg", "group-agg", "device",
              "grouped join lowered as reduce over the joined rows")


def _classified_ok(kinds):
    return len(kinds) == 1 and kinds[0] in _CLASSIFIED


def _group_col(pq, seg, env, ce, cname, extra):
    """Admit one group key / aggregate-argument expression as a
    derived scan column; returns (dtype, None) or (None, reason)."""
    if _is_bare_name(ce):
        src = ce.tree.body.id
        if src not in env:
            return None, "unknown column %r" % src
        extra.append((cname, ("pass", src)))
        return env[src][0], None
    ve, reason = E.vectorize(
        ce, {k: v[0] for k, v in env.items()},
        {k: v[1] for k, v in env.items() if v[1]},
        device=seg.device is not None)
    if ve is None:
        return None, reason
    extra.append((cname, ("vec", ve)))
    dt = np.dtype(np.int64) if ve.kind == "i" else np.dtype(np.float64)
    env[cname] = (dt, ve.bounds, None)
    return dt, None


def _admit_aggs(pq, g, nrows, admit_col, extra_pop):
    """Aggregate admission for the single-input group: device kinds,
    derived arg columns (one a distinct argument text: sum(x) and
    avg(x) read the same column), overflow proofs, UDA traceability.
    Returns (kinds, the index of each aggregate's argument column or
    None, the argument columns, names, uda)."""
    kinds, agg_args, arg_cols, agg_names = [], [], [], []
    by_text = {}
    uda = None
    for (name, fn, arg, uda_fn) in g.aggs:
        if uda_fn is not None:
            if len(g.aggs) != 1:
                raise _Decline("group-agg", "a UDA must be the only "
                               "aggregate of its query")
            cname = "__a0"
            dt, reason = admit_col(arg, cname)
            if reason is not None:
                raise _Decline("group-agg", "UDA argument: %s" % reason)
            if dt == np.dtype(object) or dt.kind == "S":
                raise _Decline("group-agg", "string UDA argument")
            _check_uda(uda_fn, dt)
            arg_cols.append(cname)
            agg_names.append(name)
            uda = uda_fn
            continue
        if fn not in DEVICE_AGGS:
            raise _Decline(
                "group-agg", "non-device aggregate %r (device "
                "aggregates: %s; adcount/first/group_concat keep the "
                "host path)" % (fn, "/".join(DEVICE_AGGS)))
        if fn == "count":
            if arg is not None:
                # count(col) skips None arguments on the host; a
                # NUMERIC argument column can never hold None, so the
                # device count(*) form is exact — but an object
                # column can, and must keep the host path
                cname = "__cnt_probe"
                dt, reason = admit_col(arg, cname)
                if reason is None and dt == np.dtype(object):
                    reason = ("count(%s) over an object column "
                              "counts non-null on the host"
                              % arg.expr)
                if reason is not None:
                    raise _Decline("group-agg", "aggregate count(%s): "
                                   "%s" % (arg.expr, reason))
                extra_pop(cname)
            kinds.append("count")
            agg_args.append(None)
            agg_names.append(name)
            continue
        shared = by_text.get(arg.expr)
        cname = "__a%d" % (len(arg_cols) if shared is None else shared[0])
        if shared is None:
            dt, reason = admit_col(arg, cname)
            if reason is not None:
                raise _Decline("group-agg", "aggregate %s(%s): %s"
                               % (fn, arg.expr, reason))
            if dt == np.dtype(object) or dt.kind == "S":
                raise _Decline("group-agg",
                               "string aggregate column %r" % arg.expr)
            shared = by_text[arg.expr] = (len(arg_cols), dt)
            arg_cols.append(cname)
        dt = shared[1]
        if fn in ("sum", "avg") and dt.kind == "i":
            # the host folds exact Python ints; the device wraps at
            # int64 — prove the total cannot leave int64
            bounds = _arg_bounds(pq, arg, cname)
            if bounds is None:
                raise _Decline(
                    "group-agg", "int %s(%s) has no value range for "
                    "the no-overflow proof" % (fn, arg.expr))
            peak = max(abs(bounds[0]), abs(bounds[1])) * max(1, nrows)
            if peak > _I64_MAX:
                raise _Decline(
                    "group-agg", "int %s(%s) may overflow int64 "
                    "(|value| <= %d over %d rows)"
                    % (fn, arg.expr, max(abs(bounds[0]),
                                         abs(bounds[1])), nrows))
        kinds.append(fn)
        agg_args.append(shared[0])
        agg_names.append(name)
    return kinds, agg_args, arg_cols, agg_names, uda


def _arg_bounds(pq, arg, cname):
    seg = pq.segs[0]
    b = seg.bounds.get(cname)
    if b is not None:
        return b
    env = getattr(seg, "env_meta", {})
    ent = env.get(cname)
    if ent is not None and ent[1] is not None:
        return ent[1]
    if _is_bare_name(arg):
        ent = env.get(arg.tree.body.id)
        if ent is not None:
            return ent[1]
    return None


def _check_uda(fn, dt):
    """A UDA must be a traceable, padding-invariant per-group function
    — the SegMapOp admission, checked HERE so a failing UDA is a
    recorded planner decline instead of a silent runtime fallback."""
    try:
        from dpark_tpu.backend.tpu import fuse
    except Exception:
        return                      # no jax: the host path serves it
    vdt = np.dtype(np.int64) if dt.kind == "i" else np.dtype(dt)
    pad, reason_or_vdef, _ = fuse.classify_seg_map(fn, vdt)
    if pad is None:
        raise _Decline("group-agg", "non-traceable UDA: %s"
                       % reason_or_vdef)


def _rule_lower_join(pq):
    """Lower the equi-join onto the device join source: shared key
    dtype (string keys share one TokenDict), side layouts, post-join
    filters pushed to their side's scan when single-sided."""
    join = pq._shape["join"]
    segs = pq.segs
    key_dts = []
    for si in range(2):
        dt = segs[si].dtypes.get(join.on)
        if dt is None:
            raise _Decline("join", "join column %r not produced by "
                           "side %d's scan" % (join.on, si))
        key_dts.append(dt)
    enc = {}
    if any(dt == np.dtype(object) for dt in key_dts):
        if key_dts[0] != key_dts[1]:
            raise _Decline("join", "join key dtypes disagree "
                           "(%s vs %s)" % tuple(key_dts))
        from dpark_tpu.native import TokenDict
        shared = TokenDict()
        enc[(0, join.on)] = shared
        enc[(1, join.on)] = shared
        pq.decide("encode-strings", "join", "device",
                  "string join key %r rides dictionary-encoded "
                  "(one shared TokenDict across both sides)" % join.on)
    else:
        bad = _key_decline(join.on, key_dts[0]) \
            or _key_decline(join.on, key_dts[1])
        if bad:
            raise _Decline("join", bad)
    # side column layouts: on-key first, then each side's needed
    # passthrough columns (join output order)
    side_needed = getattr(pq, "_side_needed", [set(), set()])
    on_out = next(o for (o, s, c) in join.colmap if s == "on")
    side_cols = [[join.on], [join.on]]
    side_dec = [[on_out], [on_out]]     # decoder names (output names)
    out_fields = []
    out_idxs = []
    idx_of = {}
    out_dtypes = {}
    # flat row layout: (on, l_needed..., r_needed...)
    lmap = [(o, s, c) for (o, s, c) in join.colmap if s == "l"]
    rmap = [(o, s, c) for (o, s, c) in join.colmap if s == "r"]
    side_outs = [[], []]
    for side_i, cmap in ((0, lmap), (1, rmap)):
        for out_name, _s, src in cmap:
            if src not in side_needed[side_i]:
                continue
            side_cols[side_i].append(src)
            side_dec[side_i].append(out_name)
            side_outs[side_i].append(out_name)
            out_dtypes[out_name] = segs[side_i].dtypes.get(
                src, np.dtype(object))
    # a side with only the key still needs one value column (the
    # device join carries (k, v) records) — a dummy zero rides along
    for si in range(2):
        if len(side_cols[si]) == 1:
            side_cols[si].append(None)      # dummy marker
            side_dec[si].append(None)
            side_outs[si].append(None)
    idx_of[on_out] = 0
    out_dtypes[on_out] = key_dts[0]
    flat_idx = 1
    for si in range(2):
        for out_name in side_outs[si]:
            if out_name is not None:
                idx_of[out_name] = flat_idx
            flat_idx += 1
    # join output order for the no-group mode
    if pq.mode == "join":
        for out_name in join.fields:
            if out_name not in idx_of:
                raise _Decline("join", "output column %r not mapped "
                               "through the join" % out_name)
            out_fields.append(out_name)
            out_idxs.append(idx_of[out_name])
    pq._join = {"side_cols": side_cols, "side_dec": side_dec,
                "enc": enc, "idx_of": idx_of,
                "out_dtypes": out_dtypes,
                "out_fields": out_fields, "out_idxs": out_idxs}
    # post-join filters: push single-side predicates into that side's
    # scan pipeline; anything cross-side declines (v1 surface)
    for op in pq._shape.get("join_ops", ()):
        for p in op.preds:
            pushed = False
            for si, cmap in ((0, lmap + [(on_out, "on", join.on)]),
                             (1, rmap + [(on_out, "on", join.on)])):
                names = {o: c for (o, _s, c) in cmap}
                if p.columns <= set(names):
                    seg = segs[si]
                    alias_dt = {names[o]: seg.dtypes.get(
                        names[o], np.dtype(object))
                        for o in p.columns}
                    remapped = E.compile_expr(
                        _rename_expr(p, names), list(alias_dt))
                    ve, reason = E.vectorize(
                        remapped, alias_dt,
                        {names[o]: seg.bounds.get(names[o])
                         for o in p.columns
                         if seg.bounds.get(names[o])},
                        boolean=True)
                    if ve is None:
                        raise _Decline(
                            "filter", "post-join predicate %r: %s"
                            % (p.expr, reason))
                    seg.steps.append(("filter", [ve]))
                    pq.decide("pushdown-predicate", "join", "device",
                              "post-join predicate %r pushed below "
                              "the join into scan[%d]" % (p.expr, si))
                    pushed = True
                    break
            if not pushed:
                raise _Decline(
                    "filter", "cross-side post-join predicate %r "
                    "stays on the host" % p.expr)
    pq.decide("lower-join", "join", "device",
              "equi-join on %r lowered onto the device join source"
              % join.on)


def _rename_expr(colexpr, name_map):
    """Expression text with output names substituted by source names
    (token-level; names are \\w+ so a regex boundary is exact)."""
    import re
    text = colexpr.expr
    for out, src in sorted(name_map.items(), key=lambda kv: -len(kv[0])):
        if out != src:
            text = re.sub(r"\b%s\b" % re.escape(out), src, text)
    return text


def _rule_price(pq):
    """Adapt decision point 2 at query granularity: with observed ms
    for both paths of this (query shape, scale) class, the cheaper one
    wins; the losing device plan records the priced reason."""
    try:
        from dpark_tpu import adapt
        if not adapt.enabled():
            return
        desc = ("query", pq.mode,
                tuple(pq.root.sketch()),
                tuple(sorted((k, str(v)) for s in pq.segs
                             for k, v in s.dtypes.items())))
        rows = max((getattr(s, "nrows", 0) or 0) for s in pq.segs)
        cls = "q%d" % (1 << max(0, int(rows - 1).bit_length())) \
            if rows else "q0"
        pq.adapt_sig = (adapt.stable_key(desc), cls)
        choice = adapt.choose_path(pq.adapt_sig)
        if choice is not None and choice["choice"] == "object":
            raise _Decline("price-path", choice["reason"])
        if choice is not None:
            pq.decide("price-path", "plan", "device", choice["reason"])
    except _Decline:
        raise
    except Exception as e:
        logger.debug("query pricing skipped: %s", e)


def _rule_reuse(pq):
    """Probe the shared result-cache plane (resultcache.py) with the
    finished plan: a full hit presets the row cache and swaps the root
    for a CachedResult leaf; a partial-aggregate hit installs the
    merge recipe (`pq._partial`); a miss leaves a store-back offer so
    the first execution populates the cache.  One `is None` check when
    the plane is off; any plane error is logged and the plan proceeds
    uncached."""
    try:
        from dpark_tpu import resultcache
        resultcache.probe(pq)
    except Exception as e:
        logger.debug("result cache probe skipped: %s", e)


# ---------------------------------------------------------------------------
# egest compilation (shared by table.py)
# ---------------------------------------------------------------------------

def compile_egest(pq):
    """Turn the egest op list (leaf-to-top) into evaluated programs:
    code objects for filters/projects/sort keys (exact host eval
    semantics at driver-side result finishing)."""
    ops = []
    for op in pq._shape.get("egest", ()):
        if isinstance(op, Filter):
            codes = [compile(p.expr, "<egest:%s>" % p.expr, "eval")
                     for p in op.preds]
            ops.append(("filter", codes))
        elif isinstance(op, Project):
            items = [(n, compile(ce.expr, "<egest:%s>" % ce.expr,
                                 "eval")) for n, ce in op.exprs]
            ops.append(("project", items))
        elif isinstance(op, Sort):
            codes = [compile(k.expr, "<egest:%s>" % k.expr, "eval")
                     for k in op.keys]
            ops.append(("sort", (codes, op.reverse)))
    if ops:
        pq.decide("egest", "result", "egest",
                  "%d result-finishing op(s) run at egest with host "
                  "eval semantics (rows are driver-resident)"
                  % len(ops))
    pq.egest_ops = ops
    return pq
