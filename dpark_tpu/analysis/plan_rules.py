"""Plan linter: a static pass over the RDD lineage DAG, run pre-flight
by DparkContext.runJob (and importable standalone via lint_plan).

Rules catch the two failure families the round-5 audit surfaced —
silent-wrong-answer shapes decided at plan-construction time, and
shuffle anti-patterns that dominate cost at production scale:

  plan-group-agg         groupByKey().mapValue(provable aggregate) that
                         the graph-build rewrite did NOT absorb: every
                         row ships to its group instead of a map-side
                         combine.
  plan-uncached-reshuffle one lineage shuffled 2+ times without
                         cache()/checkpoint(): the parent recomputes
                         once per shuffle.
  plan-wide-depth        more than conf.LINT_WIDE_DEPTH shuffle edges on
                         one lineage path with no checkpoint: a lost
                         partition replays the whole chain.
  unbounded-recovery     the same uncheckpointed depth while fault
                         injection (DPARK_FAULTS) is active: every
                         injected failure replays the whole chain —
                         chaos runs need a recovery pin.  Quiet when
                         an erasure code with parity is active
                         (DPARK_SHUFFLE_CODE, m >= 1): coded fetches
                         decode instead of replaying lineage.
  plan-join-repartition  a cogroup/join whose inputs already share a
                         partitioner, re-exchanged because the join was
                         given a different partition count.
  host-fallback-group    a groupByKey().mapValues(f) consumer that will
                         leave the array path, and why (SEG_MAP off,
                         unsupported value pytree, untraceable or
                         padding-sensitive per-group function) — the
                         pre-flight twin of the runtime fallback_reason
                         the tpu scheduler records per stage.
  host-fallback-splits   a shuffle into more partitions than the tpu
                         master has devices: object path, unless its
                         large columnar source streams.
  monoid-multileaf       reduceByKey/combineByKey with a classified
                         min/max merge over values whose pytree has >1
                         leaf or a non-scalar leaf — the exact round-5
                         silent-wrong-answer shape on the device monoid
                         path (the host compares whole records
                         lexicographically, a per-leaf device reduction
                         mixes leaves from different records; add/mul
                         over sequences are legitimate concat/repeat
                         and stay unflagged).
  adapt-stale-hint       the adaptive store's learned wave budgets
                         (dpark_tpu/adapt.py) are keyed by row-width
                         class and NONE matches this plan's columnar
                         source: schema drift left the store's hints
                         stale, so the first run re-walks the OOM
                         ladder instead of seeding.
  static-code-hint       the pinned DPARK_SHUFFLE_CODE contradicts
                         the adapt store's recorded per-peer fetch
                         tails: parity everywhere while every peer is
                         tight (wasted tax), or no parity while a
                         recorded peer straggles (lineage replay on
                         every slow fetch).  Quiet when
                         DPARK_CODE_ADAPT re-prices per exchange.
  trace-overhead-hint    DPARK_TRACE=spool with a reduce side whose
                         estimated spool writes per task (one fetch
                         span per parent map bucket) exceed
                         conf.TRACE_SPAN_WRITES_PER_TASK — on
                         tiny-task jobs the span spooling can rival
                         the work being traced; coalesce, raise the
                         threshold, or trace with ring mode.

The walk reads graph structure only (dependencies / partitioner /
cache flags) — it never touches RDD.splits (which can promote lazy
checkpoints) and never runs jobs.  Record probing for monoid-multileaf
reads only data already resident on the driver (parallelize slices);
user functions are never executed.
"""

from dpark_tpu.analysis.report import Report

# backend/tpu/layout.BYTES_WIDTH_MAX, repeated: this module never
# imports jax (tests/test_bytes_join.py holds the two together)
BYTES_WIDTH_MAX = 128


# ---------------------------------------------------------------------------
# lineage traversal
# ---------------------------------------------------------------------------

def iter_lineage(rdd):
    """Yield every RDD reachable from `rdd` (itself included) exactly
    once, parents after children discovery order — purely structural,
    no splits access."""
    seen = set()
    frontier = [rdd]
    while frontier:
        r = frontier.pop()
        if id(r) in seen:
            continue
        seen.add(id(r))
        yield r
        for dep in getattr(r, "dependencies", ()):
            parent = getattr(dep, "rdd", None)
            if parent is not None:
                frontier.append(parent)


def _is_pinned(r):
    """cache/checkpoint/snapshot pins: this RDD's lineage does not
    recompute on re-use (for lint purposes)."""
    return (getattr(r, "should_cache", False)
            or getattr(r, "_checkpoint_path", None) is not None
            or getattr(r, "_checkpoint_rdd", None) is not None
            or getattr(r, "_snapshot_path", None) is not None)


# ---------------------------------------------------------------------------
# merge classification (jax-free fallback)
# ---------------------------------------------------------------------------

def _ensure_backend_identities():
    """Register the tpu backend's jnp by-identity callables in the
    shared classifier — but ONLY when jax is already loaded: a
    pure-local job must not pay a jax import (review finding; the
    registrations only matter if the user passed a jnp callable, which
    implies jax is in sys.modules already)."""
    import sys
    if "jax" in sys.modules:
        try:
            import dpark_tpu.backend.tpu.fuse      # noqa: F401
        except ImportError:
            pass


def _classify_merge(fn):
    """The SHARED exact classifier (utils/monoid.py — the same core
    fuse.classify_merge delegates to, so linter and executor can never
    drift)."""
    _ensure_backend_identities()
    from dpark_tpu.utils.monoid import classify_merge
    return classify_merge(fn)


def _classify_segagg(fn):
    _ensure_backend_identities()
    from dpark_tpu.utils.monoid import classify_segagg
    return classify_segagg(fn)


# ---------------------------------------------------------------------------
# value-shape probing (monoid-multileaf)
# ---------------------------------------------------------------------------

def _value_leaves(v):
    """Flatten a record value the way the device path would: tuples,
    lists, and dict values are structure; everything else is one leaf."""
    if isinstance(v, (tuple, list)):
        out = []
        for item in v:
            out.extend(_value_leaves(item))
        return out
    if isinstance(v, dict):
        out = []
        for k in sorted(v, key=repr):
            out.extend(_value_leaves(v[k]))
        return out
    return [v]


def _leaf_is_scalar(leaf):
    shape = getattr(leaf, "shape", None)
    if shape:                       # ndarray with ndim > 0
        return False
    return True


def _peek_source_records(rdd, k=4, _depth=0):
    """Up to k records WITHOUT running a job: reads data already
    resident on the driver (parallelize slices) and looks through
    unions.  User functions are never replayed over the probe rows
    (they may have side effects, e.g. accumulators).  Returns a list
    of records, possibly empty, or None when the source is not cheaply
    probeable."""
    from dpark_tpu import rdd as _rdd
    if _depth > 16:
        return None
    if isinstance(rdd, _rdd.ParallelCollection):
        slices = getattr(rdd, "_slices", None)
        if slices is None:          # worker-side copy: data stripped
            return None
        out = []
        for s in slices:
            try:
                for i in range(min(k - len(out), len(s))):
                    out.append(s[i])
            except Exception:
                return None
            if len(out) >= k:
                break
        return out
    if isinstance(rdd, _rdd.UnionRDD):
        for parent in getattr(rdd, "rdds", ()):
            rows = _peek_source_records(parent, k, _depth + 1)
            if rows:
                return rows
    return None


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def _rule_group_agg(r, report):
    """MappedValuesRDD over a bare groupByKey whose mapValue function is
    a PROVABLE aggregate: the graph rewrite did not absorb it (cache
    pin, np twins, reused outputs, or conf off), so every row rides the
    exchange."""
    from dpark_tpu import conf, rdd as _rdd
    if not getattr(conf, "GROUP_AGG_REWRITE", True):
        return                      # user opted out deliberately
    if not isinstance(r, _rdd.MappedValuesRDD):
        return
    prev = r.prev
    if not isinstance(prev, _rdd.ShuffledRDD):
        return
    agg = prev.aggregator
    if not (agg.create_combiner is _rdd._mk_list
            and agg.merge_value is _rdd._append
            and agg.merge_combiners is _rdd._extend):
        return
    try:
        from dpark_tpu.env import env
        if env.map_output_tracker.get_outputs(
                prev.dep.shuffle_id) is not None:
            return          # rewrite declined to REUSE existing outputs
    except Exception:
        pass
    f = getattr(r, "f", None)
    provable = f is not None and _classify_segagg(f) is not None
    np_twin = False
    if not provable:
        try:
            import numpy as np
            np_twin = f in (np.sum, np.mean, np.min, np.max)
        except Exception:
            np_twin = False
    if not provable and not np_twin:
        return                      # f may be a real list transform
    report.add(
        "plan-group-agg", "warn", r.scope_name,
        "groupByKey().mapValue(<aggregate>) ships every row to its "
        "group; the combiner rewrite did not absorb this chain",
        "use reduceByKey/combineByKey (or drop the cache pin on the "
        "grouped RDD); np.sum/np.mean twins need the builtin forms")


def _rule_uncached_reshuffle(lineage, report):
    """The same parent RDD feeding 2+ distinct shuffles without a
    cache/checkpoint pin: its lineage recomputes once per shuffle."""
    shuffled_by = {}                # id(parent) -> (parent, {shuffle_id})
    for r in lineage:
        for dep in getattr(r, "dependencies", ()):
            if getattr(dep, "is_shuffle", False):
                parent = dep.rdd
                ent = shuffled_by.setdefault(id(parent), (parent, set()))
                ent[1].add(dep.shuffle_id)
    for parent, sids in shuffled_by.values():
        if len(sids) < 2 or _is_pinned(parent):
            continue
        report.add(
            "plan-uncached-reshuffle", "warn", parent.scope_name,
            "this lineage feeds %d separate shuffles and is not "
            "cached: it recomputes for each one" % len(sids),
            "cache() (or checkpoint()) the RDD before fanning out")


def _shuffle_depth(r, memo):
    """Max number of shuffle edges on any path below `r`; a pinned RDD
    resets the count (its lineage won't replay)."""
    key = id(r)
    if key in memo:
        return memo[key]
    memo[key] = 0                   # cycle guard (graphs are acyclic)
    if _is_pinned(r):
        return 0
    best = 0
    for dep in getattr(r, "dependencies", ()):
        d = _shuffle_depth(dep.rdd, memo) \
            + (1 if getattr(dep, "is_shuffle", False) else 0)
        best = max(best, d)
    memo[key] = best
    return best


def _excess_wide_depth(rdd):
    """(depth, limit) when the plan chains more shuffles than
    conf.LINT_WIDE_DEPTH with no checkpoint pin, else None — shared by
    plan-wide-depth and its chaos twin unbounded-recovery."""
    from dpark_tpu import conf
    limit = int(getattr(conf, "LINT_WIDE_DEPTH", 4))
    if limit <= 0:
        return None
    depth = _shuffle_depth(rdd, {})
    return (depth, limit) if depth > limit else None


def _rule_wide_depth(rdd, report, excess):
    if excess is None:
        return
    depth, limit = excess
    report.add(
        "plan-wide-depth", "warn", rdd.scope_name,
        "%d chained shuffles with no checkpoint on the path "
        "(limit %d): a lost partition replays the whole chain"
        % (depth, limit),
        "checkpoint() (or cache()) an intermediate RDD; raise "
        "conf.LINT_WIDE_DEPTH if the depth is intentional")


def _rule_unbounded_recovery(rdd, report, excess):
    """Fault injection is ACTIVE (DPARK_FAULTS) and this plan chains
    more shuffles than conf.LINT_WIDE_DEPTH with no checkpoint pin:
    every injected failure past the last pin replays the whole chain —
    a chaos run against such a plan measures recompute amplification,
    not recovery (ISSUE 5 satellite; the chaos twin of
    plan-wide-depth)."""
    from dpark_tpu import coding, faults
    if excess is None or not faults.active():
        return
    # coded shuffle quiets the rule (ISSUE 6 satellite): with m >= 1
    # parity shards on every bucket/spill payload, a failed or
    # straggling fetch is DECODED from survivors instead of replayed
    # through lineage — the chain no longer needs a checkpoint pin to
    # bound recovery under injection
    code = coding.active_code()
    if code is not None and code.m >= 1:
        return
    # adaptive per-exchange codes quiet it too (ISSUE 19): the policy
    # can escalate any exchange whose peers demonstrably straggle to
    # m >= 1 parity mid-fleet, so recovery under injection is bounded
    # by decode even with the static code off
    if coding.adaptive_enabled():
        return
    depth, limit = excess
    report.add(
        "unbounded-recovery", "warn", rdd.scope_name,
        "fault injection is active (DPARK_FAULTS) and this plan "
        "chains %d shuffles with no checkpoint (limit %d): each "
        "injected failure replays the whole uncheckpointed chain"
        % (depth, limit),
        "checkpoint() an intermediate RDD before running under "
        "chaos, or raise conf.LINT_WIDE_DEPTH deliberately")


def _rule_join_repartition(r, report):
    """A cogroup whose inputs ALL share one partitioner, forced through
    a full re-exchange because the cogroup was created with a different
    partitioner (usually an implicit numSplits default)."""
    from dpark_tpu import rdd as _rdd
    if not isinstance(r, _rdd.CoGroupedRDD):
        return
    inputs = getattr(r, "rdds", ())
    if len(inputs) < 2:
        return
    parts = [p.partitioner for p in inputs]
    if any(p is None for p in parts):
        return
    first = parts[0]
    if not all(p == first for p in parts[1:]):
        return
    if first == r.partitioner:
        return                      # narrow already — nothing to flag
    report.add(
        "plan-join-repartition", "warn", r.scope_name,
        "join/cogroup inputs already agree on a partitioner "
        "(%d parts) but the join repartitions to %d: both sides "
        "re-exchange for nothing"
        % (first.num_partitions, r.partitioner.num_partitions),
        "pass numSplits=%d (or the shared partitioner) to the join"
        % first.num_partitions)


def _rule_monoid_multileaf(r, report):
    """Combining shuffle with a classified monoid merge over multi-leaf
    or non-scalar values: the round-5 wrong-answer shape.  The host
    merges whole records (tuples compare lexicographically) while the
    device monoid path reduces each leaf independently — results mix
    leaves from different records.  The executor now refuses the
    device monoid for this shape (falling back to the raw-combiner
    exchange), so severity=error here is the pre-flight twin that
    refuses the plan outright under DPARK_LINT=error."""
    from dpark_tpu import rdd as _rdd
    if not isinstance(r, _rdd.ShuffledRDD):
        return
    agg = r.aggregator
    if (agg.create_combiner is _rdd._mk_list
            and agg.merge_value is _rdd._append
            and agg.merge_combiners is _rdd._extend):
        return                      # no-combine shuffle: no monoid path
    kind = _classify_merge(agg.merge_combiners)
    if kind not in ("min", "max"):
        # add/mul over sequences are legitimate HOST semantics (tuple
        # concat/repeat) that every master now agrees on; only ordered
        # comparisons have the lexicographic-vs-per-leaf ambiguity
        return
    rows = _peek_source_records(r.parent)
    if not rows:
        return                      # not cheaply probeable: stay quiet
    bad = None
    for row in rows:
        if not (isinstance(row, tuple) and len(row) == 2):
            continue
        leaves = _value_leaves(row[1])
        if len(leaves) > 1:
            bad = "%d value leaves" % len(leaves)
            break
        if leaves and not _leaf_is_scalar(leaves[0]):
            bad = "a non-scalar value leaf (shape %s)" \
                % (getattr(leaves[0], "shape", None),)
            break
    if bad is None:
        return
    report.add(
        "monoid-multileaf", "error", r.scope_name,
        "reduceByKey/combineByKey merge classifies as monoid %r but "
        "records carry %s: per-leaf device reduction would mix leaves "
        "from different records (host %s merges whole records)"
        % (kind, bad, kind),
        "merge per-field explicitly (e.g. lambda a, b: (min(a[0], "
        "b[0]), ...) is NOT the same as %s(a, b)) or keep a single "
        "scalar value per record" % kind)


def _fixed_bytes_width(rdd, _depth=0):
    """Widest S<w> column of the Columns source a narrow chain reads,
    or None: a `bytes` key over such a source is a fixed-width
    byte string (the column, or a slice of it), which the device
    carries as int64 words."""
    from dpark_tpu import rdd as _rdd
    while _depth < 16 and not isinstance(rdd, _rdd.ParallelCollection):
        rdd = getattr(rdd, "prev", None)
        _depth += 1
    widths = [c.dtype.itemsize
              for s in getattr(rdd, "_slices", None) or ()
              for c in getattr(s, "columns", None) or ()
              if getattr(c, "dtype", None) is not None
              and c.dtype.kind == "S"]
    return max(widths) if widths else None


def _key_fallback_reason(key, hash_keys=True, fixed_width=None):
    """Why this record KEY keeps a shuffle off the array path, or None
    when the key shape classifies (scalar numeric, a flat numeric
    tuple of 2..conf.MAX_KEY_LEAVES leaves — the composite keys the
    device path now carries end to end — or `bytes` from a fixed-width
    S<w> column of `fixed_width` <= BYTES_WIDTH_MAX bytes: a hash
    shuffle's key, and with it the key of a join's two sides, which
    the device join matches by a one-word hash and then the bytes when
    both sides have one width; byte strings in the VALUES never show
    here).  Mirrors layout.key_width /
    fuse's epilogue checks without importing jax: `hash_keys` is True
    for hash-partitioned shuffles, whose device routing additionally
    needs INT leaves (portable_hash has no device twin for floats);
    range repartitioning (sortByKey) accepts floats."""
    from dpark_tpu import conf
    ints = (int,)
    floats = (float,)
    try:
        import numpy as _np
        ints = (int, _np.integer)
        floats = (float, _np.floating)
    except ImportError:
        pass

    def leaf_reason(item):
        if isinstance(item, bool):
            return "bool key (no device hash semantics)"
        if isinstance(item, ints):
            return None
        if isinstance(item, floats):
            return ("float key on a hash shuffle (device routing "
                    "needs int keys; floats ride range/sortByKey)"
                    if hash_keys else None)
        return "non-numeric"

    if isinstance(key, bytes) and fixed_width is not None:
        if fixed_width > BYTES_WIDTH_MAX:
            return ("byte-string column of %d bytes is over the device "
                    "limit of layout.BYTES_WIDTH_MAX = %d"
                    % (fixed_width, BYTES_WIDTH_MAX))
        return None         # hash and range (sortByKey) shuffles alike
    if isinstance(key, (str, bytes)):
        return ("string key: only text-source chains ride the device "
                "(dictionary-encoded) and fixed-width byte strings (a "
                "numpy S<w> column of Columns); everything else takes "
                "the object path")
    if isinstance(key, tuple):
        if len(key) < 2 or len(key) > conf.MAX_KEY_LEAVES:
            return ("tuple key with %d leaves (device path carries "
                    "flat tuples of 2..conf.MAX_KEY_LEAVES=%d)"
                    % (len(key), conf.MAX_KEY_LEAVES))
        for i, item in enumerate(key):
            r = leaf_reason(item)
            if r == "non-numeric":
                if isinstance(item, tuple):
                    return ("nested tuple key (only FLAT numeric "
                            "tuples ride the device)")
                return ("non-numeric key leaf %d (%s) in a tuple key"
                        % (i, type(item).__name__))
            if r is not None:
                return r
        return None
    r = leaf_reason(key)
    if r == "non-numeric":
        return ("unsupported key type %s (object path)"
                % type(key).__name__)
    return r


def _bytes_bounds_reason(part, width):
    """Why a range shuffle over an S<width> key column keeps the host
    path for its BOUNDS, or None: the twin of fuse._range_bounds_array
    (`bytes` bounds that the key's column can hold)."""
    bounds = getattr(part, "bounds", None)
    if not bounds:
        return None
    if not all(isinstance(b, bytes) for b in bounds):
        return RANGE_STRING_REASON
    if any(len(b) > width for b in bounds):
        return ("range bounds of another width than the S%d key "
                "column" % width)
    return None


# backend/tpu/fuse.RANGE_STRING_REASON and MORE_SPLITS_REASON, repeated
# as BYTES_WIDTH_MAX is: this module never imports jax
RANGE_STRING_REASON = ("range shuffle (sortByKey) over string or "
                       "byte-string keys has no device form")
MORE_SPLITS_REASON = (
    "shuffle into %d partitions on %d device(s): more splits than "
    "devices ride the array path only as the streamed (out-of-core) "
    "shuffle of a large columnar source")


def _rule_host_fallback_splits(r, report):
    """A shuffle into more partitions than the `tpu` master has
    devices takes the object path (unless its source streams): the
    pre-flight twin of fuse.analyze_stage's reason.  Quiet on every
    master that has no device executor."""
    from dpark_tpu import rdd as _rdd
    if not isinstance(r, _rdd.ShuffledRDD):
        return
    executor = getattr(getattr(r.ctx, "scheduler", None), "executor",
                       None)
    ndev = getattr(executor, "ndev", None)
    n = r.partitioner.num_partitions
    if not ndev or n <= ndev:
        return
    report.add(
        "host-fallback-splits", "info", r.scope_name,
        "this shuffle leaves the array path: "
        + MORE_SPLITS_REASON % (n, ndev),
        "ask for at most as many splits as the master has devices "
        "(numSplits <= %d)" % ndev)


def _rule_host_fallback_key(r, report):
    """Shuffles whose KEY SHAPE evicts the plan from the array path:
    the pre-flight twin of fuse.analyze_stage's key checks, reporting
    WHY (unsupported key shape, non-numeric leaf) instead of silently
    running orders of magnitude slower on the object path.  Flat
    numeric tuple keys now ride the device and stay unflagged."""
    from dpark_tpu import rdd as _rdd
    from dpark_tpu.dependency import HashPartitioner
    if not isinstance(r, _rdd.ShuffledRDD):
        return
    rows = _peek_source_records(r.parent)
    if not rows:
        return                      # not cheaply probeable: stay quiet
    hash_keys = isinstance(r.partitioner, HashPartitioner)
    fixed_width = _fixed_bytes_width(r.parent)
    for row in rows:
        if not (isinstance(row, tuple) and len(row) == 2):
            continue
        reason = _key_fallback_reason(row[0], hash_keys=hash_keys,
                                      fixed_width=fixed_width)
        if reason is None and isinstance(row[0], bytes):
            reason = _bytes_bounds_reason(r.partitioner, fixed_width)
        if reason is None:
            continue
        severity = "info" if isinstance(row[0], (str, bytes)) \
            else "warn"
        report.add(
            "host-fallback-key", severity, r.scope_name,
            "this shuffle leaves the array path: %s" % reason,
            "key by ints/floats, a flat numeric tuple ((k1, k2), v) or "
            "a fixed-width byte-string column (numpy S<w> in Columns) "
            "to stay on the device; see the README device-path "
            "support matrix")
        return


def _rule_host_fallback_group(r, report):
    """Grouped-value consumers — ``groupByKey().mapValues(f)`` — that
    will leave the array path, and WHY: the pre-flight twin of
    fuse._try_seg_map's admission pipeline (the device segmented
    apply).  Quiet when the chain rides: provable aggregates go
    through SegAggOp/the combiner rewrite, traceable padding-invariant
    functions through SegMapOp.  Reported reasons mirror the runtime
    ``fallback_reason`` exactly: SEG_MAP disabled, unsupported value
    pytree, data-dependent control flow (AST, no execution).  The
    runtime classifier's not-padding-invariant verdict needs the user
    function EXECUTED on samples, which a pre-flight never does."""
    import numbers
    from dpark_tpu import conf, rdd as _rdd
    if not isinstance(r, _rdd.MappedValuesRDD):
        return
    prev = r.prev
    if not isinstance(prev, _rdd.ShuffledRDD):
        return
    agg = prev.aggregator
    if not (agg.create_combiner is _rdd._mk_list
            and agg.merge_value is _rdd._append
            and agg.merge_combiners is _rdd._extend):
        return
    f = getattr(r, "f", None)
    if f is None:
        return
    state_update = getattr(f, "__dpark_seg_state__", None)
    f_check = state_update if state_update is not None else f
    if state_update is None and _classify_segagg(f) is not None:
        return          # provable aggregate: rides (plan-group-agg
        #                 separately flags the missed rewrite)
    reason = None
    if not getattr(conf, "SEG_MAP", True):
        reason = ("grouped consumer stays on host: DPARK_SEG_MAP=0")
    rows = None
    if reason is None:
        rows = _peek_source_records(prev.parent)
        for row in rows or ():
            if not (isinstance(row, tuple) and len(row) == 2):
                continue
            leaves = _value_leaves(row[1])
            if len(leaves) != 1 or not _leaf_is_scalar(leaves[0]) \
                    or isinstance(leaves[0], bool) \
                    or not isinstance(leaves[0], numbers.Number):
                reason = ("unsupported value pytree for grouped "
                          "consumption (seg_map needs a single scalar "
                          "numeric value per record)")
                break
    if reason is None:
        # no-execution check: Python control flow on the group data
        # cannot trace — the same verdict the runtime's eval_shape
        # probe reaches, decided from the AST alone
        try:
            from dpark_tpu.analysis.closure_rules import lint_function
            sub = lint_function(f_check, tpu=True)
            if any(fd.rule == "closure-tracer-branch" for fd in sub):
                reason = ("per-group function is not traceable "
                          "(data-dependent Python control flow)")
        except Exception:
            pass
    if reason is None:
        return
    report.add(
        "host-fallback-group", "warn", r.scope_name,
        "this grouped consumer leaves the array path: %s" % reason,
        "make the per-group function traceable and padding-invariant "
        "(jnp/arithmetic ops, no data-dependent Python branching; "
        "sums zero-pad, order statistics repeat-last-pad) or use a "
        "provable aggregate / reduceByKey — see the README "
        "device-path support matrix")


def _columnar_source_row_bytes(r):
    """Bytes per record of a columnar parallelize source, jax-free
    (the linter must not pay a jax import): same arithmetic as the tpu
    backend's fuse._columnar_row_bytes, over numpy columns only.
    None for non-columnar / empty sources."""
    from dpark_tpu import rdd as _rdd
    if not isinstance(r, _rdd.ParallelCollection):
        return None
    for s in r._slices or ():
        cols = getattr(s, "columns", None)
        if cols is not None and len(s):
            import numpy as np
            return sum(np.asarray(c).dtype.itemsize
                       * int(np.prod(np.asarray(c).shape[1:] or (1,)))
                       for c in cols)
    return None


def _rule_adapt_stale_hint(r, report):
    """The adaptive-execution store (dpark_tpu/adapt.py, ISSUE 7)
    keys its learned wave budgets by row-width class; when NONE of the
    stored classes matches this plan's columnar source, the learned
    budgets silently fail to apply — the store was warmed by a
    different data shape (schema drift), and the first run of this
    shape re-derives the memory bound and re-walks the OOM ladder.
    Quiet with DPARK_ADAPT=off, with an empty store, and whenever any
    stored class matches (mixed-width workloads are legitimate)."""
    try:
        from dpark_tpu import adapt
        if not adapt.enabled():
            return
        row_bytes = _columnar_source_row_bytes(r)
        if row_bytes is None:
            return
        widths = adapt.wave_budget_row_widths()
        if not widths or row_bytes in widths:
            return
    except Exception:
        return
    report.add(
        "adapt-stale-hint", "warn", r.scope_name,
        "the adaptive store's learned wave budgets cover row widths "
        "%s bytes, but this plan's columnar source is %d bytes/row — "
        "stored budgets will not apply (stale shape class)"
        % (sorted(widths), row_bytes),
        "expected after a schema change: the first run re-learns its "
        "budget; delete the DPARK_ADAPT_DIR store (or call "
        "adapt.reset_store()) to drop stale entries"
        + ("" if adapt.steering() else
           " (note: DPARK_ADAPT=%s only records — budgets would "
           "steer under DPARK_ADAPT=on)" % adapt.mode())
        + (" (per-exchange code choices are unaffected: they key by "
           "shuffle call site, not row width — DPARK_CODE_ADAPT "
           "keeps steering across a schema change)"
           if _coding_adaptive() else ""))


def _coding_adaptive():
    try:
        from dpark_tpu import coding
        return bool(coding.adaptive_enabled())
    except Exception:
        return False


def _rule_static_code_hint(rdd, report):
    """The pinned DPARK_SHUFFLE_CODE contradicts the adapt store's
    recorded per-peer fetch tails (ISSUE 19): parity on every bucket
    while every recorded peer is tight wastes encode CPU and shuffle
    bytes; no parity while a recorded peer demonstrably straggles
    leaves recovery to lineage replay.  Quiet when the adaptive
    per-exchange policy is on (DPARK_CODE_ADAPT re-prices each
    exchange, superseding the pin), with DPARK_ADAPT off, and with no
    recorded fetch tails."""
    try:
        from dpark_tpu import adapt, coding
        from dpark_tpu.health import Sketch
        if not adapt.enabled() or coding.adaptive_enabled():
            return
        ratio_bar = coding.ADAPT_TAIL_RATIO
        min_n = coding.ADAPT_MIN_SAMPLES
        worst = None                          # (ratio, peer)
        for site, digest in adapt.site_tails().items():
            site = str(site)
            if not site.startswith("fetch.bucket:"):
                continue
            sk = Sketch.from_dict(digest)
            if sk.n < min_n or sk.sum <= 0:
                continue
            p50 = sk.quantile(0.50) or 0.0
            p99 = sk.quantile(0.99) or 0.0
            ratio = (p99 / p50) if p50 > 0 else 0.0
            if worst is None or ratio > worst[0]:
                worst = (ratio, site[len("fetch.bucket:"):])
        if worst is None:
            return
        ratio, peer = worst
        code = coding.active_code()
        protected = code is not None and code.m >= 1
    except Exception:
        return
    if protected and ratio < ratio_bar:
        report.add(
            "static-code-hint", "info", rdd.scope_name,
            "DPARK_SHUFFLE_CODE=%s pays parity on every bucket, but "
            "every recorded peer fetch tail is tight (worst p99/p50 "
            "%.1f < %.1f) — the parity tax buys nothing here"
            % (coding.describe(), ratio, ratio_bar),
            "drop the static code, or set DPARK_CODE_ADAPT=1 to "
            "price parity per exchange from the recorded tails")
    elif not protected and ratio >= ratio_bar:
        report.add(
            "static-code-hint", "warn", rdd.scope_name,
            "no parity is pinned (DPARK_SHUFFLE_CODE=%s) but recorded "
            "peer %s straggles (fetch tail p99/p50 %.1f >= %.1f) — "
            "every slow or lost fetch from it replays lineage"
            % (coding.describe(), peer, ratio, ratio_bar),
            "pin a code with m >= 1, or set DPARK_CODE_ADAPT=1 to "
            "escalate only the exchanges that peer serves")


def _width_hint(r, depth=0):
    """Best-effort partition count WITHOUT touching RDD.splits (the
    property can promote lazy checkpoints, see the module header):
    already-materialized splits, parallelize slices, a shuffle
    output's own partitioner width, or a single narrow parent's hint.
    None when the width isn't structurally knowable."""
    from dpark_tpu.dependency import OneToOneDependency, \
        ShuffleDependency
    while r is not None and depth < 64:
        depth += 1
        splits = getattr(r, "_splits", None)
        if splits is not None:
            return len(splits)
        slices = getattr(r, "_slices", None)     # ParallelCollection
        if slices is not None:
            return len(slices)
        deps = getattr(r, "dependencies", ())
        if len(deps) == 1 and isinstance(deps[0], ShuffleDependency):
            part = getattr(r, "partitioner", None)
            n = getattr(part, "num_partitions", None)
            if n:
                return int(n)
        if len(deps) == 1 and isinstance(deps[0],
                                         OneToOneDependency):
            r = getattr(deps[0], "rdd", None)    # width-preserving
            continue
        return None
    return None


def _rule_trace_overhead_hint(r, report):
    """With DPARK_TRACE=spool every reduce task appends roughly one
    fetch span PER PARENT MAP BUCKET plus its own task spans to the
    spool — an O_APPEND write each.  On a tiny-task job (many map
    partitions feeding many short reduce tasks) the spool traffic can
    rival the compute the trace is meant to explain.  Warn when the
    estimated spool writes per reduce task exceed
    conf.TRACE_SPAN_WRITES_PER_TASK.  Quiet in off/ring modes (no disk
    writes at all)."""
    try:
        from dpark_tpu import conf as _conf, trace
        if trace.mode() != "spool":
            return
        from dpark_tpu.dependency import ShuffleDependency
        widest = 0
        for dep in getattr(r, "dependencies", ()):
            if isinstance(dep, ShuffleDependency):
                widest = max(widest, _width_hint(dep.rdd) or 0)
        if not widest:
            return
        est = 2 + widest          # task span + task.run + fetch/bucket
        cap = int(getattr(_conf, "TRACE_SPAN_WRITES_PER_TASK", 64))
        if est <= cap:
            return
    except Exception:
        return
    report.add(
        "trace-overhead-hint", "warn", r.scope_name,
        "DPARK_TRACE=spool will append ~%d spans per reduce task here "
        "(%d parent map buckets each fetch-spanned) — above the "
        "TRACE_SPAN_WRITES_PER_TASK=%d hint threshold, spooling can "
        "dominate tiny tasks" % (est, widest, cap),
        "coalesce the map side (fewer, larger partitions), raise "
        "DPARK_TRACE_SPAN_WRITES_PER_TASK if the tasks are long "
        "enough to amortize it, or trace with DPARK_TRACE=ring "
        "(in-memory, no spool writes)")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _rule_window_noninv(r, report):
    """window-noninv-no-merge (ISSUE 10 satellite): a non-invertible
    windowed reduce whose op has NO registered partial-aggregate merge
    (and no invFunc) re-reduces the whole window every slide — O(w)
    per tick where the pane tree would pay O(log w).  dstream marks
    the emitted plan (`_window_noninv`) when it falls back; this rule
    surfaces the why."""
    info = getattr(r, "_window_noninv", None)
    if not info:
        return
    report.add(
        "window-noninv-no-merge", "warn", r.scope_name,
        "non-invertible windowed reduce over %r recomputes the whole "
        "window every slide (O(w) per tick): %s"
        % (info.get("op"), info.get("reason", "")),
        hint="register a partial-aggregate merge (a classified monoid "
             "op, or set func.__dpark_window_merge__ = True to assert "
             "associativity over partials) and keep window/slide/batch "
             "grid-aligned so the pane tree serves the window in "
             "O(log w); or supply invFunc for O(1) slides")


def _rule_table_host_fallback(r, report):
    """table-host-fallback (ISSUE 13 satellite): why a table/SQL query
    operator left the array path.  The query planner
    (dpark_tpu/query/planner.py) attaches its per-operator host
    decisions — non-traceable UDA, unsupported column dtype (float
    group key, string aggregate), int-overflow risk, priced object
    path — to the host-chain lineage it falls back to
    (`_query_fallbacks`); this rule surfaces them pre-flight, the
    exact mirror of the per-stage `fallback_reason` the scheduler
    records at run time."""
    fallbacks = getattr(r, "_query_fallbacks", None)
    if not fallbacks:
        return
    for fb in fallbacks:
        report.add(
            "table-host-fallback", "info", r.scope_name,
            "query operator %r left the array path: %s"
            % (fb.get("op"), fb.get("reason")),
            "see the README Table/SQL plane section for the device "
            "query support matrix (int/encoded-string keys, "
            "sum/count/min/max/avg + traceable UDAs, equi-joins); "
            "DPARK_QUERY=0 silences planning entirely")


def _rule_repeated_subplan(lineage, report):
    """repeated-subplan (ISSUE 18 satellite): the same canonical
    sub-plan signature evaluated at two DISTINCT nodes of one plan —
    each evaluation pays the scan/exchange again even though the
    result-cache plane (or plain subtree sharing: build the common
    table once and derive both queries from it) could serve the
    second for free.  Shared OBJECTS are one evaluation and never
    flag; leaves (bare scans) don't either — reading a table twice is
    the cache's job, not a plan smell.  Nodes outside the logical
    grammar (plain RDDs, unsignable expressions) are skipped."""
    from dpark_tpu.query import logical
    seen = {}                   # signature -> node ids evaluating it
    for node in lineage:
        if not isinstance(node, logical.Node) \
                or not node.children:
            continue
        try:
            sig = logical.plan_signature(node)
        except Exception:
            continue
        seen.setdefault(sig, set()).add(id(node))
    dups = {s for s, ids in seen.items() if len(ids) > 1}

    def _contains(parent, child):
        return any(c == child or (isinstance(c, tuple)
                                  and _contains(c, child))
                   for c in parent)

    for sig in sorted(dups, key=repr):
        # report only MAXIMAL duplicated subtrees: a duplicated
        # Filter inside a duplicated GroupAgg is the same finding
        if any(other != sig and _contains(other, sig)
               for other in dups):
            continue
        ids = seen[sig]
        report.add(
            "repeated-subplan", "info", str(sig[0]).lower(),
            "the same %s sub-plan is evaluated %d times in this plan "
            "without reuse" % (sig[0], len(ids)),
            "derive both queries from one shared TableRDD (a logical "
            "subtree evaluates once per object), or turn on the "
            "shared result cache (DPARK_RESULT_CACHE=mem|disk) so "
            "repeated sub-plans serve from cached rows")


def lint_plan(rdd, master="local", report=None, lineage=None):
    """Run every plan rule over the lineage of `rdd`; returns a Report.

    `master` reserved for master-specific severity policy (the rules
    themselves are master-agnostic: the monoid shape is a device-path
    hazard but the plan may run under -m tpu later).  `lineage` lets
    the pre-flight gate pass its (possibly capped) walk instead of
    re-walking."""
    report = report if report is not None else Report()
    if lineage is None:
        lineage = list(iter_lineage(rdd))
    for r in lineage:
        _rule_group_agg(r, report)
        _rule_join_repartition(r, report)
        _rule_monoid_multileaf(r, report)
        _rule_host_fallback_key(r, report)
        _rule_host_fallback_splits(r, report)
        _rule_host_fallback_group(r, report)
        _rule_adapt_stale_hint(r, report)
        _rule_trace_overhead_hint(r, report)
        _rule_window_noninv(r, report)
        _rule_table_host_fallback(r, report)
    _rule_uncached_reshuffle(lineage, report)
    _rule_repeated_subplan(lineage, report)
    excess = _excess_wide_depth(rdd)
    _rule_wide_depth(rdd, report, excess)
    _rule_unbounded_recovery(rdd, report, excess)
    _rule_static_code_hint(rdd, report)
    return report
