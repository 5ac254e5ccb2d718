"""Bagel: Pregel-style BSP graph processing.

Reference parity: dpark/bagel.py (SURVEY.md sections 2.3 and 3.2) — the
superstep loop cogroups vertices with inbound messages, applies the user
compute(vertex, messages, aggregated, superstep), emits (new vertex, out
messages), optionally pre-combines messages per target (Combiner) and
reduces a global Aggregator over all vertices each superstep; halts when
every vertex is inactive and no messages remain.

Two execution models:

* `Bagel.run` — the reference's object contract (Vertex/Message/Edge
  Python objects, arbitrary compute).  On the tpu master, NUMERIC
  object programs are auto-columnarized onto the device Pregel
  (`_run_columnar`: per-degree-class vmap of the user compute,
  supersteps as fused mesh programs); everything else warns and runs
  the host paths (driver-resident fast loop, then RDD algebra).
* `run_pregel` — the TPU-native contract (SURVEY.md 3.2 [H] mapping):
  columnar vertex state, edge-centric vectorized compute/send, monoid
  message combine.  On the tpu master each superstep runs as fused
  shard_map programs (hash-dst all_to_all for messages, segment reduce
  for the combine, psum for the aggregator and halting counters); on
  local/process masters an equivalent vectorized numpy loop is the
  golden model.  `PregelGraph` is the same contract over a graph that
  stays loaded: built once, run many times (each run one job of the
  tpu master's scheduler); `run_pregel` builds one, runs it once and
  drops it.
"""

import numpy as np

from dpark_tpu.utils.log import get_logger

logger = get_logger("bagel")


class Vertex:
    def __init__(self, id, value, outEdges=None, active=True):
        self.id = id
        self.value = value
        self.outEdges = outEdges or []
        self.active = active

    def __repr__(self):
        return "<Vertex(%s, %r, active=%s)>" % (
            self.id, self.value, self.active)


class Edge:
    def __init__(self, target_id, value=None):
        self.target_id = target_id
        self.value = value


class Message:
    def __init__(self, target_id, value):
        self.target_id = target_id
        self.value = value


class Combiner:
    """Pre-shuffle message combine (reference: Bagel Combiner)."""

    def createCombiner(self, msg):
        return [msg]

    def mergeValue(self, combiner, msg):
        combiner.append(msg)
        return combiner

    def mergeCombiners(self, a, b):
        a.extend(b)
        return a


class BasicCombiner(Combiner):
    """Combine message values with a binary op (e.g. operator.add)."""

    def __init__(self, op):
        self.op = op

    def createCombiner(self, msg):
        return msg

    def mergeValue(self, combiner, msg):
        return self.op(combiner, msg)

    def mergeCombiners(self, a, b):
        return self.op(a, b)


class Aggregator:
    """Global per-superstep reduce over all vertices; the result is
    visible to every vertex in the NEXT superstep."""

    def createAggregator(self, vert):
        raise NotImplementedError

    def mergeAggregators(self, a, b):
        raise NotImplementedError


# the Bagel.run fast path keeps the graph driver-resident and skips the
# per-superstep shuffle jobs; set to False (or DPARK_BAGEL_FAST=0) to
# force the reference-shaped RDD algebra (e.g. graphs larger than
# driver memory)
import os as _os
FAST_OBJECT_RUN = _os.environ.get("DPARK_BAGEL_FAST", "1") != "0"
# graphs beyond this many vertices stay on the RDD path (the fast path
# collects the graph to the driver; collect-then-OOM is not a fallback)
FAST_MAX_VERTICES = int(_os.environ.get("DPARK_BAGEL_FAST_MAX",
                                        str(4_000_000)))


class _ObjectPathNeeded(Exception):
    """Raised inside the fast object run when the program does
    something only the RDD path models (vertex id rebinding, per-key
    growth we mis-tracked); inputs are untouched, so the caller simply
    re-runs the classic path."""


class _NotColumnarizable(Exception):
    """Raised while deciding whether an OBJECT Bagel program can ride
    the device run_pregel path; inputs untouched, callers fall back."""


# auto-columnarize numeric object-Bagel programs onto the device Pregel
# (VERDICT r3 #7); DPARK_BAGEL_DEVICE=0 forces the host object paths
DEVICE_OBJECT_RUN = _os.environ.get("DPARK_BAGEL_DEVICE", "1") != "0"
# compile-cost bounds (VERDICT r4 #4 lifted the old degree-8 /
# own-edges-only subset): user compute is traced once per DISTINCT
# out-degree, so the class count bounds trace/compile work; degree
# itself only sizes the per-class edge-target table.  Graphs beyond
# either bound fall back to the host object paths.
MAX_DEGREE_CLASSES = int(_os.environ.get("DPARK_BAGEL_MAX_CLASSES",
                                         "24"))
MAX_DEGREE = int(_os.environ.get("DPARK_BAGEL_MAX_DEGREE", "1024"))
# power-of-two degree BUCKETS (ISSUE 4): vertices pad their edge lists
# to the next power of two with masked dummy edges, so the class count
# collapses from <= MAX_DEGREE_CLASSES arbitrary degrees to
# <= 1 + log2(MAX_DEGREE) buckets (11 at the default cap) and the
# power-law cap disappears.  Soundness is verified per (class,
# superstep) by an exact-vs-bucket canary (bagel_obj._bucket_canary);
# degree-dependent computes (len(outEdges), tail reads) fall back to
# exact degree classes, then to the host paths.  "0" disables.
DEGREE_BUCKETS = _os.environ.get("DPARK_BAGEL_BUCKETS", "1") != "0"
# compile-budget guard: each degree class costs two traces (mail /
# no-mail) per superstep; a graph whose row count (vertices + edges)
# is below (classes x 2 x this) falls back to the host loop instead of
# spending more wall time compiling than computing.  0 disables.
BAGEL_MIN_ROWS_PER_TRACE = int(_os.environ.get(
    "DPARK_BAGEL_MIN_ROWS_PER_TRACE", "0") or 0)


class Bagel:
    @classmethod
    def run(cls, ctx, verts, msgs, compute,
            combiner=None, aggregator=None,
            max_superstep=80, numSplits=None, checkpoint_interval=10):
        """verts: RDD of (id, Vertex); msgs: RDD of (id, message_value).

        compute(vertex, messages_or_combined, aggregated, superstep)
          -> (new_vertex, [Message, ...])
        Returns the final verts RDD.

        Execution: by default the superstep loop runs DRIVER-RESIDENT
        (`_run_fast`): the graph is collected once, each superstep is a
        tight host loop with vectorized message delivery, and no
        shuffle/cogroup jobs are scheduled at all — per-superstep cost
        drops from three RDD jobs to one Python pass, on every master.
        The arbitrary per-vertex compute contract (ragged outEdges,
        data-dependent message lists, `msg or 0.0` idioms) is what
        makes this API untraceable for XLA — blockwise programs should
        use run_pregel for fused device supersteps; this adapter makes
        reference-shaped programs fast without a rewrite (VERDICT r2
        ask #4).  Falls back to the reference-shaped RDD algebra when
        the fast path cannot model the program — in which case compute
        RE-EXECUTES from superstep 0, so compute must tolerate
        re-execution (the same contract every task already has under
        retry/lineage recovery: side effects may repeat).
        """
        superstep = 0
        combiner = combiner or Combiner()
        numSplits = numSplits or len(verts.splits)
        ctx.start()
        # both driver-resident paths (device columnar, host fast loop)
        # consume the same bounded collect — do it once, not per path
        collected = None
        want_columnar = DEVICE_OBJECT_RUN \
            and getattr(ctx.scheduler, "executor", None) is not None
        if want_columnar or FAST_OBJECT_RUN:
            try:
                collected = cls._collect_bounded(verts, msgs)
            except (_ObjectPathNeeded, MemoryError) as e:
                logger.warning("object Bagel driver-resident paths "
                               "unavailable (%s); running the RDD "
                               "path", e)
        if collected is not None and want_columnar:
            try:
                return cls._run_columnar(ctx, collected, compute,
                                         combiner, aggregator,
                                         max_superstep, numSplits)
            except _NotColumnarizable as e:
                logger.warning("object Bagel program is not "
                               "device-columnarizable (%s); "
                               "driver-resident host path", e)
        if collected is not None and FAST_OBJECT_RUN:
            try:
                return cls._run_fast(ctx, collected, compute,
                                     combiner, aggregator,
                                     max_superstep, numSplits)
            except (_ObjectPathNeeded, MemoryError) as e:
                logger.warning("object Bagel fast path unavailable "
                               "(%s); running the RDD path", e)
        if getattr(ctx.scheduler, "executor", None) is not None:
            logger.warning(
                "Bagel.run with object vertices executes on the HOST "
                "path even on the tpu master; use bagel.run_pregel for "
                "the device-native superstep")

        while superstep < max_superstep:
            logger.debug("superstep %d", superstep)
            aggregated = None
            if aggregator is not None:
                parts = [p for p in verts.ctx.runJob(
                    verts.map(_AggCreate(aggregator)),
                    _PartReduceBy(aggregator.mergeAggregators))
                    if p is not _NO_VALUE]
                if parts:
                    aggregated = parts[0]
                    for p in parts[1:]:
                        aggregated = aggregator.mergeAggregators(
                            aggregated, p)

            combined = msgs.combineByKey(
                combiner.createCombiner, combiner.mergeValue,
                combiner.mergeCombiners, numSplits)
            grouped = verts.groupWith(combined, numSplits=numSplits)
            processed = grouped.flatMapValue(
                _ComputeFn(compute, aggregated, superstep)).cache()

            # force evaluation; count active vertices and pending messages
            num_active, num_msgs = processed.map(_stats).fold(
                (0, 0), _merge_stats)

            verts = processed.mapValue(_fst_of_pair)
            msgs = processed.flatMap(_OutMessages())
            superstep += 1
            if checkpoint_interval and superstep % checkpoint_interval == 0 \
                    and ctx.checkpoint_dir:
                verts = verts.mapValue(_identity)
                verts.checkpoint()
            if num_msgs == 0 and num_active == 0:
                break
        return verts

    @classmethod
    def _run_columnar(cls, ctx, collected, compute, combiner,
                      aggregator, max_superstep, numSplits):
        """Auto-columnarize an object-Bagel program onto the device
        (VERDICT r3 #7, generalized per VERDICT r4 #4 — see
        backend/tpu/bagel_obj.py for the execution model: class-sliced
        vmap of the user compute, CSR-style message flattening, and a
        hash(dst) exchange, so targets may be ANY integer id, degree
        runs to MAX_DEGREE, and Vertex.value may be any numeric
        pytree).

        The detectable subset: integer vertex ids and message targets,
        numeric pytree vertex values (consistent structure), numeric
        scalar message values, Edge.value all-None or all-numeric,
        BasicCombiner with a provable monoid op, no Aggregator, at most
        MAX_DEGREE out-edges and MAX_DEGREE_CLASSES distinct degrees
        (each distinct degree is a separate trace).  Anything else
        raises _NotColumnarizable and the host object paths run instead
        — warn-and-fallback, never silent wrong answers: shape and
        dtype checks run at trace time, each superstep, before that
        superstep executes."""
        from dpark_tpu.backend.tpu.fuse import classify_merge
        import jax.tree_util as jtu
        if aggregator is not None:
            raise _NotColumnarizable("object Aggregator contract")
        if type(combiner) is BasicCombiner:
            # a provable monoid combines through single-pass segment
            # scatters; any other op rides IF it traces as a
            # treedef-preserving merge over the message value pytree
            # (DeviceObjectPregel verifies at discovery time) — the
            # per-leaf-monoid-or-traced-merge contract of vector
            # message values
            monoid = classify_merge(combiner.op)
        elif type(combiner) is Combiner:
            raise _NotColumnarizable("list-combining default Combiner")
        else:
            raise _NotColumnarizable("custom Combiner %r"
                                     % type(combiner).__name__)
        graph, pend = collected
        n = len(graph)
        if n == 0:
            raise _NotColumnarizable("empty graph")

        ids_l, act_l, deg_l = [], [], []
        vdef = None
        vleaf_lists = None
        tgt_chunks, ev_vals = [], []
        ev_state = None       # None undecided / False all-None / True
        out_edges = {}
        for vid, v in graph.items():
            if not isinstance(v, Vertex):
                raise _NotColumnarizable("vertex is %r, not Vertex"
                                         % type(v).__name__)
            if isinstance(vid, bool) or not isinstance(
                    vid, (int, np.integer)):
                raise _NotColumnarizable("non-integer vertex id %r"
                                         % (vid,))
            leaves, treedef = jtu.tree_flatten(v.value)
            if vdef is None:
                vdef = treedef
                vleaf_lists = [[] for _ in leaves]
            elif treedef != vdef:
                raise _NotColumnarizable(
                    "vertex value structure varies across vertices")
            if not leaves:
                raise _NotColumnarizable(
                    "vertex value has no numeric leaves")
            for li, leaf in enumerate(leaves):
                if isinstance(leaf, bool):
                    raise _NotColumnarizable(
                        "non-numeric vertex value leaf %r" % (leaf,))
                arr = np.asarray(leaf)
                if arr.dtype.kind not in "if":
                    raise _NotColumnarizable(
                        "non-numeric vertex value leaf %r" % (leaf,))
                vleaf_lists[li].append(arr)
            edges = list(v.outEdges)
            if len(edges) > MAX_DEGREE:
                raise _NotColumnarizable("degree %d > %d"
                                         % (len(edges), MAX_DEGREE))
            tg = np.empty(len(edges), np.int64)
            for i, e in enumerate(edges):
                t = e.target_id
                if isinstance(t, bool) or not isinstance(
                        t, (int, np.integer)):
                    raise _NotColumnarizable("non-integer edge target")
                tg[i] = int(t)
                val = getattr(e, "value", None)
                if val is None:
                    if ev_state is True:
                        raise _NotColumnarizable(
                            "mixed None/numeric edge values")
                    ev_state = False
                else:
                    if ev_state is False:
                        raise _NotColumnarizable(
                            "mixed None/numeric edge values")
                    if isinstance(val, bool) or not isinstance(
                            val, (int, float, np.integer, np.floating)):
                        raise _NotColumnarizable(
                            "non-numeric edge value %r" % (val,))
                    ev_state = True
                    ev_vals.append(val)
            tgt_chunks.append(tg)
            out_edges[int(vid)] = v.outEdges
            ids_l.append(int(vid))
            act_l.append(bool(v.active))
            deg_l.append(len(edges))
        for t, _ in pend:
            if isinstance(t, bool) or not isinstance(t, (int, np.integer)):
                raise _NotColumnarizable("non-integer message target")

        ids = np.asarray(ids_l, np.int64)
        degs = np.asarray(deg_l, np.int64)
        if not DEGREE_BUCKETS and len(set(deg_l)) > MAX_DEGREE_CLASSES:
            # with bucketing on, the class-count decision moves into
            # DeviceObjectPregel: buckets bound the count by
            # 1 + log2(MAX_DEGREE); only the exact-class FALLBACK
            # (degree-dependent computes) re-checks this cap
            raise _NotColumnarizable(
                "%d degree classes > %d (each distinct degree is a "
                "separate trace)" % (len(set(deg_l)),
                                     MAX_DEGREE_CLASSES))
        try:
            vleaves = [np.stack(col) for col in vleaf_lists]
        except ValueError:
            raise _NotColumnarizable("vertex value leaf shapes vary")
        for col in vleaves:
            if col.dtype.kind not in "if":
                raise _NotColumnarizable("vertex values dtype %s"
                                         % col.dtype)
        act = np.asarray(act_l, bool)
        tgt_flat = (np.concatenate(tgt_chunks) if tgt_chunks
                    else np.zeros(0, np.int64))
        ev_flat = None
        if ev_state:
            ev_flat = np.asarray(ev_vals)
            if ev_flat.dtype.kind not in "if":
                raise _NotColumnarizable("edge values dtype %s"
                                         % ev_flat.dtype)
        pend_cols = None
        if pend:
            # initial message VALUES may be any small numeric pytree
            # (consistent structure): leaves ride as separate columns,
            # exactly like emitted Message.value leaves
            mdef0 = None
            leaf_lists = None
            for _, v in pend:
                leaves, mdef = jtu.tree_flatten(v)
                if mdef0 is None:
                    mdef0, leaf_lists = mdef, [[] for _ in leaves]
                elif mdef != mdef0:
                    raise _NotColumnarizable(
                        "initial message value structure varies")
                if not leaves:
                    raise _NotColumnarizable(
                        "initial message value has no numeric leaves")
                for li, leaf in enumerate(leaves):
                    if isinstance(leaf, bool):
                        raise _NotColumnarizable(
                            "non-numeric message value leaf")
                    leaf_lists[li].append(np.asarray(leaf))
            try:
                pleaves = [np.stack(col) for col in leaf_lists]
            except ValueError:
                raise _NotColumnarizable(
                    "initial message leaf shapes vary")
            for col in pleaves:
                if col.dtype.kind not in "if":
                    raise _NotColumnarizable("non-numeric message value")
            pend_cols = (np.asarray([t for t, _ in pend], np.int64),
                         pleaves, mdef0)

        if BAGEL_MIN_ROWS_PER_TRACE:
            # compile-budget guard: traces ~= 2 x classes (mail +
            # no-mail) per superstep; buckets bound classes at
            # 1 + log2(MAX_DEGREE), exact classes at the distinct
            # count.  Below the budget the host loops win outright.
            n_classes = (1 + max(int(d).bit_length() for d in
                                 set(deg_l)) if DEGREE_BUCKETS
                         else len(set(deg_l))) or 1
            rows = len(ids_l) + int(tgt_flat.shape[0])
            if rows < BAGEL_MIN_ROWS_PER_TRACE * 2 * n_classes:
                raise _NotColumnarizable(
                    "compile budget: %d graph rows under "
                    "DPARK_BAGEL_MIN_ROWS_PER_TRACE=%d x ~%d traces"
                    % (rows, BAGEL_MIN_ROWS_PER_TRACE,
                       2 * n_classes))

        from dpark_tpu.backend.tpu.bagel_obj import DeviceObjectPregel
        try:
            dop = DeviceObjectPregel(
                ctx.scheduler.executor, compute, monoid, vdef, ids,
                vleaves, act, degs, tgt_flat, ev_flat, pend_cols,
                max_superstep, combine_op=combiner.op)
            out_ids, out_leaves, out_act = dop.run()
        except _NotColumnarizable:
            raise
        except PregelInputError as e:
            # inputs the device Pregel rejects (e.g. a vertex id equal
            # to its padding sentinel) ran fine on the object path
            # before this adapter existed — keep them running there
            raise _NotColumnarizable(
                "device Pregel rejected inputs (%s)" % e)
        except Exception as e:
            raise _NotColumnarizable(
                "device object Pregel failed (%s)" % str(e)[:200])
        ctx.scheduler._pregel_device_used = True
        out = []
        for i, vid in enumerate(out_ids.tolist()):
            leaves_i = []
            for col in out_leaves:
                x = col[i]
                if x.ndim == 0:
                    x = float(x) if x.dtype.kind == "f" else int(x)
                leaves_i.append(x)
            val = jtu.tree_unflatten(vdef, leaves_i)
            out.append((vid, Vertex(vid, val, out_edges[vid],
                                    bool(out_act[i]))))
        return ctx.parallelize(out, numSplits)

    @classmethod
    def _collect_bounded(cls, verts, msgs):
        """(graph dict, pending list) for the driver-resident paths —
        count first so an oversized graph never collect-then-OOMs."""
        n = verts.count()
        if n > FAST_MAX_VERTICES:
            raise _ObjectPathNeeded(
                "%d vertices > DPARK_BAGEL_FAST_MAX=%d"
                % (n, FAST_MAX_VERTICES))
        return dict(verts.collect()), list(msgs.collect())

    @classmethod
    def _run_fast(cls, ctx, collected, compute, combiner, aggregator,
                  max_superstep, numSplits):
        """Driver-resident object supersteps: semantics identical to
        the RDD loop above (same pass-through rule for inactive
        no-mail vertices, same unknown-target drop, same halting
        condition), with delivery done by per-target fold through the
        user's Combiner."""
        graph, pending = collected           # from _collect_bounded
        graph = dict(graph)                  # loop mutates its copy
        pending = list(pending)
        superstep = 0
        while superstep < max_superstep:
            aggregated = None
            if aggregator is not None:
                it = iter(graph.values())
                first = next(it, None)
                if first is not None:
                    aggregated = aggregator.createAggregator(first)
                    for v in it:
                        aggregated = aggregator.mergeAggregators(
                            aggregated, aggregator.createAggregator(v))

            mail = {}
            for target, value in pending:
                if target not in graph:
                    continue                 # parity: unknown ids drop
                if target in mail:
                    mail[target] = combiner.mergeValue(
                        mail[target], value)
                else:
                    mail[target] = combiner.createCombiner(value)

            pending = []
            num_active = 0
            new_graph = {}
            for vid, vert in graph.items():
                vmail = mail.get(vid)
                if vmail is None and not vert.active:
                    new_graph[vid] = vert    # untouched pass-through
                    continue
                out = compute(vert, vmail, aggregated, superstep)
                new_vert, out_msgs = out
                if new_vert.id != vid:
                    raise _ObjectPathNeeded(
                        "compute rebound vertex id %r -> %r"
                        % (vid, new_vert.id))
                new_graph[vid] = new_vert
                for m in out_msgs:
                    pending.append((m.target_id, m.value))
            graph = new_graph
            num_active = sum(1 for v in graph.values() if v.active)
            superstep += 1
            logger.debug("fast superstep %d: active=%d msgs=%d",
                         superstep, num_active, len(pending))
            if not pending and num_active == 0:
                break
        return ctx.parallelize(list(graph.items()), numSplits)


_NO_VALUE = "__bagel_no_value__"


class _PartReduceBy:
    def __init__(self, merge):
        self.merge = merge

    def __call__(self, it):
        out = _NO_VALUE
        for x in it:
            out = x if out is _NO_VALUE else self.merge(out, x)
        return out


class _AggCreate:
    def __init__(self, aggregator):
        self.aggregator = aggregator

    def __call__(self, kv):
        return self.aggregator.createAggregator(kv[1])


class _ComputeFn:
    """grouped value = ([vertex...], [combined_messages...]); vertices
    without an entry (messages to unknown ids) are dropped, inactive
    vertices with no mail are passed through untouched."""

    def __init__(self, compute, aggregated, superstep):
        self.compute = compute
        self.aggregated = aggregated
        self.superstep = superstep

    def __call__(self, groups):
        vs, cs = groups
        if not vs:
            return []
        vert = vs[0]
        mail = cs[0] if cs else None
        if mail is None and not vert.active:
            return [(vert, [])]
        out = self.compute(vert, mail, self.aggregated, self.superstep)
        return [out]


class _OutMessages:
    def __call__(self, kv):
        _, (vert, out_msgs) = kv
        return [(m.target_id, m.value) for m in out_msgs]


def _stats(kv):
    vert, out_msgs = kv[1]
    return (1 if vert.active else 0, len(out_msgs))


def _merge_stats(a, b):
    return (a[0] + b[0], a[1] + b[1])


def _fst_of_pair(pair):
    return pair[0]


def _identity(x):
    return x


# ----------------------------------------------------------------------
# TPU-native Pregel (SURVEY.md 3.2 [H] mapping): columnar vertex state,
# vectorized edge-centric compute/send, monoid message combine
# ----------------------------------------------------------------------

PREGEL_MONOIDS = ("add", "min", "max", "mul")


class PregelInputError(ValueError):
    """Invalid run_pregel input (bad ids/edges/messages).  Never triggers
    the silent device->host fallback: the input is wrong on both paths."""


def as_leaves(x):
    """(leaves, was_tuple) for a single-array-or-tuple user value."""
    if isinstance(x, (tuple, list)):
        return list(x), True
    return [x], False


def rewrap(leaves, was_tuple):
    return tuple(leaves) if was_tuple else leaves[0]


def monoid_identity(kind, dtype):
    """Identity element so absent messages are a no-op under combine."""
    dt = np.dtype(dtype)
    if kind == "add":
        return dt.type(0)
    if kind == "mul":
        return dt.type(1)
    if dt.kind == "f":
        return dt.type(np.inf if kind == "min" else -np.inf)
    return np.iinfo(dt).max if kind == "min" else np.iinfo(dt).min


_NP_COMBINE = {"add": np.add, "min": np.minimum,
               "max": np.maximum, "mul": np.multiply}
_NP_REDUCE = {"add": np.sum, "min": np.min,
              "max": np.max, "mul": np.prod}


class PregelGraph:
    """A graph that stays loaded: Pregel runs over it take fresh vertex
    state and the user's functions, and leave it as they found it.

    ids:     (n,) int array of unique vertex ids
    edges:   (src_ids, dst_ids) int arrays; each edge lives with its
             source, messages flow along it to dst
    edge_values: None, or an array / tuple of arrays over the edges

    On the tpu master the graph is partitioned over the mesh and put on
    the devices once, here (backend/tpu/bagel.py: DeviceGraph); every
    `run` is then one job of the scheduler whose programs outlive it, so
    a second run with the same functions builds and traces nothing.  On
    the other masters the same object runs the vectorized numpy loop
    over its host index.  `drop()` frees the device's copy.
    """

    def __init__(self, ctx, ids, edges, edge_values=None):
        ctx.start()
        self.ctx = ctx
        self._host = _HostGraph(ids, edges, edge_values)
        self._device = None
        # why the device holds no copy of a graph that a tpu master was
        # given: every run's job then records it as its fallback_reason
        self._load_error = None
        self._empty = not (self._host.n + self._host.src_idx.size)
        ex = getattr(ctx.scheduler, "executor", None)
        if ex is not None and not self._empty:
            from dpark_tpu.backend.tpu.bagel import DeviceGraph
            try:
                self._device = DeviceGraph(ex, self._host)
            except PregelInputError:
                raise              # wrong on both paths: surface it
            except Exception as e:
                self._load_error = _first_line(e)
                logger.warning("device Pregel graph not loaded (%s); "
                               "runs take the host path",
                               self._load_error)

    def run(self, values, compute, send, combine="add", active=None,
            initial_messages=None, aggregator=None, max_superstep=80,
            static_superstep=False, send_gate_leaf=None):
        """One Pregel run over the loaded graph: run_pregel's arguments
        of the same names, `values` and `active` in the order of the
        `ids` the graph was built from.  Returns (ids, values, active)
        sorted by id."""
        if combine not in PREGEL_MONOIDS:
            raise ValueError("combine must be one of %s"
                             % (PREGEL_MONOIDS,))
        host = self._host
        if self._empty:
            vleaves, v_tuple = as_leaves(values)
            return (np.zeros(0, np.int64),
                    rewrap([np.asarray(l)[:0] for l in vleaves], v_tuple),
                    np.zeros(0, bool))

        def on_host():
            return host.run(values, compute, send, combine, active,
                            initial_messages, aggregator, max_superstep,
                            send_gate_leaf)

        scheduler = self.ctx.scheduler
        if getattr(scheduler, "executor", None) is None:
            return on_host()
        device = self._device

        def on_device():
            from dpark_tpu.backend.tpu.bagel import DevicePregel
            return DevicePregel(
                device, values, compute, send, combine=combine,
                active=active, initial_messages=initial_messages,
                aggregator=aggregator, max_superstep=max_superstep,
                static_superstep=static_superstep,
                send_gate_leaf=send_gate_leaf).run()

        return scheduler.run_pregel(on_device, on_host,
                                    self._load_error)

    def drop(self):
        """Free the device's copy; later runs take the host path and
        say so."""
        if self._device is not None:
            self._device = None
            self._load_error = "the graph was dropped"


def _first_line(e):
    """An exception as `Type: first line of its message`."""
    text = str(e).strip().splitlines()
    return "%s: %s" % (type(e).__name__, text[0][:200] if text else "")


def run_pregel(ctx, ids, values, edges, compute, send, combine="add",
               edge_values=None, active=None, initial_messages=None,
               aggregator=None, max_superstep=80,
               static_superstep=False, send_gate_leaf=None):
    """Vectorized Pregel — the device-native Bagel: a PregelGraph
    built, run once and dropped.

    ids:     (n,) int array of unique vertex ids
    edges:   (src_ids, dst_ids) int arrays; each edge lives with its
             source, messages flow along it to dst
    values:  (n,) array or tuple of (n, ...) arrays — vertex state, in
             the order of the graph's `ids`
    compute(values, msg, has_msg, active, aggregated, superstep)
             -> (new_values, new_active): applied BLOCKWISE — every
             argument is an array over a whole block of vertices (all of
             them on the host path, one device's block on the tpu
             master), so it must be written with vectorized/elementwise
             array ops (jnp or np arithmetic, where(), comparisons) —
             no Python control flow on the data.  `msg` holds the
             combined inbound message per vertex (the monoid identity
             where has_msg is False); `superstep` is a scalar.
    send(src_values, edge_values, src_degree) -> per-edge message value
             (scalar leaf or tuple of scalar leaves), same blockwise
             contract over edges; only edges whose source is active
             after compute actually send — unless `send_gate_leaf` is
             given: the index of a bool vertex-state leaf that REPLACES
             post-compute active as the send mask (for contracts where
             a halting vertex still delivers, or an active one emits
             nothing — the columnarized object Bagel needs both).
    combine: message-combine monoid: "add" | "min" | "max" | "mul"
    aggregator: None or (create(values) -> leaf/tuple, monoid): global
             per-superstep reduce over the PRE-compute vertex state,
             visible to compute as `aggregated` the same superstep
    initial_messages: None or (dst_ids, msg_values) delivered at
             superstep 0

    Halts when no vertex is active and no messages are pending, or at
    max_superstep.  Returns (ids, values, active) sorted by id (numpy).

    On the tpu master the superstep runs as fused shard_map programs
    over the device mesh (backend/tpu/bagel.py); other masters use the
    equivalent vectorized numpy loop below (the golden model).
    """
    graph = PregelGraph(ctx, ids, edges, edge_values)
    try:
        return graph.run(values, compute, send, combine=combine,
                         active=active,
                         initial_messages=initial_messages,
                         aggregator=aggregator,
                         max_superstep=max_superstep,
                         static_superstep=static_superstep,
                         send_gate_leaf=send_gate_leaf)
    finally:
        graph.drop()


class _HostGraph:
    """A graph's host index: the ids sorted, where each given vertex
    went (`order`), every edge's source as a position among the sorted
    ids (`src_idx`) and the out-degrees.  The numpy loop runs over it,
    and the device graph is partitioned from it."""

    def __init__(self, ids, edges, edge_values=None):
        ids = np.asarray(ids, np.int64)
        self.n = n = ids.shape[0]
        self.order = np.argsort(ids)
        self.ids = ids[self.order]
        if n > 1 and bool((self.ids[1:] == self.ids[:-1]).any()):
            raise PregelInputError("vertex ids must be unique")
        src = np.asarray(edges[0], np.int64)
        self.dst = np.asarray(edges[1], np.int64)
        eleaves, self.e_tuple = ((None, False) if edge_values is None
                                 else as_leaves(edge_values))
        self.eleaves = [np.asarray(l) for l in eleaves] if eleaves else []
        src_idx = np.searchsorted(self.ids, src)
        self.src_idx = np.clip(src_idx, 0, max(0, n - 1))
        if src.size and (n == 0 or not np.array_equal(
                self.ids[self.src_idx], src)):
            raise PregelInputError("edge source not in vertex ids")
        self.deg = np.bincount(self.src_idx, minlength=n) if src.size \
            else np.zeros(n, np.int64)

    def run(self, values, compute, send, combine, active,
            initial_messages, aggregator, max_superstep,
            send_gate_leaf=None):
        """Single-host vectorized Pregel: the golden model for the
        device implementation.  The framework side is pure numpy, but
        user compute/send may use jnp — whose first call initializes the
        default jax backend, so honor DPARK_TPU_PLATFORM here too."""
        from dpark_tpu.utils import apply_platform_override
        apply_platform_override()
        ids, n, order = self.ids, self.n, self.order
        src_idx, dst, deg = self.src_idx, self.dst, self.deg
        eleaves, e_tuple = self.eleaves, self.e_tuple
        n_edges = src_idx.size
        vleaves, v_tuple = as_leaves(values)
        vleaves = [np.asarray(l)[order] for l in vleaves]
        act = np.ones(n, bool) if active is None \
            else np.asarray(active, bool)[order]
        # message dtypes AND trailing shapes (leaves may be scalars or
        # small fixed-size vectors — the sum-vector exchange), discovered
        # by probing `send` on empty slices (the host twin of the device
        # path's eval_shape)
        try:
            probe = send(rewrap([l[:0] for l in vleaves], v_tuple),
                         rewrap([l[:0] for l in eleaves], e_tuple)
                         if eleaves else None, deg[:0])
            m_probe, m_tuple = as_leaves(probe)
            msg_dtypes = [np.asarray(l).dtype for l in m_probe]
            msg_shapes = [np.asarray(l).shape[1:] for l in m_probe]
        except Exception:
            m_tuple = False
            msg_dtypes = [np.dtype(np.float64)]
            msg_shapes = [()]

        def deliver(pdst, pvals):
            """Combine pending messages per target; unknown targets drop
            (parity with the object path).  Vector leaves combine
            elementwise — the per-leaf monoid."""
            pos = np.searchsorted(ids, pdst)
            pos = np.clip(pos, 0, max(0, n - 1))
            known = ids[pos] == pdst
            pos = pos[known]
            bufs = []
            for l in pvals:
                buf = np.full((n,) + l.shape[1:],
                              monoid_identity(combine, l.dtype), l.dtype)
                _NP_COMBINE[combine].at(buf, pos, l[known])
                bufs.append(buf)
            has = np.bincount(pos, minlength=n) > 0
            return bufs, has

        pending = None
        if initial_messages is not None:
            idst = np.asarray(initial_messages[0], np.int64)
            ivls, _ = as_leaves(initial_messages[1])
            if idst.size and len(ivls) != len(msg_dtypes):
                raise PregelInputError(
                    "initial message leaves mismatch: got %d, send "
                    "produces %d" % (len(ivls), len(msg_dtypes)))
            pending = (idst, [np.asarray(l, dt)
                              for l, dt in zip(ivls, msg_dtypes)])

        s = 0
        while s < max_superstep:
            aggregated = None
            if aggregator is not None:
                create, amon = aggregator
                a_leaves, a_tuple = as_leaves(
                    create(rewrap(vleaves, v_tuple)))
                aggregated = rewrap(
                    [_NP_REDUCE[amon](np.asarray(l)) for l in a_leaves],
                    a_tuple)

            if pending is not None and pending[0].size:
                msg_leaves, has = deliver(*pending)
            else:
                msg_leaves = [np.full((n,) + shp,
                                      monoid_identity(combine, dt), dt)
                              for dt, shp in zip(msg_dtypes, msg_shapes)]
                has = np.zeros(n, bool)
            nv_, na_ = compute(rewrap(vleaves, v_tuple),
                               rewrap(msg_leaves, m_tuple), has, act,
                               aggregated, s)
            new_leaves, _ = as_leaves(nv_)
            vleaves = [np.broadcast_to(np.asarray(l), (n,) +
                                       np.asarray(l).shape[1:]).copy()
                       if np.asarray(l).shape[:1] != (n,)
                       else np.asarray(l) for l in new_leaves]
            act = np.broadcast_to(np.asarray(na_, bool), (n,)).copy()

            gate = (np.asarray(vleaves[send_gate_leaf], bool)
                    if send_gate_leaf is not None else act)
            src_mask = gate[src_idx] if n_edges else np.zeros(0, bool)
            if n_edges:
                msg = send(rewrap([l[src_idx] for l in vleaves], v_tuple),
                           rewrap([l for l in eleaves], e_tuple)
                           if eleaves else None,
                           deg[src_idx])
                m_leaves, m_tuple = as_leaves(msg)
                m_leaves = [np.broadcast_to(
                    np.asarray(l),
                    (n_edges,) + np.asarray(l).shape[1:]).copy()
                    for l in m_leaves]
                pending = (dst[src_mask],
                           [l[src_mask] for l in m_leaves])
            else:
                pending = (np.zeros(0, np.int64), [])
            n_active = int(act.sum())
            n_msgs = int(src_mask.sum())
            s += 1
            logger.debug("host superstep %d: active=%d msgs=%d",
                         s, n_active, n_msgs)
            if n_active == 0 and n_msgs == 0:
                break
        return ids, rewrap(vleaves, v_tuple), act


def _pregel_host(ids, values, edges, compute, send, combine,
                 edge_values, active, initial_messages, aggregator,
                 max_superstep, send_gate_leaf=None):
    """The numpy loop over a graph indexed for this one run."""
    return _HostGraph(ids, edges, edge_values).run(
        values, compute, send, combine, active, initial_messages,
        aggregator, max_superstep, send_gate_leaf)
