"""DStream: micro-batch stream processing over RDDs.

Reference parity: dpark/dstream.py (SURVEY.md sections 2.3 and 3.3) — a
DStream is a time-indexed sequence of RDDs; a recurring timer turns each
batch tick into ordinary RDD jobs generated from the output streams.
Windowing unions the parent's RDDs over the window; updateStateByKey
cogroups the previous state RDD with the new batch; reduceByKeyAndWindow
supports the incremental inverse-reduce optimization.

On the tpu master every batch reuses the structurally-keyed compiled stage
programs (backend/tpu/fuse.py), so the per-tick cost is execution, not
compilation — the DStream-specific recompile hazard of SURVEY.md 7.2.5.
"""

import numbers
import os
import socket as _socket
import threading
import time as _time

from dpark_tpu.utils.log import get_logger

logger = get_logger("dstream")


class StreamingContext:
    def __init__(self, ctx, batchDuration):
        from dpark_tpu.context import DparkContext
        if isinstance(ctx, str):
            ctx = DparkContext(ctx)
        self.ctx = ctx
        self._master = ctx.master
        self.batch_duration = float(batchDuration)
        self.zero_time = None
        self.output_streams = []
        self.input_streams = []
        self._timer = None
        self._stopped = threading.Event()
        self._thread = None
        self.checkpoint_interval = 10     # batches
        self.checkpoint_path = None
        self._batches_done = 0
        self._checkpoint_now = False
        self.last_checkpoint_t = None

    # -- checkpoint / recovery (reference: StreamingContext recovery from
    #    a checkpoint dir, SURVEY.md 5.4) --------------------------------
    def checkpoint(self, directory):
        os.makedirs(directory, exist_ok=True)
        self.checkpoint_path = directory
        self.ctx.setCheckpointDir(directory)
        return self

    def __getstate__(self):
        d = dict(self.__dict__)
        for k in ("ctx", "_thread", "_timer", "_stopped"):
            d[k] = None
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self._stopped = threading.Event()

    def _save_metadata(self, t):
        from dpark_tpu import serialize
        from dpark_tpu.context import DparkContext
        from dpark_tpu.utils import atomic_file
        self.last_checkpoint_t = t
        # persist the rdd-id high-water mark: checkpoint dirs are keyed
        # rdd-<id> in a persistent dir, so a recovered process must not
        # re-mint lower ids
        self._rdd_id_hwm = DparkContext._rdd_id_counter[0]
        path = os.path.join(self.checkpoint_path, "metadata")
        with atomic_file(path) as f:
            f.write(serialize.dumps(self))

    @classmethod
    def getOrCreate(cls, directory, create_fn):
        """Recover the stream graph + state from `directory`, or build a
        fresh context via create_fn() and enable checkpointing into it.
        Recovery resumes state streams from their last checkpointed batch;
        queue/socket input consumed after that checkpoint is not replayed
        (at-most-once, as in the reference's data-loss caveats)."""
        import os as _os
        from dpark_tpu import serialize
        path = _os.path.join(directory, "metadata")
        if _os.path.exists(path):
            with open(path, "rb") as f:
                ssc = serialize.loads(f.read())
            ssc._restore(directory)
            return ssc
        ssc = create_fn()
        ssc.checkpoint(directory)
        return ssc

    def _restore(self, directory):
        from dpark_tpu.context import DparkContext
        self.ctx = DparkContext(self._master)
        self.ctx.setCheckpointDir(directory)
        self.checkpoint_path = directory
        DparkContext.advance_rdd_ids(getattr(self, "_rdd_id_hwm", 0))
        self._recovered = True
        for stream in self._all_streams():
            stream.ssc = self
            for rdd in self._stream_rdds(stream):
                _fix_rdd_ctx(rdd, self.ctx)

    @staticmethod
    def _stream_rdds(stream):
        """Every RDD a stream holds: generated batches plus RDDs embedded
        in input streams (constant rdd, queued items, defaults)."""
        out = [r for r in stream.generated.values() if r is not None]
        for attr in ("rdd", "defaultRDD"):
            r = getattr(stream, attr, None)
            if hasattr(r, "dependencies"):
                out.append(r)
        for item in getattr(stream, "queue", []) or []:
            if hasattr(item, "dependencies"):
                out.append(item)
        return out

    def _rebase_timeline(self, new_zero):
        """After recovery, restart the clock at `new_zero`: each stream's
        latest checkpointed batch becomes the batch at new_zero so the
        first new batch (new_zero + batch) finds its predecessor state."""
        for stream in self._all_streams():
            if stream.generated:
                last_t = max(stream.generated)
                last_rdd = stream.generated[last_t]
                stream.generated = {round(new_zero, 6): last_rdd}
            stream._on_rebase()
        self.zero_time = new_zero
        self._recovered = False

    def _all_streams(self):
        out = []
        seen = set()
        frontier = list(self.output_streams) + list(self.input_streams)
        while frontier:
            s = frontier.pop()
            if id(s) in seen:
                continue
            seen.add(id(s))
            out.append(s)
            frontier.extend(s.parents)
        return out

    batchDuration = property(lambda self: self.batch_duration)

    # -- input stream constructors --------------------------------------
    def queueStream(self, queue, oneAtATime=True, defaultRDD=None):
        """queue: list/deque of RDDs or of plain lists (auto-parallelized)."""
        return QueueInputDStream(self, list(queue), oneAtATime, defaultRDD)

    def textFileStream(self, directory, filter_fn=None,
                       stamp_arrival=False):
        return FileInputDStream(self, directory, filter_fn,
                                stamp_arrival=stamp_arrival)

    fileStream = textFileStream

    def socketTextStream(self, hostname, port, stamp_arrival=False):
        return SocketInputDStream(self, hostname, port,
                                  stamp_arrival=stamp_arrival)

    def makeStream(self, rdd):
        return ConstantInputDStream(self, rdd)

    def union(self, *streams):
        return UnionDStream(list(streams))

    # -- lifecycle -------------------------------------------------------
    def start(self, t0=None):
        if not self.output_streams:
            raise ValueError("no output streams registered "
                             "(call foreachRDD / pprint)")
        self.ctx.start()
        for ins in self.input_streams:
            ins.start()
        bd = self.batch_duration
        if getattr(self, "_recovered", False):
            # recovered context: restart the clock NOW, carrying each
            # state stream's checkpointed batch over as the predecessor
            # (no replay storm over the downtime gap)
            now = t0 if t0 is not None else _time.time()
            self._rebase_timeline(now - (now % bd))
        elif self.zero_time is None or t0 is not None:
            now = t0 if t0 is not None else _time.time()
            self.zero_time = now - (now % bd)
        self._stopped.clear()
        self._thread = threading.Thread(target=self._run_loop, daemon=True)
        self._thread.start()

    def _run_loop(self):
        bd = self.batch_duration
        t = self.zero_time + bd
        while not self._stopped.is_set():
            now = _time.time()
            if now < t:
                self._stopped.wait(min(t - now, 0.05))
                continue
            try:
                self.run_batch(t)
            except Exception:
                logger.exception("batch at %s failed", t)
            t += bd

    def run_batch(self, t):
        """Generate and run one batch's jobs (called by the timer loop; in
        tests it can be driven manually for determinism).

        A TypeError escaping a batch whose state/window streams took
        the probe-based numeric union-reduce rewrite permanently
        disables that rewrite (the probe saw a numeric head; the tail
        proved it wrong) and regenerates the batch through the generic
        updateFunc/invFunc path — the 5-record probe is an accelerator
        heuristic, never the arbiter of correctness."""
        t = round(t, 6)
        self._batches_done += 1
        self._checkpoint_now = (
            self.checkpoint_path is not None
            and self._batches_done % self.checkpoint_interval == 0)
        from dpark_tpu import trace
        for out in self.output_streams:
            t0 = _time.perf_counter()
            try:
                with trace.span("stream.batch", "stream", t=t):
                    out.generate_job(t)
            except (TypeError, RuntimeError) as e:
                if not self._disable_numeric_rewrites(t, e, out):
                    raise
                try:
                    with trace.span("stream.batch", "stream", t=t,
                                    replay=True):
                        out.generate_job(t)  # regenerate, generic path
                except Exception:
                    # the generic path rejects this batch too (the
                    # user's own function raises on the data): drop the
                    # poisoned derived RDDs so LATER batches carry the
                    # last good state forward instead of replaying the
                    # failure forever.  Scope to THIS output's chain —
                    # sibling chains already emitted their batch
                    for s in self._chain_streams(out):
                        if not isinstance(s, InputDStream):
                            s.generated.pop(t, None)
                    raise
            # per-tick wall observed per output chain: pane streams
            # sample it into the adapt store (split-point pricing) —
            # chains sharing a pane stream attribute the same wall
            ms = (_time.perf_counter() - t0) * 1000.0
            for s in self._chain_streams(out):
                observe = getattr(s, "_observe_tick_ms", None)
                if observe is not None:
                    try:
                        observe(ms)
                    except Exception:
                        pass
        for out in self.output_streams:
            out.forget_old(t)
        if self._checkpoint_now:
            self._save_metadata(t)

    def _chain_streams(self, out):
        """Every stream reachable from ONE output stream (the failing
        chain) — fallback surgery must not touch sibling chains that
        already emitted their batch."""
        seen, chain, frontier = set(), [], [out]
        while frontier:
            s = frontier.pop()
            if id(s) in seen:
                continue
            seen.add(id(s))
            chain.append(s)
            frontier.extend(s.parents)
        return chain

    def _disable_numeric_rewrites(self, t, exc, out):
        """Fallback on the FIRST _NumericRewriteError from the numeric
        rewrite: flip the failing chain's _numeric latches to False
        (the rewrite never re-applies for those streams) and drop the
        failed batch's derived RDDs so the retry recomputes them
        generically.  Input streams keep their generated batch — the
        data must not be consumed twice (queue) or lost (socket).
        Returns False when the error did not come from the checked op
        (an unrelated user TypeError must NOT disable working
        rewrites) or no rewrite was active; the caller re-raises."""
        if not isinstance(exc, _NumericRewriteError) \
                and "_NumericRewriteError" not in str(exc):
            return False                # an unrelated failure
        chain = self._chain_streams(out)
        hit = False
        for s in chain:
            if getattr(s, "_numeric", None):
                s._numeric = False
                hit = True
                logger.warning(
                    "%s at t=%s: numeric union-reduce rewrite hit a "
                    "TypeError (probe saw numbers, batch holds "
                    "non-numbers); falling back to the generic path "
                    "permanently", type(s).__name__, t)
        if not hit:
            return False
        for s in chain:
            if not isinstance(s, InputDStream):
                s.generated.pop(t, None)
        return True

    def awaitTermination(self, timeout=None):
        if self._thread:
            self._thread.join(timeout)

    def stop(self, stop_context=False):
        self._stopped.set()
        if self._thread:
            self._thread.join(self.batch_duration * 2 + 1)
            self._thread = None
        for ins in self.input_streams:
            ins.stop()
        # drop this context's pane streams from the live-stats
        # registry (bounded /metrics cardinality across restarts)
        from dpark_tpu import panes as panes_mod
        for s in self._all_streams():
            sid = getattr(s, "_sid", None)
            if sid is not None:
                panes_mod.unregister_stream(sid)
        if stop_context:
            self.ctx.stop()


class DStream:
    def __init__(self, ssc):
        self.ssc = ssc
        self.generated = {}            # time -> rdd (or None)
        self.must_checkpoint = False

    @property
    def slide_duration(self):
        return self.ssc.batch_duration

    @property
    def parents(self):
        return []

    @property
    def window_duration(self):
        """How long this stream's own RDDs must be remembered by parents."""
        return self.slide_duration

    def compute(self, t):
        raise NotImplementedError

    def getOrCompute(self, t):
        t = round(t, 6)
        zero = self.ssc.zero_time
        if zero is not None and t <= zero + 1e-9:
            return None                 # before the stream started
        if t in self.generated:
            return self.generated[t]
        sd = self.slide_duration
        if zero is not None and sd:
            # slide cadence (reference parity): a stream only emits at
            # multiples of its OWN slide duration.  Off-cadence ticks
            # (a windowed stream with slide > batch) produce nothing —
            # the pane plane depends on this: pane boundaries ARE the
            # emit boundaries.
            k = (t - zero) / sd
            if abs(k - round(k)) > 1e-4:
                return None
        rdd = self.compute(t)
        self.generated[t] = rdd
        if rdd is not None and self.must_checkpoint \
                and self.ssc.ctx.checkpoint_dir \
                and getattr(self.ssc, "_checkpoint_now", False):
            rdd.checkpoint()
        return rdd

    def __getstate__(self):
        d = dict(self.__dict__)
        # only checkpointed RDDs survive serialization (their lineage is
        # truncated to on-disk partitions); everything else recomputes.
        # checkpoint() is LAZY: an RDD whose parts were all written by
        # the batch jobs may not have promoted on the driver yet —
        # promote here, or the metadata snapshot would silently drop
        # the stream state (review finding)
        for r in self.generated.values():
            if r is not None:
                r._maybe_promote_checkpoint()
        d["generated"] = {
            t: r for t, r in self.generated.items()
            if r is not None and r._checkpoint_rdd is not None}
        return d

    def forget_old(self, t, keep=None):
        keep = keep if keep is not None else self._remember_duration()
        for ts in list(self.generated):
            if ts < t - keep:
                rdd = self.generated.pop(ts)
                if rdd is not None and rdd.should_cache:
                    rdd.unpersist()     # free cached partitions, not just
                                        # the reference (long-running jobs)
        for p in self.parents:
            p.forget_old(t, keep=max(keep, self.window_duration))

    def _remember_duration(self):
        return max(self.slide_duration * 4, self.window_duration * 2)

    def _on_rebase(self):
        """Hook: the recovery timeline rebase re-keys `generated` to
        the new clock; streams holding OTHER time-keyed state (pane
        stores, per-batch reduce caches) clear it here — the carried
        predecessor window stays, stale-clock partials never mix in."""

    # -- transformations -------------------------------------------------
    def map(self, f):
        return MappedDStream(self, f)

    def flatMap(self, f):
        return TransformedDStream(self, _rdd_op("flatMap", f))

    def filter(self, f):
        return TransformedDStream(self, _rdd_op("filter", f))

    def glom(self):
        return TransformedDStream(self, _rdd_op("glom"))

    def mapPartitions(self, f):
        return TransformedDStream(self, _rdd_op("mapPartitions", f))

    def mapValue(self, f):
        return TransformedDStream(self, _rdd_op("mapValue", f))

    mapValues = mapValue

    def transform(self, func):
        """func(rdd) or func(rdd, time) -> rdd"""
        return TransformedDStream(self, func)

    def groupByKey(self, numSplits=None):
        return TransformedDStream(
            self, _rdd_op("groupByKey", numSplits))

    def reduceByKey(self, func, numSplits=None):
        return TransformedDStream(
            self, _rdd_op("reduceByKey", func, numSplits))

    def combineByKey(self, createCombiner, mergeValue, mergeCombiners,
                     numSplits=None):
        return TransformedDStream(
            self, _rdd_op("combineByKey", createCombiner, mergeValue,
                          mergeCombiners, numSplits))

    def countByValue(self):
        return TransformedDStream(
            self, lambda r: r.map(_pair_one_ds).reduceByKey(_add_ds))

    def union(self, other):
        return UnionDStream([self, other])

    def join(self, other, numSplits=None):
        return CoGroupedDStream([self, other], "join", numSplits)

    def cogroup(self, other, numSplits=None):
        return CoGroupedDStream([self, other], "cogroup", numSplits)

    # -- windows ---------------------------------------------------------
    def window(self, windowDuration, slideDuration=None):
        return WindowedDStream(self, windowDuration, slideDuration)

    def reduceByWindow(self, reduceFunc, windowDuration, slideDuration=None,
                       invReduceFunc=None):
        """Whole-window reduce; with invReduceFunc it rides the incremental
        keyed path (constant key) instead of recomputing the window."""
        if invReduceFunc is not None:
            keyed = self.map(_const_key)
            red = keyed.reduceByKeyAndWindow(
                reduceFunc, windowDuration, slideDuration,
                invFunc=invReduceFunc)
            return TransformedDStream(red, _rdd_op("map", _drop_key))
        w = self.window(windowDuration, slideDuration)
        return TransformedDStream(w, _reduce_to_rdd(reduceFunc))

    def countByWindow(self, windowDuration, slideDuration=None):
        return (self.window(windowDuration, slideDuration)
                .transform(_count_to_rdd))

    def reduceByKeyAndWindow(self, func, windowDuration, slideDuration=None,
                             numSplits=None, invFunc=None,
                             eventTime=None, lateness=None):
        """Windowed per-key reduce; with invFunc the window updates
        incrementally (prev - leaving + entering).

        PANE PLANE (ISSUE 10): when window %% slide == 0 and slide %%
        batch == 0 (and conf.STREAM_PANES is on), the window is sliced
        into slide-sized panes whose partial aggregates persist across
        ticks.  With invFunc the slide is O(1) panes (prev + new pane
        - expired pane); without invFunc a provably mergeable func (a
        classified monoid, or ``func.__dpark_window_merge__ = True``
        asserting associativity over partial aggregates) merges
        O(log w) cached dyadic tree nodes per slide instead of
        re-reducing all w panes.  A non-invertible func with NO
        registered merge keeps the whole-window O(w) recompute and the
        `window-noninv-no-merge` plan-lint rule says so.

        EVENT TIME: `eventTime` (record -> timestamp) assigns records
        to panes by event time instead of arrival batch; the watermark
        trails the max observed timestamp by `lateness` seconds
        (default conf.STREAM_ALLOWED_LATENESS).  Late records inside
        the bound patch ONLY their pane; older ones drop, counted per
        stream.  Requires the pane plane.

        PROBE CONTRACT: when (func, invFunc) prove to be plain (+, -),
        the incremental update is rewritten to one union-reduce per
        tick — but only after a one-time probe of up to 5 records from
        the first non-empty partition shows plain numeric values
        (numbers form a group under (+, -); e.g. collections.Counter
        supports both operators but is NOT invertible).  The rewrite
        then re-verifies numeric-ness on every folded pair: the first
        non-numeric value raises TypeError inside the batch, the
        rewrite is permanently disabled for this stream, and the batch
        regenerates through the generic leftOuterJoin+invFunc path —
        the probe accelerates, it never decides correctness."""
        if invFunc is None:
            from dpark_tpu import conf
            slide = float(slideDuration or self.slide_duration)
            aligned = (_grid_multiple(float(windowDuration), slide)
                       and _grid_multiple(slide, self.slide_duration))
            merge_ok = _window_merge_registered(func)
            if conf.STREAM_PANES and aligned and merge_ok:
                return PanedWindowReduceDStream(
                    self, func, windowDuration, slideDuration, numSplits,
                    eventTime=eventTime, lateness=lateness)
            if eventTime is not None:
                raise ValueError(
                    "eventTime windows need the pane plane: aligned "
                    "window/slide/batch durations, DPARK_STREAM_PANES "
                    "on, and (for non-invertible ops) a registered "
                    "merge")
            why = ("no registered merge for %r"
                   % getattr(func, "__name__", func)) if not merge_ok \
                else ("window/slide/batch durations not grid-aligned"
                      if not aligned else "DPARK_STREAM_PANES off")
            w = self.window(windowDuration, slideDuration)
            return TransformedDStream(
                w, _MarkedWindowReduce(func, numSplits, why))
        return ReducedWindowedDStream(self, func, invFunc, windowDuration,
                                      slideDuration, numSplits,
                                      eventTime=eventTime,
                                      lateness=lateness)

    # -- state -----------------------------------------------------------
    def updateStateByKey(self, updateFunc, numSplits=None):
        """updateFunc(new_values_list, prev_state_or_None) -> state|None

        PROBE CONTRACT: an updateFunc that provably is the running-sum
        idiom ``(prev or 0) + sum(vs)`` (or carries a
        __dpark_state_monoid__ hint) is rewritten to a flat
        union-reduce per batch — but only after a one-time probe of up
        to 5 records from the first non-empty partition shows plain
        numeric values (pairwise a+b == sum()-from-0 for numbers
        only).  The rewrite then re-verifies numeric-ness on every
        folded pair: the first non-numeric value raises TypeError
        inside the batch, the rewrite is permanently disabled for this
        stream, and the batch regenerates through the generic cogroup
        path — the probe accelerates, it never decides correctness."""
        return StateDStream(self, updateFunc, numSplits)

    # -- outputs ---------------------------------------------------------
    def foreachRDD(self, func):
        out = ForEachDStream(self, func)
        self.ssc.output_streams.append(out)
        return out

    def pprint(self, num=10):
        def show(rdd, t):
            items = rdd.take(num)
            print("--- time %s ---" % t)
            for it in items:
                print(it)
        return self.foreachRDD(show)

    def collect_batches(self, sink):
        """Test/utility output: append (time, list) per non-empty batch."""
        return self.foreachRDD(
            lambda rdd, t: sink.append((t, rdd.collect())))


def _fix_rdd_ctx(rdd, ctx):
    """Re-attach the live context to a recovered RDD graph (RDD pickling
    drops ctx)."""
    seen = set()
    frontier = [rdd]
    while frontier:
        r = frontier.pop()
        if id(r) in seen or r is None:
            continue
        seen.add(id(r))
        if getattr(r, "ctx", None) is None:
            r.ctx = ctx
        for attr in ("prev", "parent", "_checkpoint_rdd", "rdd1", "rdd2"):
            nxt = getattr(r, attr, None)
            if nxt is not None and hasattr(nxt, "dependencies"):
                frontier.append(nxt)
        for attr in ("rdds",):
            for nxt in getattr(r, attr, []) or []:
                if hasattr(nxt, "dependencies"):
                    frontier.append(nxt)
        for dep in getattr(r, "dependencies", []) or []:
            nxt = getattr(dep, "rdd", None)
            if nxt is not None:
                frontier.append(nxt)


def _rdd_op(name, *args):
    def op(rdd):
        f = getattr(rdd, name)
        return f(*[a for a in args if a is not None])
    return op


def _pair_one_ds(x):
    return (x, 1)


def _const_key(x):
    return (0, x)


def _drop_key(kv):
    return kv[1]


def _add_ds(a, b):
    return a + b


def _reduce_to_rdd(func):
    def op(rdd):
        vals = rdd.mapPartitions(lambda it: _safe_reduce(it, func)) \
                  .collect()
        out = None
        have = False
        for v in vals:
            out = v if not have else func(out, v)
            have = True
        return rdd.ctx.parallelize([out] if have else [], 1)
    return op


def _safe_reduce(it, func):
    out = None
    have = False
    for x in it:
        out = x if not have else func(out, x)
        have = True
    return [out] if have else []


def _count_to_rdd(rdd):
    return rdd.ctx.parallelize([rdd.count()], 1)


def _grid_multiple(a, b):
    """round(a/b) when a is an (approximate) integer multiple >= 1 of
    b, else 0 — the pane-grid alignment test."""
    if not b:
        return 0
    k = a / b
    n = int(round(k))
    return n if n >= 1 and abs(k - n) < 1e-6 else 0


def _window_merge_registered(func):
    """A non-invertible windowed reduce may merge PARTIAL aggregates
    (pane tree) only when merging partials with `func` provably equals
    folding the raw records: a classified monoid (exact bytecode /
    identity match), or the user's explicit
    ``func.__dpark_window_merge__`` assertion (truthy = func itself is
    associative over partials).  Anything else keeps the whole-window
    recompute — reduceByKey's contract nominally promises
    associativity, but the pane tree RE-ASSOCIATES across ticks, so
    only provable or asserted merges ride."""
    if getattr(func, "__dpark_window_merge__", None):
        return True
    from dpark_tpu.utils.monoid import classify_merge
    try:
        return classify_merge(func) is not None
    except Exception:
        return False


class _MarkedWindowReduce:
    """The O(w) whole-window reduce fallback, marking every emitted
    plan so the `window-noninv-no-merge` lint rule can explain the
    per-tick recompute cost (ISSUE 10 satellite)."""

    def __init__(self, func, numSplits, reason):
        self.func = func
        self.numSplits = numSplits
        self.reason = reason

    def __call__(self, rdd):
        out = rdd.reduceByKey(self.func, self.numSplits)
        out._window_noninv = {
            "reason": self.reason,
            "op": getattr(self.func, "__name__", str(self.func))}
        return out


class DerivedDStream(DStream):
    def __init__(self, parent):
        super().__init__(parent.ssc)
        self.parent = parent

    @property
    def parents(self):
        return [self.parent]

    @property
    def slide_duration(self):
        return self.parent.slide_duration


class MappedDStream(DerivedDStream):
    def __init__(self, parent, f):
        super().__init__(parent)
        self.f = f

    def compute(self, t):
        rdd = self.parent.getOrCompute(t)
        return rdd.map(self.f) if rdd is not None else None


class TransformedDStream(DerivedDStream):
    def __init__(self, parent, func):
        super().__init__(parent)
        self.func = func
        import inspect
        try:
            self._two_args = len(inspect.signature(func).parameters) >= 2
        except (TypeError, ValueError):
            self._two_args = False

    def compute(self, t):
        rdd = self.parent.getOrCompute(t)
        if rdd is None:
            return None
        return self.func(rdd, t) if self._two_args else self.func(rdd)


class UnionDStream(DStream):
    def __init__(self, streams):
        super().__init__(streams[0].ssc)
        self.streams = streams

    @property
    def parents(self):
        return list(self.streams)

    @property
    def slide_duration(self):
        return self.streams[0].slide_duration

    def compute(self, t):
        rdds = [s.getOrCompute(t) for s in self.streams]
        rdds = [r for r in rdds if r is not None]
        if not rdds:
            return None
        return self.ssc.ctx.union(rdds)


class CoGroupedDStream(DStream):
    def __init__(self, streams, how, numSplits=None):
        super().__init__(streams[0].ssc)
        self.streams = streams
        self.how = how
        self.numSplits = numSplits

    @property
    def parents(self):
        return list(self.streams)

    @property
    def slide_duration(self):
        return self.streams[0].slide_duration

    def compute(self, t):
        rdds = [s.getOrCompute(t) for s in self.streams]
        if any(r is None for r in rdds):
            empty = self.ssc.ctx.parallelize([], 1)
            rdds = [r if r is not None else empty for r in rdds]
        a, b = rdds
        if self.how == "join":
            return a.join(b, self.numSplits)
        return a.cogroup(b, numSplits=self.numSplits)


class WindowedDStream(DerivedDStream):
    def __init__(self, parent, windowDuration, slideDuration=None):
        super().__init__(parent)
        self._window = float(windowDuration)
        self._slide = float(slideDuration or parent.slide_duration)

    @property
    def slide_duration(self):
        return self._slide

    @property
    def window_duration(self):
        return self._window

    def compute(self, t):
        rdds = []
        step = self.parent.slide_duration
        # window covers (t - window, t]
        k = t
        while k > t - self._window + 1e-9:
            rdd = self.parent.getOrCompute(round(k, 6))
            if rdd is not None:
                rdds.append(rdd)
            k -= step
        if not rdds:
            return None
        return self.ssc.ctx.union(rdds)


class _PaneWindowBase(DerivedDStream):
    """Shared pane-plane machinery for the windowed streams (ISSUE 10
    tentpole; see dpark_tpu/panes.py for the decomposition): the
    window is sliced into slide-sized PANES whose partial aggregates
    live as cached reduced RDDs keyed by pane end time — on the tpu
    master their shuffle outputs stay HBM-resident between ticks, so
    sliding the window costs merge work over a constant (invertible)
    or logarithmic (merge-tree) number of panes, never a whole-window
    recompute.  Event-time classification, the bounded-lateness
    watermark, single-pane late patches, per-stream live stats
    (panes.stream_stats -> web UI + /metrics), trace events, and the
    adapt-store cost sampling all live here."""

    _kind = "win"

    def __init__(self, parent, func, windowDuration, slideDuration,
                 numSplits, eventTime=None, lateness=None):
        super().__init__(parent)
        self.func = func
        self._window = float(windowDuration)
        self._slide = float(slideDuration or parent.slide_duration)
        self.numSplits = numSplits
        self.must_checkpoint = True
        from dpark_tpu import conf
        # pane-grid admission: the window must be a whole number of
        # slides and the slide a whole number of parent batches
        self._np = _grid_multiple(self._window, self._slide)
        self._bpp = _grid_multiple(self._slide, parent.slide_duration)
        self._pane_mode = bool(conf.STREAM_PANES and self._np
                               and self._bpp)
        self.eventTime = eventTime
        if eventTime is not None and not self._pane_mode:
            raise ValueError(
                "eventTime windows need the pane plane: aligned "
                "window/slide/batch durations and DPARK_STREAM_PANES")
        if lateness is None:
            lateness = conf.STREAM_ALLOWED_LATENESS
        from dpark_tpu import panes as panes_mod
        self._wm = (panes_mod.Watermark(lateness)
                    if eventTime is not None else None)
        self._panes = {}        # pane END time -> reduced rdd or None
        self._tick_deltas = {}  # tick -> in-window late-delta rdds
        self._retired = []      # (due_time, replaced-pane rdd)
        self._anchor = None     # first emit time == pane index 0
        self._sid = None
        self._stats = None
        self._adapt_site = None
        self._tick_samples = []

    @property
    def slide_duration(self):
        return self._slide

    @property
    def window_duration(self):
        return self._window

    # -- identity / registration ----------------------------------------
    def _mode_name(self):
        return "pane"

    def _ensure_registered(self):
        from dpark_tpu import panes as panes_mod
        if self._sid is None:
            self._sid = panes_mod.new_stream_id(self._kind)
            self._stats = {
                "type": type(self).__name__, "mode": self._mode_name(),
                "window": self._window, "slide": self._slide,
                "panes": 0, "nodes": 0, "node_builds": 0, "ticks": 0,
                "watermark": None, "watermark_lag_s": None,
                "late_dropped": 0, "late_patched_rows": 0,
                "late_patches": 0}
            panes_mod.register_stream(self._sid, self._stats)
        if self._adapt_site is None:
            from dpark_tpu import adapt
            try:
                self._adapt_site = adapt.stable_key(
                    ("pane", type(self).__name__,
                     getattr(self.func, "__code__", repr(self.func)),
                     self._np))
            except Exception:
                self._adapt_site = False

    def _tag(self, rdd, role, pane=None):
        """Stage attribution (schedule.py reads `_stream_tag` into
        stage_info): which stream and which pane-plane role a stage's
        RDD serves."""
        if rdd is not None and self._sid is not None:
            tag = {"stream": self._sid, "role": role}
            if pane is not None:
                tag["pane"] = pane
            rdd._stream_tag = tag
        return rdd

    # -- pane store ------------------------------------------------------
    def _idx(self, t):
        return int(round((t - self._anchor) / self._slide))

    def _pane_time(self, idx):
        return round(self._anchor + idx * self._slide, 6)

    def _pane_by_idx(self, idx):
        return self._panes.get(self._pane_time(idx))

    def _new_data(self, t):
        """Union of the parent batches in (t - slide, t], generated in
        ASCENDING time order (queue inputs pop in arrival order)."""
        step = self.parent.slide_duration
        rdds = []
        for j in range(self._bpp - 1, -1, -1):
            r = self.parent.getOrCompute(round(t - j * step, 6))
            if r is not None:
                rdds.append(r)
        if not rdds:
            return None
        return rdds[0] if len(rdds) == 1 else self.ssc.ctx.union(rdds)

    def _reduce(self, rdd):
        return rdd.reduceByKey(self.func, self.numSplits)

    def _on_pane_patched(self, pane_time):
        """Hook: the merge tree invalidates the nodes covering a
        patched pane."""

    def _ingest_pane(self, t):
        """Build pane(t) from the tick's new data, event-time-split
        when configured: on-time records form the new pane, admissible
        late records patch ONLY their pane (bounded by the watermark,
        the window horizon, and conf.STREAM_LATE_BUFFER_ROWS), the
        rest drop (counted).  Returns the tick's in-window late-delta
        RDDs so incremental window updates can fold the patches in;
        idempotent per tick (the numeric-rewrite fallback replays a
        batch through compute())."""
        from dpark_tpu import conf, panes as panes_mod, trace
        t = round(t, 6)
        self._tick_emitted = True       # adapt sampling: a REAL emit
                                        # tick (run_batch also observes
                                        # off-cadence no-op ticks)
        if t in self._panes:
            return self._tick_deltas.get(t, [])
        self._ensure_registered()
        if self._anchor is None:
            self._anchor = t
        new = self._new_data(t)
        deltas = []
        if new is None:
            self._panes[t] = None
            self._note_tick(t)
            return deltas
        if self.eventTime is None:
            pane = self._tag(self._reduce(new).cache(), "pane-build",
                             pane=self._idx(t))
            self._panes[t] = pane
            trace.event("stream.pane.build", "stream", stream=self._sid,
                        pane=self._idx(t))
            self._note_tick(t)
            return deltas
        new = new.cache()
        # the raw tick union materializes for the scan job and feeds
        # the pane/delta filters; retire its cache at the horizon like
        # a replaced pane (its lineage stays recomputable)
        self._retired.append(
            (t + self._window + self._wm.lateness, new))
        # classify the tick's records under the PREVIOUS watermark
        # (one small job; the filters below share the same rule)
        max_back = min(self._np - 1, self._idx(t))
        floor = self._wm.floor()
        mx, on_time, late, dropped = panes_mod.event_scan(
            new, self.eventTime, t, self._slide, max_back, floor)
        pane = None
        if on_time:
            pane = new.filter(panes_mod._PaneFilter(
                self.eventTime, t, self._slide, 0, floor))
            pane = self._tag(self._reduce(pane).cache(), "pane-build",
                             pane=self._idx(t))
            trace.event("stream.pane.build", "stream", stream=self._sid,
                        pane=self._idx(t))
        self._panes[t] = pane
        cap = conf.STREAM_LATE_BUFFER_ROWS
        for back in sorted(late):
            rows = late[back]
            if cap and rows > cap:
                # bounded late buffer: an oversized patch drops WHOLE
                # (deterministic — a first-N admission would depend on
                # partition scan order)
                dropped += rows
                continue
            pt = round(t - back * self._slide, 6)
            delta = new.filter(panes_mod._PaneFilter(
                self.eventTime, t, self._slide, back, floor))
            delta = self._tag(self._reduce(delta).cache(), "late-patch",
                              pane=self._idx(pt))
            old = self._panes.get(pt)
            if old is None:
                patched = delta
            else:
                patched = self._tag(
                    self._reduce(old.union(delta)).cache(),
                    "pane-build", pane=self._idx(pt))
                # the replaced pane may still back cached lineage of
                # already-emitted windows: retire it at the horizon
                self._retired.append(
                    (pt + self._window + self._wm.lateness, old))
            self._panes[pt] = patched
            self._on_pane_patched(pt)
            deltas.append(delta)
            self._stats["late_patches"] += 1
            self._stats["late_patched_rows"] += rows
            trace.event("stream.late.patch", "stream", stream=self._sid,
                        pane=self._idx(pt), rows=rows)
        self._wm.update(mx)
        self._stats["late_dropped"] += dropped
        if deltas:
            self._tick_deltas[t] = deltas
        self._note_tick(t)
        return deltas

    def _window_pane_rdds(self, t):
        """The window's existing pane partials (cold start / flat
        emit)."""
        out = []
        k = t
        while k > t - self._window + 1e-9:
            p = self._panes.get(round(k, 6))
            if p is not None:
                out.append(p)
            k -= self._slide
        return out

    # -- bookkeeping -----------------------------------------------------
    def _note_tick(self, t):
        st = self._stats
        st["ticks"] += 1
        st["panes"] = sum(1 for r in self._panes.values()
                          if r is not None)
        if self._wm is not None:
            st["watermark"] = self._wm.value()
            lag = self._wm.lag(t)
            st["watermark_lag_s"] = (None if lag is None
                                     else round(lag, 6))

    def _observe_tick_ms(self, ms):
        """Sample the per-tick wall into the adapt store (split-point
        pricing: the planner compares tree vs flat emit costs for this
        stream signature across runs).  One append per stream — the
        median of the post-warmup ticks."""
        if not self._pane_mode or not self._adapt_site:
            return
        # only REAL emit ticks count (with slide > batch, run_batch
        # also times off-cadence no-op ticks — ~0 ms walls that would
        # poison the median), and the list stops growing once sampled
        if not getattr(self, "_tick_emitted", False) \
                or len(self._tick_samples) >= 8:
            return
        self._tick_emitted = False
        self._tick_samples.append(float(ms))
        if len(self._tick_samples) == 8:
            from dpark_tpu import adapt
            tail = sorted(self._tick_samples[4:])
            adapt.record_pane_cost(self._adapt_site, self._mode_name(),
                                   tail[len(tail) // 2], self._np)

    def forget_old(self, t, keep=None):
        super().forget_old(t, keep)
        horizon = self._window + self._slide * 2 \
            + (self._wm.lateness if self._wm is not None else 0.0)
        for ts in list(self._panes):
            if ts < t - horizon:
                rdd = self._panes.pop(ts)
                if rdd is not None and rdd.should_cache:
                    rdd.unpersist()
        for ts in list(self._tick_deltas):
            if ts < t - horizon:
                for rdd in self._tick_deltas.pop(ts):
                    if rdd.should_cache:
                        rdd.unpersist()
        keep_retired = []
        for due, rdd in self._retired:
            if due < t:
                if rdd.should_cache:
                    rdd.unpersist()
            else:
                keep_retired.append((due, rdd))
        self._retired = keep_retired
        if self._stats is not None:
            self._stats["panes"] = sum(
                1 for r in self._panes.values() if r is not None)

    def _on_rebase(self):
        # pane stores are keyed by the OLD clock: clear them (the
        # carried predecessor window survives via `generated`; panes
        # refill from the new anchor, exactly like the pre-pane
        # per-batch reduce cache)
        self._panes = {}
        self._tick_deltas = {}
        self._retired = []
        self._anchor = None

    def __getstate__(self):
        d = super().__getstate__()
        # only checkpointed panes survive the metadata snapshot (same
        # contract as `generated`); live stats/registry re-create on
        # the first tick after recovery
        for r in self._panes.values():
            if r is not None:
                r._maybe_promote_checkpoint()
        d["_panes"] = {
            ts: r for ts, r in self._panes.items()
            if r is not None and r._checkpoint_rdd is not None}
        d["_tick_deltas"] = {}
        d["_retired"] = []
        d["_sid"] = None
        d["_stats"] = None
        d["_tick_samples"] = []
        return d


class ReducedWindowedDStream(_PaneWindowBase):
    """Incremental windowed reduce: new_window = inv(prev_window - old
    slice) + new slice (reference: ReducedWindowedDStream).

    PANE PLANE (ISSUE 10): on the aligned grid the slide is O(1) PANES
    regardless of the window/slide ratio — prev + new pane - expired
    pane — where the pre-pane path paid one join/reduce per BATCH
    leaving and entering (O(slide/batch) per tick, O(window/batch) on
    cold start).  Pane partials are cached reduced RDDs; the expired
    pane was built when it entered, so no recompute.  Misaligned
    windows (or DPARK_STREAM_PANES=0) keep the per-batch path."""

    _kind = "rwin"

    def __init__(self, parent, func, invFunc, windowDuration,
                 slideDuration=None, numSplits=None, eventTime=None,
                 lateness=None):
        super().__init__(parent, func, windowDuration, slideDuration,
                         numSplits, eventTime=eventTime,
                         lateness=lateness)
        self.invFunc = invFunc
        self._reduced = {}      # time -> per-batch reduced rdd
                                # (pre-pane path only)
        # provably (add, sub): the incremental update rewrites to
        # prev + new - old as ONE union-reduce — every branch is a
        # reduced shuffle, so the whole window update rides the device
        # union path instead of leftOuterJoin + per-pair Python inv.
        # The operators alone don't prove the VALUES form a group
        # under them (collections.Counter supports + and - but its -
        # saturates at zero and its negation drops positives), so the
        # rewrite additionally needs the one-time numeric value probe
        # below (_numeric) before it applies.
        self._linear_ops = _is_plain_add(func) and _is_plain_sub(invFunc)
        self._numeric = None            # undecided until data shows up
        # ONE checked-op instance for the stream's lifetime: the tpu
        # backend keys compiled programs by merge-callable identity, so
        # a fresh wrapper per batch would defeat the program cache
        # (and leak one compiled entry per tick — review finding)
        self._checked_op = (_CheckedNumericOp(func, "add")
                            if self._linear_ops else None)

    def _mode_name(self):
        return "inv"

    def _batch_reduced(self, t):
        if t not in self._reduced:
            rdd = self.parent.getOrCompute(t)
            self._reduced[t] = (rdd.reduceByKey(self.func, self.numSplits)
                                if rdd is not None else None)
        return self._reduced[t]

    def _probe_numeric(self, prev):
        if self._linear_ops and self._numeric is None:
            # one-time value probe (a one-partition job on the cached
            # window): plain numbers form a group under (+, -); other
            # +/- types (Counter saturates) must keep the join path.
            # Probe SEVERAL records, not one (ADVICE r4): a stream whose
            # first reduced value is a number but whose later ones are
            # not would otherwise silently take the union-negate
            # rewrite and diverge from the leftOuterJoin+invFunc path.
            # The verdict caches per (op, value type) process-wide —
            # sibling streams folding the same op over the same record
            # type skip the re-derivation (ISSUE 10 satellite)
            probe = _probe_values(prev)
            if probe:
                self._numeric = _numeric_verdict(
                    "add", [rec[1] for rec in probe])

    def compute(self, t):
        if not self._pane_mode:
            return self._compute_batchwise(t)
        from dpark_tpu import trace
        t = round(t, 6)
        prev = self.generated.get(round(t - self._slide, 6))
        deltas = self._ingest_pane(t)
        pane_new = self._panes.get(t)
        if prev is None:
            # cold start: flat union-reduce over the window's panes
            # (each pane already reduced; deltas are folded into the
            # patched panes, so they must NOT be added again here)
            rdds = self._window_pane_rdds(t)
            if not rdds:
                return None
            if len(rdds) == 1:
                return rdds[0]
            out = rdds[0].union(*rdds[1:]) \
                         .reduceByKey(self.func, self.numSplits).cache()
            trace.event("stream.window.emit", "stream",
                        stream=self._sid, branches=len(rdds))
            return self._tag(out, "window-emit")
        pane_old = self._panes.get(round(t - self._window, 6))
        self._probe_numeric(prev)
        if self._linear_ops and self._numeric:
            # prev + new pane - expired pane (+ late patch deltas), ONE
            # union-reduce over a CONSTANT number of branches.  Key-set
            # parity with the join formulation: every key in the
            # expired pane also appears in prev (prev's window
            # contained that pane), so negated orphan keys cannot
            # materialize; keys at the zero element stay present,
            # exactly like leftOuterJoin + sub
            branches = [prev]
            if pane_new is not None:
                branches.append(pane_new)
            branches.extend(deltas)
            if pane_old is not None:
                branches.append(pane_old.mapValue(_neg_value))
            if len(branches) == 1:
                return prev             # quiet tick: window unchanged
            # checked op: a non-numeric tail raises TypeError and
            # run_batch falls back to the join+invFunc path
            out = branches[0].union(*branches[1:]) \
                .reduceByKey(self._checked_op, self.numSplits).cache()
            trace.event("stream.window.emit", "stream",
                        stream=self._sid, branches=len(branches))
            return self._tag(out, "window-emit")
        # generic invFunc path, pane granularity: ONE inverse join for
        # the expired pane (invFunc sees the pane's AGGREGATE — the
        # reference contract: old values are reduced first, then
        # inverse-reduced once) + one union-reduce for the new pane
        # and any late patches
        out = prev
        if pane_old is not None:
            out = out.leftOuterJoin(pane_old, self.numSplits) \
                     .mapValue(_InvApply(self.invFunc))
        entering = ([pane_new] if pane_new is not None else []) + deltas
        if entering:
            out = out.union(*entering) \
                     .reduceByKey(self.func, self.numSplits)
        if out is prev:
            return prev
        # drop keys whose count reached the zero element is left to the
        # user's invFunc semantics (parity with reference)
        trace.event("stream.window.emit", "stream", stream=self._sid,
                    branches=1 + len(entering))
        return self._tag(out.cache(), "window-emit")

    def _compute_batchwise(self, t):
        """The pre-pane per-batch path (misaligned windows or
        DPARK_STREAM_PANES=0 — also the parity suite's reference
        side)."""
        prev = self.generated.get(round(t - self._slide, 6))
        step = self.parent.slide_duration
        if prev is None:
            # cold start: plain window reduce
            rdds = []
            k = t
            while k > t - self._window + 1e-9:
                r = self._batch_reduced(round(k, 6))
                if r is not None:
                    rdds.append(r)
                k -= step
            if not rdds:
                return None
            out = rdds[0]
            for r in rdds[1:]:
                out = out.union(r)
            return out.reduceByKey(self.func, self.numSplits).cache()
        # incremental: subtract slices leaving the window, add new ones
        leaving, entering = [], []
        k = t - self._window
        while k > t - self._window - self._slide + 1e-9:
            r = self._batch_reduced(round(k, 6))
            if r is not None:
                leaving.append(r)
            k -= step
        k = t
        while k > t - self._slide + 1e-9:
            r = self._batch_reduced(round(k, 6))
            if r is not None:
                entering.append(r)
            k -= step
        self._probe_numeric(prev)
        if self._linear_ops and self._numeric:
            branches = ([prev] + entering
                        + [r.mapValue(_neg_value) for r in leaving])
            out = branches[0]
            if len(branches) > 1:
                # checked op: a non-numeric tail raises TypeError and
                # run_batch falls back to the join+invFunc path
                out = out.union(*branches[1:]) \
                         .reduceByKey(self._checked_op, self.numSplits)
            return out.cache()
        out = prev
        for r in leaving:
            joined = out.leftOuterJoin(r, self.numSplits)
            out = joined.mapValue(_InvApply(self.invFunc))
        for r in entering:
            out = out.union(r).reduceByKey(self.func, self.numSplits)
        return out.cache()

    def forget_old(self, t, keep=None):
        super().forget_old(t, keep)
        for ts in list(self._reduced):
            if ts < t - (self._window + self._slide * 2):
                rdd = self._reduced.pop(ts)
                if rdd is not None and rdd.should_cache:
                    rdd.unpersist()

    def _on_rebase(self):
        super()._on_rebase()
        self._reduced = {}


class PanedWindowReduceDStream(_PaneWindowBase):
    """Non-invertible windowed reduce over the pane plane: each tick
    merges the window's pane range through a cache of ALIGNED dyadic
    merge nodes (panes.MergeTree) — at most ~2*log2(w) branches per
    emit and amortized O(1) node builds per pane, vs. re-reducing all
    w panes (let alone all raw batches) every slide.  Below
    conf.STREAM_PANE_TREE_MIN panes the tree's extra cached
    intermediate shuffles don't pay and the panes union FLAT; with
    DPARK_ADAPT=on the split-point choice comes from OBSERVED per-tick
    costs instead (adapt.steer_pane_mode).

    Admission (checked by reduceByKeyAndWindow before constructing
    this class): merging PARTIAL aggregates with `func` must provably
    equal folding raw records — a classified monoid or an explicit
    ``func.__dpark_window_merge__`` assertion.  Float caveat: the tree
    re-associates the fold, so float low-order bits can differ from
    the whole-window recompute (the GROUP_AGG_REWRITE caveat); integer
    and min/max aggregates are exact."""

    _kind = "pwin"

    def __init__(self, parent, func, windowDuration, slideDuration=None,
                 numSplits=None, eventTime=None, lateness=None):
        super().__init__(parent, func, windowDuration, slideDuration,
                         numSplits, eventTime=eventTime,
                         lateness=lateness)
        assert self._pane_mode, "constructed without pane admission"
        self._tree = None
        self._use_tree = None           # decided at first emit
        # a node wider than half the window is covered at most once
        # per window length — not worth caching
        half = max(1, self._np // 2)
        self._max_node = 1 << (half.bit_length() - 1)

    def _mode_name(self):
        if self._use_tree is None:
            return "pane"
        return "tree" if self._use_tree else "flat"

    def _get_tree(self):
        if self._tree is None:
            from dpark_tpu import panes as panes_mod
            self._tree = panes_mod.MergeTree(self._pane_by_idx,
                                             self._merge_node)
        return self._tree

    def _merge_node(self, kids, size, start):
        from dpark_tpu import trace
        out = kids[0].union(*kids[1:]) \
            .reduceByKey(self.func, self.numSplits).cache()
        self._tag(out, "tree-merge", pane=start)
        trace.event("stream.tree.merge", "stream", stream=self._sid,
                    start=start, size=size)
        return out

    def _on_pane_patched(self, pane_time):
        if self._tree is not None:
            # a late patch dirties exactly the O(log w) nodes covering
            # its pane; the next emit rebuilds only those
            self._tree.invalidate(self._idx(pane_time))

    def _decide_mode(self):
        from dpark_tpu import adapt, conf
        static = self._np >= max(2, conf.STREAM_PANE_TREE_MIN)
        self._use_tree = adapt.steer_pane_mode(
            self._adapt_site, self._np, static)
        if self._stats is not None:
            self._stats["mode"] = self._mode_name()

    def compute(self, t):
        from dpark_tpu import trace
        t = round(t, 6)
        self._ingest_pane(t)    # deltas fold via the patched panes
        if self._use_tree is None:
            self._decide_mode()
        hi = self._idx(t)
        lo = max(0, hi - self._np + 1)
        if self._use_tree:
            tree = self._get_tree()
            rdds = tree.cover(lo, hi, max_size=self._max_node)
            if self._stats is not None:
                self._stats["nodes"] = len(tree.nodes)
                self._stats["node_builds"] = tree.builds
        else:
            rdds = self._window_pane_rdds(t)
        if not rdds:
            return None
        trace.event("stream.window.emit", "stream", stream=self._sid,
                    branches=len(rdds))
        if len(rdds) == 1:
            return rdds[0]
        out = rdds[0].union(*rdds[1:]) \
            .reduceByKey(self.func, self.numSplits).cache()
        return self._tag(out, "window-emit")

    def forget_old(self, t, keep=None):
        super().forget_old(t, keep)
        if self._tree is not None and self._anchor is not None:
            horizon = self._window + self._slide * 2 + (
                self._wm.lateness if self._wm is not None else 0.0)
            self._tree.forget(self._idx(t - horizon))

    def _on_rebase(self):
        super()._on_rebase()
        self._tree = None

    def __getstate__(self):
        d = super().__getstate__()
        d["_tree"] = None               # rebuilt from panes on demand
        return d


class _InvApply:
    def __init__(self, invFunc):
        self.invFunc = invFunc

    def __call__(self, pair):
        cur, old = pair
        return self.invFunc(cur, old) if old is not None else cur


def _code_is_2arg(f, template):
    """f is a closure-free 2-arg function with the template's bytecode
    (the classify_merge idiom — exact identification, never probing)."""
    code = getattr(f, "__code__", None)
    if code is None or getattr(f, "__closure__", None):
        return False
    t = template.__code__
    return (code.co_code == t.co_code
            and code.co_consts == t.co_consts
            and code.co_names == t.co_names
            and code.co_argcount == 2)


def _is_plain_add(f):
    import operator
    return (f is operator.add
            or _code_is_2arg(f, lambda a, b: a + b)
            or _code_is_2arg(f, lambda a, b: b + a))


def _is_plain_sub(f):
    import operator
    return f is operator.sub or _code_is_2arg(f, lambda a, b: a - b)


def _neg_value(v):
    return -v


def _arraylike(x):
    """NUMERIC array-likes only: jax tracers during the merge-fn trace,
    numpy numeric scalars/arrays on ingested columns.  dtype.kind is
    checked so np.str_ (which carries dtype+shape) cannot slip a
    string concatenation past the numeric rewrite."""
    dt = getattr(x, "dtype", None)
    # sentinel default: a dtype WITHOUT .kind must default-deny ("" is
    # a substring of every string — review finding)
    return (dt is not None and hasattr(x, "shape")
            and getattr(dt, "kind", "?") in "biufc")


class _NumericRewriteError(TypeError):
    """Raised by _CheckedNumericOp when a rewritten union-reduce folds
    a non-numeric pair.  A DEDICATED type (with a distinctive name that
    survives traceback stringification across task retries) so
    run_batch never attributes an unrelated user TypeError to the
    rewrite and never disables healthy rewrites for it."""


class _CheckedNumericOp:
    """The binary op a numeric union-reduce rewrite folds with,
    re-verifying PER PAIR what the 5-record probe asserted: both
    operands are plain numbers.  A mixed batch (numeric head,
    non-numeric tail) raises TypeError instead of silently
    concatenating/diverging; StreamingContext.run_batch catches it,
    latches the stream's _numeric off, and regenerates the batch
    through the generic path.

    Carries the __dpark_monoid__ hint so the tpu master still
    classifies the merge: the device path only ever runs over ingested
    NUMERIC columns (non-numeric rows can't ingest and fall back to
    the host object path, where this check executes), so the hint is
    sound.

    The per-operand verdict caches per (class, dtype kind) in a table
    SHARED across streams (ISSUE 10 satellite): the isinstance probe
    runs once per value type seen in the process, and every later fold
    over that type is one dict hit — not one isinstance chain per pair
    per batch.  The dtype kind is part of the key because np.ndarray
    is one class over many dtypes (an int array must not pre-approve a
    string array); the verdict itself is op-independent (the op was
    vetted at rewrite admission), so the table is shared by add/min/
    max/mul checked ops alike."""

    __slots__ = ("op", "__dpark_monoid__")

    _HINTS = {"add": "add", "min": "min", "max": "max", "mul": "mul"}

    # (operand class, dtype kind or None) -> bool, process-global
    _TYPE_VERDICTS = {}

    def __init__(self, op, hint=None):
        self.op = op
        if hint in self._HINTS:
            self.__dpark_monoid__ = hint

    @classmethod
    def _operand_ok(cls, x):
        dt = getattr(x, "dtype", None)
        key = (x.__class__, getattr(dt, "kind", None))
        ok = cls._TYPE_VERDICTS.get(key)
        if ok is None:
            # array-likes (jax tracers during the merge-fn trace,
            # numpy scalars/arrays on ingested columns) are numeric by
            # construction — the check targets arbitrary Python
            # objects on the host object path (str concatenation was
            # the r5 finding)
            ok = isinstance(x, numbers.Number) or _arraylike(x)
            cls._TYPE_VERDICTS[key] = ok
        return ok

    def __call__(self, a, b):
        if self._operand_ok(a) and self._operand_ok(b):
            return self.op(a, b)
        raise _NumericRewriteError(
            "numeric union-reduce rewrite saw a non-numeric pair "
            "(%s, %s): the probe-based rewrite does not apply to "
            "this stream" % (type(a).__name__, type(b).__name__))


# probe-verdict cache (ISSUE 10 satellite): (op kind, value type) ->
# bool, so sibling streams folding the same op over the same record
# type skip re-deriving the numeric verdict from their own probe rows
_PROBE_VERDICTS = {}


def _numeric_verdict(op_kind, values):
    """Are these probed values plain numbers (the union-reduce rewrite
    admission)?  Cached per (op kind, value type) when the sample is
    type-homogeneous; a mixed sample never caches (its verdict is not
    a property of one type)."""
    vt = values[0].__class__
    if all(v.__class__ is vt for v in values):
        key = (op_kind, vt)
        v = _PROBE_VERDICTS.get(key)
        if v is None:
            v = all(isinstance(x, numbers.Number) for x in values)
            _PROBE_VERDICTS[key] = v
        return v
    return all(isinstance(x, numbers.Number) for x in values)


def _probe_values(rdd, k=5):
    """Up to k records from the first non-empty partition.  Every scan
    is a parts==1 job — the array path skips single-task jobs by
    design, so the rewrite probes never pollute steady-state
    stage-kind accounting (take(k)'s expanding multi-partition scans
    did, r5 test fallout).  Scans EVERY partition like take(k) would
    (review finding: stopping early would leave _numeric undecided
    forever on streams whose leading partitions are empty); empty
    partitions cost one trivial job each, and a non-empty stream
    resolves the probe once."""
    from itertools import islice

    def head(it):
        return list(islice(it, k))
    for p in range(len(rdd.splits)):
        rows = list(rdd.ctx.runJob(rdd, head, partitions=[p]))[0]
        if rows:
            return rows
    return []


def _classify_state_update(f):
    """EXACT identification of the running-sum updateFunc — the
    streaming counter idiom ``(prev or 0) + sum(vs)`` and its spelling
    variants — as a binary monoid op for the union-reduce rewrite
    (VERDICT r4 #5: monoid state rides the mesh per batch).  Such an
    updateFunc never evicts (returns None) and treats absent prev as
    the identity, so ``prev UNION reduce(batch) -> reduceByKey(op)``
    is observationally identical.  A user function equivalent to a
    monoid fold but written differently opts in via
    ``f.__dpark_state_monoid__ = "add"|"min"|"max"|"mul"`` (contract:
    state' = op(op-reduce(new_values), prev-if-present), no eviction).
    Everything else returns None and keeps the cogroup path."""
    import operator
    hint = getattr(f, "__dpark_state_monoid__", None)
    if hint in ("add", "min", "max", "mul"):
        return {"add": operator.add, "min": min, "max": max,
                "mul": operator.mul}[hint]
    for tmpl in (lambda vs, prev: (prev or 0) + sum(vs),
                 lambda vs, prev: sum(vs) + (prev or 0),
                 lambda vs, prev: (prev if prev is not None else 0)
                 + sum(vs)):
        from dpark_tpu.utils import builtin_globals_ok
        if _code_is_2arg(f, tmpl) and builtin_globals_ok(f):
            return operator.add
    return None


class _TagState:
    """Record-level tag map for the seg-state rewrite: value -> (value
    cast to the state dtype, flag).  `+ zero` is the cast that means
    the same thing on the host (numpy promotion) and under the tracer
    (jax promotion) — flag 1 marks the carried state row.  ONE instance
    per (stream, role) so the tpu program cache stays warm across
    ticks."""

    def __init__(self, zero, flag):
        self.zero = zero
        self.flag = flag

    def __call__(self, v):
        return (v + self.zero, self.flag)


class _SegStateApply:
    """Per-group consumer of the general-updateStateByKey rewrite:
    the group's items are (value, flag) pairs — flag 1 is the carried
    state (at most one per key), flag 0 the batch's new values.  On the
    host paths this callable executes directly over the list; on the
    tpu master fuse.py recognizes `__dpark_seg_state__` and runs the
    user's update as a state-mode SegMapOp (vmapped over padded value
    segments, prev/no-prev dual trace).  Admitted updates return a
    numeric scalar in BOTH traces, so they never evict (return None) —
    the rewrite therefore skips the cogroup path's None filter."""

    def __init__(self, update):
        self.update = update
        self.__dpark_seg_state__ = update

    def __call__(self, items):
        prev = None
        vs = []
        for v, fl in items:
            if fl:
                prev = v
            else:
                vs.append(v)
        return self.update(vs, prev)


class StateDStream(DerivedDStream):
    def __init__(self, parent, updateFunc, numSplits=None):
        super().__init__(parent)
        self.updateFunc = updateFunc
        self.numSplits = numSplits
        self.must_checkpoint = True
        self._monoid_op = _classify_state_update(updateFunc)
        self._numeric = None            # undecided until data shows up
        # general TRACEABLE updateFunc (beyond the provable monoid
        # fold): rewrite to flag-union + groupByKey + _SegStateApply so
        # the tpu master's state-mode SegMapOp keeps the whole per-tick
        # update on device (state as HBM-resident columns, padded value
        # segments, vmapped update(prev, values)).  None = undecided
        # (needs a data probe), False = declined, else (zero_new,
        # zero_old, applyer) — built once, stable identities
        self._seg_state = None
        # one instance for the stream's lifetime — stable identity
        # keeps the tpu backend's compiled-program cache warm across
        # batches (review finding)
        self._checked_op = None
        if self._monoid_op is not None:
            # hint name from the SHARED classifier (utils/monoid) — no
            # fourth copy of the op->name table (review finding)
            from dpark_tpu.utils.monoid import classify_merge
            self._checked_op = _CheckedNumericOp(
                self._monoid_op,
                getattr(updateFunc, "__dpark_state_monoid__", None)
                or classify_merge(self._monoid_op))

    def compute(self, t):
        prev = self.generated.get(round(t - self.slide_duration, 6))
        if prev is None:
            # a failed/dropped batch leaves a hole in `generated`; carry
            # the most recent state forward instead of silently
            # resetting to empty (the hole batch's data is lost either
            # way, the accumulated state must not be)
            earlier = [ts for ts, rdd in self.generated.items()
                       if ts < t - 1e-9 and rdd is not None]
            if earlier:
                prev = self.generated[max(earlier)]
        batch = self.parent.getOrCompute(t)
        ctx = self.ssc.ctx
        if self._monoid_op is not None and self._numeric is None \
                and batch is not None:
            # one-time value probe (same idiom as the window rewrite,
            # ADVICE r4: several records, all must be numbers): the
            # union-reduce rewrite folds values PAIRWISE where the
            # updateFunc summed a list from 0 — identical for numbers,
            # different for e.g. strings (sum() raises, a + b doesn't).
            # The verdict caches per (op, value type) process-wide
            # (ISSUE 10 satellite)
            probe = _probe_values(batch)
            if probe:
                self._numeric = _numeric_verdict(
                    getattr(self._checked_op, "__dpark_monoid__", "add"),
                    [rec[1] for rec in probe])
        if self._monoid_op is not None and self._numeric:
            # monoid state: state' = prev U reduce(batch), one flat
            # union-reduce per batch — every stage rides the array path
            # in steady state (HBM-resident prev shuffle + new batch),
            # exactly like the (add, sub) window rewrite above.  The
            # checked op re-verifies numeric-ness PER PAIR: a batch
            # that defeats the probe (numeric head, string tail) raises
            # TypeError and run_batch falls back to the generic path
            if batch is None and prev is not None:
                return prev              # state unchanged this tick
            if batch is not None:
                op = self._checked_op
                reduced = batch.reduceByKey(op, self.numSplits)
                if prev is None:
                    return reduced.cache()
                return prev.union(reduced) \
                    .reduceByKey(op, self.numSplits).cache()
        if self._monoid_op is None \
                and self._seg_state is None and batch is not None:
            self._seg_state = self._classify_seg_state(batch)
        if self._monoid_op is None and self._seg_state:
            tag_new, tag_old, applyer = self._seg_state
            if batch is None and prev is not None:
                b = ctx.parallelize([], 1).mapValue(tag_new)
            elif batch is None:
                return None
            else:
                b = batch.mapValue(tag_new)
            u = b if prev is None else b.union(prev.mapValue(tag_old))
            return u.groupByKey(self.numSplits) \
                    .mapValues(applyer).cache()
        if batch is None:
            batch = ctx.parallelize([], 1)
        if prev is None:
            prev = ctx.parallelize([], 1)
        grouped = batch.cogroup(prev, numSplits=self.numSplits)
        updated = grouped.mapValue(_StateUpdate(self.updateFunc)) \
                         .filter(_state_not_none)
        return updated.mapValue(_unwrap_state).cache()

    def _classify_seg_state(self, batch):
        """(tag_new, tag_old, applyer) when the updateFunc is a
        traceable, padding-invariant update(values, prev) over numeric
        scalar values — the admission the state-mode SegMapOp needs —
        else False (cogroup path).  The state DTYPE is discovered by a
        fixed-point trace (int values whose update decays to float
        carry float state; both tag maps cast to it so host and device
        agree on every column)."""
        import numbers
        f = self.updateFunc
        code = getattr(f, "__code__", None)
        if code is not None and code.co_argcount != 2:
            return False
        probe = _probe_values(batch)
        if not probe:
            return None                  # stay undecided: no data yet
        vals = [rec[1] for rec in probe
                if isinstance(rec, tuple) and len(rec) == 2]
        if len(vals) != len(probe) or not all(
                isinstance(v, numbers.Number)
                and not isinstance(v, bool) for v in vals):
            return False
        try:
            import numpy as np
            import jax
            from dpark_tpu.backend.tpu import fuse
        except Exception:
            return False
        # device value dtype per layout.record_spec conventions
        vdt = np.result_type(*[np.asarray(v).dtype for v in vals])
        vdt = np.dtype(np.int64) if vdt.kind in "iu" else \
            np.dtype(np.float32)
        ds = vdt
        try:
            for _ in range(3):
                fn_p, _fn_n = fuse._seg_state_row_fns(f)
                outs = jax.eval_shape(
                    fn_p, jax.ShapeDtypeStruct((4,), ds),
                    jax.ShapeDtypeStruct((), ds))
                if len(outs) != 1 or outs[0].shape != ():
                    return False
                nxt = np.result_type(ds, outs[0].dtype)
                if nxt == ds:
                    break
                ds = np.dtype(nxt)
            else:
                return False             # state dtype does not settle
        except Exception:
            return False
        pad, reason, _ = fuse.classify_seg_map(f, ds, state=True)
        if pad is None:
            logger.debug("updateStateByKey stays on the cogroup path: "
                         "%s", reason)
            return False
        zero = ds.type(0)
        return (_TagState(zero, 0), _TagState(zero, 1),
                _SegStateApply(f))


class _StateUpdate:
    def __init__(self, updateFunc):
        self.updateFunc = updateFunc

    def __call__(self, groups):
        new_values, old_states = groups
        prev = old_states[0] if old_states else None
        return (self.updateFunc(new_values, prev),)


def _state_not_none(kv):
    return kv[1][0] is not None


def _unwrap_state(wrapped):
    return wrapped[0]


class ForEachDStream(DerivedDStream):
    def __init__(self, parent, func):
        super().__init__(parent)
        self.func = func
        import inspect
        try:
            self._two_args = len(inspect.signature(func).parameters) >= 2
        except (TypeError, ValueError):
            self._two_args = False

    def compute(self, t):
        return self.parent.getOrCompute(t)

    def generate_job(self, t):
        rdd = self.getOrCompute(t)
        if rdd is None:
            return
        if self._two_args:
            self.func(rdd, t)
        else:
            self.func(rdd)


# --------------------------------------------------------------------------
# input streams
# --------------------------------------------------------------------------

class InputDStream(DStream):
    def __init__(self, ssc):
        super().__init__(ssc)
        ssc.input_streams.append(self)

    def start(self):
        pass

    def stop(self):
        pass


class ConstantInputDStream(InputDStream):
    def __init__(self, ssc, rdd):
        super().__init__(ssc)
        self.rdd = rdd

    def compute(self, t):
        return self.rdd


class QueueInputDStream(InputDStream):
    def __init__(self, ssc, queue, oneAtATime=True, defaultRDD=None):
        super().__init__(ssc)
        self.queue = queue
        self.oneAtATime = oneAtATime
        self.defaultRDD = defaultRDD

    def put(self, item):
        self.queue.append(item)

    def _to_rdd(self, item):
        from dpark_tpu.rdd import RDD
        if isinstance(item, RDD):
            return item
        # default parallelism (== the device mesh on the tpu master):
        # a hardcoded slice count forfeited the array path for every
        # queue batch
        return self.ssc.ctx.parallelize(item)

    def compute(self, t):
        if self.queue:
            if self.oneAtATime:
                return self._to_rdd(self.queue.pop(0))
            items = list(self.queue)
            del self.queue[:len(items)]
            rdds = [self._to_rdd(i) for i in items]
            return rdds[0] if len(rdds) == 1 else self.ssc.ctx.union(rdds)
        return self.defaultRDD


class _ArrivalStamp:
    """record -> (arrival_ts, record); one picklable instance per scan
    so every record a scan picked up carries the same timestamp."""

    def __init__(self, ts):
        self.ts = ts

    def __call__(self, rec):
        return (self.ts, rec)


class FileInputDStream(InputDStream):
    """Scan a directory each batch; per-file byte offsets are tracked so a
    batch picks up both new files AND data appended to known files
    (tail -f semantics; reference FileInputDStream scans by mtime).

    CLOCK CONTRACT (ISSUE 10 satellite): with ``stamp_arrival=True``
    every record is emitted as ``(arrival_ts, line)``.  The arrival
    time is the DRIVER's wall clock at the directory scan that first
    observed the bytes — one timestamp per scan, so all lines a batch
    picked up share it, and the stamp is monotonically non-decreasing
    across batches of one stream.  That makes it a consistent
    event-time source for the watermark plane (e.g.
    ``eventTime=lambda kv: kv[1][0]`` after keying) when records carry
    no domain timestamp; file mtimes are deliberately NOT used (they
    follow the writer's clock, which may jump)."""

    def __init__(self, ssc, directory, filter_fn=None, newFilesOnly=True,
                 stamp_arrival=False):
        super().__init__(ssc)
        self.directory = directory
        self.filter_fn = filter_fn or (lambda n: not n.startswith("."))
        self.offsets = {}               # path -> bytes already consumed
        self.new_files_only = newFilesOnly
        self.stamp_arrival = stamp_arrival

    def start(self):
        if self.new_files_only:
            for name in os.listdir(self.directory):
                p = os.path.join(self.directory, name)
                if os.path.isfile(p):
                    self.offsets[p] = os.path.getsize(p)

    def compute(self, t):
        rdds = []
        scan_ts = _time.time()
        for name in sorted(os.listdir(self.directory)):
            if not self.filter_fn(name):
                continue
            p = os.path.join(self.directory, name)
            if not os.path.isfile(p):
                continue
            size = os.path.getsize(p)
            off = self.offsets.get(p, 0)
            if size > off:
                rdds.append(self.ssc.ctx.partialTextFile(p, off, size))
                self.offsets[p] = size
        if not rdds:
            return None
        out = rdds[0] if len(rdds) == 1 else self.ssc.ctx.union(rdds)
        if self.stamp_arrival:
            out = out.map(_ArrivalStamp(scan_ts))
        return out


class SocketInputDStream(InputDStream):
    """TCP line reader: a background thread accumulates lines; each batch
    drains the buffer (reference: socketTextStream).

    CLOCK CONTRACT (ISSUE 10 satellite): with ``stamp_arrival=True``
    every record is emitted as ``(arrival_ts, line)``.  The arrival
    time is the RECEIVER thread's wall clock at the moment the line
    was parsed off the socket — assigned BEFORE batching, so two lines
    that arrive around a batch boundary keep their true arrival order
    in their stamps even when the boundary splits them into different
    batches; stamps are monotonically non-decreasing per stream.  Use
    it as the watermark plane's event-time source when the wire
    carries no domain timestamp."""

    def __init__(self, ssc, hostname, port, stamp_arrival=False):
        super().__init__(ssc)
        self.hostname = hostname
        self.port = port
        self.stamp_arrival = stamp_arrival
        self.buffer = []
        self.lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None

    def start(self):
        self._stop.clear()
        self._thread = threading.Thread(target=self._read, daemon=True)
        self._thread.start()

    def _read(self):
        while not self._stop.is_set():
            try:
                sock = _socket.create_connection(
                    (self.hostname, self.port), timeout=2)
                f = sock.makefile("rb")
                for line in f:
                    if self._stop.is_set():
                        break
                    rec = line.rstrip(b"\r\n").decode("utf-8", "replace")
                    if self.stamp_arrival:
                        rec = (_time.time(), rec)
                    with self.lock:
                        self.buffer.append(rec)
                sock.close()
            except OSError:
                if self._stop.wait(0.5):
                    return

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(3)
            self._thread = None

    def __getstate__(self):
        d = dict(self.__dict__)
        for k in ("lock", "_stop", "_thread"):
            d[k] = None
        d["buffer"] = []
        d["generated"] = {}
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        self.lock = threading.Lock()
        self._stop = threading.Event()

    def compute(self, t):
        with self.lock:
            lines, self.buffer = self.buffer, []
        if not lines:
            return None
        return self.ssc.ctx.parallelize(lines, 2)
