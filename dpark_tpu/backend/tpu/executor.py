"""JAXExecutor: compiles and runs fused stage programs over the device mesh.

This replaces the reference's executor + shuffle services for the tpu
master (dpark/executor.py, dpark/shuffle.py): partitions live in HBM as
sharded arrays, a stage is one jitted shard_map program, and the map->reduce
hop is a count-exchange + multi-round lax.all_to_all over ICI (SURVEY.md
sections 2.8 and 7.1 step 5).

Shuffle data written by the array path stays device-resident in
`shuffle_store`; a host bridge exports buckets as (k, combiner) items so
downstream host-path stages (untraceable user code) can consume them
through the ordinary ShuffleFetcher protocol.
"""

import os
import threading
import time
import types
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from dpark_tpu import aotcache, conf, faults, locks, trace
from dpark_tpu.backend.tpu import collectives, fuse, layout
from dpark_tpu.utils.log import get_logger

logger = get_logger("tpu.executor")


def _plan_sig(plan):
    """Short stable program signature for health-plane site keys
    (ISSUE 14): the adapt store's cross-process program id, memoized
    on the plan by fuse.plan_adapt_signature."""
    try:
        return fuse.plan_adapt_signature(plan)[0]
    except Exception:
        return "?"


def _combine_arg(merge_fn, monoid):
    """The `combine=` of a `compile` event: what the program's segment
    merge (collectives._segment_merge) scans with — the monoid's own
    operation, the traced user function, or nothing to combine."""
    if monoid is not None:
        return "seg_scan"
    return "none" if merge_fn is None else "user_scan"


def _sort_arg(plan, sorts):
    """The `sort=` of a `compile` event: how the program's orderings
    (collectives._lex_sort: shuffle write, reduce, filter, sort) move
    their rows — every operand `carried` through the one sort,
    `carried+gathered` where a leaf of rank > 1 (which XLA's sort
    cannot carry) is gathered behind it, `keys+rows` where the record
    is wider than the sort carries (the keys and an iota ride it, the
    row follows as whole rows), `none` without an ordering.  Read off
    the plan's record specs, as the sort sees them at trace time (the
    wider of the records in and out, and one word for `dst`)."""
    if not sorts:
        return "none"
    specs = tuple(plan.in_specs), tuple(plan.out_specs)
    if 1 + max(sum(max(1, np.dtype(dt).itemsize // 4)
                   for dt, shape in side if not shape)
               for side in specs) > collectives._CARRIED_WORDS:
        return "keys+rows"
    wide = any(shape for side in specs for _, shape in side)
    return "carried+gathered" if wide else "carried"


def _order_args(plan):
    """The `dst=`, `key=` and `order=` of a `compile` event, for a
    program that partitions by range or sorts by key (else nothing):
    where a row goes (`hash` | `range`), what the key is (`int`, a
    float among them | `tuple` | `bytes`) and how its columns compare
    (`signed`, or `unsigned` for the words of a byte string: memcmp)."""
    ranged = plan.epilogue is not None and plan.epi_spec is not None \
        and plan.epi_spec[0] == "range"
    sorts = [op for op in plan.ops if isinstance(op, fuse.SortOp)]
    if not (ranged or sorts):
        return {}
    if ranged:
        nk, unsigned = plan.epi_nk, fuse.epi_unsigned(plan.epi_spec)
    else:
        nk, unsigned = sorts[0].nk, sorts[0].unsigned
    args = {"key": "bytes" if unsigned else "tuple" if nk > 1 else "int",
            "order": "unsigned" if unsigned else "signed"}
    if plan.epilogue is not None:
        args["dst"] = "range" if ranged else "hash"
    return args


AXIS = conf.MESH_AXIS


def _even_ranges(n, parts):
    """parts contiguous [lo, hi) ranges covering n rows as evenly as
    possible."""
    base, extra = divmod(n, parts)
    out = []
    lo = 0
    for d in range(parts):
        hi = lo + base + (1 if d < extra else 0)
        out.append((lo, hi))
        lo = hi
    return out


def _reslice_parts(slices, ndev):
    """Re-split host partitions to the mesh width (shuffle-map stages
    only: the write redistributes by key, so partition boundaries carry
    no semantics there).  Columnar slices re-slice without building
    Python rows."""
    from dpark_tpu.rdd import _ColumnarSlice
    if slices and all(isinstance(s, _ColumnarSlice) for s in slices):
        ncols = len(slices[0].columns)
        cols = [np.concatenate([np.asarray(s.columns[i])
                                for s in slices])
                for i in range(ncols)]
        return [_ColumnarSlice([c[lo:hi] for c in cols])
                for lo, hi in _even_ranges(len(cols[0]), ndev)]
    rows = [r for s in slices for r in s]
    return [rows[lo:hi] for lo, hi in _even_ranges(len(rows), ndev)]


def _prefetch_iter(it, depth=1, name="dpark-wave-prefetch"):
    """Run `it` in a background thread, `depth` items ahead: the host
    tokenizes/slices (or, for the ingest stage, device_puts) wave k+1
    while the device computes wave k.  If the consumer abandons the
    generator (exception mid-stream, GeneratorExit), the producer is
    told to stop — it must not sit blocked on a full queue holding a
    wave of columns — and the SOURCE iterator is closed from the
    producer thread, so a chain of pipeline stages (tokenize ->
    ingest) unwinds stage by stage instead of leaking the upstream
    thread blocked on its own full queue."""
    import queue
    import threading
    q = queue.Queue(maxsize=depth)
    done = object()
    stop = threading.Event()

    def _put(x):
        while not stop.is_set():
            try:
                q.put(x, timeout=0.5)
                return True
            except queue.Full:
                continue
        return False

    def run():
        try:
            for x in it:
                if not _put(x):
                    return
            _put(done)
        except BaseException as e:          # re-raised in the consumer
            _put(e)
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except BaseException:
                    pass

    threading.Thread(target=run, daemon=True, name=name).start()
    try:
        while True:
            x = q.get()
            if x is done:
                return
            if isinstance(x, BaseException):
                raise x
            yield x
    finally:
        stop.set()


def _async_d2h(arrays):
    """Start device->host copies without blocking (the wave pipeline
    reads them one wave later, by which point the transfer has ridden
    along behind the next wave's compute).  A process-spanning array
    has no direct host copy (host_read replicates it later anyway)."""
    for a in arrays:
        if a.is_fully_addressable:
            a.copy_to_host_async()


class _StreamStats:
    """Per-stream pipeline accounting: ingest/compute/exchange/spill
    seconds plus a host-observed device-idle fraction.

    The idle fraction is computed from "device active" intervals, one
    per wave: [first program dispatch, the blocking host read of that
    wave's outputs returning].  With the pipeline on, a wave's interval
    stretches over its neighbors' host work (ingest of k+1, spill of
    k-1 happen while wave k computes), so the union covers more of the
    wall clock and the idle fraction drops — the observable the
    overlap is graded on.  It is an approximation from the host side
    (dispatch is async; the device may finish inside an interval), but
    it moves monotonically with real overlap."""

    PER_WAVE_CAP = 128

    def __init__(self, depth, donated):
        import time
        self._clock = time.perf_counter
        self.t0 = self._clock()
        self.wall_t0 = time.time()   # epoch twin of t0 (trace spans)
        self.depth = depth
        self.donated = donated
        self.waves = 0
        self.ingest_s = 0.0
        self.compute_s = 0.0
        self.exchange_s = 0.0
        self.spill_s = 0.0
        self._busy = []              # (start, end) device-active spans
        self.per_wave = []           # bounded per-wave ms dicts

    def now(self):
        return self._clock()

    def add_busy(self, start, end):
        if end > start:
            self._busy.append((start, end))

    def wave_done(self, ingest_s, compute_s, exchange_s, spill_s=0.0):
        self.waves += 1
        self.ingest_s += ingest_s
        self.compute_s += compute_s
        self.exchange_s += exchange_s
        self.spill_s += spill_s
        if len(self.per_wave) < self.PER_WAVE_CAP:
            self.per_wave.append({
                "ingest_ms": round(ingest_s * 1e3, 2),
                "compute_ms": round(compute_s * 1e3, 2),
                "exchange_ms": round(exchange_s * 1e3, 2),
                "spill_ms": round(spill_s * 1e3, 2)})

    def add_spill(self, seconds, wave=None):
        self.spill_s += seconds
        if wave is not None and wave < len(self.per_wave):
            self.per_wave[wave]["spill_ms"] = round(
                self.per_wave[wave]["spill_ms"] + seconds * 1e3, 2)

    def _busy_union(self, until):
        total = 0.0
        end_prev = None
        for s, e in sorted(self._busy):
            e = min(e, until)
            if end_prev is None or s > end_prev:
                total += max(0.0, e - s)
                end_prev = e
            elif e > end_prev:
                total += e - end_prev
                end_prev = e
        return total

    def snapshot(self):
        now = self._clock()
        wall = max(now - self.t0, 1e-9)
        idle = max(0.0, wall - self._busy_union(now))
        return {
            "waves": self.waves,
            "ingest_ms": round(self.ingest_s * 1e3, 1),
            "compute_ms": round(self.compute_s * 1e3, 1),
            "exchange_ms": round(self.exchange_s * 1e3, 1),
            "spill_ms": round(self.spill_s * 1e3, 1),
            "wall_ms": round(wall * 1e3, 1),
            "device_idle_frac": round(idle / wall, 4),
            "pipeline_depth": self.depth,
            "donated": self.donated,
            "per_wave": list(self.per_wave),
        }


class _SpillWriter:
    """Background run writer for the spilled-run stream: compress +
    write happen on a dedicated thread with a bounded queue, taking
    disk I/O off the wave loop.  Worker errors surface on the next
    put() or at finish(); abort() (the cancellation path) drops queued
    work and joins without writing it."""

    def __init__(self, write_fn, depth=4):
        import queue
        import threading
        self._write = write_fn
        self._q = queue.Queue(maxsize=depth)
        self._err = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="dpark-spill-writer")
        self._thread.start()

    def _run(self):
        import queue
        while True:
            try:
                item = self._q.get(timeout=0.5)
            except queue.Empty:
                if self._stop.is_set():
                    return          # aborted and drained
                continue
            try:
                if item is None:
                    return
                if self._stop.is_set():
                    continue        # aborted: drain without writing
                try:
                    self._write(*item)
                except BaseException as e:
                    # never leave a partial chunk file behind: a later
                    # reader would mistake it for a (short) valid run
                    try:
                        os.unlink(item[0])
                    except OSError:
                        pass
                    self._err = e
                    self._stop.set()
            finally:
                self._q.task_done()

    def _raise_pending(self):
        if self._err is not None:
            err, self._err = self._err, None
            raise err

    def put(self, path, cols):
        self._raise_pending()
        self._q.put((path, cols))

    def finish(self):
        """Wait for every queued run to hit disk; re-raise any writer
        error.  Must be called before the shuffle registers."""
        self._q.join()
        self._q.put(None)
        self._thread.join()
        self._raise_pending()

    def abort(self):
        """Cancellation: drop queued runs, stop the thread."""
        self._stop.set()
        try:
            self._q.put_nowait(None)
        except Exception:
            pass
        self._thread.join(timeout=10)


class _RunPremerger:
    """Export bridge for spilled runs: pre-merges a partition's
    key-sorted runs into ONE run file in the background as soon as the
    stream ends, instead of eagerly at the first reduce-task fetch.
    ensure(rid) is shared by the background walker and export_bucket
    (which may race from several fetcher threads): per-rid once,
    behind per-rid locks.  Runs are written key-sorted per wave, so a
    single-run partition is already merged and the export can skip its
    argsort."""

    def __init__(self, runs, read_run, write_run, spool, key_cols=1):
        import threading
        self._runs = runs            # the SAME list object the store holds
        self._read = read_run
        self._write = write_run
        self._spool = spool
        self._key_cols = max(1, key_cols)   # composite keys: sort ALL
        self._locks = [threading.Lock() for _ in runs]
        self._merged = [len(p) <= 1 for p in runs]
        self._stop = threading.Event()
        self._thread = None

    def start_background(self):
        import threading
        self._thread = threading.Thread(
            target=self._walk, daemon=True, name="dpark-run-premerge")
        self._thread.start()

    def _walk(self):
        for rid in range(len(self._runs)):
            if self._stop.is_set():
                return
            try:
                self.ensure(rid)
            except Exception as e:
                logger.debug("premerge of partition %d failed "
                             "(export will merge inline): %s", rid, e)

    def ensure(self, rid):
        """Merge partition `rid`'s runs if not yet merged.  Returns
        (paths, presorted): presorted means the (single) run is
        key-sorted and the export can skip its argsort."""
        import os
        with self._locks[rid]:
            if self._merged[rid]:
                return self._runs[rid], True
            paths = self._runs[rid]
            parts = [self._read(p) for p in paths]
            cols = [np.concatenate([pt[li] for pt in parts])
                    for li in range(len(parts[0]))]
            # lexicographic over every key column (np.lexsort sorts by
            # the LAST key first); equal-key group order must survive
            # the merge or the export's adjacent-group fold would emit
            # split groups for tuple keys
            nk = min(self._key_cols, len(cols))
            order = (np.argsort(cols[0], kind="stable") if nk == 1
                     else np.lexsort(tuple(cols[:nk][::-1])))
            merged = os.path.join(self._spool, "merged-%d" % rid)
            self._write(merged, [c[order] for c in cols])
            self._runs[rid] = [merged]
            self._merged[rid] = True
            for p in paths:
                try:
                    os.unlink(p)
                except OSError:
                    pass
            return self._runs[rid], True

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)


def _export_read(arr):
    """One blocking read of the host bridge, which serves bucket
    exports to host-path stages and the spill of dead stores (there
    the `readback` lies inside an `hbm.spill` span)."""
    return layout.host_read(arr, site="bridge.export")


class _StoreInFlight(Exception):
    """An HBM shuffle store whose producing stage has not registered
    its outputs yet — the eviction scan must skip it, not drop it."""


class _ProgramCache:
    """Bounded LRU over compiled stage programs (ISSUE 9 satellite).

    The executor compiles one jitted program per (kind, program_key,
    size class, ...) — fine for a one-job process, unbounded for a
    RESIDENT service compiling across every job it ever serves.
    conf.PROGRAM_CACHE_MAX bounds the entry count (0 = unbounded, the
    pre-service behavior); hit/miss/evict counters ride /metrics
    (dpark_program_cache_*_total), the web UI's per-job cache column,
    and the bench `service` section — the warm-submit A/B asserts a
    re-submitted DAG compiles NOTHING from these counters.

    Thread-safe: the service's slot threads compile concurrently
    (device dispatch serializes on the mesh lock, but host-side
    tracing does not)."""

    def __init__(self, cap=None):
        self._d = OrderedDict()
        self.cap = conf.PROGRAM_CACHE_MAX if cap is None else cap
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._lock = locks.named_lock("executor.program_cache")
        # exact per-job attribution (ISSUE 15 satellite): each probe
        # also counts against the job the probing THREAD is executing
        # for (`_job_of`, installed by the executor to read its
        # per-thread job stamp).  The process-wide delta the per-job
        # record used to ship overlapped under concurrency (the
        # documented PR 9 caveat); these buckets do not.  Bounded:
        # oldest job bucket evicts past the cap.
        self._job_of = None
        self._job_counts = OrderedDict()     # job -> [hits, misses]

    def _count_job(self, hit):
        # called under self._lock
        job_of = self._job_of
        if job_of is None:
            return
        try:
            job = job_of()
        except Exception:
            return
        if job is None:
            return
        ent = self._job_counts.get(job)
        if ent is None:
            ent = self._job_counts[job] = [0, 0]
            while len(self._job_counts) > 128:
                self._job_counts.popitem(last=False)
        else:
            # recency-refresh: a long-running job that keeps probing
            # must not lose its bucket to 128 short jobs minted after
            # it (eviction is least-recently-PROBED, not insertion
            # order — the exactness guarantee holds for any job still
            # doing work)
            self._job_counts.move_to_end(job)
        ent[0 if hit else 1] += 1

    def job_stats(self, job):
        """Exact {hits, misses} attributed to one job's threads (0/0
        for a job that never probed)."""
        with self._lock:
            ent = self._job_counts.get(job) or (0, 0)
            return {"hits": ent[0], "misses": ent[1]}

    # Speaks the plain-dict idiom every compile site already uses —
    # `if key in cache: return cache[key]` / `cache[key] = jitted` —
    # so bounding the cache changed no call site.  The membership
    # probe is where hit/miss counts: each compile site probes exactly
    # once per call, and a probe that misses is always followed by a
    # compile.

    def __contains__(self, key):
        with self._lock:
            if key in self._d:
                # LRU-touch at probe time: the caller's next statement
                # is `cache[key]`, and a concurrent insert at capacity
                # must never evict the key between the two (the probe
                # makes it MRU)
                self._d.move_to_end(key)
                self.hits += 1
                self._count_job(True)
                return True
            self.misses += 1
            self._count_job(False)
            return False

    def __getitem__(self, key):
        with self._lock:
            return self._d[key]     # probe already counted + touched

    def __setitem__(self, key, fn):
        # the AOT plane seam (ISSUE 17): with a plane installed every
        # inserted program wraps in the lazy two-tier proxy whose
        # first call consults disk before compiling; off costs this
        # one `is None` check (plane-contract rule)
        plane = aotcache._PLANE
        if plane is not None:
            fn = plane.wrap(key, fn)
        evicted = []
        with self._lock:
            self._d[key] = fn
            self._d.move_to_end(key)
            if self.cap:
                while len(self._d) > max(1, self.cap):
                    evicted.append(self._d.popitem(last=False)[1])
                    self.evictions += 1
        # write-back OUTSIDE the cache lock: serializing an evicted
        # executable is disk work no concurrent probe should wait on
        for old in evicted:
            wb = getattr(old, "writeback", None)
            if wb is not None:
                wb()

    def __len__(self):
        return len(self._d)

    def stats(self):
        with self._lock:
            return {"entries": len(self._d), "cap": self.cap,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}


class _MeshLock:
    """The mesh lock, metered (ISSUE 15 tentpole): a reentrant lock
    whose every DEPTH-0 acquisition measures its wait (how long the
    caller queued behind other tenants' device work — the invisible
    cost of the resident service) and its hold (mesh busy time, the
    denominator of the ledger's conservation check).

    Counters are always on — two clock reads per outer acquisition —
    and mutated only while the lock is HELD, so they need no lock of
    their own.  With a trace plane installed, each depth-0 release
    additionally emits a ``mesh.lock`` span: ts = the acquisition
    request, dur = the WAIT, args.hold_s = the hold — the ledger sink
    folds the wait into the owning job's ``lock_wait_ms`` account and
    the hold into the offline mesh-busy view."""

    __slots__ = ("_lock", "_tls", "wait_s", "busy_s", "acquisitions",
                 "contended", "t_created")

    def __init__(self):
        self._lock = threading.RLock()
        self._tls = threading.local()
        self.wait_s = 0.0
        self.busy_s = 0.0
        self.acquisitions = 0
        self.contended = 0
        self.t_created = time.time()

    def __enter__(self):
        self._enter(True)
        return self

    def try_enter(self):
        """Enter only if that takes no waiting (the lock is free, or
        this thread's already); False otherwise.  Leave by __exit__."""
        return self._enter(False)

    def _enter(self, blocking):
        tls = self._tls
        depth = getattr(tls, "depth", 0)
        if depth:
            # reentrant re-acquire by the holder: no wait, no second
            # busy interval
            self._lock.acquire()
            tls.depth = depth + 1
            return True
        # lockcheck plane: one global load + `is None` check when off;
        # noted BEFORE a blocking acquire so a strict-mode cycle raises
        # as a stack trace instead of wedging here (a try cannot
        # wedge: noted once held, the edges are the same)
        if blocking:
            locks.note_acquire("executor.mesh")
        t0 = time.time()
        wait = 0.0
        if not self._lock.acquire(False):
            if not blocking:
                return False
            self._lock.acquire()
            wait = time.time() - t0
        if not blocking:
            locks.note_acquire("executor.mesh")
        tls.depth = 1
        tls.t_request = t0
        tls.t_acquired = time.time()
        tls.wait = wait
        return True

    def __exit__(self, *exc):
        tls = self._tls
        tls.depth -= 1
        if tls.depth:
            self._lock.release()
            return False
        hold = time.time() - tls.t_acquired
        wait = tls.wait
        t_req = tls.t_request
        # mutated while still holding: race-free by construction
        self.busy_s += hold
        self.acquisitions += 1
        if wait > 0.0:
            self.wait_s += wait
            self.contended += 1
        self._lock.release()
        locks.note_release("executor.mesh")
        if trace._PLANE is not None:
            trace.emit("mesh.lock", "exec", t_req, wait,
                       hold_s=round(hold, 6))
        return False

    def meter(self):
        return {"busy_s": round(self.busy_s, 6),
                "wait_s": round(self.wait_s, 6),
                "acquisitions": self.acquisitions,
                "contended": self.contended,
                "wall_s": round(time.time() - self.t_created, 6)}


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# where accelerator backends keep XLA's persistent compilation cache
# when JAX_COMPILATION_CACHE_DIR does not place it: <checkout>/.jax_cache
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def _place_compile_cache(platform):
    """Persistent XLA compilation cache: stream programs compile per
    (size class, slot) and an accelerator compile can run minutes — pay
    each once per program EVER, not once per process.  A directory
    given from outside (JAX_COMPILATION_CACHE_DIR) is left alone;
    otherwise the cache sits at a FIXED path beside the package (the
    path is part of the cache key, so a directory that moves never
    hits).  Device backends only: XLA:CPU AOT entries are
    machine-feature-sensitive (observed "could lead to SIGILL" loads),
    and CPU compiles are cheap."""
    if platform != "cpu" \
            and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          _COMPILE_CACHE_DIR)


class JAXExecutor:
    def __init__(self, devices=None):
        # 64-bit ints on device: dpark semantics are Python ints, and a
        # counting/summing workload must not silently wrap at 2**31
        # (parity contract with the local master)
        jax.config.update("jax_enable_x64", True)
        # donation is best-effort: when XLA cannot alias a donated
        # buffer into an output (shape/layout mismatch) it falls back
        # to a copy and jax warns per program — correct behavior, noisy
        # at one-per-compiled-program volume.  Installed here, not at
        # import time, so merely importing the module doesn't mutate
        # the process-global warning filter.
        import warnings
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        self.mesh = layout.make_mesh(devices)
        _place_compile_cache(self.mesh.devices.flat[0].platform)
        self.ndev = int(self.mesh.devices.size)
        self.shuffle_store = {}       # sid -> stored map output metadata
        self._store_bytes = 0
        self.result_cache = {}        # rdd id -> HBM-resident Batch meta
        self._result_bytes = 0
        self._hbm_seq = 0             # global LRU clock across both tiers
        self.exchange_wire_bytes = 0  # ICI bytes moved by all_to_all
        self.export_seconds = 0.0     # host bridge export wall time
        self._exchange_real_rows = 0  # valid rows offered for exchange
        self.exchange_slot_rows = 0   # padded slots moved over the wire;
        #   pad efficiency = real/slot
        # slots that never cross a wire (ndev==1 identity exchange) are
        # tracked separately so single-chip runs measure ingest padding
        # under its own name, not as bogus wire padding
        self.ingest_slot_rows = 0
        # always-on host-side count (one integer add, traced or not):
        # calls of a compiled stage program through _launch.  Blocking
        # device-to-host reads are `host_reads`, below.  Both are plain
        # `+= 1`, exact for one thread of jobs: the export server's
        # threads reach host_read too, and a read-modify-write that
        # races there can lose a count.
        self.program_launches = 0
        # rows the bounds samples of sortByKey brought to the host
        # (_sample_keys): sampleSize a sort, never the table
        self.sort_sample_rows = 0
        # stores dropped because their ShuffleDependency died (the
        # scheduler's drain): the release engaging, once a store
        self.stores_released = 0
        # in-core shuffle writes registered `pre_reduced` (_finish_stage:
        # one device, an exact combine): the reduce stage's exchange and
        # reduce program not run, once a store
        self.stores_pre_reduced = 0
        # the query plane's scans (bare `+=`): rows of a table resident
        # on the device that a stage program was launched over
        # (_source_outs: the count read when the table was made,
        # resident_table), and rows a planned query's Scan evaluated on
        # the driver in numpy and reported here (note_host_scan)
        self.scan_rows_device = 0
        self.scan_rows_host = 0
        # device Pregel (backend/tpu/bagel.py; bare `+=`, as
        # program_launches): supersteps run, the messages their gen
        # programs reported (edges that sent, before the combine; read
        # every superstep anyway), graphs partitioned and put on the
        # devices (DeviceGraph: once a resident graph, never a run), and
        # supersteps whose messages were combined and delivered over the
        # load's destination order (one device: no sort, no exchange)
        self.pregel_supersteps = 0
        self.pregel_messages = 0
        self.pregel_graph_loads = 0
        self.pregel_static_supersteps = 0
        # program_key -> does that plan's shuffle write combine on the
        # device (_write_combines' memo, bounded like the program cache)
        self._combines_memo = {}
        # the device join: pairs it emitted (the totals `join.totals`
        # reads), and pairs of a byte-string join whose one-word hash
        # matched and whose bytes did not (dropped on the device; the
        # count rides the NEXT join's `join.totals` read, never a read
        # of its own, so it lags one join)
        self.join_rows_out = 0
        self.join_pairs_dropped = 0
        self._join_dropped = None
        # count arrays whose host sum is deferred (the ndev==1 fast
        # path must not pay a blocking readback per wave just for this
        # metric); flushed on first metric read, or opportunistically
        # once the list exceeds a small bound so an embedder that never
        # reads the metric doesn't pin device buffers forever
        self._pending_real_counts = []
        self._PENDING_COUNTS_MAX = 64
        # slots already compiled per leaf config: sizing snaps to a
        # cached slot within the padding tolerance so data-size drift
        # between jobs reuses programs instead of recompiling adjacent
        # 1/16-octave classes
        self._slot_memo = {}
        # bounded LRU over compiled programs (ISSUE 9 satellite):
        # conf.PROGRAM_CACHE_MAX entries, hit/miss/evict counters for
        # /metrics and the warm-submit A/B
        self._compiled = _ProgramCache()
        # buffer donation is gated off on multi-controller meshes:
        # donating a process-spanning global array switches XLA:CPU to
        # a multiprocess aliasing path it doesn't implement
        # (INVALID_ARGUMENT in the SPMD dryrun), and on real multi-host
        # meshes the reuse economics are per-process anyway
        try:
            self._single_proc = all(
                d.process_index == jax.process_index()
                for d in self.mesh.devices.flat)
        except Exception:
            self._single_proc = False
        # overlapped wave pipeline observability: per-stream snapshot of
        # ingest/compute/exchange/spill ms + device-idle fraction
        # (reset by run_stage; the scheduler attaches it to stage_info)
        self.last_stream_stats = None
        # (rows/device, row bytes) the last streamed stage budgeted its
        # waves at — the OOM degradation ladder persists this into the
        # adaptive store (ISSUE 7) so the next run seeds from it
        self.last_wave_budget = None
        # live per-wave stage_info callback, set by the scheduler around
        # run_stage so a long stream's progress shows in the web UI
        self._stage_note = None
        # let rdd.unpersist() reach device-resident caches
        from dpark_tpu import cache as cache_mod
        cache_mod.DEVICE_CACHES[id(self)] = self.drop_result
        self._cache_key = id(self)
        # register the host bridge so file-path stages can read HBM shuffles
        from dpark_tpu import shuffle as shuffle_mod
        shuffle_mod.HBM_EXPORTERS[id(self)] = self.export_bucket
        # columnar twin (ISSUE 12): the bulk data plane serves flat
        # (k, v) buckets as raw column bytes to peer controllers — no
        # per-row pickling on the cross-process path
        shuffle_mod.HBM_COL_EXPORTERS[id(self)] = self.export_bucket_cols
        self._exporter_key = id(self)
        # ONE mesh lock serializes every device-program dispatch path:
        # stage programs (run_stage), device joins/gathers, AND the
        # export bridge's sharded-leaf reads.  Two collective programs
        # dispatched concurrently deadlock the XLA:CPU rendezvous
        # (each run pins one device participant; observed as the
        # classic multi-thread lookup/fetch wedge — PR 3 addendum),
        # and with a resident job server (ISSUE 9) CONCURRENT jobs'
        # stages now genuinely race for the mesh.  Reentrant so the
        # eviction spiller can export under a stage's lock.  Disk-run
        # exports stay lock-free — they touch no device.  Lock order
        # where both are held: _mesh_lock -> _shard_build_lock.
        self._mesh_lock = _MeshLock()
        self._export_lock = self._mesh_lock
        # ledger plane (ISSUE 15): backend compiles become measured
        # compile.backend spans via jax.monitoring; the listener costs
        # one predicate per (rare) compile when tracing is off
        trace.install_compile_listener()
        # jobs currently RUNNING on the owning scheduler (ISSUE 9):
        # their HBM shuffle stores are preferred-KEEP when the budget
        # evicts; completed jobs' buckets spill to disk first
        self.live_jobs = set()
        self._job_tls = threading.local()   # job id of this thread's stage
        # exact per-job program-cache attribution (ISSUE 15 satellite,
        # closing the PR 9 caveat): hits/misses tag the slot thread's
        # CURRENT job, so concurrent jobs' record["program_cache"]
        # deltas no longer overlap
        self._compiled._job_of = \
            lambda: getattr(self._job_tls, "job", None)
        # scheduler hook: called as (sid, uri) after an HBM store is
        # spilled to disk so stage output locations follow the move
        self._spill_notify = None
        # scheduler hook: drop the stores whose dependency died, so
        # eviction never spills what nothing can read
        self._release_unreachable = None
        # coded-shuffle shard serving (ISSUE 6): each hbm bucket is
        # lazily serialized + erasure-encoded ONCE, then individual
        # framed shards answer per-shard fetches.  Builds serialize
        # behind one lock (the n concurrent shard reads of one bucket
        # must not each export the bucket); the cache is a small
        # byte-bounded FIFO — shard fetches for one bucket arrive
        # within one reduce task's fan-out, so entries age out fast.
        self._shard_cache = {}        # (sid, map, reduce) -> [frames]
        self._shard_cache_bytes = 0
        self._shard_build_lock = locks.named_lock(
            "executor.shard_build")
        self._tracing = False
        if conf.XPROF_DIR:
            try:
                jax.profiler.start_trace(conf.XPROF_DIR)
                self._tracing = True
                logger.info("jax profiler trace -> %s", conf.XPROF_DIR)
            except Exception as e:
                logger.warning("profiler trace unavailable: %s", e)

    @property
    def host_reads(self):
        """Blocking device-to-host reads (calls of layout.host_read) in
        this process so far; a job's delta is its round trips."""
        return layout.HOST_READS

    @property
    def bytes_rows_packed(self):
        """Rows whose byte-string columns were packed into device words
        at ingest, in this process so far (layout._pack_parts)."""
        return layout.BYTES_ROWS_PACKED

    @property
    def bytes_rows_unpacked(self):
        """Rows whose byte strings were rebuilt as host `bytes` at a
        host exit (layout.host_columns: egest and the export bridge)."""
        return layout.BYTES_ROWS_UNPACKED

    @property
    def exchange_real_rows(self):
        """Valid rows offered for exchange.  Reading flushes deferred
        per-wave count arrays (one batched readback at metric-read
        time, e.g. the scheduler's per-stage accounting — never inside
        the wave loop)."""
        if self._pending_real_counts:
            pending, self._pending_real_counts = \
                self._pending_real_counts, []
            for c in layout.host_read(pending, site="exchange.real_rows"):
                self._exchange_real_rows += int(c.sum())
        return self._exchange_real_rows

    @exchange_real_rows.setter
    def exchange_real_rows(self, value):
        self._exchange_real_rows = value

    # ------------------------------------------------------------------
    # compilation
    # ------------------------------------------------------------------
    def _sharding(self):
        return NamedSharding(self.mesh, P(AXIS))

    def _donation_enabled(self):
        """DONATE_BUFFERS, and the mesh lives in this one process (see
        __init__: multi-controller donation is unimplemented in
        XLA:CPU and unprofitable elsewhere)."""
        return conf.DONATE_BUFFERS and self._single_proc

    def _epilogue_merge(self, plan):
        """(merge_fn, monoid) for a combining shuffle write, or
        (None, None) for the no-combine (list-aggregator) mode.

        The two are independent: a PROVABLE monoid combines through a
        scan of its own operation even when the user's function does
        not trace (``max(a, b)`` forces a tracer bool) — discarding the
        monoid with the failed trace crashed the streamed combine (r5
        fuzz finding).  Untraceable AND unclassified merges exchange
        raw created combiners.

        A classified monoid WITHOUT a traced merge_fn only stands in
        for the user's function when the record carries exactly one
        SCALAR value leaf: the host merges whole records (max over
        tuples compares lexicographically) while the monoid-only call
        sites (_epilogue_block, the carry_rid bucketize, and
        _prereduce_received — all of which get their pair from here
        via _merge_probe) reduce each leaf independently, mixing
        leaves from different records (r5 advisor finding: silent
        wrong answers for tuple-valued reduceByKey(min/max)).  For
        any other value shape the pair degrades to (None, None) and
        the raw-combiner exchange folds with the user's function on
        the host — slower, correct."""
        dep = plan.epilogue[1]
        if fuse.is_list_agg(dep.aggregator):
            return None, None
        try:
            monoid = fuse.classify_merge(dep.aggregator.merge_combiners)
        except Exception:
            monoid = None
        try:
            merge_fn = fuse._leaves_merge_fn(
                dep.aggregator.merge_combiners, plan.out_treedef)
            structs = fuse._batched_spec_struct(
                plan.out_specs[getattr(plan, "epi_nk", 1) or 1:])
            jax.eval_shape(lambda *v: merge_fn(list(v), list(v)),
                           *structs)
        except Exception:
            merge_fn = None
        if merge_fn is None and monoid is not None:
            specs = plan.out_specs
            nk = getattr(plan, "epi_nk", 1) or 1
            single_scalar_value = (len(specs) == nk + 1
                                   and specs[nk][1] == ())
            if not single_scalar_value:
                return None, None
        return merge_fn, monoid

    @staticmethod
    def _epilogue_params(plan):
        """What _epilogue_block needs of a shuffle-writing plan, as
        plain values: a compiled program's closure outlives the job
        in the program cache, and must hold neither the plan nor,
        through it, the ShuffleDependency (whose death releases the
        store)."""
        return (getattr(plan, "epi_nk", 1) or 1,
                plan.epilogue[1].partitioner.num_partitions,
                plan.epi_spec)

    @staticmethod
    def _epilogue_block(epi, lv, n, n_dst, merge_fn, monoid, bounds):
        """Shared shuffle-write tail: destination assignment (hash or
        range bounds over the LOGICAL partition count r <= mesh size) +
        bucketize[-combine].  `epi` is _epilogue_params(plan).
        Composite (tuple) keys occupy the first nk columns:
        destinations hash over all of them with the pair-extended
        phash, and the combine merges rows equal in every key
        column."""
        nk, r, epi_spec = epi
        k = lv[0]
        valid = jnp.arange(k.shape[0]) < n
        if epi_spec is not None and epi_spec[0] == "range":
            if nk == 1 and not fuse.epi_unsigned(epi_spec):
                dst = collectives.range_dst(k, bounds, epi_spec[1],
                                            n_dst, valid, r=r)
            else:
                bcols = [bounds[:, i] for i in range(nk)]
                dst = collectives.range_dst_cols(
                    lv[:nk], bcols, epi_spec[1], n_dst, valid, r=r,
                    unsigned=fuse.epi_unsigned(epi_spec))
        else:
            dst = collectives.hash_dst_cols(
                lv[:nk], n_dst, valid, r=r,
                bytes_width=fuse.epi_bytes_width(epi_spec))
        if merge_fn is not None or monoid is not None:
            k2s, v2, cnts, offs = collectives.bucketize_combine_keys(
                lv[:nk], lv[nk:], n, n_dst, merge_fn, monoid=monoid,
                dst=dst, r=r)
        else:
            sorted_lv, cnts, offs = collectives.bucketize(
                k, lv, n, n_dst, dst=dst, r=r)
            k2s, v2 = sorted_lv[:nk], sorted_lv[nk:]
        return (cnts, offs) + tuple(k2s) + tuple(v2)

    @staticmethod
    def _widen_entry(in_specs, lv):
        """Cast program inputs up to the spec dtypes (plan.in_specs):
        ingest may ship int64 leaves over the host->device wire as i32
        (layout.ingest's fit scan); compute always runs at spec
        width."""
        return [v if v.dtype == dt else v.astype(dt)
                for v, (dt, _) in zip(lv, in_specs)]

    def _compile_narrow(self, plan, cap, nleaves_in, in_dtypes=(),
                        donate=False, extra_key=()):
        """Program A: (counts, [bounds,] in_leaves) -> ops -> result or
        bucketized shuffle output.  Shapes (ndev, cap, ...), dim 0
        sharded.  `donate` hands the input leaves to XLA for in-place
        reuse — STREAMED waves only, where the ingest buffers are dead
        after this program (in-core callers may pass result-cache or
        shuffle-store leaves, which must survive the call).
        `extra_key` extends the program identity for op state decided
        per run (the SegMapOp bucket layout)."""
        key = ("narrow", plan.program_key, cap, nleaves_in, in_dtypes,
               donate, extra_key)
        if key in self._compiled:
            return self._compiled[key]
        faults.hit("executor.compile")     # chaos site: per cache miss
        ops = plan.ops
        epilogue = plan.epilogue
        n_dst = self.ndev
        has_bounds = plan.epi_bounds is not None
        merge_fn = monoid = epi = None
        if epilogue is not None:
            merge_fn, monoid = self._merge_probe(plan)
            epi = self._epilogue_params(plan)
        if trace._PLANE is not None:
            trace.event("compile", "exec", program="narrow", cap=cap,
                        sig=_plan_sig(plan),
                        combine=_combine_arg(merge_fn, monoid),
                        sort=_sort_arg(plan, epilogue is not None or any(
                            isinstance(op, (fuse.SortOp, fuse.FilterOp))
                            for op in ops)), **_order_args(plan))
        in_specs = plan.in_specs

        def per_device(counts, *rest):
            # (ndev,) counts of a batch; a pre_reduced in-core store
            # hands its (ndev, R=1) bucket counts as they were written
            n = counts[0].reshape(()) if counts.ndim > 1 else counts[0]
            bounds = rest[0][0] if has_bounds else None
            leaves = rest[1:] if has_bounds else rest
            lv = self._widen_entry(in_specs, [l[0] for l in leaves])
            for op in ops:
                lv, n = op.apply(lv, n)
            if epi is None:
                return (jnp.expand_dims(n, 0),) + tuple(
                    jnp.expand_dims(l, 0) for l in lv)
            out = self._epilogue_block(epi, lv, n, n_dst, merge_fn,
                                       monoid, bounds)
            return tuple(jnp.expand_dims(o, 0) for o in out)

        n_in = 1 + nleaves_in + (1 if has_bounds else 0)
        n_out = (1 + len(plan.out_specs)) if epilogue is None \
            else (2 + len(plan.out_specs))
        fn = _shard_map(per_device, self.mesh,
                        in_specs=(P(AXIS),) * n_in,
                        out_specs=(P(AXIS),) * n_out)
        leaf0 = 1 + (1 if has_bounds else 0)
        jitted = jax.jit(fn, donate_argnums=tuple(
            range(leaf0, leaf0 + nleaves_in)) if donate else ())
        self._compiled[key] = jitted
        # read back through the cache: with the AOT plane on, the
        # stored value is the two-tier proxy, and EVERY call path must
        # route through it or the first call double-compiles
        return self._compiled[key]

    def _compile_exchange(self, dtypes, nleaves, slot, cap,
                          narrow=None, donate=False):
        """The exchange program: (offsets, counts, sent, destination-
        sorted leaves) -> (recv_cnt, new_sent, overflow, receive
        buffers (ndev, slot, ...)), one collectives.exchange_round a
        call; _exchange_all calls it once a round with the `sent` the
        round before returned.  Its send side cuts ndev contiguous
        blocks of `slot` rows out of each leaf (`send="slices"` in the
        `compile` event): about 2 s to build for the chip at 14 leaves,
        0.1 ms a 32-bit plane and call at 2M rows (PR 34).
        `donate` releases the destination-sorted send buffers for
        in-place reuse: only the LAST round of a streamed wave's
        exchange may donate (earlier rounds re-read the same buffers;
        the in-core path passes shuffle-store leaves, never donated)."""
        key = ("exchange", dtypes, nleaves, slot, cap, narrow, donate)
        if key in self._compiled:
            return self._compiled[key]
        if trace._PLANE is not None:
            trace.event("compile", "exec", program="exchange", cap=cap,
                        slot=slot, nleaves=nleaves, send="slices")

        def per_device(offsets, counts, sent, *leaves):
            lv = [l[0] for l in leaves]
            recv, recv_cnt, new_sent, overflow = collectives.exchange_round(
                AXIS, lv, offsets[0], counts[0], sent[0], slot,
                narrow=narrow)
            out = (recv_cnt, new_sent,
                   jnp.reshape(overflow, (1,))) + tuple(recv)
            return tuple(jnp.expand_dims(o, 0) for o in out)

        fn = _shard_map(per_device, self.mesh,
                        in_specs=(P(AXIS),) * (3 + nleaves),
                        out_specs=(P(AXIS),) * (3 + nleaves))
        jitted = jax.jit(fn, donate_argnums=tuple(
            range(3, 3 + nleaves)) if donate else ())
        self._compiled[key] = jitted
        return self._compiled[key]

    def _compile_minmax(self, nleaves, cap):
        """(counts, int64 leaves) -> per-device (lo, hi) over each
        leaf's VALID destination-sorted prefix (rows past sum(counts)
        are padding and may hold sentinels that would defeat
        narrowing)."""
        key = ("minmax", nleaves, cap)
        if key in self._compiled:
            return self._compiled[key]
        imax = jnp.iinfo(jnp.int64).max
        imin = jnp.iinfo(jnp.int64).min

        def per_device(counts, *leaves):
            total = jnp.sum(counts[0]).astype(jnp.int32)
            valid = jnp.arange(cap) < total
            outs = []
            for l in leaves:
                lv = l[0]
                lo = jnp.min(jnp.where(valid, lv, imax))
                hi = jnp.max(jnp.where(valid, lv, imin))
                outs.append(jnp.stack([lo, hi]))
            return tuple(jnp.expand_dims(o, 0) for o in outs)

        fn = _shard_map(per_device, self.mesh,
                        in_specs=(P(AXIS),) * (1 + nleaves),
                        out_specs=(P(AXIS),) * nleaves)
        self._compiled[key] = jax.jit(fn)
        return self._compiled[key]

    def _narrow_plan(self, leaves, counts):
        """Per-leaf wire dtype for the exchange (None = keep).

        TPUs (v5e) have no native 64-bit integer datapath — XLA emulates
        i64 as i32 pairs and an i64 all_to_all moves 2x the ICI bytes.
        dpark semantics demand i64 *compute* (counting must not wrap at
        2**31), so narrowing is decided per exchange by a runtime
        min/max guard over the valid rows: int64 scalar columns whose
        values all fit int32 ride the wire at i32 and widen back
        immediately after the collective (VERDICT r2 ask #1)."""
        if not conf.NARROW_EXCHANGE:
            return None
        cand = [li for li, l in enumerate(leaves)
                if l.dtype == jnp.int64 and l.ndim == 2]
        if not cand:
            return None
        cap = leaves[0].shape[1]
        probe = self._compile_minmax(len(cand), cap)
        ranges = self._launch("minmax", probe, counts,
                              *[leaves[li] for li in cand])
        plan = [None] * len(leaves)
        i32 = np.iinfo(np.int32)
        for li, rng in zip(cand, ranges):
            r = layout.host_read(rng, site="narrow.minmax")  # (ndev, 2)
            lo, hi = int(r[:, 0].min()), int(r[:, 1].max())
            if lo >= i32.min and hi <= i32.max:
                plan[li] = "int32"
        if not any(plan):
            return None
        return tuple(plan)

    def _compile_reduce(self, plan, rounds, slot, nleaves,
                        donate=False):
        """Program B: ([bounds,] recv counts, recv buffers over `rounds`)
        -> flatten -> segment reduce (or key-sort for no-combine) -> ops
        -> result or bucketize.  `donate` releases the receive buffers
        (exchange outputs, dead after this program) for in-place reuse;
        the single-device identity exchange aliases store leaves, so
        callers only donate on a real multi-device exchange."""
        key = ("reduce", plan.program_key, rounds, slot, nleaves,
               donate)
        if key in self._compiled:
            return self._compiled[key]
        dep = plan.source[1]
        merge_fn = monoid = None
        if plan.src_combine:
            merge_fn = fuse._leaves_merge_fn(
                dep.aggregator.merge_combiners, plan.in_treedef)
            try:
                monoid = fuse.classify_merge(
                    dep.aggregator.merge_combiners)
            except Exception:
                monoid = None
        ops = plan.ops
        epilogue = plan.epilogue
        n_dst = self.ndev
        has_bounds = plan.epi_bounds is not None
        out_merge_fn = out_monoid = epi = None
        if epilogue is not None:
            out_merge_fn, out_monoid = self._merge_probe(plan)
            epi = self._epilogue_params(plan)

        src_nk = getattr(plan, "src_nk", 1) or 1
        src_hash = getattr(plan, "src_hash", False)
        # equal keys only have to be adjacent, unless sortByKey's SortOp
        # takes this order for its own: then a byte string orders as
        # its bytes do
        ordered = bool(plan.ops) and isinstance(
            plan.ops[0], fuse.SortOp) and plan.ops[0].presorted \
            and plan.ops[0].unsigned
        if trace._PLANE is not None:    # combine= is the source reduce's
            trace.event("compile", "exec", program="reduce", slot=slot,
                        sig=_plan_sig(plan),
                        combine=_combine_arg(merge_fn, monoid),
                        sort=_sort_arg(plan, True), **_order_args(plan))

        def per_device(*args):
            bounds = args[0][0] if has_bounds else None
            args = args[1:] if has_bounds else args
            cnts = [c[0] for c in args[:rounds]]
            buf_args = args[rounds:]
            recvs = []
            for r in range(rounds):
                recvs.append([buf_args[r * nleaves + li][0]
                              for li in range(nleaves)])
            flat, mask = collectives.flatten_received(recvs, cnts)
            if merge_fn is not None:
                ks, vs, n = collectives.segment_reduce_keys(
                    flat[:src_nk], flat[src_nk:], mask, merge_fn,
                    monoid=monoid)
                lv = list(ks) + list(vs)
            else:
                # no-combine repartition: sort rows by the FULL key
                # (every column of a tuple key), valid first; the
                # join's byte-string sides by ONE word, a hash of the
                # key's words that then leads the row
                nk = src_nk
                if src_hash:
                    flat = [collectives.key_hash64(flat[:src_nk], mask)] \
                        + flat
                    nk = 1
                lv = collectives.sort_by_key(flat, nk, unsigned=ordered)
                n = jnp.sum(mask).astype(jnp.int32)
            for op in ops:
                lv, n = op.apply(lv, n)
            if epi is None:
                return (jnp.expand_dims(n, 0),) + tuple(
                    jnp.expand_dims(l, 0) for l in lv)
            out = self._epilogue_block(epi, lv, n, n_dst, out_merge_fn,
                                       out_monoid, bounds)
            return tuple(jnp.expand_dims(o, 0) for o in out)

        n_in = rounds + rounds * nleaves + (1 if has_bounds else 0)
        n_out = (1 + len(plan.out_specs)) if epilogue is None \
            else (2 + len(plan.out_specs))
        fn = _shard_map(per_device, self.mesh,
                        in_specs=(P(AXIS),) * n_in,
                        out_specs=(P(AXIS),) * n_out)
        buf0 = rounds + (1 if has_bounds else 0)
        jitted = jax.jit(fn, donate_argnums=tuple(
            range(buf0, buf0 + rounds * nleaves)) if donate else ())
        self._compiled[key] = jitted
        return self._compiled[key]

    def _bounds_arg(self, plan):
        """plan.epi_bounds tiled per device and sharded, or None.
        Tuple-key range bounds are 2D (len(bounds), nk) and tile to
        (ndev, len(bounds), nk)."""
        if plan.epi_bounds is None:
            return None
        b = plan.epi_bounds
        reps = (self.ndev,) + (1,) * b.ndim
        tiled = np.tile(b, reps) if b.size else np.zeros(
            (self.ndev,) + b.shape, b.dtype)
        return layout.put_sharded(tiled, self._sharding())

    # ------------------------------------------------------------------
    # running
    # ------------------------------------------------------------------
    def _launch(self, program, fn, *args):
        """Call a compiled stage program.  The `launch` span is the
        host's enqueue wall (jit cache lookup, argument handling, a
        compile if one happens); the device runs on after the return,
        and whoever reads the result waits for it in a `readback`."""
        self.program_launches += 1
        plane = trace._PLANE
        if plane is not None:
            with trace.span("launch", "exec", program=program):
                return fn(*args)
        return fn(*args)

    def run_stage(self, plan):
        """Execute the whole stage for all partitions at once.

        Returns ("result", list_of_row_lists) or ("shuffle", sid).
        Holds the mesh lock throughout: with a resident job server
        (ISSUE 9) concurrent jobs' stages race for the device, and two
        collective programs in flight wedge the XLA:CPU rendezvous."""
        # the span carries the adapt program signature (ISSUE 15): the
        # ledger's device-seconds account and the health plane's
        # wave sketches key by it — only worth computing when traced
        extra = {}
        if trace._PLANE is not None:
            sig = _plan_sig(plan)
            extra = {"sig": sig}
            # every backend compile inside this stage (narrow,
            # exchange, egest, ...) attributes to the stage's program
            trace.set_compile_sig(sig)
        if aotcache._PLANE is not None:
            # programs inserted under this stage carry its adapt
            # signature into the disk index / warm ranking
            aotcache.set_current_sig(fuse.plan_adapt_signature(plan))
        with self._mesh_lock, \
                trace.span("stage.exec", "exec", source=plan.source[0],
                           **extra):
            return self._run_stage(plan)

    def _run_stage(self, plan):
        self.last_stream_stats = None       # set by streamed runs only
        self.last_wave_budget = None
        mode = self._stream_mode(plan)
        if mode is not None:
            kind, waves = mode
            if kind == "combine":
                return self._run_streamed_shuffle(plan, waves)
            return self._run_streamed_nocombine(plan, waves)
        if getattr(plan, "logical_spill", False):
            # analyze only admits logical_spill when the input clears
            # the streaming bar, so this is a safety net, not a route
            raise ValueError("logical_spill plan without streaming")
        if plan.source[0] == "text":
            outs = self._run_narrow(plan, self._ingest_text(plan))
            return self._finish_stage(plan, outs)
        if plan.source[0] == "union":
            keyed = plan.epilogue is not None
            batch = self._concat_batches(
                [layout.Batch(sp.out_treedef, list(o[1:]), o[0])
                 for sp in plan.source[1]
                 for o in (self._source_outs(sp, keyed),)])
            outs = self._run_narrow(plan, batch)
        else:
            outs = self._source_outs(plan, plan.epilogue is not None)
        return self._finish_stage(plan, outs)

    def _source_outs(self, plan, keyed):
        """Load the plan's source and run its narrow/reduce program;
        shared by whole-stage runs and union-branch materialization."""
        if plan.source[0] == "ingest":
            pc = plan.source[1]
            slices = pc._slices
            if getattr(plan, "reslice", False):
                slices = _reslice_parts(slices, self.ndev)
            sp = trace._NOOP
            plane = trace._PLANE
            if plane is not None:
                sp = trace.span("ingest", "exec",
                                rows=sum(len(p) for p in slices))
            # any shuffle write pads with the key sentinel; a real key
            # equal to it must force the host path
            with sp:
                batch = layout.ingest(self.mesh, slices, plan.in_treedef,
                                      plan.in_specs,
                                      key_leaf=0 if keyed else None)
            return self._run_narrow(plan, batch)
        if plan.source[0] == "cached":
            meta = self.result_cache[plan.source[1].id]
            meta["seq"] = self._next_seq()           # LRU touch
            batch = layout.Batch(meta["treedef"], meta["leaves"],
                                 meta["counts"])
            if keyed:
                self._check_cached_keys(batch)
            outs = self._run_narrow(plan, batch)
            table = meta.get("table")
            if table:
                # a table was made over this batch (resident_table): its
                # rows went to a stage program and not to the driver
                self.scan_rows_device += table["rows"]
            return outs
        if plan.source[0] == "join":
            dep_a, dep_b = plan.source[1]
            batch = self.device_join_batch(dep_a, dep_b)
            return self._run_narrow(plan, batch)
        store = self.shuffle_store[plan.source[1].shuffle_id]
        if plan.ops and (isinstance(plan.ops[0], fuse.SegMapOp)
                         or (isinstance(plan.ops[0], fuse.SegAggOp)
                             and "host_runs" in store)):
            # segmented apply (and segment aggregates over spilled
            # runs): two-phase — sort the rows, read the group-size
            # histogram, compile with the bucket layout
            return self._run_seg_map(plan)
        if store.get("pre_reduced"):
            # device d already holds reduce partition d, every key
            # once: a streamed shuffle exchanged and combined wave by
            # wave; an in-core write on ONE device combined exactly
            # (_finish_stage), and its exchange would be the identity.
            # No exchange and no reduce program: the plan's narrow tail
            # over the store's batch, never donated (the store outlives
            # the result, and a cache()d result the store)
            store["seq"] = self._next_seq()
            if "offsets" in store:      # in-core: the identity
                # exchange's row accounting, as _exchange_all keeps it
                self._note_identity_exchange(store["counts"],
                                             store["leaves"][0].shape[1])
            batch = layout.Batch(store["out_treedef"], store["leaves"],
                                 store["counts"])
            return self._run_narrow(plan, batch)
        return self._run_exchange_and_reduce(plan)

    def _run_narrow(self, plan, batch, bounds=None, donate=False,
                    extra_key=()):
        """Compile + invoke the narrow stage program on one batch.
        `donate` is for streamed waves only: the batch's leaves are
        dead after this call and XLA may reuse them in place."""
        faults.hit("executor.dispatch")    # chaos site: per dispatch
        if trace._PLANE is not None:
            trace.event("dispatch", "exec", program="narrow",
                        sig=_plan_sig(plan))
            # backend compiles fired by the jitted call below
            # attribute to this program (ledger plane, ISSUE 15)
            trace.set_compile_sig(_plan_sig(plan))
        if aotcache._PLANE is not None:
            aotcache.set_current_sig(fuse.plan_adapt_signature(plan))
        jitted = self._compile_narrow(
            plan, batch.cap, len(batch.cols),
            tuple(str(c.dtype) for c in batch.cols), donate=donate,
            extra_key=extra_key)
        if bounds is None:
            bounds = self._bounds_arg(plan)
        args = (batch.counts,) + ((bounds,) if bounds is not None
                                  else ()) + tuple(batch.cols)
        self._capture_cost(plan, jitted, args)
        return self._launch("narrow", jitted, *args)

    def _capture_cost(self, plan, jitted, args):
        """Static program cost profile at first dispatch (ISSUE 15):
        once per plan signature, BEFORE the call (donated buffers are
        dead after it; lower() reads only avals).  Gated on BOTH the
        ledger sink and an installed trace plane — the documented
        contract is that the whole attribution plane is inert with
        DPARK_TRACE=off, and the capture's re-trace must never ride
        an untraced production dispatch under the mesh lock."""
        from dpark_tpu import ledger
        if ledger._SINK is None or trace._PLANE is None:
            return
        try:
            ledger.capture_program_cost(
                fuse.plan_adapt_signature(plan), jitted, args)
        except Exception:
            pass

    # ------------------------------------------------------------------
    # text-source ingest (SURVEY.md 3.1 hot loop #1): the narrow chain
    # over a file source runs as a host prologue per split — the user's
    # own generators (always correct) or, for the verified canonical
    # wordcount shape, the C++ tokenizer — then string keys are
    # dictionary-encoded and the device shuffle takes over
    # ------------------------------------------------------------------
    def _token_dict(self):
        if not hasattr(self, "token_dict"):
            from dpark_tpu.native import TokenDict
            self.token_dict = TokenDict()
        return self.token_dict

    @staticmethod
    def _read_text_split(text_rdd, sp):
        """The bytes of one newline-aligned split (same boundary rule as
        TextFileRDD.compute: skip a partial first line, finish the line
        that crosses the end)."""
        from dpark_tpu import file_manager
        with file_manager.open_file(sp.path) as f:
            begin = sp.begin
            if begin > 0:
                f.seek(begin - 1)
                if f.read(1) != b"\n":
                    f.readline()
                begin = f.tell()
            else:
                f.seek(0)
            data = f.read(sp.end - begin) if sp.end > begin else b""
            if data and not data.endswith(b"\n"):
                data += f.readline()
            return data

    @staticmethod
    def _tokenizer_safe(data, sep=None):
        """True iff the ASCII byte tokenizer provably equals the
        Python chain on these bytes.

        Whitespace mode (sep=None): every byte must be printable ASCII
        or \\t \\n \\r — bytes >= 0x80 can decode to unicode whitespace
        (\\xc2\\xa0 etc.) and control bytes \\x0b \\x0c \\x1c-\\x1f ARE
        str.split() whitespace but not the byte tokenizer's (ADVICE r2:
        the 4KB first-split check alone missed divergence appearing
        later in the file).

        Separator mode: str.split(sep) splits ONLY on the separator, so
        control bytes pass through both paths verbatim — only >= 0x80
        (utf-8 'replace' decoding can rewrite token bytes) forces the
        host prologue."""
        if not data:
            return True
        a = np.frombuffer(data, np.uint8)
        if sep is not None:
            return not bool((a >= 0x80).any())
        bad = (a >= 0x80) | ((a < 0x20) & (a != 9) & (a != 10)
                             & (a != 13))
        return not bool(bad.any())

    def _verify_canonical(self, plan, data, td):
        """Run the user's own flatMap/map on a prefix of this split and
        compare with the C++ tokenizer: any divergence (e.g. unicode
        whitespace the byte tokenizer doesn't split on) disables the
        native path for this run — correctness first."""
        prefix = data[:4096]
        cut = prefix.rfind(b"\n")
        prefix = b"" if cut < 0 else prefix[:cut + 1]
        if not prefix:
            # nothing to verify against (empty split or a >4KB first
            # line): do NOT trust the byte tokenizer unverified
            return False
        fm, mp = plan.text_chain
        expect = []
        # EXACT TextFileRDD line iteration: \n-separated, trailing \r
        # stripped (str.splitlines would also split on \x0b etc.)
        for raw in prefix.split(b"\n")[:-1]:
            line = raw.rstrip(b"\r\n").decode("utf-8", "replace")
            for w in fm.f(line):
                rec = mp.f(w)
                if rec[1] != 1:
                    return False
                expect.append(rec[0])
        sep = getattr(plan, "canonical_sep", None)
        got = [td.decode(int(t)) for t in td.encode(prefix, sep=sep)]
        return got == expect

    def _encode_rows(self, plan, top, sp, td):
        """Host prologue for one split: run the user chain, columnarize,
        dictionary-encode string keys."""
        import jax.tree_util as jtu
        keys = []
        leaf_lists = [[] for _ in plan.in_specs[1:]]
        encode = plan.encoded_keys
        for rec in top.iterator(sp):
            k, v = rec
            keys.append(td.put(k) if encode else k)
            for li, leaf in enumerate(jtu.tree_leaves(v)):
                leaf_lists[li].append(leaf)
        cols = [np.asarray(keys, np.int64)]
        for ll, (dt, shape) in zip(leaf_lists, plan.in_specs[1:]):
            cols.append(np.asarray(ll, dt))
        return cols

    def _text_split_cols(self, plan, sp, td, state):
        """Columns for one split: C++ tokenizer on the canonical path
        (bytecode-proven chain + per-split byte-safety scan + a sample
        verification), the user's own generators otherwise."""
        if state["canonical"]:
            sep = getattr(plan, "canonical_sep", None)
            data = self._read_text_split(plan.text_rdd, sp)
            if not state["checked"] and self._tokenizer_safe(
                    data[:4096], sep):
                state["checked"] = True
                if not self._verify_canonical(plan, data, td):
                    logger.info("canonical tokenizer diverges from the "
                                "user chain; using the host prologue")
                    state["canonical"] = False
            if state["canonical"] and self._tokenizer_safe(data, sep):
                ids = td.encode(data, sep=sep)
                return [np.asarray(ids, np.int64),
                        np.ones(len(ids), np.int64)]
        return self._encode_rows(plan, plan.stage.rdd, sp, td)

    def _text_parts(self, plan, chunks):
        """Concatenate per-split columns and redistribute rows EVENLY
        across devices regardless of the file split layout (one big file
        = one split must not put everything on device 0); the hash
        exchange owns placement anyway.  The host bridge compensates via
        the store's single_map mode."""
        from dpark_tpu.rdd import _ColumnarSlice
        nleaves = len(plan.in_specs)
        if chunks:
            cols = [np.concatenate([c[li] for c in chunks])
                    for li in range(nleaves)]
        else:
            cols = [np.zeros((0,) + shape, dt)
                    for dt, shape in plan.in_specs]
        return [_ColumnarSlice([c[lo:hi] for c in cols])
                for lo, hi in _even_ranges(len(cols[0]), self.ndev)]

    def _split_cols_parallel(self, plan, splits, td, state):
        """Per-split columns with CONCURRENT tokenize/encode (VERDICT
        r2 ask #2 — the serial driver walk was the 10GB wordcount's
        bottleneck): worker threads read + tokenize each split into a
        PRIVATE TokenDict (ctypes releases the GIL, so the C++ loops
        run truly parallel), then the driver merges the private
        vocabularies into the global dict in split order — global ids
        come out identical to the serial walk.  The first split
        resolves the canonical-vs-prologue decision serially (it
        mutates shared state and runs the sample verification)."""
        import concurrent.futures as cf
        import os as _os
        nw = conf.INGEST_THREADS or (_os.cpu_count() or 1)
        nw = min(nw, max(1, len(splits)))
        if nw <= 1 or len(splits) <= 1:
            return [self._text_split_cols(plan, sp, td, state)
                    for sp in splits]
        # walk serially until the sample verification has actually run
        # (splits whose prefix is byte-unsafe take the host prologue and
        # leave state['checked'] False): the C++ path must NEVER run
        # unverified, in the parallel path exactly as in the serial one
        results = []
        i = 0
        while i < len(splits) and state["canonical"] \
                and not state["checked"]:
            results.append(self._text_split_cols(plan, splits[i], td,
                                                 state))
            i += 1
        rest = splits[i:]
        if not rest:
            return results
        if not (state["canonical"] and state["checked"]):
            # host-prologue chain: USER code — keep it on the driver
            # thread (the reference isolates user code in processes;
            # interleaving a stateful closure across threads would
            # silently change results), and the GIL would serialize it
            # anyway
            results.extend(self._text_split_cols(plan, sp, td, state)
                           for sp in rest)
            return results

        sep = getattr(plan, "canonical_sep", None)

        def work(sp):
            # C++ only in workers: read + byte-scan + tokenize into a
            # PRIVATE dict (ctypes releases the GIL).  Byte-unsafe
            # splits are handed back for the driver-thread prologue.
            data = self._read_text_split(plan.text_rdd, sp)
            if not self._tokenizer_safe(data, sep):
                return None
            from dpark_tpu.native import TokenDict
            ltd = TokenDict()
            return (ltd, ltd.encode(data, sep=sep))

        with cf.ThreadPoolExecutor(max_workers=nw) as pool:
            done = list(pool.map(work, rest))
        for sp, out in zip(rest, done):       # split order: ids stable
            if out is None:
                results.append(self._encode_rows(
                    plan, plan.stage.rdd, sp, td))
                continue
            ltd, local_ids = out
            ids = td.merge_from(ltd)[local_ids] if len(ltd) \
                else local_ids
            results.append([np.asarray(ids, np.int64),
                            np.ones(len(ids), np.int64)])
        return results

    def _ingest_text(self, plan):
        td = self._token_dict() if plan.encoded_keys else None
        state = {"canonical": plan.canonical, "checked": False}
        chunks = self._split_cols_parallel(plan, plan.stage.rdd.splits,
                                           td, state)
        parts = self._text_parts(plan, chunks)
        return layout.ingest(self.mesh, parts, plan.in_treedef,
                             plan.in_specs, key_leaf=0)

    # -- HBM result cache (rdd.cache() on the device path) --------------
    def result_cache_ids(self):
        return self.result_cache.keys()

    def result_cache_meta(self, rdd_id):
        return self.result_cache[rdd_id]

    def _next_seq(self):
        self._hbm_seq += 1
        return self._hbm_seq

    def store_result(self, rdd_id, batch):
        if rdd_id in self.result_cache:
            self.drop_result(rdd_id)        # re-store: no double count
        nbytes = sum(int(l.nbytes) for l in batch.cols)
        self.result_cache[rdd_id] = {
            "treedef": batch.treedef, "leaves": batch.cols,
            "counts": batch.counts, "nbytes": nbytes,
            "seq": self._next_seq(),
            "specs": [(np.dtype(l.dtype), tuple(l.shape[2:]))
                      for l in batch.cols],
        }
        self._result_bytes += nbytes
        self._evict_hbm(keep_rdd=rdd_id)

    def resident_table(self, rdd_id):
        """What the query plane needs of a cached RDD to plan over it
        as a table resident on the device, or None when the RDD is not
        in the result cache or its records are not flat rows of scalar
        and byte-string columns.  {"rows", "columns"}: a column is
        (numpy dtype, (lo, hi)) with the range of an int column's
        valid rows (None for floats and bools), or for an S<w> column
        (S dtype, [(lo, hi) a word]).  The ranges are read ONCE, the
        first time a table is made over the RDD (one program a column
        and one blocking read, site `table.stats`), and kept with the
        cached batch: a query plans from them and reads nothing."""
        meta = self.result_cache.get(rdd_id)
        if meta is None:
            return None
        if "table" not in meta:
            meta["table"] = self._table_stats(meta)
        return meta["table"]

    def _table_stats(self, meta):
        specs = meta["specs"]
        found = layout.column_groups(meta["treedef"], len(specs))
        if found is None:
            outer, groups = meta["treedef"], list(range(len(specs)))
        else:
            outer, groups = found
        flat = jax.tree_util.tree_unflatten(outer, list(range(len(groups))))
        if flat != tuple(range(len(groups))) or any(
                shape != () for _, shape in specs):
            return None
        ints = [i for i, (dt, _) in enumerate(specs) if dt.kind == "i"]
        read = layout.host_read(
            [meta["counts"]] + [layout._masked_minmax(
                meta["leaves"][i], meta["counts"]) for i in ints],
            site="table.stats")
        rows = int(read[0].sum())
        # an empty table's masked min/max are the type's extremes
        ranges = {i: (int(r[0]), int(r[1])) if rows else (0, 0)
                  for i, r in zip(ints, read[1:])}
        columns = []
        for g in groups:
            if layout._is_bytestr(g):
                columns.append((np.dtype("S%d" % g.width),
                                [ranges[i] for i in g.words]))
            else:
                columns.append((specs[g][0], ranges.get(g)))
        return {"rows": rows, "columns": columns}

    def note_host_scan(self, rows):
        """The query plane's driver-side scan (planner._ScanSeg.run)
        reports the rows it evaluated in numpy."""
        self.scan_rows_host += rows

    def drop_result(self, rdd_id):
        meta = self.result_cache.pop(rdd_id, None)
        if meta:
            self._result_bytes -= meta["nbytes"]

    def _evict_hbm(self, keep_sid=None, keep_rdd=None):
        """One budget across BOTH HBM tiers (shuffle outputs + cached
        results): shed the least-recently-used entries until under
        conf.SHUFFLE_HBM_BUDGET.

        Shuffle stores SPILL TO DISK instead of dropping (ISSUE 9
        satellite): each bucket round-trips through the host bridge
        into the standard on-disk bucket files — crc-framed erasure
        SHARD CONTAINERS when a shuffle code is active, so coded reads
        still decode — and the map-output locations follow the move.
        A later consumer pays a disk read, never a lineage recompute
        (the pre-service behavior on eviction).  COMPLETED jobs' stores
        spill first, least-recently-fetched order; a store the live
        jobs still grow (keep_sid) stays pinned.  Cached results still
        drop — they recompute on next use and have no disk format.
        A spill that fails (disk full) falls back to dropping the
        store, which is exactly the old lineage-recovery contract.

        LIFETIME (ISSUE 25): a store lives as long as the RDD that
        shuffled it.  The stores of chains nobody holds any more are
        released first (the scheduler's `_release_unreachable`, which
        also runs when a job starts and when one finishes), so what
        is left to spill here is what something can still read: a
        dropped chain frees its HBM at the next job, a held one
        spills under pressure as before."""
        # the budget is PER DEVICE (conf.py) and the byte counters sum
        # whole sharded arrays: a four-chip mesh holds four budgets
        release = self._release_unreachable
        if release is not None:
            release()
        budget = conf.SHUFFLE_HBM_BUDGET * self.ndev
        pinned = set()      # in-flight stores (outputs not registered)
        while self._store_bytes + self._result_bytes > budget:
            # spilled (host_runs) stores hold no HBM: evicting them
            # frees nothing and destroys on-disk runs
            live = self.live_jobs
            cands = [(meta["seq"], "sid", sid)
                     for sid, meta in self.shuffle_store.items()
                     if sid != keep_sid and sid not in pinned
                     and "host_runs" not in meta
                     and meta.get("job") not in live]
            if not cands:
                # every store belongs to a RUNNING job: prefer
                # dropping recomputable cached results before touching
                # a live job's working set
                cands = [(meta["seq"], "rdd", rid)
                         for rid, meta in self.result_cache.items()
                         if rid != keep_rdd]
            if not cands:
                # still over: spill live jobs' stores too (quota
                # arbitration — the job with the most HBM pays first,
                # least-recently-fetched bucket of that job)
                by_job = {}
                for sid, meta in self.shuffle_store.items():
                    if sid == keep_sid or sid in pinned \
                            or "host_runs" in meta:
                        continue
                    by_job.setdefault(meta.get("job"), []).append(
                        (meta["seq"], sid, meta["nbytes"]))
                if by_job:
                    biggest = max(
                        by_job.values(),
                        key=lambda ss: sum(b for _, _, b in ss))
                    seq, sid, _ = min(biggest)
                    cands = [(seq, "sid", sid)]
            if not cands:
                break
            _, kind, victim = min(cands)
            if kind == "sid":
                try:
                    self._spill_shuffle_to_disk(victim)
                except _StoreInFlight:
                    # its producing stage hasn't reported outputs yet:
                    # the buckets are in flight — pinned, try the next
                    # candidate instead
                    pinned.add(victim)
                except Exception as e:
                    logger.warning(
                        "spill of HBM shuffle %d failed (%s); "
                        "dropping it — consumers recover via lineage",
                        victim, e)
                    self.drop_shuffle(victim)
            else:
                logger.debug("evicting HBM cached result %d", victim)
                self.drop_result(victim)

    def _spill_shuffle_to_disk(self, sid):
        """Round-trip one HBM shuffle store into the standard on-disk
        bucket layout (shard containers when coding is active) and
        re-point its map-output locations at the files.  Runs under
        the mesh lock (the export reads device slices).  One
        `hbm.spill` span, beside the `hbm.release` event (reason
        `spill`) that drop_shuffle emits."""
        from dpark_tpu.env import env
        from dpark_tpu.shuffle import LocalFileShuffle
        store = self.shuffle_store[sid]
        with self._mesh_lock:
            locs = env.map_output_tracker.get_outputs(sid)
            if locs is None:
                # the producing stage hasn't completed/registered yet:
                # its buckets are in flight — treat as pinned
                raise _StoreInFlight(sid)
            sp = trace._NOOP
            plane = trace._PLANE
            if plane is not None:
                sp = trace.span("hbm.spill", "exec", sid=sid,
                                bytes=store["nbytes"])
            with sp:
                n_reduce = int(store.get(
                    "n_reduce",
                    _export_read(store["counts"]).shape[-1]))
                uri = None
                for map_id, old in enumerate(locs):
                    if old is None or not str(old).startswith("hbm://"):
                        continue        # lost or already host-resident
                    # the rows are a temporary: they die here, inside
                    # the span, not when this frame is torn down (4-10
                    # ms a store on the chip's host)
                    uri = LocalFileShuffle.write_buckets(
                        sid, map_id, [self._export_bucket(sid, map_id, r)
                                      for r in range(n_reduce)])
                if uri is None:
                    uri = LocalFileShuffle.get_server_uri()
                new_locs = [uri if (l and str(l).startswith("hbm://"))
                            else l for l in locs]
                env.map_output_tracker.register_outputs(sid, new_locs)
                notify = self._spill_notify
                if notify is not None:
                    # the owning scheduler re-points its
                    # Stage.output_locs so a later job reusing the
                    # stage sees disk locations
                    notify(sid, uri)
                logger.info("spilled HBM shuffle %d (%d bytes) to disk "
                            "buckets at %s", sid, store["nbytes"], uri)
                self.drop_shuffle(sid, reason="spill")

    def _finish_stage(self, plan, outs):
        if plan.epilogue is None:
            counts, leaves = outs[0], list(outs[1:])
            batch = layout.Batch(plan.out_treedef, leaves, counts)
            encoded = (plan.source[0] == "hbm"
                       and self.shuffle_store.get(
                           plan.source[1].shuffle_id, {})
                       .get("encoded_keys", False))
            if plan.stage is not None \
                    and getattr(plan.stage.rdd, "should_cache", False) \
                    and not plan.group_output and not encoded:
                # encoded batches never enter the result cache: a later
                # device stage would see raw ids where the user expects
                # strings
                self.store_result(plan.stage.rdd.id, batch)
            if getattr(plan, "count_only", False):
                # count() consumes only cardinalities: one scalar-leaf
                # read instead of egesting every row.  group_output
                # counts KEYS — the no-combine reduce leaves each
                # device's rows key-sorted, so distinct keys count on
                # device with one boundary scan
                if plan.group_output:
                    counts = layout.host_read(
                        self._distinct_key_counts(
                            batch, nk=getattr(plan, "src_nk", 1) or 1),
                        site="finish.counts")
                else:
                    counts = layout.host_read(batch.counts,
                                              site="finish.counts")
                return ("counts", [int(c) for c in counts])
            monoid = getattr(plan, "reduce_monoid", None)
            if (monoid is not None and not plan.group_output
                    and len(batch.cols) == 1
                    and batch.cols[0].ndim == 2
                    # bools have no monoid identity table; integer mul
                    # overflows almost immediately where the host fold
                    # used exact Python ints — both keep the egest path
                    and np.dtype(batch.cols[0].dtype).kind in "if"
                    and not (monoid == "mul"
                             and np.dtype(batch.cols[0].dtype).kind
                             == "i")):
                # reduce(provable monoid) over scalar records: one
                # per-device masked reduction, ndev scalars egested.
                # Float add/mul REASSOCIATES here (per-device tree
                # reduction vs the host's partition-order fold): results
                # can differ from the local master in low-order bits —
                # parity checks must compare floats with a tolerance
                # (ADVICE r4; test_parity_fuzz does)
                vals, lo, hi = (
                    layout.host_read(a, site="finish.reduced") for a in
                    self._monoid_reduce(batch, monoid))
                counts = layout.host_read(batch.counts,
                                          site="finish.counts")
                intk = vals.dtype.kind == "i"
                safe = True
                if intk and monoid == "add":
                    # host fold used exact Python ints: only answer
                    # from the device when the i64 sum provably cannot
                    # have wrapped (n * max|v| bound; empty devices
                    # hold identities — exclude them from the bound)
                    total = int(counts.sum())
                    nz = counts > 0
                    mabs = (max(abs(int(lo[nz].min())),
                                abs(int(hi[nz].max())))
                            if nz.any() else 0)
                    safe = total * mabs < 2 ** 62
                if safe:
                    py = float if not intk else int
                    return ("reduced", [(py(v), int(n))
                                        for v, n in zip(vals, counts)])
            head = getattr(plan, "sample_keys", None)
            if head is not None and not plan.group_output \
                    and not encoded:
                # sortByKey's bounds sample: the first `head` keys of
                # each partition and nothing else cross to the host
                keys = self._sample_keys(plan, batch, head)
                if keys is not None:
                    return ("sampled", keys)
            top = getattr(plan, "top_candidate", None)
            if top is not None and not plan.group_output:
                # top(k): select each device's k best rows ON DEVICE
                # and egest ndev*k rows instead of the whole batch
                # (exact semantics: the per-partition _TopN then runs
                # on its own partition's pre-top — top-k of top-k —
                # and the driver heap merge is unchanged): ndev*k rows
                # cross D2H instead of every row.
                kspec = fuse.classify_top_key(
                    top[1], plan.out_treedef, plan.out_specs, encoded)
                if kspec is None and top[1] is not None \
                        and not encoded:
                    # ranged-int probe: integer key EXPRESSIONS ride
                    # the device when the interval check over the
                    # batch's actual per-column min/max proves no
                    # intermediate can leave int64 (one tiny masked
                    # min/max program per int column)
                    kspec = fuse.classify_top_key(
                        top[1], plan.out_treedef, plan.out_specs,
                        encoded, col_ranges=self._int_col_ranges(batch))
                if kspec is not None:
                    batch = self._device_topk(plan, batch, kspec,
                                              top[0], top[2])
                    plan.topk_used = True
            rows_per_part = layout.egest(batch)
            if plan.group_output:
                # bare groupByKey: rows arrive key-sorted; group runs
                # into (k, [v]) host-side
                import itertools as _it
                grouped = []
                for rows in rows_per_part:
                    parts = []
                    for k, grp in _it.groupby(rows, key=lambda r: r[0]):
                        parts.append((k, [r[1] for r in grp]))
                    grouped.append(parts)
                rows_per_part = grouped
            if encoded:
                store = self.shuffle_store[plan.source[1].shuffle_id]
                rows_per_part = [self._maybe_decode(store, rows)
                                 for rows in rows_per_part]
            return ("result", rows_per_part)
        dep = plan.epilogue[1]
        cnts, offs = outs[0], outs[1]
        leaves = list(outs[2:])
        # ONE device, and this stage's one program combined its whole
        # input for the shuffle (bucketize_combine_keys: by the true
        # key columns when n_dst == 1): the store holds every key once,
        # in key order, packed.  That is what the reduce stage's
        # identity exchange and reduce program would hand on, so the
        # store is what a streamed combine's is: _source_outs runs the
        # narrow tail alone.  Raw combiners (the (None, None) merge),
        # list aggregators and range writes combine nothing and keep
        # the exchange + reduce; so does every write past one device
        pre_reduced = (self.ndev == 1
                       and dep.partitioner.num_partitions <= self.ndev
                       and self._write_combines(plan))
        if pre_reduced:
            self.stores_pre_reduced += 1
            leaves = self._trim_combined(leaves, cnts)
        return self._register_shuffle(dep, plan, {
            "leaves": leaves,            # (ndev, cap, ...) dst-sorted
            "counts": cnts,              # (ndev, R)
            "offsets": offs,             # (ndev, R)
            "pre_reduced": pre_reduced,
            "no_combine": fuse.is_list_agg(dep.aggregator),
            "encoded_keys": getattr(plan, "encoded_keys", False),
            # text ingest, union concat, and resliced ingest all
            # redistribute rows across devices, so device index !=
            # logical map partition: the host bridge reads the whole
            # shuffle through map_id 0 (object-path consumers fetch
            # every reported map id; non-zero ids return empty)
            "single_map": (plan.source[0] in ("text", "union")
                           or getattr(plan, "reslice", False)),
        })

    def _trim_combined(self, leaves, counts):
        """A combined store's leaves keep their INPUT's capacity however
        few keys the combine left: four groups of 8M rows are 448 MiB
        of padding.  Where registering them whole would pass
        conf.SHUFFLE_HBM_BUDGET (and _evict_hbm would spill a store or
        drop a cached table for that padding), the count is read (one
        blocking read, site `store.counts`: the host waits for the map
        program here and not at the egest) and the leaves are cut to the
        capacity class of the count: every key is packed at the front.
        Under the budget nothing is read and nothing is cut."""
        budget = conf.SHUFFLE_HBM_BUDGET * self.ndev - sum(
            int(l.nbytes) for l in leaves)
        if self._store_bytes + self._result_bytes <= budget:
            return leaves
        release = self._release_unreachable
        if release is not None:
            release()       # as _evict_hbm: what nobody can read goes first
            if self._store_bytes + self._result_bytes <= budget:
                return leaves
        rows = int(layout.host_read(counts, site="store.counts").max())
        cap = layout.round_capacity(rows)
        if cap >= leaves[0].shape[1]:
            return leaves
        return [layout._head(l, cap) for l in leaves]

    def _int_col_ranges(self, batch):
        """Exact (lo, hi) Python ints per int64 scalar column of a
        result batch (valid rows only; None for other leaves) — the
        input of classify_top_key's ranged-int probe."""
        ranges = []
        for c in batch.cols:
            if c.ndim == 2 and np.dtype(c.dtype).kind == "i":
                try:
                    r = layout.host_read(
                        layout._masked_minmax(c, batch.counts),
                        site="topk.minmax")
                    ranges.append((int(r[0]), int(r[1])))
                except Exception:
                    ranges.append(None)
            else:
                ranges.append(None)
        return ranges

    def _sample_keys(self, plan, batch, n):
        """The first n KEYS of every partition of a result batch, as
        the rows rdd._TakeSampleKeys would keep: one slice program
        over the key columns, then an egest of ndev * n keys (no value
        column, no row past the prefix).  None where the key is not
        the record's leading scalar columns."""
        nk = layout.key_width(batch.treedef, plan.out_specs, kinds="if")
        if nk is None:
            return None
        cap = batch.cap
        dtypes = tuple(str(c.dtype) for c in batch.cols[:nk])
        key = ("sample", cap, dtypes, n)
        if key not in self._compiled:
            def per_device(counts, *cols):
                return (jnp.minimum(counts, n).astype(jnp.int32),) \
                    + tuple(c[:, :n] for c in cols)

            fn = _shard_map(per_device, self.mesh,
                            in_specs=(P(AXIS),) * (1 + nk),
                            out_specs=(P(AXIS),) * (1 + nk))
            self._compiled[key] = jax.jit(fn)
        outs = self._launch("sample", self._compiled[key], batch.counts,
                            *batch.cols[:nk])
        record = jax.tree_util.tree_unflatten(
            batch.treedef, list(range(len(batch.cols))))
        head = layout.Batch(jax.tree_util.tree_structure(record[0]),
                            list(outs[1:]), outs[0])
        sp = trace._NOOP
        if trace._PLANE is not None:
            sp = trace.span("sort.sample", "exec", splits=batch.ndev,
                            bytes=sum(int(c.nbytes) for c in head.cols))
        with sp:
            keys = layout.egest(head)
            rows = sum(len(part) for part in keys)
            if sp is not trace._NOOP:
                sp.args["rows"] = rows
        self.sort_sample_rows += rows
        return keys

    def _device_topk(self, plan, batch, kspec, n, smallest):
        """Per-device top-n of a result batch by the classified key:
        one stable sort per device, n rows kept (ties resolve by
        device row order — top()'s tie membership is already
        partition-order-dependent on every master)."""
        cap = batch.cap
        nlv = len(batch.cols)
        dtypes = tuple(str(c.dtype) for c in batch.cols)
        if kspec[0] == "leaf":
            skey = ("leaf", kspec[1])
        else:
            skey = ("fn", fuse.fn_key(kspec[1]))
        key = ("topk", plan.program_key, cap, nlv, dtypes, n,
               bool(smallest), skey)
        if key not in self._compiled:
            if kspec[0] == "fn":
                row_fn = fuse._row_fn(kspec[1], plan.out_treedef)
                vkey = jax.vmap(lambda *lv: row_fn(*lv)[0])
            leaf_i = kspec[1] if kspec[0] == "leaf" else None

            def per_device(counts, *leaves):
                nv = counts[0]
                lv = [l[0] for l in leaves]
                kcol = lv[leaf_i] if leaf_i is not None else vkey(*lv)
                valid = jnp.arange(cap) < nv
                # VALIDITY is the primary sort key, not a key-value
                # sentinel: a real key equal to the extreme (or a
                # padding slot) must never outrank data (review
                # finding — ±inf keys tied with padding and the
                # reversal picked the padding rows).  Largest-first
                # uses an order-REVERSING bijection (-1-k for ints,
                # -k for floats) so ties stay stable in row order.
                if smallest:
                    sk = kcol
                elif jnp.issubdtype(kcol.dtype, jnp.floating):
                    sk = -kcol
                else:
                    sk = -1 - kcol
                inval = (~valid).astype(jnp.int32)
                packed = collectives._lex_sort(
                    (inval, sk) + tuple(lv), 2)
                out = [l[:n] for l in packed[2:]]
                new_n = jnp.minimum(nv, n).astype(jnp.int32)
                return (jnp.expand_dims(new_n, 0),) + tuple(
                    jnp.expand_dims(o, 0) for o in out)

            fn = _shard_map(per_device, self.mesh,
                            in_specs=(P(AXIS),) * (1 + nlv),
                            out_specs=(P(AXIS),) * (1 + nlv))
            self._compiled[key] = jax.jit(fn)
        outs = self._compiled[key](batch.counts, *batch.cols)
        return layout.Batch(batch.treedef, list(outs[1:]), outs[0])

    def _monoid_reduce(self, batch, monoid):
        """Per-device (reduced, min, max) over the valid rows of a
        single-scalar-leaf batch, each (ndev,) (empty devices yield
        identities — the caller masks them out via the counts leaf;
        min/max feed the integer-add overflow bound)."""
        from dpark_tpu.backend.tpu.bagel import _local_reduce
        from dpark_tpu.bagel import monoid_identity
        cap = batch.cap
        col = batch.cols[0]
        ident = monoid_identity(monoid, col.dtype)
        lo_id = monoid_identity("min", col.dtype)
        hi_id = monoid_identity("max", col.dtype)
        key = ("monoid_reduce", monoid, cap, str(col.dtype))
        if key not in self._compiled:
            def per_device(counts, vals):
                n, x = counts[0], vals[0]
                valid = jnp.arange(cap) < n
                masked = jnp.where(valid, x, ident)
                lo = jnp.min(jnp.where(valid, x, lo_id))
                hi = jnp.max(jnp.where(valid, x, hi_id))
                return tuple(jnp.expand_dims(o, 0) for o in
                             (_local_reduce(monoid, masked), lo, hi))
            fn = _shard_map(per_device, self.mesh,
                            in_specs=(P(AXIS),) * 2,
                            out_specs=(P(AXIS),) * 3)
            self._compiled[key] = jax.jit(fn)
        return self._compiled[key](batch.counts, col)

    def _distinct_key_counts(self, batch, nk=1):
        """(ndev,) distinct-key counts of a per-device KEY-SORTED batch
        (the no-combine reduce's row order) — group cardinality without
        egesting a single row.  `nk` key columns: a boundary is ANY of
        them changing (tuple keys group on every column)."""
        cap = batch.cap
        kcols = batch.cols[:nk]
        key = ("distinct", cap, nk,
               tuple(str(k.dtype) for k in kcols))
        if key not in self._compiled:
            def per_device(counts, *keys):
                n = counts[0]
                ks = [k[0] for k in keys]
                idx = jnp.arange(cap)
                valid = idx < n
                changed = ks[0] != jnp.roll(ks[0], 1)
                for kc in ks[1:]:
                    changed = changed | (kc != jnp.roll(kc, 1))
                bound = valid & ((idx == 0) | changed)
                return (jnp.expand_dims(
                    jnp.sum(bound).astype(jnp.int32), 0),)
            fn = _shard_map(per_device, self.mesh,
                            in_specs=(P(AXIS),) * (1 + nk),
                            out_specs=(P(AXIS),))
            self._compiled[key] = jax.jit(fn)
        (out,) = self._launch("distinct", self._compiled[key],
                              batch.counts, *kcols)
        return out

    def _register_shuffle(self, dep, plan, store):
        """Shared HBM shuffle-store bookkeeping (re-run guard, byte
        accounting, eviction) for the in-core and streamed write paths."""
        sid = dep.shuffle_id
        if sid in self.shuffle_store:
            self.drop_shuffle(sid)          # re-run: no double count
        store["out_treedef"] = plan.out_treedef
        store["out_specs"] = plan.out_specs
        # composite keys span the first key_cols columns: readers (the
        # gather sort, run premerger, export bridge) order and group by
        # ALL of them, not just column 0
        store["key_cols"] = getattr(plan, "epi_nk", 1) or 1
        store["nbytes"] = sum(int(l.nbytes) for l in store["leaves"])
        store["seq"] = self._next_seq()
        # eviction metadata (ISSUE 9 satellite): the reduce width the
        # disk spiller writes bucket files for, and the owning job —
        # completed jobs' stores spill FIRST when a new exchange would
        # blow conf.SHUFFLE_HBM_BUDGET
        store["n_reduce"] = dep.partitioner.num_partitions
        store["job"] = getattr(self._job_tls, "job", None)
        self.shuffle_store[sid] = store
        self._store_bytes += store["nbytes"]
        if trace._PLANE is not None:
            # ledger plane (ISSUE 15): HBM residency starts — the
            # byte-seconds account accrues from here to the matching
            # hbm.release (drop or spill-to-disk eviction)
            trace.event("hbm.store", "exec", sid=sid,
                        bytes=store["nbytes"],
                        job=store["job"],
                        pre_reduced=bool(store.get("pre_reduced")))
        self._evict_hbm(keep_sid=sid)
        self._observe_combine_ratio(dep, plan, store)
        return ("shuffle", sid)

    def _observe_combine_ratio(self, dep, plan, store):
        """Adaptive-store observation (ISSUE 7 decision point 4): a
        COMBINING shuffle write over a columnar ingest source knows
        both its input rows and its post-combine stored rows — the
        observed combine ratio prices the map-side-combine rewrite for
        this call site on the next run.  Never raises; no-op with
        DPARK_ADAPT=off."""
        from dpark_tpu import adapt
        try:
            if not adapt.enabled() or fuse.is_list_agg(dep.aggregator):
                return
            site = (getattr(dep, "adapt_combine_site", None)
                    or getattr(dep, "adapt_site", None))
            counts = store.get("counts")
            if not site or counts is None \
                    or plan.source[0] != "ingest":
                return
            rows_in = sum(len(s) for s in plan.source[1]._slices or ())
            rows_out = int(layout.host_read(
                counts, site="adapt.combine_ratio").sum())
            if rows_in:
                adapt.record_combine_ratio(site, rows_in, rows_out)
        except Exception as e:
            logger.debug("combine-ratio observation failed: %s", e)

    def _run_exchange_and_reduce(self, plan):
        dep = plan.source[1]
        store = self.shuffle_store[dep.shuffle_id]
        store["seq"] = self._next_seq()              # LRU touch
        leaves = store["leaves"]
        nleaves = len(leaves)
        recv_rounds, cnt_rounds, slot = self._exchange_all(
            leaves, store["counts"], store["offsets"])
        rounds = len(recv_rounds)
        # receive buffers are exchange outputs, dead after the reduce —
        # donate them on a real multi-device exchange (the ndev==1
        # identity exchange aliases the store's leaves: never donated)
        reduce_fn = self._compile_reduce(
            plan, rounds, slot, nleaves,
            donate=self._donation_enabled() and self.ndev > 1)
        bounds = self._bounds_arg(plan)
        args = ([bounds] if bounds is not None else []) + list(cnt_rounds)
        for r in range(rounds):
            args.extend(recv_rounds[r])
        return self._launch("reduce", reduce_fn, *args)

    # ------------------------------------------------------------------
    # device segmented apply (fuse.SegMapOp — ISSUE 4 tentpole): an
    # arbitrary traceable per-group function over groupByKey output
    # runs as a vmap over power-of-two padded group buckets.  Two-phase
    # like the device join: sort the rows (exchange, or premerged
    # spilled runs), read the bucket histogram back, compile the apply
    # program with that static layout.
    # ------------------------------------------------------------------
    def _run_seg_map(self, plan):
        dep = plan.source[1]
        store = self.shuffle_store[dep.shuffle_id]
        store["seq"] = self._next_seq()
        nk = plan.ops[0].nk if isinstance(plan.ops[0], fuse.SegMapOp) \
            else getattr(plan, "src_nk", 1) or 1
        if "host_runs" in store:
            batch = self._seg_batch_from_runs(store)
            hist_np = None
        else:
            counts, hist, leaves = self._seg_exchange_sorted(store, nk)
            batch = layout.Batch(store["out_treedef"], leaves, counts)
            hist_np = layout.host_read(hist, site="segmap.hist")
            self._observe_seg_skew(dep, batch, hist_np)
        op = plan.ops[0]
        extra = ()
        if isinstance(op, fuse.SegMapOp):
            op.layout = self._seg_bucket_layout(op.nk, batch, hist_np)
            extra = (op.layout,)
        return self._run_narrow(plan, batch, extra_key=extra)

    def _observe_seg_skew(self, dep, batch, hist_np):
        """Adaptive-store observation (ISSUE 7 decision point 3): the
        segment path's bucket histogram — computed anyway for the
        apply layout — gives per-key-group sizes for free.  Record
        total rows, group count, the largest group's approximate size
        (size classes are powers of two), and the reduce width, keyed
        by the grouping call site: a dominant group widens the next
        run's default reduce side.  The same (rows, groups) pair
        doubles as the combine-ratio signal that can re-enable the
        map-side rewrite once the ratio drops.  Never raises."""
        from dpark_tpu import adapt
        try:
            if not adapt.enabled():
                return
            site = getattr(dep, "adapt_site", None)
            if not site:
                return
            rows = int(layout.host_read(
                batch.counts, site="adapt.seg_skew").sum())
            per_bucket = np.asarray(hist_np).max(axis=0)
            nonzero = np.nonzero(per_bucket)[0]
            if not rows or not len(nonzero):
                return
            groups = int(np.asarray(hist_np).sum())
            max_group = 1 << int(nonzero[-1])
            adapt.record_skew(site, rows, groups, max_group,
                              dep.partitioner.num_partitions)
            adapt.record_combine_ratio(site, rows, groups)
        except Exception as e:
            logger.debug("seg-skew observation failed: %s", e)

    def _seg_exchange_sorted(self, store, nk):
        """The seg path's gather: exchange + key sort, with the bucket
        HISTOGRAM computed inside the same program — one dispatch and
        one readback fewer per run than a separate histogram pass."""
        leaves = store["leaves"]
        nleaves = len(leaves)
        recv_rounds, cnt_rounds, slot = self._exchange_all(
            leaves, store["counts"], store["offsets"])
        rounds = len(recv_rounds)
        key = ("seg_gather", rounds, slot, nleaves, nk,
               tuple(str(l.dtype) for l in leaves))
        if key not in self._compiled:
            def per_device(*args):
                cnts = [c[0] for c in args[:rounds]]
                bufs = args[rounds:]
                recvs = []
                for r in range(rounds):
                    recvs.append([bufs[r * nleaves + li][0]
                                  for li in range(nleaves)])
                flat, mask = collectives.flatten_received(recvs, cnts)
                packed = collectives._lex_sort(tuple(flat), nk)
                n = jnp.sum(mask).astype(jnp.int32)
                hist, _ = collectives.bucket_histogram(
                    list(packed[:nk]), n)
                out = (n, hist) + tuple(packed)
                return tuple(jnp.expand_dims(o, 0) for o in out)

            fn = _shard_map(per_device, self.mesh,
                            in_specs=(P(AXIS),) * (rounds
                                                   + rounds * nleaves),
                            out_specs=(P(AXIS),) * (2 + nleaves))
            self._compiled[key] = jax.jit(fn)
        args = list(cnt_rounds)
        for r in range(rounds):
            args.extend(recv_rounds[r])
        outs = self._compiled[key](*args)
        return outs[0], outs[1], list(outs[2:])

    def _seg_bucket_layout(self, nk, batch, hist=None):
        """((bucket, width, group_capacity), ...) for the batch's
        power-of-two group-size classes: read from the gather program's
        fused histogram (already on host) when available, else one tiny
        histogram program (the spilled-run ingest path).  Group
        capacities round to power-of-two classes so data drift between
        runs (DStream ticks) reuses compiled apply programs."""
        if hist is None:
            cap = batch.cap
            key = ("seghist", cap, nk,
                   tuple(str(c.dtype) for c in batch.cols[:nk]))
            if key not in self._compiled:
                def per_device(counts, *kcols):
                    h, _ = collectives.bucket_histogram(
                        [k[0] for k in kcols], counts[0])
                    return (jnp.expand_dims(h, 0),)
                fn = _shard_map(per_device, self.mesh,
                                in_specs=(P(AXIS),) * (1 + nk),
                                out_specs=(P(AXIS),))
                self._compiled[key] = jax.jit(fn)
            (hist,) = self._compiled[key](batch.counts,
                                          *batch.cols[:nk])
        gmax = layout.host_read(hist, site="segmap.hist").max(axis=0)
        lay = tuple((b, 1 << b, layout.round_capacity(int(g)))
                    for b, g in enumerate(gmax.tolist()) if g)
        return lay or ((0, 1, 8),)

    def _partition_run_cols(self, store, rid):
        """One spilled partition's columns, KEY-SORTED (the background
        premerger's single run when it got there first, sorted here
        otherwise) — shared by the export bridge and the seg-map batch
        loader so the run-reading convention lives once.  None when the
        partition has no runs."""
        runs = store["host_runs"]
        if rid >= len(runs) or not runs[rid]:
            return None
        premerge = store.get("premerge")
        if premerge is not None:
            paths, presorted = premerge.ensure(rid)
        else:
            paths, presorted = runs[rid], False
        if not paths:
            return None
        pieces = [self._read_run(p) for p in paths]
        cols = [np.concatenate([pt[li] for pt in pieces])
                for li in range(len(pieces[0]))]
        if not presorted and len(cols[0]) > 1:
            nk = min(store.get("key_cols", 1) or 1, len(cols))
            order = (np.argsort(cols[0], kind="stable") if nk == 1
                     else np.lexsort(tuple(cols[:nk][::-1])))
            cols = [c[order] for c in cols]
        return cols

    def _seg_batch_from_runs(self, store):
        """Premerged spilled runs -> per-device key-sorted Batch:
        reduce partition d loads on device d (analyze only admits
        r <= ndev spilled sources for segment ops).  A whole partition
        loads at once — groups must be contiguous for the segment scan
        — so partitions whose columns would blow the HBM budget raise
        here and the scheduler's object fallback consumes the runs
        through the (streaming) export bridge instead."""
        from dpark_tpu.rdd import _ColumnarSlice
        specs = store["out_specs"]
        budget = conf.SHUFFLE_HBM_BUDGET // 2
        total = 0
        parts = []
        for d in range(self.ndev):
            cols = self._partition_run_cols(store, d)
            if cols is None:
                parts.append(_ColumnarSlice(
                    [np.zeros((0,) + shape, dt) for dt, shape in specs]))
                continue
            total += sum(int(c.nbytes) for c in cols)
            if total > budget:
                raise ValueError(
                    "spilled partitions (%d MB so far) exceed the "
                    "seg-map load budget (%d MB): host merge consumes "
                    "the runs" % (total >> 20, budget >> 20))
            parts.append(_ColumnarSlice(cols))
        return layout.ingest(self.mesh, parts, store["out_treedef"],
                             specs)

    # ------------------------------------------------------------------
    # union-source stages (the windowed-stream shape, BASELINE config
    # #4): each branch materializes to a device Batch through its own
    # sub-plan (epilogue=None, via _source_outs), the batches
    # concatenate ON DEVICE, and the stage's narrow ops + shuffle write
    # run over the whole union
    # ------------------------------------------------------------------
    def _concat_batches(self, batches):
        """Per-device concatenation of same-spec Batches into one."""
        if len(batches) == 1:
            return batches[0]
        counts = [layout.host_read(b.counts, site="concat.counts")
                  for b in batches]
        total = np.sum(np.stack(counts), axis=0)
        cap_out = layout.round_capacity(int(total.max()) or 1)
        caps = tuple(b.cap for b in batches)
        nleaves = len(batches[0].cols)
        dtypes = tuple(str(c.dtype) for c in batches[0].cols)
        jitted = self._compile_concat(len(batches), caps, dtypes,
                                      nleaves, cap_out)
        args = [b.counts for b in batches]
        for b in batches:
            args.extend(b.cols)
        outs = jitted(*args)
        return layout.Batch(batches[0].treedef, list(outs[1:]), outs[0])

    def _compile_concat(self, k, caps, dtypes, nleaves, cap_out):
        """Program: (counts x k, leaves x k) -> (total, leaves) with each
        device's valid rows packed contiguously.  Writes go into a
        sum(caps)-sized scratch (dynamic_update_slice never clamps:
        offset_j + cap_j <= sum(caps[:j+1])), then slice to cap_out.
        Input leaves are per-branch narrow outputs, dead after the
        concat — donated for in-place reuse when enabled."""
        donate = self._donation_enabled()
        key = ("concat", k, caps, dtypes, nleaves, cap_out, donate)
        if key in self._compiled:
            return self._compiled[key]
        scratch = max(sum(caps), cap_out)

        def per_device(*args):
            cnts = [c[0] for c in args[:k]]
            leaves = args[k:]
            total = cnts[0]
            for j in range(1, k):
                total = total + cnts[j]
            outs = []
            for li in range(nleaves):
                segs = [leaves[j * nleaves + li][0] for j in range(k)]
                buf = jnp.zeros((scratch,) + segs[0].shape[1:],
                                segs[0].dtype)
                off = jnp.int32(0)
                for j in range(k):
                    idx = (off,) + (0,) * (segs[j].ndim - 1)
                    buf = jax.lax.dynamic_update_slice(
                        buf, segs[j].astype(buf.dtype), idx)
                    off = off + cnts[j].astype(jnp.int32)
                outs.append(buf[:cap_out])
            out = (jnp.asarray(total, jnp.int32),) + tuple(outs)
            return tuple(jnp.expand_dims(o, 0) for o in out)

        fn = _shard_map(per_device, self.mesh,
                        in_specs=(P(AXIS),) * (k + k * nleaves),
                        out_specs=(P(AXIS),) * (1 + nleaves))
        jitted = jax.jit(fn, donate_argnums=tuple(
            range(k, k + k * nleaves)) if donate else ())
        self._compiled[key] = jitted
        return self._compiled[key]

    # ------------------------------------------------------------------
    # out-of-core streaming shuffle (SURVEY.md 7.2 item 4): input bigger
    # than a chunk runs in ingest -> exchange waves so HBM holds one
    # chunk (plus combined state for monoid reduces).  Covers columnar
    # parallelize AND text-source stages; no-combine shuffles (sortByKey
    # range exchange, groupByKey, partitionBy) spill key-sorted runs to
    # host disk and merge lazily at the export bridge.
    # ------------------------------------------------------------------
    def _stream_mode(self, plan):
        """None, or ("combine"|"nocombine", wave iterator).  Each wave
        is a list of per-device _ColumnarSlice parts."""
        if plan.epilogue is None:
            return None
        dep = plan.epilogue[1]
        no_combine = fuse.is_list_agg(dep.aggregator)
        monoid = None if no_combine else fuse.classify_merge(
            dep.aggregator.merge_combiners)
        # ONE eligibility predicate shared with fuse's analyze-time
        # logical_spill gate — divergence would turn the run_stage
        # safety net into a user-facing error
        if plan.source[0] == "ingest":
            if not fuse._big_columnar(plan.source[1]):
                return None
            row_bytes = fuse._columnar_row_bytes(plan.source[1]._slices)
            chunk = conf.stream_chunk_rows(row_bytes)
            self.last_wave_budget = (int(chunk), row_bytes)
            self._check_wave_oom(chunk)
            waves = self._wave_iter_columnar(plan, chunk)
        elif plan.source[0] == "text":
            if not fuse._big_text(plan.stage):
                return None
            sizes = [fuse._split_bytes(sp)
                     for sp in plan.stage.rdd.splits]
            waves = self._wave_iter_text(plan, sizes)
        else:
            return None
        # host tokenize/slice lookahead: STREAM_PIPELINE_DEPTH waves
        # ahead (the pre-pipeline behavior was a fixed depth of 1;
        # depth 0 keeps that single-wave lookahead — "off" only
        # disables the NEW ingest/readback overlap stages)
        tok_depth = max(1, conf.STREAM_PIPELINE_DEPTH)
        if no_combine:
            return ("nocombine", _prefetch_iter(waves, depth=tok_depth))
        # monoids combine by a segmented scan of their own operation,
        # any other TRACEABLE merge by one of the user's function — ONE
        # probe (shared with compile time), memoized per plan
        merge_fn, _ = self._merge_probe(plan)
        if monoid is not None or merge_fn is not None:
            if dep.partitioner.num_partitions <= self.ndev:
                return ("combine", _prefetch_iter(waves,
                                                  depth=tok_depth))
            # traceable merge but r exceeds the mesh: the per-device
            # combined state cannot hold r partitions — ride the
            # spilled-run stream, which pre-reduces each wave per
            # (rid, key) on device before spilling
            return ("nocombine", _prefetch_iter(waves, depth=tok_depth))
        # UNTRACEABLE merge (object-valued combiner semantics the
        # tracer can't see): ride the spilled-run stream — device
        # exchange of created combiners, key-sorted runs on host disk,
        # user's merge_combiners folded per key at export (the
        # reference's external merger; VERDICT r2 ask #7)
        return ("nocombine", _prefetch_iter(waves, depth=tok_depth))

    @staticmethod
    def _check_wave_oom(chunk_rows):
        """Deterministic stand-in for a device HBM ceiling
        (conf.EMULATED_WAVE_OOM_ROWS, bench/test aid): a wave budget
        over the ceiling raises the RESOURCE_EXHAUSTED class the
        degradation ladder halves on, so the OOM ladder — and the
        adaptive store's learned budgets (ISSUE 7) — can be exercised
        on backends that report no memory limit (XLA:CPU)."""
        limit = getattr(conf, "EMULATED_WAVE_OOM_ROWS", 0)
        if limit and chunk_rows > limit:
            raise MemoryError(
                "RESOURCE_EXHAUSTED: emulated HBM ceiling: wave "
                "budget %d rows/device exceeds "
                "DPARK_EMULATED_WAVE_OOM_ROWS=%d"
                % (chunk_rows, limit))

    def _merge_probe(self, plan):
        """_epilogue_merge's (merge_fn, monoid) for the plan's shuffle
        write, memoized on the plan: the stream mode, the program
        builders and _write_combines share one probe."""
        if not hasattr(plan, "_merge_probe_result"):
            plan._merge_probe_result = self._epilogue_merge(plan)
        return plan._merge_probe_result

    def _write_combines(self, plan):
        """Does the plan's shuffle write combine on the device
        (_epilogue_merge gave a merge_fn or a monoid, so its program
        ran bucketize_combine_keys)?  Remembered by program key, which
        decides it: every job builds a fresh plan, and the probe traces
        the user's merge (too dear to repeat a job).  The memo is
        bounded like the program cache, oldest entry out."""
        memo = self._combines_memo
        combines = memo.get(plan.program_key)
        if combines is None:
            merge_fn, monoid = self._merge_probe(plan)
            combines = merge_fn is not None or monoid is not None
            if conf.PROGRAM_CACHE_MAX \
                    and len(memo) >= conf.PROGRAM_CACHE_MAX:
                del memo[next(iter(memo))]
            memo[plan.program_key] = combines
        return combines

    def _wave_iter_columnar(self, plan, chunk=None):
        from dpark_tpu.rdd import _ColumnarSlice
        slices = plan.source[1]._slices
        if chunk is None:      # caller usually passes the budget it
            # already derived (one store consult per stage, not two)
            chunk = conf.stream_chunk_rows(
                fuse._columnar_row_bytes(slices))
        nchunks = (max(len(s) for s in slices) + chunk - 1) // chunk
        for c in range(nchunks):
            yield [
                _ColumnarSlice([col[c * chunk:(c + 1) * chunk]
                                for col in s.columns])
                for s in slices]

    def _wave_iter_text(self, plan, sizes):
        """Groups of splits whose byte size fits one wave budget; each
        wave's splits tokenize/encode concurrently."""
        td = self._token_dict() if plan.encoded_keys else None
        state = {"canonical": plan.canonical, "checked": False}
        budget = conf.STREAM_TEXT_BYTES
        group, acc = [], 0
        for sp, size in zip(plan.stage.rdd.splits, sizes):
            group.append(sp)
            acc += size if size > 0 else budget
            if acc >= budget:
                yield self._text_parts(plan, self._split_cols_parallel(
                    plan, group, td, state))
                group, acc = [], 0
        if group:
            yield self._text_parts(plan, self._split_cols_parallel(
                plan, group, td, state))

    def _ingest_stage(self, plan, waves, cap_state, stats):
        """Pipeline stage 2: host columns -> device Batch (device_put).
        Run through _prefetch_iter so wave k+1's H2D transfer overlaps
        wave k's compute; `cap_state` carries the sticky capacity class
        across waves (owned by whichever thread runs this generator).
        Yields (batch, ingest_seconds)."""
        try:
            for parts in waves:
                t0 = stats.now()
                batch = layout.ingest(self.mesh, parts, plan.in_treedef,
                                      plan.in_specs, key_leaf=0,
                                      cap_floor=cap_state[0])
                cap_state[0] = max(cap_state[0], batch.cap)
                yield batch, stats.now() - t0
        finally:
            # unwind the upstream tokenize stage too: a for loop does
            # not close an abandoned iterator on its own
            close = getattr(waves, "close", None)
            if close is not None:
                close()

    def _stream_batches(self, plan, waves, stats):
        """The ingest pipeline stage, threaded when the pipeline is on:
        wave k+1 device_puts while wave k computes (double-buffered
        ingest — up to one ingested wave queued plus one in flight)."""
        cap_state = [0]
        batches = self._ingest_stage(plan, waves, cap_state, stats)
        if conf.STREAM_PIPELINE_DEPTH > 0:
            batches = _prefetch_iter(batches, depth=1,
                                     name="dpark-wave-ingest")
        return batches

    def _note_pipeline(self, stats):
        """Live per-wave stage_info update (web UI) + the final stream
        snapshot the scheduler attaches to the stage record."""
        self.last_stream_stats = stats.snapshot()
        cb = getattr(self, "_stage_note", None)
        if cb is not None:
            try:
                cb(pipeline=self.last_stream_stats)
            except Exception:
                pass

    def _trace_stream_phases(self, stats):
        """Per-stage phase spans (trace plane, ISSUE 8) from the SAME
        snapshot scheduler.phase_table() reads, laid back-to-back from
        the stream's wall start — tools/dtrace's critical-path phase
        totals therefore reconcile with the phase table by
        construction."""
        if trace._PLANE is None or self.last_stream_stats is None:
            return
        snap = self.last_stream_stats
        ts = stats.wall_t0
        for phase, key in (("ingest_tokenize", "ingest_ms"),
                           ("narrow", "compute_ms"),
                           ("exchange", "exchange_ms"),
                           ("spill", "spill_ms")):
            dur = float(snap.get(key, 0.0) or 0.0) / 1e3
            trace.emit("phase." + phase, "phase", ts, dur,
                       waves=snap.get("waves"))
            ts += dur

    def _run_streamed_shuffle(self, plan, waves):
        dep = plan.epilogue[1]
        # classified monoids combine by a segmented scan of their own
        # operation; any other TRACEABLE user merge by one of the user's
        # function (_stream_mode verified it traces, same memoized probe)
        merge_fn, monoid = self._merge_probe(plan)
        donate = self._donation_enabled()
        stats = _StreamStats(conf.STREAM_PIPELINE_DEPTH, donate)
        state = None                    # (leaves, counts) combined so far
        busy_start = None               # dispatch time of state's wave
        bounds = self._bounds_arg(plan)      # loop-invariant
        slot_floor = 0                  # sticky size classes: a smaller
        # tail wave reuses earlier waves' compiled programs
        batches = self._stream_batches(plan, waves, stats)
        try:
            for c, (batch, ingest_s) in enumerate(batches):
                t_wall = time.time() if trace._PLANE is not None \
                    else 0.0
                t_disp = stats.now()
                outs = self._run_narrow(plan, batch, bounds=bounds,
                                        donate=donate)
                cnts, offs = outs[0], outs[1]
                leaves = list(outs[2:])
                t_x = stats.now()
                recv = self._exchange_all(leaves, cnts, offs,
                                          slot_floor=slot_floor,
                                          donate=donate)
                exchange_s = stats.now() - t_x
                slot_floor = max(slot_floor, recv[2])
                if state is not None:
                    # deferred from the PREVIOUS wave: its async counts
                    # copy has been in flight through this wave's ingest
                    # + narrow + exchange, so this read doesn't stall
                    state = self._shrink_state(state)
                    stats.add_busy(busy_start, stats.now())
                state = self._merge_into_state(plan, state, recv, monoid,
                                               merge_fn, donate=donate)
                busy_start = t_disp
                stats.wave_done(ingest_s,
                                (stats.now() - t_disp) - exchange_s,
                                exchange_s)
                self._note_pipeline(stats)
                if trace._PLANE is not None:
                    trace.emit("wave", "exec", t_wall,
                               time.time() - t_wall, wave=c,
                               sig=_plan_sig(plan))
                logger.debug("streamed wave %d", c + 1)
        finally:
            close = getattr(batches, "close", None)
            if close is not None:
                close()
        leaves, counts = self._shrink_state(state)
        stats.add_busy(busy_start, stats.now())
        self._note_pipeline(stats)
        self._trace_stream_phases(stats)
        return self._register_shuffle(dep, plan, {
            "leaves": leaves, "counts": counts,
            "pre_reduced": True,        # device d holds reduce part d
            "no_combine": False,
            "encoded_keys": getattr(plan, "encoded_keys", False),
            "single_map": plan.source[0] == "text",
        })

    def _compile_stream_nocombine(self, plan, cap, nleaves_in, r,
                                  in_dtypes=(), donate=False):
        """Map-side program for the spilled-run stream: narrow ops, then
        LOGICAL partition assignment (rid in [0, r), r may exceed the
        mesh), then bucketize by rid % ndev with rid riding along as an
        extra column.  `donate` reuses the ingest leaves in place (they
        are dead after this program in the wave loop)."""
        key = ("snc", plan.program_key, cap, nleaves_in, r, in_dtypes,
               donate)
        if key in self._compiled:
            return self._compiled[key]
        faults.hit("executor.compile")     # chaos site: per cache miss
        if trace._PLANE is not None:
            trace.event("compile", "exec", program="snc", cap=cap,
                        sig=_plan_sig(plan))
        ops = plan.ops
        ndev = self.ndev
        has_bounds = plan.epi_bounds is not None
        ascending = (plan.epi_spec[1] if plan.epi_spec[0] == "range"
                     else True)
        # the rid column rides the exchange only when needed: with
        # r <= ndev the receiving device IS the logical partition
        carry_rid = r > ndev
        # traceable merge riding the spilled stream: pre-combine equal
        # (rid, key) rows on the map side too, BEFORE the wire (the
        # program cache is safe to branch on this — program_key encodes
        # the merge function)
        merge_fn = monoid = None
        if carry_rid and not fuse.is_list_agg(plan.epilogue[1].aggregator):
            merge_fn, monoid = self._merge_probe(plan)

        nk = getattr(plan, "epi_nk", 1) or 1
        in_specs = plan.in_specs
        bytes_width = fuse.epi_bytes_width(plan.epi_spec)

        def per_device(counts, *rest):
            n = counts[0]
            bounds = rest[0][0] if has_bounds else None
            leaves = rest[1:] if has_bounds else rest
            lv = self._widen_entry(in_specs, [l[0] for l in leaves])
            for op in ops:
                lv, n = op.apply(lv, n)
            k = lv[0]
            capn = k.shape[0]
            valid = jnp.arange(capn) < n
            if has_bounds:
                if nk == 1:
                    rid = collectives.range_dst(k, bounds, ascending,
                                                r, valid, r=r)
                else:
                    bcols = [bounds[:, i] for i in range(nk)]
                    rid = collectives.range_dst_cols(
                        lv[:nk], bcols, ascending, r, valid, r=r)
            else:
                rid = collectives.hash_dst_cols(
                    lv[:nk], r, valid, r=r, bytes_width=bytes_width)
            if carry_rid and (merge_fn is not None
                              or monoid is not None):
                cols, cnts, offs = collectives.bucketize_combine_rid(
                    rid, lv[:nk], lv[nk:], n, ndev, merge_fn,
                    monoid=monoid)
            elif carry_rid:
                dev = jnp.where(valid, rid % ndev,
                                ndev).astype(jnp.int32)
                cols, cnts, offs = collectives.bucketize(
                    k, [rid.astype(jnp.int64)] + lv, n, ndev, dst=dev)
            else:
                dev = jnp.where(valid, rid, ndev).astype(jnp.int32)
                cols, cnts, offs = collectives.bucketize(
                    k, lv, n, ndev, dst=dev)
            out = (cnts, offs) + tuple(cols)
            return tuple(jnp.expand_dims(o, 0) for o in out)

        n_in = 1 + nleaves_in + (1 if has_bounds else 0)
        n_out = 2 + (1 if carry_rid else 0) + len(plan.out_specs)
        fn = _shard_map(per_device, self.mesh,
                        in_specs=(P(AXIS),) * n_in,
                        out_specs=(P(AXIS),) * n_out)
        leaf0 = 1 + (1 if has_bounds else 0)
        self._compiled[key] = jax.jit(fn, donate_argnums=tuple(
            range(leaf0, leaf0 + nleaves_in)) if donate else ())
        return self._compiled[key]

    def _spill_wave(self, spool, runs, carry_rid, wave,
                    sorted_batch, writer, stats):
        """Host side of one wave's spill: read the (rid, key)-sorted
        columns back (the D2H copy was started async when the wave's
        sort finished, so this read rides behind the NEXT wave's
        compute), slice per logical partition, and hand runs to the
        background writer (or write inline when it's disabled)."""
        t0 = stats.now()
        counts = layout.host_read(sorted_batch.counts,
                                  site="wave_spill.counts")
        cols = [layout.host_read(l, site="wave_spill.col")
                for l in sorted_batch.cols]
        read_done = stats.now()
        for d in range(self.ndev):
            n = int(counts[d])
            if not n:
                continue
            if not carry_rid:                # device IS the partition
                path = os.path.join(spool, "%d-%d" % (d, wave))
                # COPY the slices for the background writer: views would
                # pin the whole wave's (ndev, cap) host arrays across the
                # writer queue, multiplying peak host RSS
                run_cols = [np.ascontiguousarray(col[d, :n])
                            for col in cols] if writer is not None \
                    else [col[d, :n] for col in cols]
                if writer is not None:
                    writer.put(path, run_cols)
                else:
                    self._write_run(path, run_cols)
                runs[d].append(path)
                continue
            rid = cols[0][d, :n]
            uniq = np.unique(rid)
            los = np.searchsorted(rid, uniq, side="left")
            his = np.searchsorted(rid, uniq, side="right")
            for u, lo, hi in zip(uniq.tolist(), los.tolist(),
                                 his.tolist()):
                path = os.path.join(spool, "%d-%d-%d" % (u, wave, d))
                run_cols = [np.ascontiguousarray(col[d, lo:hi])
                            for col in cols[1:]] if writer is not None \
                    else [col[d, lo:hi] for col in cols[1:]]
                if writer is not None:
                    writer.put(path, run_cols)
                else:
                    self._write_run(path, run_cols)
                runs[int(u)].append(path)
        stats.add_spill(stats.now() - t0, wave=wave)
        return read_done

    def _run_streamed_nocombine(self, plan, waves):
        """No-combine shuffle (sortByKey range exchange, groupByKey,
        partitionBy) over big input: each wave exchanges (with the
        LOGICAL partition id riding along when r exceeds the mesh),
        sorts by (rid, key) on device, and spills one key-sorted COLUMN
        run per logical partition to host disk; the export bridge
        premerges a partition's runs in the background once the stream
        ends (see _RunPremerger).  HBM holds one wave (one copy with
        donation on); host RAM holds one wave of columns (no Python row
        objects until the reduce).  r may exceed the mesh size — the
        cure for partition-sized reduce memory.

        The wave loop is a pipeline: while wave k computes on device,
        wave k+1 is device_putting (ingest thread), wave k-1's columns
        — whose D2H copy was started when its sort was dispatched —
        are being read back, split, and handed to the spill-writer
        thread.  STREAM_PIPELINE_DEPTH=0 restores the serial loop."""
        from dpark_tpu.env import env
        dep = plan.epilogue[1]
        r = dep.partitioner.num_partitions
        # unique per run: a re-run must never write into (then delete,
        # via the old store's drop_shuffle) the same directory
        self._spool_seq = getattr(self, "_spool_seq", 0) + 1
        spool = os.path.join(env.workdir, "hbmruns", "%d-%d"
                             % (dep.shuffle_id, self._spool_seq))
        os.makedirs(spool, exist_ok=True)
        runs = [[] for _ in range(r)]
        bounds = self._bounds_arg(plan)
        carry_rid = r > self.ndev
        # TRACEABLE merge riding the spilled stream (r > mesh): each
        # wave pre-reduces per (rid, key) on device before spilling, so
        # runs hold one combiner per distinct key per wave instead of
        # every row; export still folds across waves with the user's
        # merge_combiners (host_combine below)
        pre_merge = pre_monoid = None
        if carry_rid and not fuse.is_list_agg(dep.aggregator):
            pre_merge, pre_monoid = self._merge_probe(plan)
        donate = self._donation_enabled()
        depth = conf.STREAM_PIPELINE_DEPTH
        stats = _StreamStats(depth, donate)
        writer = _SpillWriter(self._write_run) if conf.SPILL_WRITER \
            else None
        slot_floor = 0                  # sticky size classes (see
        # _run_streamed_shuffle)
        pending = None          # (wave, sorted_batch, dispatch_time)
        batches = self._stream_batches(plan, waves, stats)
        ok = False
        try:
            for c, (batch, ingest_s) in enumerate(batches):
                t_wall = time.time() if trace._PLANE is not None \
                    else 0.0
                t_disp = stats.now()
                faults.hit("executor.dispatch")   # chaos site: per wave
                if trace._PLANE is not None:
                    trace.set_compile_sig(_plan_sig(plan))
                if aotcache._PLANE is not None:
                    aotcache.set_current_sig(
                        fuse.plan_adapt_signature(plan))
                jitted = self._compile_stream_nocombine(
                    plan, batch.cap, len(batch.cols), r,
                    tuple(str(c.dtype) for c in batch.cols),
                    donate=donate)
                args = (batch.counts,) + ((bounds,)
                                          if bounds is not None
                                          else ()) + tuple(batch.cols)
                self._capture_cost(plan, jitted, args)
                outs = jitted(*args)
                cnts, offs = outs[0], outs[1]
                leaves = list(outs[2:])      # [rid +] row leaves
                t_x = stats.now()
                recv = self._exchange_all(leaves, cnts, offs,
                                          slot_floor=slot_floor,
                                          donate=donate)
                exchange_s = stats.now() - t_x
                slot_floor = max(slot_floor, recv[2])
                nk = getattr(plan, "epi_nk", 1) or 1
                if pre_merge is not None or pre_monoid is not None:
                    sorted_batch = self._prereduce_received(
                        plan, recv, pre_merge, pre_monoid,
                        donate=donate)
                else:
                    sorted_batch = self._sort_received(
                        plan, recv,
                        nkeys=(1 + nk) if carry_rid else nk,
                        donate=donate)
                # start the wave's D2H now; the blocking read happens
                # one wave later (or immediately when depth == 0)
                _async_d2h([sorted_batch.counts] + sorted_batch.cols)
                stats.wave_done(ingest_s,
                                (stats.now() - t_disp) - exchange_s,
                                exchange_s)
                if depth <= 0:
                    read_done = self._spill_wave(
                        spool, runs, carry_rid, c, sorted_batch,
                        writer, stats)
                    stats.add_busy(t_disp, read_done)
                else:
                    if pending is not None:
                        pw, pb, pd = pending
                        read_done = self._spill_wave(
                            spool, runs, carry_rid, pw, pb,
                            writer, stats)
                        stats.add_busy(pd, read_done)
                    pending = (c, sorted_batch, t_disp)
                self._note_pipeline(stats)
                if trace._PLANE is not None:
                    trace.emit("wave", "exec", t_wall,
                               time.time() - t_wall, wave=c,
                               sig=_plan_sig(plan))
                logger.debug("streamed no-combine wave %d", c + 1)
            if pending is not None:
                pw, pb, pd = pending
                read_done = self._spill_wave(spool, runs, carry_rid,
                                             pw, pb, writer, stats)
                stats.add_busy(pd, read_done)
                pending = None
            if writer is not None:
                writer.finish()
                writer = None
            ok = True
        finally:
            close = getattr(batches, "close", None)
            if close is not None:
                close()
            if writer is not None:      # error path: drop queued runs
                writer.abort()
            if not ok:
                # the store never registered — nothing will ever call
                # drop_shuffle for this spool
                import shutil
                shutil.rmtree(spool, ignore_errors=True)
        self._note_pipeline(stats)
        self._trace_stream_phases(stats)
        host_combine = not fuse.is_list_agg(dep.aggregator)
        premerge = _RunPremerger(runs, self._read_run, self._write_run,
                                 spool,
                                 key_cols=getattr(plan, "epi_nk", 1)
                                 or 1)
        if conf.SPILL_WRITER:
            # pre-merge each partition's runs in the background NOW —
            # the reduce tasks that fetch later find a single sorted
            # run instead of paying the merge at first fetch
            premerge.start_background()
        return self._register_shuffle(dep, plan, {
            "leaves": [], "counts": None, "offsets": None,
            "host_runs": runs, "spool_dir": spool,
            "premerge": premerge,
            "no_combine": not host_combine,
            # untraceable merge: runs hold CREATED combiners (the
            # create op ran device-side); export folds equal keys with
            # the user's merge_combiners
            "host_combine": host_combine,
            "agg": dep.aggregator if host_combine else None,
            "encoded_keys": getattr(plan, "encoded_keys", False),
            "single_map": True,
        })

    def _run_recv_program(self, plan, recv, tag, extra_key, body,
                          donate=False):
        """Shared scaffolding for compiled programs consuming the
        exchange output (_sort_received / _prereduce_received): slice
        per-round receive buffers per device, run body(recvs, cnts) ->
        (count, leaves...), cache the jitted program per
        (tag, program_key, rounds, slot, nleaves, *extra_key).
        `donate` releases the receive buffers (dead after this program
        in the streamed wave loop) for in-place reuse."""
        recv_rounds, cnt_rounds, slot = recv
        rounds = len(recv_rounds)
        nleaves = len(recv_rounds[0])
        key = (tag, plan.program_key, rounds, slot,
               nleaves, donate) + tuple(extra_key)
        if key not in self._compiled:
            def per_device(*args):
                cnts = [c[0] for c in args[:rounds]]
                bufs = args[rounds:]
                recvs = []
                for r in range(rounds):
                    recvs.append([bufs[r * nleaves + li][0]
                                  for li in range(nleaves)])
                outs = body(recvs, cnts)
                return tuple(jnp.expand_dims(o, 0) for o in outs)

            fn = _shard_map(per_device, self.mesh,
                            in_specs=(P(AXIS),) * (rounds
                                                   + rounds * nleaves),
                            out_specs=(P(AXIS),) * (1 + nleaves))
            self._compiled[key] = jax.jit(fn, donate_argnums=tuple(
                range(rounds, rounds + rounds * nleaves))
                if donate else ())
        args = list(cnt_rounds)
        for r in range(rounds):
            args.extend(recv_rounds[r])
        return self._launch(tag, self._compiled[key], *args)

    def _rid_prefixed_treedef(self, plan):
        """plan.out_treedef with the rid column prepended FLAT: egested
        rows read (rid, k, v...) so callers can strip row[0]."""
        import jax.tree_util as jtu
        sample = jtu.tree_unflatten(
            plan.out_treedef, list(range(len(plan.out_specs))))
        assert isinstance(sample, tuple), sample
        return jtu.tree_structure((0,) + sample)

    def _sort_received(self, plan, recv, nkeys=1, donate=False):
        """Flatten exchange rounds and sort per device by the first
        `nkeys` leaves -> Batch (extra leading leaves beyond
        plan.out_specs, e.g. the rid column, ride along)."""
        def body(recvs, cnts):
            flat, mask = collectives.flatten_received(recvs, cnts)
            packed = collectives._lex_sort(tuple(flat), nkeys)
            n = jnp.sum(mask).astype(jnp.int32)
            return (n,) + tuple(packed)

        outs = self._run_recv_program(plan, recv, "wave_sort",
                                      (nkeys,), body, donate=donate)
        leaves = list(outs[1:])
        extra = len(leaves) - len(plan.out_specs)
        treedef = plan.out_treedef
        if extra:
            assert extra == 1, extra
            treedef = self._rid_prefixed_treedef(plan)
        return layout.Batch(treedef, leaves, outs[0])

    def _prereduce_received(self, plan, recv, merge_fn, monoid,
                            donate=False):
        """Flatten exchange rounds and segment-reduce per (rid, key) on
        device — the spilled-run stream's per-wave pre-combine for
        traceable merges with r beyond the mesh.  Returns the same
        rid-prefixed Batch shape as _sort_received, with rows equal in
        (rid, every key column) already merged."""
        nk = getattr(plan, "epi_nk", 1) or 1

        def body(recvs, cnts):
            flat, mask = collectives.flatten_received(recvs, cnts)
            ks, vs, n = collectives.segment_reduce_keys(
                flat[:1 + nk], flat[1 + nk:], mask, merge_fn,
                monoid=monoid)
            return (n,) + tuple(ks) + tuple(vs)

        outs = self._run_recv_program(plan, recv, "wave_prereduce",
                                      (nk,), body, donate=donate)
        return layout.Batch(self._rid_prefixed_treedef(plan),
                            list(outs[1:]), outs[0])

    @staticmethod
    def _write_run(path, rows):
        """One spill run to disk, framed with its crc32c (ISSUE 5):
        corruption surfaces at read as SpillCorruption -> FetchFailed
        (lineage recompute), never unpickled garbage.  A failed write
        (ENOSPC & co, including the shuffle.spill_write chaos site)
        cleans up its partial file and raises SpillWriteError so the
        consuming stage fails VISIBLY into the scheduler's task
        retry/escalation accounting."""
        import pickle
        import struct
        from dpark_tpu import coding, faults
        from dpark_tpu.shuffle import SpillWriteError, spill_crc
        from dpark_tpu.utils import atomic_file, compress
        blob = compress(pickle.dumps(rows, -1))
        # a SPAN with the measured write wall (was an instant event):
        # the health plane's spill.write latency sketch needs real
        # durations (ISSUE 14)
        t_w0 = time.time() if trace._PLANE is not None else 0.0
        code = coding.active_code()
        try:
            if code is not None:
                # coded run (ISSUE 6): a shard container with
                # per-shard crcs — a corrupted region is decoded
                # around at read instead of failing the whole run
                body = coding.encode_container(
                    blob, code, fault_site="shuffle.spill_write")
                with atomic_file(path) as f:
                    f.write(body)
                if trace._PLANE is not None:
                    trace.emit("spill.write", "shuffle", t_w0,
                               time.time() - t_w0, bytes=len(body))
                return
            # over the TRUE bytes, pre-corruption
            crc = spill_crc(blob)
            blob = faults.hit("shuffle.spill_write", blob)
            # tmp+rename: a failed or killed write never leaves a
            # partial file a reader could mistake for a short run
            with atomic_file(path) as f:
                f.write(struct.pack("<I", crc))
                f.write(blob)
            if trace._PLANE is not None:
                trace.emit("spill.write", "shuffle", t_w0,
                           time.time() - t_w0, bytes=len(blob))
        except OSError as e:
            raise SpillWriteError(
                "spill run %s write failed: %s" % (path, e)) from e

    @staticmethod
    def _read_run(path):
        import pickle
        import struct
        from dpark_tpu import coding, faults
        from dpark_tpu.shuffle import SpillCorruption, spill_crc
        from dpark_tpu.utils import decompress
        t_r0 = time.time() if trace._PLANE is not None else 0.0
        with open(path, "rb") as f:
            raw = f.read()
        if trace._PLANE is not None:
            trace.emit("spill.read", "shuffle", t_r0,
                       time.time() - t_r0, bytes=len(raw))
        if coding.is_container(raw):
            # coded run: per-shard crcs; corruption repairs by decode,
            # and only a sub-k survivor count escalates to lineage
            try:
                blob = coding.decode_container(
                    raw, fault_site="shuffle.spill_read")
            except coding.ShardShortfall as e:
                raise SpillCorruption(
                    "spill run %s: %d of %d shards survived "
                    "(%d needed)" % (path, e.found, e.total,
                                     e.needed)) from e
            return pickle.loads(decompress(blob))
        (crc,) = struct.unpack("<I", raw[:4])
        blob = faults.hit("shuffle.spill_read", raw[4:])
        if spill_crc(blob) != crc:
            # the export bridge's readers turn this into FetchFailed:
            # the parent device stage recomputes through lineage
            raise SpillCorruption(
                "spill run %s: crc32c mismatch (corrupted run)" % path)
        return pickle.loads(decompress(blob))

    def _exchange_all(self, leaves, counts, offsets, slot_floor=0,
                      donate=False):
        """Run exchange rounds for already-bucketized buffers; returns
        (recv_rounds, cnt_rounds, slot).  `slot_floor` pins the slot
        size class from below (stream loops pass their running max so
        light tail waves reuse the compiled exchange/merge programs).
        `donate` (streamed waves only, where the bucketized buffers die
        with this call) lets the LAST round reuse them in place —
        earlier rounds re-read the same buffers and never donate."""
        nleaves = len(leaves)
        cap = leaves[0].shape[1]
        if self.ndev == 1:
            # single-device mesh: the exchange is the identity — the
            # bucketized valid prefix IS the received data.  Skip the
            # narrowing probe (there is no wire), the collective
            # program, and every blocking readback (this runs per
            # wave, and a sync stalls the dispatch queue).  Who still
            # comes here: streamed waves, no-combine stores (groupByKey,
            # the join's sides, sortByKey's range write) and raw
            # combiners; an in-core COMBINED store is pre_reduced and
            # its reader skips this call too (_source_outs)
            self._note_identity_exchange(counts, cap)
            # consumers expect per-device (R=1, slot, ...) receive
            # buffers and (R=1,) counts — counts is already the (1, 1)
            # per-bucket array, leaves gain the source-device axis
            recv = [l.reshape((1, 1) + l.shape[1:]) for l in leaves]
            return [recv], [counts], cap
        host_counts = layout.host_read(counts, site="exchange.counts")
        max_run = int(host_counts.max()) if host_counts.size else 1
        mean = int(host_counts.sum()) // max(1, host_counts.size)
        # slot sizing: fine (1/16-octave) classes — power-of-two slots
        # alone cost up to 2x wire padding; uniform loads pad <=6.25%.
        # Sizing first snaps to an ALREADY-COMPILED slot within the
        # same tolerance, so a few percent of data drift between jobs
        # reuses the cached exchange/reduce programs instead of
        # compiling the adjacent fine class.
        ideal = min(max(64, 2 * mean), max(1, max_run))
        memo = self._slot_memo.setdefault(
            (tuple(str(l.dtype) for l in leaves), nleaves), set())
        cached = [s for s in memo if ideal <= s <= ideal + (ideal >> 4)]
        slot = min(cached) if cached else layout.round_capacity_fine(ideal)
        slot = max(slot, min(slot_floor, layout.round_capacity_fine(cap)))
        memo.add(slot)
        self.exchange_real_rows += int(host_counts.sum())
        narrow = self._narrow_plan(leaves, counts)
        exchange = self._compile_exchange(
            tuple(str(l.dtype) for l in leaves), nleaves, slot, cap,
            narrow=narrow)
        wire_itemsize = sum(
            (np.dtype(narrow[li]).itemsize if narrow and narrow[li]
             else leaves[li].dtype.itemsize)
            * int(np.prod(leaves[li].shape[2:], dtype=np.int64))
            for li in range(nleaves))
        sent = layout.put_sharded(
            np.zeros((self.ndev, self.ndev), np.int32), self._sharding())
        # the round count is KNOWN on the host (each round moves up to
        # `slot` rows of every src->dst bucket, so ceil(max_bucket/slot)
        # rounds drain everything) — no per-round blocking overflow
        # readback serializing dispatch; the program's overflow output
        # is ignored
        rounds = max(1, -(-max_run // slot))
        recv_rounds, cnt_rounds = [], []
        for r in range(rounds):
            fn = exchange
            if donate and r == rounds - 1:
                fn = self._compile_exchange(
                    tuple(str(l.dtype) for l in leaves), nleaves, slot,
                    cap, narrow=narrow, donate=True)
            outs = self._launch("exchange", fn, offsets, counts, sent,
                                *leaves)
            recv_cnt, sent = outs[0], outs[1]
            recv_rounds.append(list(outs[3:]))
            cnt_rounds.append(recv_cnt)
            self.exchange_wire_bytes += (
                self.ndev * self.ndev * slot * wire_itemsize)
            self.exchange_slot_rows += self.ndev * self.ndev * slot
        return recv_rounds, cnt_rounds, slot

    def _note_identity_exchange(self, counts, cap):
        """Row accounting of a one-device exchange that moves nothing:
        the valid rows offered (`exchange_real_rows`; the host sum is
        deferred to the next metric read, never a readback here) and
        the slots they sat in (`ingest_slot_rows`; the scheduler's
        `ingest_pad_efficiency` stage note is their ratio)."""
        self._pending_real_counts.append(counts)
        if len(self._pending_real_counts) > self._PENDING_COUNTS_MAX:
            self.exchange_real_rows  # property read drains the list
        self.ingest_slot_rows += cap

    def _merge_into_state(self, plan, state, recv, monoid,
                          merge_fn=None, donate=False):
        """Combine received rows (and the running state) into the new
        per-device unique-key state: a segmented scan of the monoid's
        operation for classified monoids, of the traced user merge
        otherwise.  `donate` releases the OLD state leaves (replaced by
        the program's output) and the receive buffers (dead after the
        merge) for in-place reuse; the per-round counts stay live (the
        ndev==1 fast path defers their host readback)."""
        recv_rounds, cnt_rounds, slot = recv
        rounds = len(recv_rounds)
        nleaves = len(recv_rounds[0])
        nk = getattr(plan, "epi_nk", 1) or 1
        has_state = state is not None
        state_cap = state[0][0].shape[1] if has_state else 0
        key = ("stream_merge", plan.program_key, rounds, slot, nleaves,
               state_cap, donate)
        if key not in self._compiled:
            def per_device(*args):
                i = 0
                if has_state:
                    st_leaves = [a[0] for a in args[:nleaves]]
                    st_n = args[nleaves][0]
                    i = nleaves + 1
                cnts = [c[0] for c in args[i:i + rounds]]
                bufs = args[i + rounds:]
                recvs = []
                for r in range(rounds):
                    recvs.append([bufs[r * nleaves + li][0]
                                  for li in range(nleaves)])
                flat, mask = collectives.flatten_received(recvs, cnts)
                if has_state:
                    stv = jnp.arange(state_cap) < st_n
                    kcol = jnp.where(
                        stv, st_leaves[0],
                        collectives._sentinel(st_leaves[0].dtype))
                    flat = [jnp.concatenate([kcol, flat[0]])] + [
                        jnp.concatenate([sl, fl])
                        for sl, fl in zip(st_leaves[1:], flat[1:])]
                    mask = jnp.concatenate([stv, mask])
                ks, vs, n = collectives.segment_reduce_keys(
                    flat[:nk], flat[nk:], mask, merge_fn,
                    monoid=monoid)
                out = (jnp.expand_dims(n, 0),) + tuple(
                    jnp.expand_dims(k, 0) for k in ks) + tuple(
                    jnp.expand_dims(v, 0) for v in vs)
                return out

            n_in = (nleaves + 1 if has_state else 0) \
                + rounds + rounds * nleaves
            dn = ()
            if donate:
                # old state leaves (args 0..nleaves-1 when present; NOT
                # the state counts at index nleaves) + receive buffers
                # (after the per-round counts)
                base = (nleaves + 1) if has_state else 0
                dn = (tuple(range(nleaves)) if has_state else ()) \
                    + tuple(range(base + rounds,
                                  base + rounds + rounds * nleaves))
            fn = _shard_map(per_device, self.mesh,
                            in_specs=(P(AXIS),) * n_in,
                            out_specs=(P(AXIS),) * (1 + nleaves))
            self._compiled[key] = jax.jit(fn, donate_argnums=dn)
        args = []
        if has_state:
            args.extend(state[0])
            args.append(state[1])
        args.extend(cnt_rounds)
        for r in range(rounds):
            args.extend(recv_rounds[r])
        outs = self._compiled[key](*args)
        counts, leaves = outs[0], list(outs[1:])
        # start the counts D2H without blocking: the caller shrinks the
        # state one wave later (_shrink_state), by which point the
        # transfer has ridden along behind the merge — the wave loop
        # never stalls on a device round trip just for a slice bound
        _async_d2h([counts])
        return (leaves, counts)

    def _shrink_state(self, state):
        """Slice the combined state down to the size class its counts
        need — bounds state growth across waves and keeps the merge
        program's state_cap compile key sticky.  The counts readback
        was issued async at merge time; reading it here is (near-)free."""
        leaves, counts = state
        host_n = int(layout.host_read(
            counts, site="stream.state_counts").max() or 1)
        want_cap = layout.round_capacity(host_n)
        if leaves[0].shape[1] > want_cap:
            leaves = [l[:, :want_cap] for l in leaves]
        return (leaves, counts)

    # ------------------------------------------------------------------
    # cogroup support: exchange one dep's rows to their reduce partitions
    # and return them key-sorted per partition (no combining)
    # ------------------------------------------------------------------
    def gather_rows(self, dep):
        """Device exchange + key sort for one no-combine shuffle dep;
        returns per-partition sorted row lists (host)."""
        with self._mesh_lock:
            store = self.shuffle_store[dep.shuffle_id]
            counts, leaves = self._exchange_sorted(dep, store)
            batch = layout.Batch(store["out_treedef"], leaves, counts)
            return [self._maybe_decode(store, rows)
                    for rows in layout.egest(batch)]

    # ------------------------------------------------------------------
    # device join: two exchanged+sorted sides expand to key-matched pairs
    # entirely on device (two-phase: count totals, then a static-capacity
    # gather program) — replaces the host merge for a.join(b)
    # ------------------------------------------------------------------
    def _exchange_sorted(self, dep, store, by_hash=False):
        """No-combine exchange leaving the result ON DEVICE: per-device
        key-sorted rows as (counts, leaves...) global arrays.  With
        `by_hash` the rows are ordered by one int64 word instead, a
        hash of the key's columns (collectives.key_hash64), which is
        returned as the leading leaf: a byte-string key of 13 words
        orders and matches as one."""

        # an instance, not a class made per call: a class is part of
        # reference cycles (its __dict__, its mro), so only the cyclic
        # collector would free it, and `source` holds the dependency
        # whose death releases the store
        out_specs = list(store["out_specs"])
        if by_hash:
            out_specs.insert(0, (np.dtype(np.int64), ()))
        plan = types.SimpleNamespace(
            source=("hbm", dep), ops=[], epilogue=None,
            src_combine=False, group_output=False, epi_spec=None,
            epi_bounds=None, epi_nk=1,
            # sort gathered rows by the FULL key (tuple keys span
            # key_cols columns) so cogroup/join consumers see the same
            # lexicographic order the host merge expects
            src_nk=store.get("key_cols", 1) or 1, src_hash=by_hash,
            in_treedef=store["out_treedef"],
            in_specs=store["out_specs"],
            out_treedef=store["out_treedef"],
            out_specs=out_specs, stage=None)
        plan.program_key = ("gather", plan.src_nk,
                            tuple((str(dt), shape)
                                  for dt, shape in store["out_specs"])) \
            + (("by_hash",) if by_hash else ())
        outs = self._run_exchange_and_reduce(plan)
        return outs[0], list(outs[1:])          # counts, leaves

    def run_device_join(self, dep_a, dep_b):
        """Per-partition inner join of two HBM-resident no-combine
        shuffles; returns per-partition host rows (k, (va, vb))."""
        with self._mesh_lock:
            return self._run_device_join(dep_a, dep_b)

    def _run_device_join(self, dep_a, dep_b):
        store_a = self.shuffle_store[dep_a.shuffle_id]
        batch = self.device_join_batch(dep_a, dep_b)
        rows_per_part = layout.egest(batch)
        if store_a.get("encoded_keys"):
            # both sides of a str-keyed join encode through the SAME
            # executor dict, so id equality == string equality; decode
            # at this host exit like every other
            rows_per_part = [self._maybe_decode(store_a, rows)
                             for rows in rows_per_part]
        return rows_per_part

    def device_join_batch(self, dep_a, dep_b):
        """Inner join of two HBM no-combine shuffles as a device Batch
        of (k, (va, vb)) rows — the array-path "join" source (keys stay
        on device; downstream ops + shuffle writes ride the mesh).
        With the trace plane on, one `join` span around its exchanges,
        launches and the `join.totals` read."""
        if trace._PLANE is None:
            return self._device_join_batch(dep_a, dep_b)[0]
        with trace.span("join", "exec") as sp:
            batch, args = self._device_join_batch(dep_a, dep_b)
            sp.args.update(args)
            return batch

    def _device_join_batch(self, dep_a, dep_b):
        store_a = self.shuffle_store[dep_a.shuffle_id]
        store_b = self.shuffle_store[dep_b.shuffle_id]
        if store_a.get("encoded_keys", False) != \
                store_b.get("encoded_keys", False):
            # ids on one side, user ints on the other: id equality would
            # be spurious — the host path compares decoded keys
            raise ValueError("mixed encoded/plain join keys")
        # composite (tuple) keys span the first nk columns on BOTH
        # sides (fuse.join_sides verified that widths and dtypes
        # agree).  Every key kind matches the same way, by the one
        # merge sort of collectives.join_ranges over the mk matched
        # columns, and the count program hands the ranges it found on
        # to the expansion.  What a byte-string key adds is what its
        # type forces: both sides come ordered by a 64-bit hash of the
        # key's words that leads their leaves (k0: where the record
        # begins) and is the one matched column, and the expansion
        # compares every key word of each pair it emits and drops the
        # pairs that differ, so the answer is exact whatever the hash
        # does
        nk = store_a.get("key_cols", 1) or 1
        key_bytes = layout.bytes_key_width(store_a["out_treedef"],
                                           len(store_a["out_specs"]))
        hashed = key_bytes is not None
        k0, mk = (1, 1) if hashed else (0, nk)
        cnt_a, lv_a = self._exchange_sorted(dep_a, store_a, hashed)
        cnt_b, lv_b = self._exchange_sorted(dep_b, store_b, hashed)
        na, nb = len(lv_a), len(lv_b)
        cap_a, cap_b = lv_a[0].shape[1], lv_b[0].shape[1]
        rec_a, rec_b = lv_a[k0:], lv_b[k0:]     # behind the hash word
        nra = len(rec_a)

        dtypes = tuple(str(l.dtype) for l in lv_a + lv_b)
        count_key = ("join_count", cap_a, cap_b, na, nb, mk, dtypes)
        if count_key not in self._compiled:
            if trace._PLANE is not None:
                trace.event("compile", "exec", program="join_count",
                            cap_a=cap_a, cap_b=cap_b, match="merge",
                            ranges="handed")

            def count_dev(ca, cb, *keys):
                lo, per = collectives.join_ranges(
                    [k[0] for k in keys[:mk]], [k[0] for k in keys[mk:]],
                    ca[0], cb[0])
                return tuple(jnp.expand_dims(o, 0)
                             for o in (jnp.sum(per), lo, per))
            fn = _shard_map(count_dev, self.mesh,
                            in_specs=(P(AXIS),) * (2 + 2 * mk),
                            out_specs=(P(AXIS),) * 3)
            self._compiled[count_key] = jax.jit(fn)
        totals, lo, per = self._launch(
            "join_count", self._compiled[count_key],
            cnt_a, cnt_b, *lv_a[:mk], *lv_b[:mk])
        # the pairs the LAST byte-string join dropped ride this read
        pending, self._join_dropped = self._join_dropped, None
        if pending is None:
            totals = layout.host_read(totals, site="join.totals")
        else:
            totals, dropped = layout.host_read([totals, pending],
                                               site="join.totals")
            self.join_pairs_dropped += int(dropped.sum())
        self.join_rows_out += int(totals.sum())
        cap_out = layout.round_capacity(int(totals.max() or 1))

        exp_key = ("join_expand", cap_a, cap_b, cap_out, na, nb, mk,
                   dtypes) + ((("hashed", nk),) if hashed else ())
        if exp_key not in self._compiled:
            if trace._PLANE is not None:
                trace.event("compile", "exec", program="join_expand",
                            cap_a=cap_a, cap_b=cap_b, cap_out=cap_out,
                            match="merge", ranges="handed")

            def expand_dev(lo, per, *records):
                A = [l[0] for l in records[:nra]]
                B = [l[0] for l in records[nra:]]
                total = jnp.sum(per[0])
                i, bi = collectives.join_slots(lo[0], per[0], cap_out,
                                               cap_b)
                out = collectives.take_rows(A, i)
                if not hashed:
                    out += collectives.take_rows(B[nk:], bi)
                    return (jnp.expand_dims(total, 0),) + tuple(
                        jnp.expand_dims(o, 0) for o in out)
                picked = collectives.take_rows(B, bi)
                same = jnp.arange(cap_out) < total
                for x, y in zip(out[:nk], picked[:nk]):
                    same = same & (x == y)
                out = out + picked[nk:]
                kept = jnp.sum(same).astype(total.dtype)
                # a hash that matched where the bytes do not: pack the
                # true pairs to the front (a sort; never taken while
                # 64 bits tell the keys apart)
                out = lax.cond(
                    kept < total,
                    lambda: collectives.compact(out, same)[0],
                    lambda: list(out))
                return tuple(jnp.expand_dims(o, 0)
                             for o in [kept, total - kept] + out)
            # count [, dropped], a's record, b's values
            n_out = 1 + int(hashed) + nra + len(rec_b) - nk
            fn = _shard_map(expand_dev, self.mesh,
                            in_specs=(P(AXIS),) * (2 + nra + len(rec_b)),
                            out_specs=(P(AXIS),) * n_out)
            self._compiled[exp_key] = jax.jit(fn)
        outs = self._launch("join_expand", self._compiled[exp_key],
                            lo, per, *rec_a, *rec_b)
        counts, leaves = outs[0], list(outs[1:])
        if hashed:
            self._join_dropped, leaves = leaves[0], leaves[1:]

        # rows are (k..., va..., vb...); records are (k, (va, vb)) with
        # the key subtree (scalar or flat tuple) taken from side a
        import jax.tree_util as jtu
        ta = store_a["out_treedef"]
        tb = store_b["out_treedef"]
        sample_a = jtu.tree_unflatten(ta, list(range(nra)))
        sample_b = jtu.tree_unflatten(tb, list(range(len(rec_b))))
        joined_sample = (sample_a[0], (sample_a[1], sample_b[1]))
        out_treedef = jtu.tree_structure(joined_sample)
        return layout.Batch(out_treedef, leaves, counts), {
            "rows_out": int(totals.sum()), "key_bytes": key_bytes or 8 * nk,
            "cap_a": cap_a, "cap_b": cap_b}

    # ------------------------------------------------------------------
    # host bridge
    # ------------------------------------------------------------------
    def has_shuffle(self, sid):
        return sid in self.shuffle_store

    def export_bucket(self, sid, map_id, reduce_id, shard=None):
        """Device-resident map output -> host (k, combiner) items, for
        host-path reduce stages (shuffle.read_bucket 'hbm://' uris).
        With `shard` set (coded shuffle, ISSUE 6) returns ONE framed
        erasure shard of the bucket's serialized payload instead —
        the fetch side decodes from the fastest k of n.  Wall time
        accumulates in `export_seconds` (the per-phase bench table's
        "export" column)."""
        import time as _time
        t0 = _time.perf_counter()
        t_wall = _time.time() if trace._PLANE is not None else 0.0
        try:
            if shard is not None:
                return self._export_shard(sid, map_id, reduce_id,
                                          shard)
            return self._export_bucket(sid, map_id, reduce_id)
        finally:
            self.export_seconds += _time.perf_counter() - t0
            if trace._PLANE is not None:
                # named phase.export so the critical-path analyzer's
                # export total matches phase_table()'s export column
                trace.emit("phase.export", "phase", t_wall,
                           _time.time() - t_wall, shuffle=sid,
                           map=map_id, reduce=reduce_id)

    def export_bucket_cols(self, sid, map_id, reduce_id):
        """Device-resident map output -> (meta, [numpy column arrays])
        for the bulk data plane (ISSUE 12): a peer controller receives
        the RAW COLUMN BYTES and assembles them zero-copy into
        np.frombuffer views / device_put batches — the per-row
        pickle/unpickle of the host bridge never runs.  Raises
        KeyError when this executor owns no such shuffle (the server
        tries the next exporter) and ValueError when the record shape
        cannot columnarize (encoded keys, spilled host runs, nested
        records) — the server then falls back to the pickled payload,
        still chunk-framed on the bulk channel.  The materialized
        columns are bit-equal sources of the rows export_bucket would
        have pickled (both sides materialize via .tolist())."""
        import time as _time
        t0 = _time.perf_counter()
        t_wall = _time.time() if trace._PLANE is not None else 0.0
        try:
            return self._export_bucket_cols(sid, map_id, reduce_id)
        finally:
            self.export_seconds += _time.perf_counter() - t0
            if trace._PLANE is not None:
                trace.emit("phase.export", "phase", t_wall,
                           _time.time() - t_wall, shuffle=sid,
                           map=map_id, reduce=reduce_id, cols=True)

    def _export_bucket_cols(self, sid, map_id, reduce_id):
        import jax.tree_util as jtu
        store = self.shuffle_store.get(sid)
        if store is None:
            raise KeyError("no HBM shuffle %d" % sid)
        if store.get("encoded_keys") or "host_runs" in store \
                or store.get("single_map"):
            raise ValueError("store %d cannot columnarize for the "
                             "bulk plane" % sid)
        if store["out_treedef"] != jtu.tree_structure((0, 0)):
            raise ValueError("columnar export needs flat (k, v) "
                             "records")
        store["seq"] = self._next_seq()     # least-recently-FETCHED
        if store.get("pre_reduced"):
            # device d holds reduce partition d fully combined: the
            # whole bucket exposes as map 0 (same contract as
            # _export_bucket)
            if map_id != 0:
                return {"no_combine": False}, []
            with self._export_lock:
                # (ndev,), or an in-core store's (ndev=1, R=1)
                counts = _export_read(store["counts"]).reshape(-1)
                cnt = int(counts[reduce_id])
                if not cnt:
                    return {"no_combine": False}, []
                mats = [np.ascontiguousarray(
                    self._read_dev_slice(l, reduce_id)[:cnt])
                    for l in store["leaves"]]
            return {"no_combine": False}, mats
        wrap = bool(store.get("no_combine"))
        with self._export_lock:
            counts = _export_read(store["counts"])
            offsets = _export_read(store["offsets"])
            off = int(offsets[map_id, reduce_id])
            cnt = int(counts[map_id, reduce_id])
            if not cnt:
                return {"no_combine": wrap}, []
            mats = [np.ascontiguousarray(
                self._read_dev_slice(l, map_id)[off:off + cnt])
                for l in store["leaves"]]
        return {"no_combine": wrap}, mats

    # serialized+encoded bucket shards kept for re-fetch; beyond this
    # the oldest buckets drop (re-encoding is cheap vs re-exporting)
    _SHARD_CACHE_BYTES = 64 << 20

    def _export_shard(self, sid, map_id, reduce_id, idx):
        from dpark_tpu import coding
        from dpark_tpu.utils import compress
        # per-exchange code (ISSUE 19): an adaptively-escalated
        # shuffle serves coded frames even with the global code off,
        # and a pinned-uncoded one refuses the shard protocol so the
        # fetch side falls back to whole buckets
        code = coding.shuffle_code(sid)
        if code is None:
            raise ValueError(
                "shard export requested with no shuffle code active")
        import pickle
        key = (sid, map_id, reduce_id)
        # lock-free fast path: a built bucket's n shard requests must
        # not queue behind another bucket's export (dict reads are
        # GIL-atomic; entries are only ever replaced whole)
        frames = self._shard_cache.get(key)
        if frames is None:
            # lock ORDER on the build path: mesh before shard_build —
            # _export_bucket's device read takes the mesh lock, and a
            # stage registering a shuffle holds the mesh lock while
            # drop_shuffle takes shard_build; acquiring shard_build
            # first here would deadlock those two threads (ISSUE 9:
            # concurrent jobs make this race real)
            with self._mesh_lock, self._shard_build_lock:
                frames = self._shard_cache.get(key)
                if frames is None:
                    # KeyError (no such hbm shuffle) propagates so the
                    # fetch side tries the next exporter, same as the
                    # whole-bucket protocol
                    rows = self._export_bucket(sid, map_id, reduce_id)
                    blob = compress(pickle.dumps(rows, -1))
                    frames = coding.encode_bucket_frames(blob, code)
                    self._shard_cache[key] = frames
                    self._shard_cache_bytes += sum(
                        len(f) for f in frames)
                    # insertion-ordered (FIFO) eviction: shard fetches
                    # for one bucket arrive within one reduce task's
                    # fan-out, so age tracks usefulness closely enough
                    while (self._shard_cache_bytes
                           > self._SHARD_CACHE_BYTES
                           and len(self._shard_cache) > 1):
                        old_key = next(iter(self._shard_cache))
                        if old_key == key:
                            break
                        dropped = self._shard_cache.pop(old_key)
                        self._shard_cache_bytes -= sum(
                            len(f) for f in dropped)
        if not 0 <= idx < len(frames):
            raise ValueError("shard index %d out of range (n=%d)"
                             % (idx, len(frames)))
        return frames[idx]

    def program_cache_stats(self):
        """Hit/miss/evict counters of the bounded compiled-program
        cache (ISSUE 9): /metrics, the web UI per-job cache column,
        and the warm-submit bench read these.  With the AOT plane
        installed (ISSUE 17) the disk tier's load/store/warm counters
        ride along under "aot"."""
        out = self._compiled.stats()
        aot = aotcache.stats()
        if aot is not None:
            out["aot"] = aot
        return out

    def _export_bucket(self, sid, map_id, reduce_id):
        store = self.shuffle_store.get(sid)
        if store is None:
            raise KeyError("no HBM shuffle %d" % sid)
        store["seq"] = self._next_seq()     # least-recently-FETCHED
        #   ordering for the disk spiller (ISSUE 9 satellite)
        if store.get("pre_reduced"):
            # device d holds reduce partition d fully combined: expose it
            # as map 0's bucket (other maps contribute nothing)
            if map_id != 0:
                return []
            with self._export_lock:
                # (ndev,), or an in-core store's (ndev=1, R=1)
                counts = _export_read(store["counts"]).reshape(-1)
                cnt = int(counts[reduce_id])
                if not cnt:
                    return []
                mats = [self._read_dev_slice(l, reduce_id)[:cnt]
                        for l in store["leaves"]]
            treedef, mats = layout.host_columns(store["out_treedef"],
                                                mats)
            lists = [m.tolist() for m in mats]
            rows = [jax.tree_util.tree_unflatten(
                treedef, [pl[i] for pl in lists]) for i in range(cnt)]
            return self._maybe_decode(store, rows)
        if "host_runs" in store:
            # streamed no-combine shuffle: per-partition COLUMN runs on
            # host disk.  The background premerger usually got here
            # first (one merged key-sorted run per partition); a
            # not-yet-merged partition merges via the same per-rid
            # once-lock, so first-fetch never races the walker.  The
            # whole shuffle exports through map 0.
            if map_id != 0:
                return []
            cols = self._partition_run_cols(store, reduce_id)
            if cols is None:
                return []
            treedef, cols = layout.host_columns(store["out_treedef"],
                                                cols)
            lists = [c.tolist() for c in cols]
            flat2 = jax.tree_util.tree_structure((0, 0))
            if store.get("host_combine"):
                # fold the user's merge_combiners over each sorted key
                # group: values in the runs are already CREATED
                # combiners, so this is exactly the reference's
                # external merge of sorted runs — O(1) state per key
                mc = store["agg"].merge_combiners
                rows = []
                cur_k = cur_c = None
                have = False
                for i in range(len(lists[0])):
                    if treedef == flat2:
                        k, v = lists[0][i], lists[1][i]
                    else:
                        rec = jax.tree_util.tree_unflatten(
                            treedef, [pl[i] for pl in lists])
                        k, v = rec[0], rec[1]
                    if have and k == cur_k:
                        cur_c = mc(cur_c, v)
                    else:
                        if have:
                            rows.append((cur_k, cur_c))
                        cur_k, cur_c, have = k, v, True
                if have:
                    rows.append((cur_k, cur_c))
            elif treedef == flat2:
                # flat (k, v) records — one zip, no per-row treedef work
                rows = [(k, [v]) for k, v in zip(lists[0], lists[1])]
            else:
                rows = []
                for i in range(len(lists[0])):
                    rec = jax.tree_util.tree_unflatten(
                        treedef, [pl[i] for pl in lists])
                    rows.append((rec[0], [rec[1]]))
            return self._maybe_decode(store, rows)
        if store.get("single_map"):
            # device rows don't correspond to logical map partitions
            # (text ingest): the whole shuffle exports through map 0
            if map_id != 0:
                return []
            with self._export_lock:
                counts = _export_read(store["counts"])
                offsets = _export_read(store["offsets"])
                rows = []
                for dev in range(counts.shape[0]):
                    rows.extend(self._export_one(store, dev, reduce_id,
                                                 counts, offsets))
            return self._maybe_decode(store, rows)
        with self._export_lock:
            counts = _export_read(store["counts"])
            offsets = _export_read(store["offsets"])
            rows = self._export_one(store, map_id, reduce_id, counts,
                                    offsets)
        return self._maybe_decode(store, rows)

    @staticmethod
    def _read_dev_slice(arr, dev):
        """One device's row of a (ndev, ...) store leaf as numpy.  The
        fully-addressable case pulls just that slice off the device; a
        process-spanning leaf replicates through host_read first (the
        host bridge is the slow path — correctness over bytes here)."""
        if getattr(arr, "is_fully_addressable", True):
            arr = lax.slice_in_dim(arr, dev, dev + 1, axis=0)
            dev = 0
        return _export_read(arr)[dev]

    def _export_one(self, store, dev, reduce_id, counts, offsets):
        """One device's bucket for one reduce partition as host rows."""
        off = int(offsets[dev, reduce_id])
        cnt = int(counts[dev, reduce_id])
        if not cnt:
            return []
        mats = [self._read_dev_slice(l, dev)[off:off + cnt]
                for l in store["leaves"]]
        treedef, mats = layout.host_columns(store["out_treedef"], mats)
        lists = [m.tolist() for m in mats]
        wrap = store.get("no_combine", False)
        rows = []
        for i in range(cnt):
            rec = jax.tree_util.tree_unflatten(
                treedef, [pl[i] for pl in lists])
            if wrap:
                # no-combine rows are raw (k, v); the host merge
                # contract expects (k, combiner=[v])
                rec = (rec[0], [rec[1]])
            rows.append(rec)
        return rows

    def _maybe_decode(self, store, rows):
        """Dictionary-encoded string keys leave the device as ids; every
        host-facing exit decodes them back."""
        if not store.get("encoded_keys") or not rows:
            return rows
        td = self.token_dict
        return [(td.decode(int(r[0])),) + tuple(r[1:]) for r in rows]

    def drop_shuffle(self, sid, reason="drop"):
        with self._shard_build_lock:
            for key in [k for k in self._shard_cache if k[0] == sid]:
                self._shard_cache_bytes -= sum(
                    len(f) for f in self._shard_cache.pop(key))
        store = self.shuffle_store.pop(sid, None)
        if store:
            self._store_bytes -= store["nbytes"]
            if trace._PLANE is not None:
                # ledger plane (ISSUE 15): residency ends — the sink
                # accrues bytes x held seconds against the account
                # that STORED it (reason "spill" marks an eviction
                # adjusting the live HBM picture, not a data drop)
                trace.event("hbm.release", "exec", sid=sid,
                            bytes=store["nbytes"], reason=reason,
                            job=store.get("job"))
            else:
                # tracing turned off after the store registered: the
                # sink's residency entry must still settle, or the
                # live gauge reports freed memory forever and the
                # tenant's byte-seconds never accrue
                from dpark_tpu import ledger
                sink = ledger._SINK
                if sink is not None:
                    try:
                        sink.fold({"name": "hbm.release",
                                   "ts": time.time(),
                                   "job": store.get("job"),
                                   "args": {"sid": sid,
                                            "bytes": store["nbytes"],
                                            "reason": reason}})
                    except Exception:
                        pass
            if store.get("premerge") is not None:
                # stop the background merger BEFORE deleting the spool
                # it is reading/writing
                store["premerge"].stop()
            if store.get("spool_dir"):
                import shutil
                shutil.rmtree(store["spool_dir"], ignore_errors=True)

    def _check_cached_keys(self, batch):
        """Cached batches feeding a shuffle get the same sentinel guard as
        ingest: a real key equal to the padding sentinel (or inf/nan)
        would be silently dropped by the reduce — force host fallback.
        The test is a handful of eager jnp operations, each an XLA
        dispatch of its own and none a compiled stage program: one
        `eager` span (site `keycheck`) covers them, and
        program_launches does not count them."""
        counts = batch.counts
        # every key column of a composite (or byte-string) key; one
        # column takes exactly the operations it always took
        nk = layout.key_width(
            batch.treedef, [(np.dtype(c.dtype), tuple(c.shape[2:]))
                            for c in batch.cols], kinds="if") or 1
        sp = trace._NOOP
        plane = trace._PLANE
        if plane is not None:
            sp = trace.span("eager", "exec", site="keycheck")
        with sp:
            bad = None
            for keys in batch.cols[:nk]:
                valid = jnp.arange(keys.shape[1])[None, :] \
                    < counts[:, None]
                if jnp.issubdtype(keys.dtype, jnp.floating):
                    hit = jnp.any(valid & (jnp.isinf(keys)
                                           | jnp.isnan(keys)))
                else:
                    hit = jnp.any(valid
                                  & (keys == jnp.iinfo(keys.dtype).max))
                bad = hit if bad is None else bad | hit
        if bool(layout.host_read(bad, site="keycheck")):
            raise ValueError("cached key equals the device sentinel; "
                             "taking the host path")

    def stop(self):
        from dpark_tpu import cache as cache_mod
        from dpark_tpu import shuffle as shuffle_mod
        shuffle_mod.HBM_EXPORTERS.pop(self._exporter_key, None)
        shuffle_mod.HBM_COL_EXPORTERS.pop(self._exporter_key, None)
        cache_mod.DEVICE_CACHES.pop(self._cache_key, None)
        if self._tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._tracing = False
        for sid in list(self.shuffle_store):
            self.drop_shuffle(sid)      # also removes spool dirs
        self.result_cache.clear()
