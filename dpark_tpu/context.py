"""DparkContext — the user entry point.

Reference parity: dpark/context.py — root-RDD constructors
(parallelize/makeRDD/textFile/partialTextFile/csvFile/binaryFile/tableFile/
union/zip), broadcast/accumulator factories, master selection by -m
(local / process / tpu), and the runJob funnel every action goes through
(SURVEY.md sections 2.1 and 3.4).

The reference's masters are local/process/mesos; mesos is replaced by the
TPU master (`-m tpu`), which executes stages as jitted SPMD programs over a
jax device mesh (backend/tpu/).
"""

import argparse
import atexit
import os
import sys
import threading
import time

import importlib
import itertools

_accumulator = importlib.import_module("dpark_tpu.accumulator")
import dpark_tpu.rdd as _rdd
from dpark_tpu import trace
from dpark_tpu.broadcast import Broadcast
from dpark_tpu.env import env
from dpark_tpu.utils.log import get_logger

logger = get_logger("context")

parser = argparse.ArgumentParser(add_help=False)
parser.add_argument("-m", "--master", default=None,
                    help="master: local, process[:N], tpu (default local)")
parser.add_argument("-p", "--parallel", type=int, default=0,
                    help="default parallelism")
parser.add_argument("-c", "--cpus", type=float, default=1.0,
                    help="cpus per task (process master)")
parser.add_argument("-M", "--mem", type=float, default=None,
                    help="MB per task")
parser.add_argument("--profile", action="store_true",
                    help="profile task execution")
parser.add_argument("--conf", default=None, help="path to conf file")
parser.add_argument("--webui", nargs="?", const="127.0.0.1:0",
                    default=None, metavar="HOST:PORT",
                    help="serve a live progress UI")

optParser = parser          # reference-parity alias


def parse_options(args=None):
    options, _ = parser.parse_known_args(args)
    if options.conf:
        from dpark_tpu import conf
        conf.load_conf(options.conf)
    return options


class DparkContext:
    _active = None

    def __init__(self, master=None, **kw):
        options = parse_options([])
        self.master = (master or options.master
                       or os.environ.get("DPARK_MASTER") or "local")
        self.options = options
        self.scheduler = None
        self.started = False
        self.checkpoint_dir = None
        self._parallel = kw.get("parallel", options.parallel)
        DparkContext._active = self

    # -- lifecycle -------------------------------------------------------
    def start(self):
        if self.started:
            return
        env.start(is_master=True)
        if self.options.mem:
            env.mem_limit = self.options.mem
        if self.options.profile:
            env.profile = True
        master, _, arg = self.master.partition(":")
        # resident executor service (ISSUE 9): with DPARK_SERVICE set
        # (or master "service[:spec]"), this context attaches to the
        # process-global JobServer instead of owning a scheduler — the
        # mesh, compiled-program cache, and HBM shuffle store amortize
        # across every context/job in the process.  Unset, the seam
        # costs one string check.
        from dpark_tpu import conf as _conf
        svc = _conf.DPARK_SERVICE
        if master == "service" or svc:
            from dpark_tpu import service as service_mod
            spec = arg if master == "service" and arg else (svc or None)
            self.scheduler = service_mod.client_scheduler(spec)
        elif master == "local":
            from dpark_tpu.schedule import LocalScheduler
            self.scheduler = LocalScheduler()
        elif master in ("process", "multiprocess"):
            from dpark_tpu.schedule import MultiProcessScheduler
            self.scheduler = MultiProcessScheduler(
                int(arg) if arg else None)
        elif master == "fleet":
            # N workdir-distinct inline executors on this host with
            # locality-aware placement (chunkserver / cached-partition
            # hints route tasks to the holder)
            from dpark_tpu.schedule import LocalFleetScheduler
            self.scheduler = LocalFleetScheduler(
                int(arg) if arg else 2)
        elif master == "tpu":
            try:
                from dpark_tpu.backend.tpu import TPUScheduler
            except ImportError as e:
                raise NotImplementedError(
                    "the tpu master requires dpark_tpu.backend.tpu "
                    "(import failed: %s)" % e) from e
            self.scheduler = TPUScheduler(int(arg) if arg else None)
        else:
            raise ValueError(
                "unknown master %r (local/process/fleet/tpu)"
                % self.master)
        self.scheduler.start()
        webui = self.options.webui or os.environ.get("DPARK_WEBUI")
        if webui:
            from dpark_tpu.web import start_ui
            host, _, port = str(webui).partition(":")
            self._web, url = start_ui(self.scheduler, host or "127.0.0.1",
                                      int(port or 0))
            print("dpark_tpu web ui: %s" % url, file=sys.stderr)
        self.started = True
        atexit.register(self.stop)

    def stop(self):
        if not self.started:
            return
        self.started = False
        web = getattr(self, "_web", None)
        if web is not None:
            web.shutdown()
            web.server_close()
            self._web = None
        if self.scheduler:
            prof = getattr(self.scheduler, "profile", None)
            if prof is not None:
                import sys
                print(prof.summary(20), file=sys.stderr)
            self.scheduler.stop()
        # a service-attached context shares env (workdir, fetcher,
        # trackers) with every other tenant of the resident server —
        # one tenant leaving must not tear the process down
        if not getattr(self.scheduler, "is_service_client", False):
            env.stop()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- ids / config ----------------------------------------------------
    # process-global, not per-context: the partition cache and HBM stores
    # key by rdd id, and multiple contexts (e.g. streaming recovery)
    # share those singletons in one process
    _rdd_id_counter = [0]
    # concurrent drivers on a resident job server (ISSUE 9) mint rdd
    # ids from their own threads; the read-modify-write must be atomic
    _rdd_id_lock = threading.Lock()

    def new_rdd_id(self):
        with DparkContext._rdd_id_lock:
            DparkContext._rdd_id_counter[0] += 1
            return DparkContext._rdd_id_counter[0]

    @classmethod
    def advance_rdd_ids(cls, minimum):
        """Recovery: never re-mint ids at or below a restored high-water
        mark (checkpoint dirs are keyed rdd-<id> in a persistent dir)."""
        with cls._rdd_id_lock:
            cls._rdd_id_counter[0] = max(cls._rdd_id_counter[0],
                                         int(minimum))

    @property
    def default_parallelism(self):
        if self._parallel:
            return self._parallel
        self.start()
        return self.scheduler.default_parallelism()

    defaultParallelism = default_parallelism

    def setCheckpointDir(self, path):
        os.makedirs(path, exist_ok=True)
        self.checkpoint_dir = path

    # -- root RDD constructors ------------------------------------------
    def parallelize(self, seq, numSlices=None):
        return _rdd.ParallelCollection(self, seq, numSlices)

    def makeRDD(self, seq, numSlices=None):
        return self.parallelize(seq, numSlices)

    def textFile(self, path, ext="", followLink=True, numSplits=None,
                 splitSize=None):
        if path.endswith(".gz"):
            return _rdd.GZipFileRDD(self, path, splitSize, numSplits)
        if path.endswith(".bz2"):
            return _rdd.BZip2FileRDD(self, path, splitSize, numSplits)
        return _rdd.TextFileRDD(self, path, numSplits, splitSize)

    def partialTextFile(self, path, begin, end, splitSize=None):
        return _rdd.PartialTextFileRDD(self, path, begin, end, splitSize)

    def csvFile(self, path, dialect="excel", numSplits=None,
                splitSize=None):
        # record-aware splits: quoted fields may contain newlines
        return _rdd.CSVFileRDD(self, path, dialect, splitSize,
                               numSplits)

    def binaryFile(self, path, fmt="I", length=None, numSplits=None):
        return _rdd.BinaryFileRDD(self, path, fmt, length, numSplits)

    def tableFile(self, path, numSplits=None):
        """Pickle-part-file table reader (pairs with saveAsTableFile)."""
        return _rdd.CheckpointRDD(self, path)

    def table(self, rdd_or_path, fields=None):
        from dpark_tpu.table import TableRDD
        if isinstance(rdd_or_path, str):
            rdd_or_path = self.tableFile(rdd_or_path)
        return TableRDD(rdd_or_path, fields)

    def sql(self, query, /, **tables):
        """Minimal SELECT front over TableRDDs:
        ctx.sql("select region, sum(qty) as q from t group by region",
                t=my_table).
        Where the query scans is what its table is made of
        (query/planner.py): part files and driver-resident slices scan
        on the driver in numpy; a table over a cached RDD that is
        resident on the device (`ctx.table(rdd.cache(), fields)` after
        an action has filled the cache) scans, filters and projects
        inside the stage program that aggregates it; any other RDD is
        served by the host row chain."""
        from dpark_tpu.table import execute
        return execute(query, tables)

    def beansdb(self, path, raw=False, check_crc=True):
        from dpark_tpu.beansdb import BeansdbFileRDD
        return BeansdbFileRDD(self, path, raw, check_crc)

    def tabular(self, path, fields=None, wanted=None,
                predicate_ranges=None):
        from dpark_tpu.tabular import TabularRDD
        return TabularRDD(self, path, fields, wanted, predicate_ranges)

    def union(self, rdds):
        return _rdd.UnionRDD(self, list(rdds))

    def zip(self, rdds):
        return _rdd.ZippedRDD(self, list(rdds))

    # -- shared state ----------------------------------------------------
    def accumulator(self, init=0, param=None):
        return _accumulator.Accumulator(
            init, param or _accumulator.numAcc)

    def broadcast(self, value):
        self.start()
        return Broadcast(value)

    # -- execution -------------------------------------------------------
    def runJob(self, rdd, func, partitions=None, allow_local=False):
        self.start()
        # pre-flight gate (dpark_tpu/analysis/): lint the lineage —
        # shuffle anti-patterns and silent-wrong-answer shapes — before
        # the scheduler sees it.  Runs EAGERLY here (run_job returns a
        # lazy generator), so DPARK_LINT=error refuses a bad plan at
        # submit time, not at first iteration.
        from dpark_tpu.analysis import preflight
        plane = trace._PLANE
        if plane is not None:
            # the `preflight` span: no job id exists yet, so the reading
            # rides on the scheduler's thread-local to the job's way in
            # (DAGScheduler._begin_job), which emits it under the id
            t0 = time.time()
            preflight(rdd, master=self.master, func=func)
            self.scheduler._tls.preflight = (t0, time.time() - t0)
        else:
            preflight(rdd, master=self.master, func=func)
        return self.scheduler.run_job(rdd, func, partitions, allow_local)

    def clear(self):
        pass
