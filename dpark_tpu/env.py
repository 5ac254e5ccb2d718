"""Process-level runtime wiring for driver and workers.

Reference parity: dpark/env.py (DparkEnv singleton + global `env`) — picks a
writable workdir from DPARK_WORK_DIR candidates and wires the map-output
tracker, cache tracker and shuffle fetcher into every process (SURVEY.md
sections 1 and 2.1).

Single-host simplification vs the reference: the zmq TrackerServer becomes an
in-process dict on the driver; workers receive map-output *snapshots*
embedded in task payloads (parent stages are always complete before a reduce
task is serialized, so a snapshot is exact — see schedule.py).  A TCP tracker
for multi-host DCN deployments lives in tracker.py.
"""

import os
import socket
import tempfile
import uuid


class MapOutputTracker:
    """shuffle_id -> list of per-map-task output URIs (None = missing)."""

    def __init__(self):
        self.locs = {}

    def register_outputs(self, shuffle_id, locs):
        self.locs[shuffle_id] = list(locs)

    def get_outputs(self, shuffle_id):
        return self.locs.get(shuffle_id)

    def remove_outputs(self, shuffle_id):
        self.locs.pop(shuffle_id, None)

    def invalidate_host(self, shuffle_id, host):
        locs = self.locs.get(shuffle_id, [])
        for i, uri in enumerate(locs):
            if uri and host in uri:
                locs[i] = None

    def snapshot(self, shuffle_ids):
        return {sid: self.locs[sid] for sid in shuffle_ids
                if sid in self.locs}

    def update(self, snap):
        self.locs.update(snap)


class DparkEnv:
    def __init__(self):
        from dpark_tpu.hostatus import TaskHostManager
        self.started = False
        self.is_master = False
        self.workdir = None
        self.map_output_tracker = MapOutputTracker()
        self.cache = None                 # set by cache.py on start
        self.shuffle_fetcher = None       # set by shuffle.py on start
        self.session_id = None
        self.bucket_server = None         # DCN data plane, opt-in
        self.tracker_client = None        # DCN metadata plane, opt-in
        self.tracker_addr = None
        # ONE host-health view per process, shared by the scheduler's
        # task placement AND the shuffle fetcher's replica choice —
        # fetch failures inform placement and vice versa (SURVEY.md
        # section 5.3; hostatus.py)
        self.host_manager = TaskHostManager()

    def start(self, is_master=True, environ=None):
        if self.started:
            return
        self.started = True
        self.is_master = is_master
        environ = environ or {}
        self.session_id = environ.get(
            "DPARK_SESSION", uuid.uuid4().hex[:12])
        self.workdir = environ.get("DPARK_WORKDIR") or self._pick_workdir()
        os.makedirs(self.workdir, exist_ok=True)

        # trace plane (ISSUE 8): a worker process inherits the
        # driver's mode/dir through the shipped environ (covers
        # programmatic trace.configure() on the driver, which env vars
        # alone would miss).  Re-configuring also re-stamps the plane
        # with THIS process's pid — a plane inherited by fork (the
        # forkserver imported trace with DPARK_TRACE set) carries the
        # parent's pid, which would corrupt the latest-counter-per-pid
        # merge.  Spool files are per-pid, so workers never contend.
        if not is_master:
            from dpark_tpu import trace
            tmode = environ.get("DPARK_TRACE")
            try:
                if tmode:
                    trace.configure(tmode,
                                    environ.get("DPARK_TRACE_DIR"),
                                    run=environ.get("DPARK_TRACE_RUN"))
                elif trace.active():
                    trace.configure(trace.mode(), trace.trace_dir(),
                                    run=trace.run_id())
            except Exception:
                pass

        from dpark_tpu.shuffle import ParallelShuffleFetcher
        from dpark_tpu.cache import Cache
        self.shuffle_fetcher = ParallelShuffleFetcher()
        self.cache = Cache(self.workdir)
        if environ.get("DPARK_BUCKET_SERVER") \
                or os.environ.get("DPARK_BUCKET_SERVER"):
            self.start_bucket_server()
        addr = environ.get("DPARK_TRACKER") \
            or os.environ.get("DPARK_TRACKER")
        if addr:
            from dpark_tpu.tracker import TrackerClient
            self.tracker_client = TrackerClient(addr)
            self.tracker_addr = addr

    def start_bucket_server(self, port=0):
        """Serve this process's shuffle buckets + broadcast chunks over
        TCP (the DCN data plane); shuffle URIs switch to tcp://."""
        if self.bucket_server is None:
            from dpark_tpu.dcn import BucketServer
            self.bucket_server = BucketServer(
                self.workdir, port=port).start()
        return self.bucket_server

    def _pick_workdir(self):
        from dpark_tpu import conf
        for cand in conf.DPARK_WORK_DIR.split(","):
            cand = cand.strip()
            if not cand:
                continue
            try:
                path = os.path.join(cand, "dpark-%s" % self.session_id)
                os.makedirs(path, exist_ok=True)
                return path
            except OSError:
                continue
        return tempfile.mkdtemp(prefix="dpark-")

    def environ_for_worker(self):
        out = {"DPARK_SESSION": self.session_id,
               "DPARK_WORKDIR": self.workdir}
        if getattr(self, "mem_limit", None):
            out["DPARK_MEM_LIMIT"] = str(self.mem_limit)
        if getattr(self, "profile", False):
            out["DPARK_PROFILE"] = "1"
        from dpark_tpu import trace
        if trace.active():
            out["DPARK_TRACE"] = trace.mode()
            out["DPARK_TRACE_DIR"] = trace.trace_dir()
            out["DPARK_TRACE_RUN"] = trace.run_id()
        return out

    def stop(self):
        if not self.started:
            return
        self.started = False
        if self.shuffle_fetcher:
            self.shuffle_fetcher.stop()
        if self.bucket_server is not None:
            self.bucket_server.stop()
            self.bucket_server = None
        if self.tracker_client is not None:
            self.tracker_client.close()
            self.tracker_client = None
            self.tracker_addr = None

    @property
    def host(self):
        return socket.gethostname()


env = DparkEnv()
