"""Multi-controller bulk data plane (ISSUE 12): the 2-process-on-one-
box topology — a PEER CONTROLLER process runs real jobs on a tpu:2
mesh, keeps its shuffle stores HBM-resident, and serves them over its
bucket server; THIS process (a second controller with its own workdir)
fetches the map outputs over the chunked bulk channel and reduces them
through the production fetch/merge machinery, asserting bit-identical
results to the peer's own in-process collect().  Nothing is shared but
the network.

Plus in-process protocol cells: torn/corrupt frame rejection
(dcn.transfer chaos site both sides), bounded retry on the shared
backoff schedule, the per-peer stream window, zero-copy column
assembly into device_put batches, HMAC-tagged streams, and the
JobServer's per-tenant bulk result streams.
"""

import json
import operator
import os
import pickle
import subprocess
import sys
import threading
import time

import pytest

from dpark_tpu import bulkplane, coding, conf, dcn, faults, trace
from dpark_tpu.dependency import Aggregator
from dpark_tpu.shuffle import (DiskSpillMerger, FetchFailed,
                               LocalFileShuffle, read_bucket,
                               read_bucket_any)
from dpark_tpu.utils import atomic_file, compress

# reduce-side merge triples matching the peer's jobs: combined values
# merge with +, no-combine group lists concatenate
_ADD_AGG = Aggregator(lambda v: v, operator.add, operator.add)
_LIST_AGG = Aggregator(lambda v: [v], lambda c, v: c + [v],
                       lambda a, b: a + b)


def _fetch_partition(sid, rid, agg):
    """Exactly what ShuffledRDD.compute does: the production fetcher
    feeding a DiskSpillMerger."""
    from dpark_tpu.env import env
    merger = DiskSpillMerger(agg, shuffle_id=sid, reduce_id=rid)
    env.shuffle_fetcher.fetch(sid, rid, merger.merge)
    return list(merger)


def _register(peer, sid):
    from dpark_tpu.env import env
    env.map_output_tracker.register_outputs(
        sid, list(peer["locs"][str(sid)]))


# ---------------------------------------------------------------------------
# the peer controller process (module-scoped: jax + 3 jobs once)
# ---------------------------------------------------------------------------

_PEER_SCRIPT = r'''
import json, os, pickle, sys, time
workdir, tracker_addr = sys.argv[1], sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2"
                           ).strip()
import jax
jax.config.update("jax_platforms", "cpu")
from dpark_tpu.env import env
env.start(is_master=True, environ={"DPARK_WORKDIR": workdir,
                                   "DPARK_BUCKET_SERVER": "1"})
from dpark_tpu.tracker import TrackerClient
from dpark_tpu import DparkContext
t = TrackerClient(tracker_addr)
ctx = DparkContext("tpu:2")
ctx.start()
uri = env.bucket_server.addr
locs = env.map_output_tracker.locs
jobs = {}

def new_sids(before):
    return sorted(s for s in locs if s not in before)

pairs = [(i % 33, i % 7) for i in range(4000)]

before = set(locs)
red = (ctx.parallelize(pairs, 2).map(lambda kv: (kv[0], kv[1] + 1))
       .reduceByKey(lambda a, b: a + b, 2))
ref_red = dict(red.collect())
(sid_red,) = new_sids(before)
jobs["reduce"] = {"sid": sid_red, "nsplits": 2,
                  "ref": pickle.dumps(ref_red, -1).hex()}

before = set(locs)
grp = ctx.parallelize(pairs, 2).groupByKey(2) \
         .mapValue(lambda vs: (len(vs), sum(vs)))
ref_grp = dict(grp.collect())
(sid_grp,) = new_sids(before)
jobs["group"] = {"sid": sid_grp, "nsplits": 2,
                 "ref": pickle.dumps(ref_grp, -1).hex()}

before = set(locs)
left = [(i % 16, i) for i in range(512)]
right = [(j % 16, j * 10) for j in range(64)]
jn = ctx.parallelize(left, 2).join(ctx.parallelize(right, 2), 2)
ref_join = sorted(jn.collect())
sids_join = new_sids(before)
assert len(sids_join) == 2, sids_join
jobs["join"] = {"sids": sids_join, "nsplits": 2,
                "ref": pickle.dumps(ref_join, -1).hex()}

# every map output of every shuffle is served by THIS controller's
# bucket server: peers fetch hbm:// stores through it
pub = {str(s): [uri for _ in ls] for s, ls in locs.items()}
t.set("bulk:jobs", json.dumps(jobs))
t.set("bulk:locs", json.dumps(pub))
t.set("bulk:ready", "1")
print("PEER_READY", flush=True)
deadline = time.time() + 600
while time.time() < deadline and not t.get("bulk:done"):
    time.sleep(0.1)
ctx.stop()
print("PEER_EXIT", flush=True)
'''


@pytest.fixture(scope="module")
def peer(tmp_path_factory):
    """Spawn the serving controller; yields {"jobs", "locs", "proc"}.
    The peer runs with DPARK_SHUFFLE_CODE=rs(4,2) so its export bridge
    can answer per-shard frame requests (the coded chaos cell); its
    OWN jobs are unaffected (the device all_to_all never carries
    parity)."""
    from dpark_tpu.tracker import TrackerServer, TrackerClient
    srv = TrackerServer()
    srv.start()
    tmp = tmp_path_factory.mktemp("bulk-peer")
    script = str(tmp / "peer.py")
    with open(script, "w") as f:
        f.write(_PEER_SCRIPT)
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = repo_root + os.pathsep + \
        child_env.get("PYTHONPATH", "")
    child_env["DPARK_SHUFFLE_CODE"] = "rs(4,2)"
    child_env.pop("DPARK_FAULTS", None)
    child_env.pop("XLA_FLAGS", None)
    wd = str(tmp / "wd-peer")
    os.makedirs(wd, exist_ok=True)
    proc = subprocess.Popen(
        [sys.executable, script, wd, srv.addr],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=child_env)
    cli = TrackerClient(srv.addr)
    try:
        deadline = time.time() + 300
        while time.time() < deadline and not cli.get("bulk:ready"):
            if proc.poll() is not None:
                out, _ = proc.communicate()
                raise RuntimeError("peer died during setup:\n%s" % out)
            time.sleep(0.1)
        assert cli.get("bulk:ready"), "peer never became ready"
        jobs = json.loads(cli.get("bulk:jobs"))
        locs = json.loads(cli.get("bulk:locs"))
        for job in jobs.values():
            job["ref"] = pickle.loads(bytes.fromhex(job["ref"]))
        yield {"jobs": jobs, "locs": locs, "proc": proc}
    finally:
        try:
            cli.set("bulk:done", "1")
        except Exception:
            pass
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
        cli.close()
        srv.stop()


# ---------------------------------------------------------------------------
# 2-process parity matrix (cross-controller hbm:// over the bulk
# channel, hot path asserted via trace spans)
# ---------------------------------------------------------------------------

def _assert_bulk_only_spans():
    """The acceptance assert: every dcn transfer during the fetches
    rode the bulk channel — the pickled host bridge (a `dcn.transfer`
    span with a bucket kind) never ran."""
    spans = trace.snapshot()
    bulk = [r for r in spans if r["name"] == "dcn.bulk.fetch"]
    bridge = [r for r in spans
              if r["name"] == "dcn.transfer"
              and (r.get("args") or {}).get("kind")
              in ("bucket", "bucket_shard")]
    assert bulk, "no dcn.bulk.fetch spans recorded"
    assert not bridge, "pickled host bridge used: %r" % bridge


def test_two_controller_reduce_parity(peer):
    """reduceByKey: the peer's HBM-resident map outputs, fetched over
    the bulk channel and merged by the production reduce machinery
    INSIDE A REAL LOCAL JOB, are bit-identical to the peer's own
    collect() — with zero resubmits/recomputes, and the stage record
    carrying the remote-fetch byte count."""
    from dpark_tpu import DparkContext
    job = peer["jobs"]["reduce"]
    sid, nsplits = job["sid"], job["nsplits"]
    _register(peer, sid)
    trace.configure("ring")
    rx0 = bulkplane.total_received_bytes()
    try:
        ctx = DparkContext("local")

        def fetch_part(rid):
            return _fetch_partition(sid, rid, _ADD_AGG)

        parts = ctx.parallelize(list(range(nsplits)), nsplits) \
                   .map(fetch_part).collect()
        got = dict(kv for part in parts for kv in part)
        assert got == job["ref"]
        rec = ctx.scheduler.history[-1]
        assert rec.get("resubmits", 0) == 0, rec
        assert rec.get("recomputes", 0) == 0, rec
        # per-stage remote-fetch byte accounting (web UI column)
        assert any(st.get("remote_fetch_bytes", 0) > 0
                   for st in rec.get("stage_info", ())), \
            rec.get("stage_info")
        assert bulkplane.total_received_bytes() > rx0
        _assert_bulk_only_spans()
        ctx.stop()
    finally:
        trace.configure("off")


def test_two_controller_group_parity(peer):
    """groupByKey().mapValues: the peer's no-combine group store,
    fetched over the bulk channel, reproduces the peer's
    mapValue((len, sum)) bit-identically."""
    job = peer["jobs"]["group"]
    sid, nsplits = job["sid"], job["nsplits"]
    _register(peer, sid)
    trace.configure("ring")
    try:
        got = {}
        for rid in range(nsplits):
            for k, vs in _fetch_partition(sid, rid, _LIST_AGG):
                got[k] = (len(vs), sum(vs))
        assert got == job["ref"]
        _assert_bulk_only_spans()
    finally:
        trace.configure("off")


def test_two_controller_join_parity(peer):
    """join: both parent shuffles fetched cross-controller, cogrouped
    with the production CoGroupMerger, pair-expanded — bit-identical
    to the peer's joined collect()."""
    from dpark_tpu.shuffle import CoGroupMerger
    job = peer["jobs"]["join"]
    sid_l, sid_r = job["sids"]
    nsplits = job["nsplits"]
    _register(peer, sid_l)
    _register(peer, sid_r)
    trace.configure("ring")
    try:
        rows = []
        for rid in range(nsplits):
            merger = CoGroupMerger(2)
            for si, sid in enumerate((sid_l, sid_r)):
                merger.extend(si, _fetch_partition(sid, rid,
                                                   _LIST_AGG))
            for k, (ls, rs) in merger:
                for va in ls:
                    for vb in rs:
                        rows.append((k, (va, vb)))
        assert sorted(rows) == job["ref"]
        _assert_bulk_only_spans()
    finally:
        trace.configure("off")


def _coded_round(peer, spec):
    """One seeded chaos round of the cross-controller coded reduce,
    run as a REAL local job: returns (coding stats delta is read by
    the caller) after asserting bit-identical results and zero
    resubmits/recomputes on the job record."""
    from dpark_tpu import DparkContext
    job = peer["jobs"]["reduce"]
    sid, nsplits = job["sid"], job["nsplits"]
    _register(peer, sid)
    faults.configure(spec)
    ctx = DparkContext("local")
    try:
        def fetch_part(rid):
            return _fetch_partition(sid, rid, _ADD_AGG)

        parts = ctx.parallelize(list(range(nsplits)), nsplits) \
                   .map(fetch_part).collect()
        got = dict(kv for part in parts for kv in part)
        assert got == job["ref"]
        rec = ctx.scheduler.history[-1]
        assert rec.get("resubmits", 0) == 0, rec
        assert rec.get("recomputes", 0) == 0, rec
        assert faults.stats()["shuffle.fetch"]["fired"] > 0
    finally:
        ctx.stop()
        faults.configure(None)


def test_two_controller_coded_decode_under_faults(peer, monkeypatch):
    """Coded decode ACROSS CONTROLLERS (the chaos cell): with rs(4,2)
    active, the fastest-k-of-n shard race runs process-to-process over
    bulk shard frames.  Two injection shapes, both completing
    bit-identically with ZERO resubmits/recomputes (decode instead of
    lineage):

    * REPAIR — single-attempt shard fetches with the first two
      attempts failing outright (`times=2` bounds the erasures below
      any bucket's parity count m=2, so a decode failure is
      structurally impossible): parity reconstructs the lost data
      shards, repair > 0.
    * STRAGGLER WIN — injected delays lose the race: parity arrives
      before the slow data shards, straggler_win > 0, no failure
      anywhere.

    The hit->shard mapping rides thread scheduling, so each shape
    retries a few seeded rounds until its counter moves — every round
    still asserts parity and zero lineage recovery."""
    coding.configure("rs(4,2)")
    trace.configure("ring")
    try:
        # repair: permanent loss of the first two shard attempts
        monkeypatch.setattr(conf, "SHUFFLE_SHARD_ATTEMPTS", 1)
        coding.reset_counters()
        for _ in range(8):
            _coded_round(peer, "shuffle.fetch:p=1,seed=0,times=2")
            if coding.stats()["repair"] > 0:
                break
        stats = coding.stats()
        assert stats["repair"] > 0, stats
        assert stats["decode_failures"] == 0, stats

        # straggler win: delays only — no failure mode exists at all
        monkeypatch.setattr(conf, "SHUFFLE_SHARD_ATTEMPTS", 3)
        coding.reset_counters()
        for round_no in range(5):
            _coded_round(
                peer, "shuffle.fetch:p=0.5,seed=%d,kind=delay,ms=250"
                % (11 + round_no))
            if coding.stats()["straggler_win"] > 0:
                break
        stats = coding.stats()
        assert stats["straggler_win"] > 0, stats
        assert stats["decode_failures"] == 0, stats
        _assert_bulk_only_spans()
    finally:
        trace.configure("off")
        faults.configure(None)
        coding.configure(None)
        coding.reset_counters()


def test_two_controller_midstream_loss_recovers(peer):
    """A deterministic mid-stream frame loss on the READING side
    (dcn.transfer nth=1): the first bulk stream dies mid-transfer, the
    bounded-backoff retry re-reads on a fresh connection, and the
    reduce still matches bit-identically."""
    job = peer["jobs"]["reduce"]
    sid, nsplits = job["sid"], job["nsplits"]
    _register(peer, sid)
    before = bulkplane.stats()
    faults.configure("dcn.transfer:nth=1")
    try:
        got = {}
        for rid in range(nsplits):
            got.update(dict(_fetch_partition(sid, rid, _ADD_AGG)))
        assert got == job["ref"]
        after = bulkplane.stats()
        assert after["torn_streams"] > before["torn_streams"]
        assert after["retries"] > before["retries"]
        assert faults.stats()["dcn.transfer"]["fired"] == 1
    finally:
        faults.configure(None)


# ---------------------------------------------------------------------------
# in-process protocol cells
# ---------------------------------------------------------------------------

@pytest.fixture()
def disk_server(tmp_path):
    """A BucketServer over a workdir holding one 2-partition shuffle,
    written by the real map-side path."""
    wd = str(tmp_path / "srv-wd")
    os.makedirs(wd)
    buckets = {0: [("a", 1), ("b", 2)], 1: [("c", 3)]}
    for rid, items in buckets.items():
        path = LocalFileShuffle.get_output_file(51, 0, rid, workdir=wd)
        with atomic_file(path) as f:
            f.write(compress(pickle.dumps(items, -1)))
    srv = dcn.BucketServer(wd, host="127.0.0.1").start()
    yield srv, buckets
    srv.stop()


def test_disk_bucket_rides_bulk_channel(disk_server):
    srv, buckets = disk_server
    before = bulkplane.stats()
    assert read_bucket(srv.addr, 51, 0, 0) == buckets[0]
    assert read_bucket(srv.addr, 51, 0, 1) == buckets[1]
    after = bulkplane.stats()
    assert after["streams"] >= before["streams"] + 2
    assert after["received"].get(srv.addr, 0) \
        > before["received"].get(srv.addr, 0)


def test_bulk_plane_off_uses_plain_protocol(disk_server, monkeypatch):
    srv, buckets = disk_server
    monkeypatch.setattr(conf, "BULK_PLANE", False)
    trace.configure("ring")
    try:
        assert read_bucket(srv.addr, 51, 0, 0) == buckets[0]
        spans = trace.snapshot()
        assert any(r["name"] == "dcn.transfer" for r in spans)
        assert not any(r["name"] == "dcn.bulk.fetch" for r in spans)
    finally:
        trace.configure("off")


def test_corrupt_frame_rejected_then_retried(disk_server):
    """kind=corrupt at the dcn.transfer site flips payload bytes AFTER
    the frame crc was computed over the true bytes (in-flight
    corruption): the receiver rejects the frame, retries on a fresh
    connection, and returns the correct data — never garbage."""
    srv, buckets = disk_server
    before = bulkplane.stats()
    faults.configure("dcn.transfer:nth=1,kind=corrupt")
    try:
        assert read_bucket(srv.addr, 51, 0, 0) == buckets[0]
        after = bulkplane.stats()
        assert after["corrupt_frames"] > before["corrupt_frames"]
        assert after["retries"] > before["retries"]
        assert faults.stats()["dcn.transfer"]["fired"] == 1
    finally:
        faults.configure(None)


def test_peer_death_every_attempt_surfaces_fetchfailed(disk_server):
    """Persistent mid-stream death (every chunk transfer dies): the
    bounded retries exhaust and read_bucket_any translates the
    transport error into FetchFailed — lineage recovery's signal,
    with the real error chained."""
    srv, _ = disk_server
    faults.configure("dcn.transfer:p=1,seed=0")
    try:
        with pytest.raises(FetchFailed) as ei:
            read_bucket_any([srv.addr], 51, 0, 0)
        assert ei.value.__cause__ is not None
    finally:
        faults.configure(None)


def test_midstream_peer_kill_surfaces_fetchfailed(tmp_path):
    """A REAL peer process killed mid-stream: the peer serves a large
    bucket with a per-chunk delay, the fetcher starts reading, the
    peer is SIGKILLed — the torn stream retries against a dead port
    and surfaces as FetchFailed."""
    wd = str(tmp_path / "victim-wd")
    os.makedirs(wd)
    path = LocalFileShuffle.get_output_file(61, 0, 0, workdir=wd)
    big = [(i, os.urandom(64).hex()) for i in range(40000)]
    with atomic_file(path) as f:
        f.write(compress(pickle.dumps(big, -1)))
    script = str(tmp_path / "victim.py")
    with open(script, "w") as f:
        f.write(
            "import sys, time\n"
            "from dpark_tpu.dcn import BucketServer\n"
            "from dpark_tpu import faults\n"
            # slow every chunk so the parent can kill mid-stream
            "faults.configure('dcn.transfer:p=1,seed=0,kind=delay,"
            "ms=400')\n"
            "srv = BucketServer(sys.argv[1], host='127.0.0.1')"
            ".start()\n"
            "print('ADDR %s' % srv.addr, flush=True)\n"
            "time.sleep(600)\n")
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    child_env = dict(os.environ)
    child_env["PYTHONPATH"] = repo_root + os.pathsep + \
        child_env.get("PYTHONPATH", "")
    child_env["DPARK_BULK_CHUNK_BYTES"] = "65536"
    proc = subprocess.Popen([sys.executable, script, wd],
                            stdout=subprocess.PIPE, text=True,
                            env=child_env)
    try:
        addr = proc.stdout.readline().split()[1]
        got = {}

        def fetch():
            try:
                read_bucket_any([addr], 61, 0, 0)
                got["result"] = "ok"
            except FetchFailed as e:
                got["result"] = e

        t = threading.Thread(target=fetch)
        t.start()
        time.sleep(1.0)          # several 400ms chunk delays in
        proc.kill()              # peer dies mid-stream
        proc.wait()
        t.join(timeout=60)
        assert not t.is_alive(), "fetch hung after peer death"
        assert isinstance(got["result"], FetchFailed), got["result"]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


def test_retry_backoff_reuses_connect_schedule(disk_server,
                                               monkeypatch):
    """The bulk read retry sleeps the SAME exponential-full-jitter
    schedule as the dcn connect retry (dcn.backoff_delays — one
    implementation, two call sites): attempt k sleeps uniform in
    [base*2^k/2, base*2^k]."""
    srv, _ = disk_server
    slept = []
    monkeypatch.setattr(bulkplane.time, "sleep",
                        lambda d: slept.append(d))
    faults.configure("dcn.transfer:p=1,seed=0")
    try:
        with pytest.raises(Exception):
            bulkplane.fetch(srv.addr, ("bulk_bucket", 51, 0, 0))
    finally:
        faults.configure(None)
    attempts = conf.BULK_READ_ATTEMPTS
    assert len(slept) == attempts - 1, slept
    base = conf.DCN_CONNECT_BACKOFF
    for k, d in enumerate(slept):
        assert base * (2 ** k) * 0.5 <= d <= base * (2 ** k), (k, d)


def test_per_peer_stream_window(monkeypatch):
    """BULK_STREAMS_PER_PEER=1 serializes concurrent streams against
    one peer: two fetches of a 0.3s-to-serve payload take >= 0.55s
    wall."""
    monkeypatch.setattr(conf, "BULK_STREAMS_PER_PEER", 1)
    bulkplane._windows.clear()

    def serve(req):
        data = b"x" * 128

        def gen():
            time.sleep(0.3)
            yield data

        return dcn.BulkPayload(
            {"kind": "blob", "nchunks": 1, "total_bytes": len(data)},
            gen())

    srv = dcn.FramedServer(serve, host="127.0.0.1").start()
    uri = "tcp://%s:%d" % srv.bind_address
    try:
        t0 = time.time()
        ts = [threading.Thread(
            target=lambda: bulkplane.fetch(uri, ("bulk_win",)))
            for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert time.time() - t0 >= 0.55
    finally:
        srv.stop()
        bulkplane._windows.clear()


def test_cols_assemble_zero_copy_into_device_put(disk_server,
                                                 monkeypatch):
    """The columnar stream assembles as np.frombuffer VIEWS over the
    received buffer (no copy) and goes straight to jax.device_put —
    and the item reconstruction is bit-identical to the pickled-rows
    form."""
    import numpy as np
    from dpark_tpu import shuffle as shuffle_mod
    srv, _ = disk_server
    cols = [np.arange(100, dtype=np.int64),
            (np.arange(100, dtype=np.int64) * 3) % 17]

    def col_exporter(sid, map_id, reduce_id):
        if sid != 77:
            raise KeyError(sid)
        return {"no_combine": False}, cols

    def rows_exporter(sid, map_id, reduce_id, shard=None):
        if sid != 77:
            raise KeyError(sid)
        return list(zip(cols[0].tolist(), cols[1].tolist()))

    monkeypatch.setitem(shuffle_mod.HBM_COL_EXPORTERS, "t",
                        col_exporter)
    monkeypatch.setitem(shuffle_mod.HBM_EXPORTERS, "t", rows_exporter)
    meta, view = bulkplane.fetch(srv.addr, ("bulk_bucket", 77, 0, 0))
    assert meta["kind"] == "cols", meta
    got_cols = bulkplane.cols_from_buf(meta, view)
    assert [c.tolist() for c in got_cols] == [c.tolist() for c in cols]
    # zero-copy: the views share the received buffer, no owning copy
    assert all(c.base is not None for c in got_cols)
    dev = bulkplane.device_put_cols(meta, view)
    assert [np.asarray(d).tolist() for d in dev] \
        == [c.tolist() for c in cols]
    # and the item form is bit-identical to what the bridge pickles
    assert read_bucket(srv.addr, 77, 0, 0) == rows_exporter(77, 0, 0)


def test_bulk_stream_hmac_tagged_with_secret(disk_server,
                                             monkeypatch):
    srv, buckets = disk_server
    monkeypatch.setenv("DPARK_DCN_SECRET", "s3cret")
    assert read_bucket(srv.addr, 51, 0, 0) == buckets[0]
    # an in-flight corrupted chunk under the secret fails the chunk
    # MAC — which keeps the crc path's BOUNDED RETRY (a transient flip
    # must not skip straight to lineage recovery on secured clusters)
    before = bulkplane.stats()
    faults.configure("dcn.transfer:nth=1,kind=corrupt")
    try:
        assert read_bucket(srv.addr, 51, 0, 1) == buckets[1]
        after = bulkplane.stats()
        assert after["corrupt_frames"] > before["corrupt_frames"]
        assert after["retries"] > before["retries"]
    finally:
        faults.configure(None)


def test_executor_cols_export_matches_rows(tmp_path):
    """export_bucket_cols is a bit-equal columnar twin of
    export_bucket on a real tpu:2 HBM store, for every (map, reduce)
    bucket."""
    from dpark_tpu import DparkContext
    ctx = DparkContext("tpu:2")
    ctx.start()
    try:
        # held: the store lives as long as the RDD that shuffled it
        rdd = ctx.parallelize([(i % 11, i % 5) for i in range(2000)], 2) \
            .reduceByKey(lambda a, b: a + b, 2)
        got = dict(rdd.collect())
        assert len(got) == 11
        ex = ctx.scheduler.executor
        assert ex.shuffle_store, "job did not ride the array path"
        sid = sorted(ex.shuffle_store)[-1]
        nonempty = 0
        for map_id in range(2):
            for rid in range(2):
                rows = ex.export_bucket(sid, map_id, rid)
                meta, cols = ex.export_bucket_cols(sid, map_id, rid)
                items = list(zip(cols[0].tolist(),
                                 cols[1].tolist())) if cols else []
                if meta.get("no_combine"):
                    items = [(k, [v]) for k, v in items]
                assert items == rows, (map_id, rid)
                nonempty += bool(rows)
        assert nonempty, "store exported no data at all"
    finally:
        ctx.stop()


def test_service_bulk_result_streams_per_tenant():
    """Remote tenants' job results multiplex over the bulk channel;
    per-tenant stream bytes land in service_stats()['bulk'], and the
    plain path still serves pre-bulk clients (BULK_PLANE off)."""
    from dpark_tpu import service as svc_mod
    framed = svc_mod.serve("127.0.0.1:0", master="local")
    try:
        host, port = framed.bind_address
        addr = "%s:%d" % (host, port)

        def job(ctx):
            return dict(ctx.parallelize(
                [(i % 3, 1) for i in range(300)], 2)
                .reduceByKey(lambda a, b: a + b, 2).collect())

        expect = {0: 100, 1: 100, 2: 100}
        c1 = svc_mod.ServiceClient(addr, client="tenant-a")
        c2 = svc_mod.ServiceClient(addr, client="tenant-b")
        got = {}
        ts = [threading.Thread(
                  target=lambda: got.update(a=c1.run(job))),
              threading.Thread(
                  target=lambda: got.update(b=c2.run(job)))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert got.get("a") == expect and got.get("b") == expect, got
        # the server counts a stream's bytes AFTER its last chunk is
        # written, so a client can hold its result before the counter
        # moves: wait for both tenants, bounded
        deadline = time.time() + 10
        st = c1.stats()
        while time.time() < deadline and not (
                st["bulk"].get("tenant-a") and st["bulk"].get("tenant-b")):
            time.sleep(0.05)
            st = c1.stats()
        assert st["bulk"].get("tenant-a", 0) > 0, st
        assert st["bulk"].get("tenant-b", 0) > 0, st
        # result streams also land in the bulk plane's per-peer sent
        # counters (/metrics must see ALL bulk traffic)
        assert sum(bulkplane.stats()["sent"].values()) > 0
        # pre-bulk client compatibility: the plain single-frame path
        old = conf.BULK_PLANE
        conf.BULK_PLANE = False
        try:
            assert svc_mod.ServiceClient(
                addr, client="tenant-old").run(job) == expect
        finally:
            conf.BULK_PLANE = old
    finally:
        framed.stop()
        svc_mod.shutdown()


def test_broadcast_chunks_ride_bulk(tmp_path):
    """Broadcast chunk files serve over the bulk channel with the
    same P2P serve accounting the origin-serves assertions rely on."""
    from dpark_tpu.broadcast import Broadcast
    from dpark_tpu.env import env
    env.start_bucket_server()
    b = Broadcast({"payload": list(range(200000))})
    uri = env.bucket_server.addr
    d = os.path.join(env.workdir, "broadcast")
    with open(os.path.join(d, "b%d.0" % b.bid), "rb") as f:
        want = f.read()
    got = bulkplane.fetch_bcast(uri, b.bid, 0)
    assert got == want
    assert env.bucket_server.bcast_serves.get((b.bid, 0), 0) >= 1
    b.clear()


def test_metrics_exports_bulk_counters(disk_server):
    """/metrics carries the per-peer byte counters and the stream
    gauge/counters."""
    from dpark_tpu import DparkContext
    from dpark_tpu.web import render_metrics
    srv, buckets = disk_server
    assert read_bucket(srv.addr, 51, 0, 0) == buckets[0]
    ctx = DparkContext("local")
    try:
        text = render_metrics(ctx.scheduler)
        assert "dpark_bulk_bytes_total" in text
        assert 'direction="received"' in text
        assert "dpark_bulk_streams_active" in text
        assert "dpark_bulk_streams_total" in text
    finally:
        ctx.stop()
