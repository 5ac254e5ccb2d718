"""Resource attribution plane (ISSUE 15): per-tenant mesh ledger,
program cost profiles, utilization/conservation.

The contracts under test:

* PARITY — the ledger sink OBSERVES, it never perturbs: on/off runs
  are bit-identical across the chaos matrix (the health-plane bar),
  and off mode is one `is None` check per trace record.
* ACCOUNTS — merges are associative/commutative, memory stays bounded
  past the key cap (overflow folds into coarse accounts so totals
  stay honest), and device/compile/lock/HBM activity lands on the
  right (tenant, job, stage, signature) key.
* MESH LOCK — acquisition wait is measured (the new mesh.lock span),
  hold time meters mesh-busy, and the conservation check reconciles
  attributed occupancy with the meter under two concurrent tenants.
* COST PROFILES — compile-time jax cost analysis persists to the
  adapt store keyed by the plan signature and reads back in a FRESH
  process (the items-2/3 pricing prior).
* PROGRAM CACHE — per-job hit/miss counts are EXACT under concurrency
  (the PR 9 caveat, closed).
* CROSS-PROCESS — multiproc workers' fetch activity surfaces in the
  driver's merged accounts via the O(1) ledger-<host>-<pid>.jsonl
  sidecar (the health-file idiom).
* CONSUMERS — /api/ledger, per-tenant /metrics counters, the web UI
  table, dtrace --ledger (offline twin == live), flight dumps, and
  /api/health's top-k + attribution evidence.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from dpark_tpu import conf, faults, health, ledger, trace


@pytest.fixture(autouse=True)
def _clean_planes():
    """Every test starts and ends with fresh sinks and no trace/chaos
    planes; the cost-capture seen-set resets so per-test stores see
    their own captures."""
    from dpark_tpu import service
    trace.configure("off")
    faults.configure(None)
    health.configure("on")
    ledger.configure("on")
    ledger.reset_cost_capture()
    yield
    service.shutdown()
    trace.configure("off")
    faults.configure(None)
    health.configure("on")
    ledger.configure("on")
    ledger.reset_cost_capture()


@pytest.fixture()
def tctx2():
    from dpark_tpu import DparkContext
    c = DparkContext("tpu:2")
    c.start()
    yield c
    c.stop()


def _reduce_job(c, n=500, parts=4, reduce_parts=3):
    return dict(c.parallelize([(i % 5, 1) for i in range(n)], parts)
                .reduceByKey(lambda a, b: a + b,
                             reduce_parts).collect())


def _device_data(n=20000, keys=37):
    import numpy as np
    from dpark_tpu import Columns
    i = np.arange(n, dtype=np.int64)
    return Columns(i % keys, i & 0xFF)


# ---------------------------------------------------------------------------
# accounts
# ---------------------------------------------------------------------------

def test_account_merge_associative_and_roundtrip():
    import random
    rng = random.Random(11)
    parts = []
    for _ in range(4):
        a = ledger.Account()
        a.device_ms = rng.random() * 100
        a.fetches = rng.randrange(50)
        a.hbm_byte_s = rng.random() * 1e6
        a.compiles = rng.randrange(3)
        parts.append(a)

    def fold(order):
        acc = ledger.Account()
        for i in order:
            acc.merge(ledger.Account.from_dict(parts[i].to_dict()))
        return acc.to_dict()

    a = fold([0, 1, 2, 3])
    b = fold([3, 1, 0, 2])
    left = ledger.merge_account_digests(
        ledger.merge_account_digests(parts[0].to_dict(),
                                     parts[1].to_dict()),
        ledger.merge_account_digests(parts[2].to_dict(),
                                     parts[3].to_dict()))
    assert a == b == left
    assert ledger.Account.from_dict(a).fetches == \
        sum(p.fetches for p in parts)
    # garbage digests fold to empty, never raise
    assert ledger.Account.from_dict(
        {"fetches": "x", "bogus": 1}).to_dict() == {}


def test_key_string_roundtrip():
    for key in ((3, 5, "abc"), (None, None, None), (7, None, "~")):
        assert ledger.parse_key(ledger._key_str(key)) == key


def test_sink_bounded_past_key_cap(monkeypatch):
    monkeypatch.setattr(conf, "LEDGER_MAX_KEYS", 8)
    s = ledger.LedgerSink()
    for i in range(1000):
        s.fold({"name": "stage.exec", "dur": 0.001, "job": 1,
                "stage": i, "args": {"sig": "s%d" % i}})
    assert len(s.accounts) <= 8 + 16
    assert s.dropped_keys > 0
    # totals stay honest: every observation landed somewhere
    total = sum(a.stages for a in s.accounts.values())
    assert total == 1000


def test_resident_server_attribution_survives_job_churn(monkeypatch):
    """Regression (review finding): a long-lived server's finished
    jobs RETIRE into the bounded per-(tenant, sig) archive, so live
    keys never exhaust the cap into the unattributed overflow —
    tenant attribution and conservation stay exact forever."""
    monkeypatch.setattr(conf, "LEDGER_MAX_KEYS", 8)
    s = ledger.LedgerSink()
    for job in range(1, 501):
        tenant = "tenant-%d" % (job % 2)
        s.note_job(job, tenant)
        s.fold({"name": "stage.exec", "dur": 0.01, "job": job,
                "stage": 1, "ts": float(job), "args": {"sig": "P"}})
        s.fold({"name": "mesh.lock", "dur": 0.0, "job": job,
                "stage": 1, "ts": float(job),
                "args": {"hold_s": 0.01}})
        s.fold({"name": "job", "ts": float(job), "dur": 0.01,
                "job": job, "args": {"client": tenant,
                                     "state": "done"}})
    assert not s.accounts                # everything retired
    assert s.dropped_keys == 0           # the cap was never pressed
    snap = s.snapshot(now=1000.0)
    # every one of the 500 jobs' time still attributes to its tenant
    for t in ("tenant-0", "tenant-1"):
        assert snap["tenants"][t]["device_ms"] == \
            pytest.approx(2500.0), snap["tenants"]
    cons = ledger.conservation(
        meter={"busy_s": 5.0, "wall_s": 500.0}, snap=snap)
    assert cons["ok"] is True and cons["ratio"] == 1.0, cons
    top = ledger.top_programs(snap=snap)
    assert top[0]["sig"] == "P" and top[0]["device_s"] == 5.0


def test_off_mode_is_one_predicate():
    ledger.configure("off")
    assert ledger._SINK is None
    assert ledger.mode() == "off"
    assert ledger.summary() == {"mode": "off", "tenants": {},
                                "accounts": 0}
    assert ledger.tenant_totals() == {}
    with pytest.raises(ValueError):
        ledger.configure("loud")


# ---------------------------------------------------------------------------
# parity: the sink observes, never perturbs (chaos matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    None,
    "shuffle.fetch:p=0.3,seed=11,times=3",
    "shuffle.spill_write:nth=1,kind=corrupt",
])
def test_ledger_on_off_parity_chaos_matrix(ctx, tmp_path, spec):
    pairs = [(i % 11, i) for i in range(500)]

    def run():
        faults.configure(spec)
        try:
            return dict(ctx.parallelize(pairs, 4)
                        .groupByKey(3)
                        .mapValues(sorted).collect())
        finally:
            faults.configure(None)

    ledger.configure("off")
    expected = run()                     # ledger off, trace off
    for mode in ("ring", "spool"):
        trace.configure(mode, str(tmp_path / mode))
        ledger.configure("on")
        try:
            assert run() == expected, (mode, spec)
            snap = ledger.snapshot()
            assert snap["folded"] > 0
            # finished jobs' accounts compact into the archive
            assert snap["accounts"] or snap["archive"], snap
        finally:
            trace.configure("off")
        trace.configure(mode, str(tmp_path / (mode + "-off")))
        ledger.configure("off")
        try:
            assert run() == expected, (mode, spec)
        finally:
            trace.configure("off")
        ledger.configure("on")


@pytest.mark.parametrize("spec", [
    None,
    "shuffle.fetch:p=0.3,seed=11,times=3",
])
def test_ledger_parity_device(tctx2, tmp_path, spec):
    data = _device_data(4000)

    def run():
        faults.configure(spec)
        try:
            return dict(tctx2.parallelize(data, 2)
                        .reduceByKey(lambda a, b: a + b, 2).collect())
        finally:
            faults.configure(None)

    ledger.configure("off")
    expected = run()
    trace.configure("spool", str(tmp_path / "dev"))
    ledger.configure("on")
    try:
        assert run() == expected
        snap = ledger.snapshot()
        # device execution landed in an account keyed by the adapt
        # program signature (retired to the per-tenant archive once
        # the job span folded)
        sigs = [k.split("|", 1)[1]
                for k, d in snap["archive"].items()
                if d.get("device_ms")]
        assert any(s and s != ledger.OVERFLOW for s in sigs), snap
        # mesh occupancy folded from the mesh.lock spans
        assert snap["mesh"]["acquisitions"] > 0, snap["mesh"]
        assert snap["mesh"]["busy_s"] > 0
    finally:
        trace.configure("off")


# ---------------------------------------------------------------------------
# mesh lock: measured wait + busy meter
# ---------------------------------------------------------------------------

def test_mesh_lock_wait_measured_and_span_emitted(tmp_path):
    from dpark_tpu.backend.tpu.executor import _MeshLock
    trace.configure("ring")
    lock = _MeshLock()
    entered = threading.Event()
    release = threading.Event()

    def holder():
        with lock:
            entered.set()
            release.wait(5)

    t = threading.Thread(target=holder)
    t.start()
    assert entered.wait(5)
    time.sleep(0.05)

    def waiter():
        with lock:
            pass

    w = threading.Thread(target=waiter)
    w.start()
    time.sleep(0.08)             # the waiter queues behind the holder
    release.set()
    w.join(5)
    t.join(5)
    assert lock.acquisitions == 2
    assert lock.contended == 1
    assert lock.wait_s >= 0.05, lock.wait_s
    assert lock.busy_s >= lock.wait_s
    spans = [r for r in trace.snapshot() if r["name"] == "mesh.lock"]
    assert len(spans) == 2
    waited = [r for r in spans if r["dur"] > 0.04]
    assert len(waited) == 1, spans
    assert waited[0]["args"]["hold_s"] >= 0
    # reentrant re-acquire counts one acquisition, one hold
    with lock:
        with lock:
            pass
    assert lock.acquisitions == 3
    trace.configure("off")


def test_lock_wait_attributed_to_waiting_job():
    s = ledger.LedgerSink()
    s.note_job(7, "tenant-x")
    s.fold({"name": "mesh.lock", "dur": 0.25, "job": 7, "stage": 2,
            "ts": 100.0, "args": {"hold_s": 0.5}})
    s.fold({"name": "mesh.lock", "dur": 0.0, "job": 8, "stage": 3,
            "ts": 101.0, "args": {"hold_s": 0.25}})
    snap = s.snapshot(now=102.0)
    t = snap["tenants"]["tenant-x"]
    assert t["lock_wait_ms"] == 250.0
    assert t["lock_hold_ms"] == 500.0
    assert snap["mesh"]["busy_s"] == 0.75
    assert snap["mesh"]["contended"] == 1


# ---------------------------------------------------------------------------
# HBM byte-seconds
# ---------------------------------------------------------------------------

def test_hbm_byte_seconds_accrue_on_release_and_spill():
    s = ledger.LedgerSink()
    s.fold({"name": "hbm.store", "job": 1, "stage": 2, "ts": 10.0,
            "args": {"sid": 5, "bytes": 1000}})
    s.fold({"name": "hbm.store", "job": 1, "stage": 2, "ts": 10.0,
            "args": {"sid": 6, "bytes": 500}})
    # live gauge before any release
    snap = s.snapshot(now=12.0)
    assert snap["hbm_live_bytes"] == 1500
    assert snap["hbm_live_byte_s"] == pytest.approx(3000.0)
    s.fold({"name": "hbm.release", "ts": 13.0,
            "args": {"sid": 5, "bytes": 1000, "reason": "drop"}})
    s.fold({"name": "hbm.release", "ts": 14.0,
            "args": {"sid": 6, "bytes": 500, "reason": "spill"}})
    snap = s.snapshot(now=20.0)
    acct = snap["accounts"]["1|2|-"]
    # 1000 B x 3 s + 500 B x 4 s, attributed to the STORING account
    assert acct["hbm_byte_s"] == pytest.approx(5000.0)
    assert acct["hbm_spills"] == 1
    assert snap["hbm_live_bytes"] == 0
    # double release is a no-op, not a crash
    s.fold({"name": "hbm.release", "ts": 15.0,
            "args": {"sid": 6, "bytes": 500}})


def test_hbm_release_settles_after_tracing_turned_off(tctx2):
    """Regression (review finding): a store registered while traced
    but released after trace.configure("off") must still settle the
    sink's residency entry — else the live gauge reports freed memory
    forever and the byte-seconds never accrue."""
    trace.configure("ring")
    rdd = tctx2.parallelize(_device_data(6000), 2) \
        .reduceByKey(lambda a, b: a + b, 2)       # held: so is its store
    dict(rdd.collect())
    assert ledger.snapshot()["hbm_live_bytes"] > 0
    trace.configure("off")
    ex = tctx2.scheduler.executor
    for sid in list(ex.shuffle_store):
        ex.drop_shuffle(sid)
    snap = ledger.snapshot()
    assert snap["hbm_live_bytes"] == 0, snap
    accrued = sum(d.get("hbm_byte_s", 0.0)
                  for d in list(snap["accounts"].values())
                  + list(snap["archive"].values()))
    assert accrued > 0, snap


def test_hbm_byte_seconds_on_device_store_drop(tctx2):
    trace.configure("ring")
    try:
        rdd = tctx2.parallelize(_device_data(8000), 2) \
            .reduceByKey(lambda a, b: a + b, 2)   # held: so is its store
        got = dict(rdd.collect())
        assert len(got) == 37
        ex = tctx2.scheduler.executor
        assert ledger.snapshot()["hbm_live_bytes"] > 0
        for sid in list(ex.shuffle_store):
            ex.drop_shuffle(sid)
        snap = ledger.snapshot()
        assert snap["hbm_live_bytes"] == 0
        # the job retired before the drop: accrual lands in the
        # tenant's archive, never a resurrected live account
        accrued = sum(d.get("hbm_byte_s", 0.0)
                      for d in snap["archive"].values())
        assert accrued > 0, snap
        assert not snap["accounts"], snap["accounts"]
    finally:
        trace.configure("off")


# ---------------------------------------------------------------------------
# conservation: two concurrent tenants on one mesh
# ---------------------------------------------------------------------------

def test_conservation_two_concurrent_tenants(tmp_path):
    from dpark_tpu import DparkContext, service
    from dpark_tpu.service import ClientScheduler
    trace.configure("ring")
    ctx = DparkContext("service:tpu:2")
    ctx.start()
    try:
        srv = ctx.scheduler.server
        ta = ClientScheduler(srv, client="tenant-a")
        tb = ClientScheduler(srv, client="tenant-b")
        data = _device_data(30000, 97)

        def run(tenant, out, key):
            # each tenant builds its OWN graph so both genuinely
            # compute on the mesh (a shared RDD would let the second
            # job reuse the first's shuffle outputs)
            rdd = ctx.parallelize(data, 2) \
                .reduceByKey(lambda a, b: a + b, 2)
            got = dict(x for part in tenant.run_job(
                rdd, lambda it: list(it)) for x in part)
            out[key] = got

        got = {}
        th = threading.Thread(target=run, args=(ta, got, "a"))
        th.start()
        run(tb, got, "b")
        th.join(60)
        assert len(got["a"]) == 97 and got["a"] == got["b"]
        totals = ledger.tenant_totals()
        assert totals["tenant-a"]["device_seconds"] > 0, totals
        assert totals["tenant-b"]["device_seconds"] > 0, totals
        cons = ledger.conservation(ctx.scheduler)
        # every mesh-busy second names a tenant: attributed occupancy
        # reconciles with the lock meter (the ISSUE 15 acceptance has
        # a 10% bar; job-ctx attribution makes this ~exact)
        assert cons["ok"] is True, cons
        assert cons["ratio"] >= 0.9, cons
        util = ledger.utilization(ctx.scheduler)
        assert util["meter"]["acquisitions"] > 0
        assert 0.0 <= util["busy_frac"] <= 1.0
    finally:
        trace.configure("off")
        ctx.stop()
        service.shutdown()


# ---------------------------------------------------------------------------
# conservation: cache-served jobs (ISSUE 18 satellite)
# ---------------------------------------------------------------------------

def test_resultcache_events_attribute_by_tenant():
    """The shared result cache runs NO job for a served query, so its
    events carry the tenant explicitly: residency byte-seconds bill
    the STORING tenant at release, hits/served-bytes the SERVED
    tenant — and a cache-served query conserves trivially (zero scan
    device-seconds, nothing on the mesh to reconcile)."""
    s = ledger.LedgerSink()
    s.fold({"name": "resultcache.store", "ts": 10.0,
            "args": {"sid": "k1", "bytes": 1000,
                     "tenant": "tenant-a"}})
    snap = s.snapshot(now=12.0)
    assert snap["resultcache_live_bytes"] == 1000
    assert snap["resultcache_live_byte_s"] == pytest.approx(2000.0)
    s.fold({"name": "resultcache.serve", "ts": 11.0,
            "args": {"sid": "k1", "bytes": 1000, "tier": "full",
                     "tenant": "tenant-b"}})
    s.fold({"name": "resultcache.release", "ts": 15.0,
            "args": {"sid": "k1", "bytes": 1000, "reason": "evict",
                     "tenant": "tenant-a"}})
    snap = s.snapshot(now=15.0)
    assert snap["resultcache_live_bytes"] == 0
    totals = ledger.tenant_totals_from_snapshot(snap)
    a, b = totals["tenant-a"], totals["tenant-b"]
    # 1000 bytes held 10.0..15.0 bills the storing tenant
    assert a["resultcache_byte_seconds"] == pytest.approx(5000.0)
    assert a["resultcache_hits"] == 0
    # the hit bills the SERVED tenant — at ZERO device-seconds
    assert b["resultcache_hits"] == 1
    assert b["resultcache_served_bytes"] == 1000
    assert b["device_seconds"] == 0.0
    # nothing ran on the mesh: the conservation check has nothing to
    # reconcile and must NOT flag the served query as unattributed
    cons = ledger.conservation(meter={"busy_s": 0.0, "wall_s": 5.0},
                               snap=snap)
    assert cons["ok"] is not False, cons
    assert cons["attributed_device_s"] == 0.0


def test_conservation_holds_with_cache_served_tenant():
    """One tenant pays the scan (mesh-busy, job-attributed), another
    is served from the cache (no job): attributed occupancy still
    reconciles exactly — the served tenant adds hits, not holds."""
    s = ledger.LedgerSink()
    s.note_job(1, "tenant-a")
    s.fold({"name": "stage.exec", "dur": 0.4, "job": 1, "stage": 1,
            "ts": 10.0, "args": {"sig": "Q"}})
    s.fold({"name": "mesh.lock", "dur": 0.0, "job": 1, "stage": 1,
            "ts": 10.0, "args": {"hold_s": 0.4}})
    s.fold({"name": "resultcache.store", "ts": 10.5,
            "args": {"sid": "kq", "bytes": 512,
                     "tenant": "tenant-a"}})
    s.fold({"name": "job", "ts": 10.6, "dur": 0.5, "job": 1,
            "args": {"client": "tenant-a", "state": "done"}})
    s.fold({"name": "resultcache.serve", "ts": 11.0,
            "args": {"sid": "kq", "bytes": 512, "tier": "full",
                     "tenant": "tenant-b"}})
    snap = s.snapshot(now=12.0)
    cons = ledger.conservation(meter={"busy_s": 0.4, "wall_s": 2.0},
                               snap=snap)
    # every mesh-busy second names tenant-a; the served tenant-b
    # consumed none and broke nothing
    assert cons["ok"] is True and cons["ratio"] == 1.0, cons
    totals = ledger.tenant_totals_from_snapshot(snap)
    assert totals["tenant-a"]["device_seconds"] == \
        pytest.approx(0.4)
    assert totals["tenant-b"]["device_seconds"] == 0.0
    assert totals["tenant-b"]["resultcache_hits"] == 1


def test_cache_served_query_end_to_end_ledger(tmp_path):
    """Live integration: a repeated tabular group-by under
    trace=ring + ledger=on + resultcache=mem.  The second tenant's
    query is served from the cache — the ledger shows the hit billed
    to it with zero scan device work."""
    from dpark_tpu import DparkContext, resultcache
    from dpark_tpu.tabular import write_tabular
    d = str(tmp_path / "tab")
    os.makedirs(d)
    write_tabular(os.path.join(d, "part-00000.tab"), ["t", "k", "a"],
                  [(i, i % 7, i % 50) for i in range(4000)],
                  chunk_rows=1000)
    trace.configure("ring")
    ledger.configure("on")
    resultcache.configure(mode="mem",
                          cache_dir=str(tmp_path / "rc"))
    ctx = DparkContext("local")
    try:
        def q():
            return ctx.tabular(d, ["t", "k", "a"]).asTable("e") \
                .where("t >= 1000").groupBy("k", "sum(a) as s")
        with resultcache.tenant("tenant-a"):
            cold = sorted(q().collect())
        with resultcache.tenant("tenant-b"):
            warm = sorted(q().collect())
        assert warm == cold
        totals = ledger.tenant_totals()
        b = totals["tenant-b"]
        assert b["resultcache_hits"] == 1, totals
        assert b["resultcache_served_bytes"] > 0
        assert b["device_seconds"] == 0.0
        assert totals["tenant-a"]["resultcache_hits"] == 0
        cons = ledger.conservation()
        assert cons["ok"] is not False, cons
    finally:
        resultcache.configure(mode="off")
        trace.configure("off")
        ledger.configure("off")
        ctx.stop()


# ---------------------------------------------------------------------------
# program cost profiles (the items-2/3 pricing prior)
# ---------------------------------------------------------------------------

def test_program_cost_profile_roundtrip_fresh_process(
        tctx2, tmp_path, monkeypatch):
    from dpark_tpu import adapt
    monkeypatch.setattr(conf, "LEDGER_COST", "compile")
    store = str(tmp_path / "adapt")
    adapt.configure(mode="observe", store_dir=store)
    trace.configure("ring")
    try:
        got = dict(tctx2.parallelize(_device_data(8000), 2)
                   .reduceByKey(lambda a, b: a + b, 2).collect())
        assert len(got) == 37
        profiles = adapt.program_costs()
        assert profiles, "no cost profile captured"
        key, prof = next(iter(profiles.items()))
        assert prof["flops"] > 0, prof
        assert prof["bytes_accessed"] > 0, prof
        # the compile path captured measured memory analysis
        assert prof.get("peak_hbm_bytes", 0) > 0, prof
        assert key in adapt.summary()["programs"]
        # a FRESH process reads the persisted profile back (the
        # acceptance criterion: pricing before the first observed run)
        out = subprocess.run(
            [sys.executable, "-c",
             "import json\n"
             "from dpark_tpu import adapt\n"
             "adapt.configure(mode='observe', store_dir=%r)\n"
             "print(json.dumps(adapt.program_costs()))" % store],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        fresh = json.loads(out.stdout.strip().splitlines()[-1])
        assert fresh.get(key, {}).get("flops") == prof["flops"], fresh
    finally:
        trace.configure("off")
        adapt.configure()


def test_cost_capture_once_per_signature(tctx2, tmp_path, monkeypatch):
    from dpark_tpu import adapt
    adapt.configure(mode="observe", store_dir=str(tmp_path / "a"))
    trace.configure("ring")
    try:
        data = _device_data(6000)
        for _ in range(3):
            dict(tctx2.parallelize(data, 2)
                 .reduceByKey(lambda a, b: a + b, 2).collect())
        events = [r for r in trace.snapshot()
                  if r["name"] == "ledger.cost"]
        sigs = [r["args"]["sig"] for r in events]
        assert len(sigs) == len(set(sigs)), sigs
    finally:
        trace.configure("off")
        adapt.configure()


def test_cost_capture_off_mode_records_nothing(
        tctx2, tmp_path, monkeypatch):
    from dpark_tpu import adapt
    monkeypatch.setattr(conf, "LEDGER_COST", "off")
    adapt.configure(mode="observe", store_dir=str(tmp_path / "a"))
    trace.configure("ring")
    try:
        dict(tctx2.parallelize(_device_data(6000), 2)
             .reduceByKey(lambda a, b: a + b, 2).collect())
        assert adapt.program_costs() == {}
    finally:
        trace.configure("off")
        adapt.configure()


# ---------------------------------------------------------------------------
# exact per-job program-cache counts (the PR 9 caveat, closed)
# ---------------------------------------------------------------------------

def test_program_cache_per_job_counts_exact_across_threads():
    from dpark_tpu.backend.tpu.executor import _ProgramCache
    pc = _ProgramCache(cap=0)
    tls = threading.local()
    pc._job_of = lambda: getattr(tls, "job", None)
    errs = []

    def worker(job, keys):
        tls.job = job
        try:
            for k in keys:
                if k not in pc:
                    pc[k] = k
                assert k in pc           # second probe: hit
        except Exception as e:           # pragma: no cover
            errs.append(e)

    t1 = threading.Thread(target=worker,
                          args=(1, ["a%d" % i for i in range(50)]))
    t2 = threading.Thread(target=worker,
                          args=(2, ["b%d" % i for i in range(80)]))
    t1.start()
    t2.start()
    t1.join()
    t2.join()
    assert not errs
    assert pc.job_stats(1) == {"hits": 50, "misses": 50}
    assert pc.job_stats(2) == {"hits": 80, "misses": 80}
    assert pc.job_stats(99) == {"hits": 0, "misses": 0}


def test_program_cache_exact_under_overlapping_jobs():
    """Regression (ISSUE 15 satellite): a warm job's
    record["program_cache"] used to be a process-wide delta, so a
    CONCURRENT job's compiles leaked into it.  With per-job tagging
    the warm job reports misses == 0 even while another tenant
    compiles a different program mid-flight."""
    from dpark_tpu import DparkContext, service
    from dpark_tpu.service import ClientScheduler
    ctx = DparkContext("service:tpu:2")
    ctx.start()
    try:
        srv = ctx.scheduler.server
        ta = ClientScheduler(srv, client="tenant-warm")
        tb = ClientScheduler(srv, client="tenant-cold")
        warm_rdd = ctx.parallelize(_device_data(20000), 2) \
            .reduceByKey(lambda a, b: a + b, 2)

        def collect(tenant, rdd):
            return dict(x for part in tenant.run_job(
                rdd, lambda it: list(it)) for x in part)

        # pass 1: compile tenant-warm's program
        ref = collect(ta, warm_rdd)
        # a DIFFERENT program (different key space + min merge) the
        # cold tenant compiles while the warm job re-runs
        cold_rdd = ctx.parallelize(_device_data(60000, 251), 2) \
            .reduceByKey(min, 2)
        got = {}
        th = threading.Thread(
            target=lambda: got.update(cold=collect(tb, cold_rdd)))
        th.start()
        warm2 = collect(ta, warm_rdd)
        th.join(60)
        assert warm2 == ref
        assert len(got["cold"]) == 251
        sched = srv.scheduler
        warm_recs = [r for r in sched.history
                     if r.get("client") == "tenant-warm"]
        assert len(warm_recs) == 2
        pc = warm_recs[-1]["program_cache"]
        # EXACT: zero misses even though tenant-cold compiled during
        # the overlap (the old process-wide delta would count them)
        assert pc["misses"] == 0, pc
        assert pc["hits"] >= 1, pc
        cold_pc = [r for r in sched.history
                   if r.get("client") == "tenant-cold"][-1][
                       "program_cache"]
        assert cold_pc["misses"] >= 1, cold_pc
    finally:
        ctx.stop()
        service.shutdown()


# ---------------------------------------------------------------------------
# cross-process: multiproc worker attribution via the O(1) sidecar
# ---------------------------------------------------------------------------

@pytest.fixture()
def fresh_forkserver():
    from multiprocessing import forkserver

    def stop():
        try:
            forkserver._forkserver._stop()
        except Exception:
            pass

    stop()
    yield
    stop()


def test_worker_accounts_surface_on_driver(fresh_forkserver, pctx,
                                           tmp_path):
    d = str(tmp_path / "mp")
    trace.configure("spool", d)
    try:
        assert _reduce_job(pctx, n=400) == {k: 80 for k in range(5)}
        # the driver process itself fetched nothing...
        own = ledger.snapshot()["accounts"]
        assert not any(a.get("fetches") for a in own.values()), own
        # ...but the merged view carries the workers' accounts,
        # attributed to the job (task._trace_job ships the id)
        merged = ledger.merged_account_digests()
        fetched = {k: a for k, a in merged.items()
                   if a.get("fetches")}
        assert fetched, merged
        assert any(ledger.parse_key(k)[0] is not None
                   for k in fetched), fetched
        # the sidecar files exist and are O(1): ONE record each,
        # atomically rewritten (the health-<host>-<pid>.jsonl idiom)
        sidecars = [fn for fn in os.listdir(d)
                    if fn.startswith("ledger-")]
        assert sidecars, os.listdir(d)
        from dpark_tpu.utils import unframe_jsonl
        for fn in sidecars:
            with open(os.path.join(d, fn), "rb") as f:
                recs, skipped = unframe_jsonl(f.read())
            assert len(recs) == 1 and skipped == 0, fn
            assert recs[0]["name"] == "process.ledger"
    finally:
        trace.configure("off")


# ---------------------------------------------------------------------------
# offline twin: dtrace --ledger vs the live snapshot
# ---------------------------------------------------------------------------

def _load_dtrace():
    from tests.conftest import load_tool
    return load_tool("dtrace")


def test_dtrace_ledger_matches_live(tctx2, tmp_path, capsys):
    d = str(tmp_path / "spool")
    trace.configure("spool", d)
    ledger.configure("on")           # fresh sink scoped to this run
    got = dict(tctx2.parallelize(_device_data(8000), 2)
               .reduceByKey(lambda a, b: a + b, 2).collect())
    assert len(got) == 37
    live = ledger.snapshot()
    trace.configure("off")
    dtrace = _load_dtrace()
    assert dtrace.main(["--ledger", "--dir", d]) == 0
    offline = json.loads(capsys.readouterr().out)
    # the offline twin folded the SAME records the live sink saw:
    # accounts agree exactly (byte-second GAUGES depend on the wall
    # clock and are excluded by construction — accrual-at-release is
    # in the accounts)
    assert offline["accounts"] == live["accounts"]
    assert offline["archive"] == live["archive"]
    assert offline["mesh"] == live["mesh"]
    # the twin's tenants field ships the LIVE rollup shape
    assert offline["tenants"] == \
        ledger.tenant_totals_from_snapshot(live)
    assert "device_seconds" in offline["tenants"]["local"]
    assert offline["job_tenant"] == live["job_tenant"]
    assert offline["conservation"]["attributed_device_s"] == \
        ledger.conservation(snap=live)["attributed_device_s"]
    # empty spool fails (the CI gate contract)
    assert dtrace.main(["--ledger", "--dir",
                        str(tmp_path / "empty")]) == 1


# ---------------------------------------------------------------------------
# consumers: /api/ledger, /metrics, web page, flight, /api/health
# ---------------------------------------------------------------------------

def test_api_ledger_endpoint_and_tenant_metrics(tctx2):
    from dpark_tpu.web import render_metrics, start_ui
    trace.configure("ring")
    try:
        dict(tctx2.parallelize(_device_data(8000), 2)
             .reduceByKey(lambda a, b: a + b, 2).collect())
        server, url = start_ui(tctx2.scheduler)
        try:
            with urllib.request.urlopen(url + "api/ledger") as r:
                assert r.status == 200
                api = json.loads(r.read().decode())
        finally:
            server.shutdown()
        assert api["mode"] == "on"
        assert api["accounts"] or api["archive"], api
        assert api["tenants"]["local"]["device_seconds"] > 0, api
        assert api["conservation"]["ratio"] is not None
        u = api["utilization"]
        assert abs(u["busy_frac"] + u["contended_frac"]
                   + u["idle_frac"] - 1.0) < 1e-6
        assert api["top_programs"], api
        body = render_metrics(tctx2.scheduler)
        assert 'dpark_tenant_device_seconds_total{tenant="local"}' \
            in body
        assert "dpark_tenant_hbm_byte_seconds_total" in body
        assert "dpark_tenant_lock_wait_seconds_total" in body
        assert "dpark_tenant_bulk_bytes_total" in body
    finally:
        trace.configure("off")


def test_page_has_ledger_table():
    from dpark_tpu import web
    assert "resource ledger" in web._PAGE
    assert "/api/ledger" in web._PAGE
    assert "conservation" in web._PAGE


def test_api_ledger_never_throws_when_off(ctx):
    ledger.configure("off")
    api = ledger.api_ledger(ctx.scheduler)
    assert api["mode"] == "off"
    assert json.dumps(api)


def test_flight_dump_carries_ledger(ctx, tmp_path):
    trace.configure("ring")
    _reduce_job(ctx)
    conf.DPARK_FLIGHT_DIR = str(tmp_path / "flight")
    try:
        health._flight_dumps = 0
        p = health.flight_dump("test", scheduler=ctx.scheduler)
        assert p
        recs = health.load_flight(p)
        led = [r for r in recs if r.get("kind") == "flight.ledger"]
        assert led, [r.get("kind") for r in recs]
        lsnap = led[0]["snapshot"]
        assert lsnap["accounts"] or lsnap["archive"], led[0]
    finally:
        conf.DPARK_FLIGHT_DIR = ""
        trace.configure("off")


def test_health_evidence_gains_ledger_topk(tctx2):
    trace.configure("ring")
    try:
        dict(tctx2.parallelize(_device_data(8000), 2)
             .reduceByKey(lambda a, b: a + b, 2).collect())
        api = health.api_health(tctx2.scheduler)
        ev = api["subsystems"]["executor"]["evidence"]
        assert ev.get("top_programs"), ev
        top = ev["top_programs"][0]
        assert top["device_s"] > 0 and top["sig"]
        att = api["subsystems"]["attribution"]
        assert att["grade"] in ("green", "yellow")
        assert "ratio" in att["evidence"]
        assert "mesh_busy_s" in att["evidence"]
    finally:
        trace.configure("off")


def test_untraced_master_never_grades_attribution_yellow(tctx2):
    """Regression (review finding): DPARK_TRACE=off with the ledger
    on (the DEFAULT config) — the always-on lock meter accrues busy
    time the sink never sees, which must read as 'nothing to
    conserve', not as unattributed consumption."""
    assert trace.mode() == "off"
    dict(tctx2.parallelize(_device_data(6000), 2)
         .reduceByKey(lambda a, b: a + b, 2).collect())
    cons = ledger.conservation(tctx2.scheduler)
    assert cons["mesh_busy_s"] > 0           # the meter did run
    assert cons["ratio"] is None and cons["ok"] is None, cons
    api = health.api_health(tctx2.scheduler)
    att = api["subsystems"].get("attribution")
    assert att is not None and att["grade"] == "green", att


def test_note_job_backstop_never_clobbers_new_tenant():
    """Regression (review finding): once the 4096-job backstop fires
    on every note_job, the evicted job's tenant must not leak into
    the NEW job's mapping."""
    s = ledger.LedgerSink()
    for job in range(4097):
        s.note_job(job, "tenant-old")
    s.note_job(5000, "tenant-new")       # backstop fires here too
    assert s.job_tenant[5000] == "tenant-new"


def test_conservation_graded_over_observed_window_only():
    """Regression (review finding): tracing enabled mid-life — busy
    time the meter accrued while untraced must not count against the
    attribution (the live path grades vs the sink's folded view)."""
    s = ledger.LedgerSink()
    s.note_job(1, "t")
    s.fold({"name": "mesh.lock", "dur": 0.0, "job": 1, "stage": 1,
            "ts": 10.0, "args": {"hold_s": 1.0}})
    s.fold({"name": "stage.exec", "dur": 1.0, "job": 1, "stage": 1,
            "ts": 10.0, "args": {"sig": "P"}})
    # lifetime meter saw 100 s of pre-tracing busy; the sink's folded
    # window saw 1 s, all attributed — conservation must hold
    cons = ledger.conservation(snap=s.snapshot(now=12.0))
    assert cons["ok"] is True and cons["ratio"] == 1.0, cons


def test_archive_key_with_pipe_in_tenant_name():
    s = ledger.LedgerSink()
    s.note_job(1, "team|alpha")
    s.fold({"name": "stage.exec", "dur": 0.5, "job": 1, "stage": 1,
            "ts": 1.0, "args": {"sig": "P"}})
    s.fold({"name": "job", "ts": 0.5, "dur": 1.0, "job": 1,
            "args": {"client": "team|alpha", "state": "done"}})
    top = ledger.top_programs(snap=s.snapshot(now=2.0))
    assert top == [{"sig": "P", "device_s": 0.5,
                    "tenant": "team|alpha"}], top


def test_ledger_summary_schema(ctx):
    trace.configure("ring")
    try:
        _reduce_job(ctx)
        s = ledger.summary()
        assert s["mode"] == "on"
        assert isinstance(s["tenants"], dict)
        assert s["accounts"] >= 1
        assert "conservation" in s and "mesh" in s
        assert json.dumps(s)
    finally:
        trace.configure("off")


def test_tenant_rollup_uses_note_job():
    s = ledger.LedgerSink()
    s.note_job(1, "alice")
    s.note_job(2, None)              # defaults to "local"
    s.fold({"name": "stage.exec", "dur": 0.5, "job": 1, "stage": 1,
            "ts": 1.0, "args": {"sig": "x"}})
    s.fold({"name": "stage.exec", "dur": 0.25, "job": 2, "stage": 1,
            "ts": 1.0, "args": {"sig": "x"}})
    snap = s.snapshot(now=2.0)
    assert snap["tenants"]["alice"]["device_ms"] == 500.0
    assert snap["tenants"]["local"]["device_ms"] == 250.0


def test_top_programs_name_the_dominant_tenant():
    """The evidence a yellow grade attaches must name the tenant that
    actually burned the device-seconds, regardless of account
    iteration order."""
    s = ledger.LedgerSink()
    s.note_job(1, "heavy")
    s.note_job(2, "light")
    s.fold({"name": "stage.exec", "dur": 10.0, "job": 1, "stage": 1,
            "ts": 1.0, "args": {"sig": "P"}})
    s.fold({"name": "stage.exec", "dur": 0.1, "job": 2, "stage": 1,
            "ts": 2.0, "args": {"sig": "P"}})
    s.fold({"name": "stage.exec", "dur": 0.5, "job": 2, "stage": 2,
            "ts": 3.0, "args": {"sig": "Q"}})
    top = ledger.top_programs(snap=s.snapshot(now=4.0))
    assert top[0] == {"sig": "P", "device_s": 10.1,
                      "tenant": "heavy"}
    assert top[1]["sig"] == "Q" and top[1]["tenant"] == "light"


def test_program_cache_job_bucket_survives_churn():
    """A long-running job that keeps probing must not lose its exact
    counts to newer short jobs (recency-refresh, not insertion-order
    eviction)."""
    from dpark_tpu.backend.tpu.executor import _ProgramCache
    pc = _ProgramCache(cap=0)
    tls = threading.local()
    pc._job_of = lambda: getattr(tls, "job", None)
    tls.job = 1
    pc["warm"] = 1
    assert "warm" in pc                 # job 1's bucket born
    for j in range(2, 200):             # 198 newer jobs churn through
        tls.job = j
        assert "warm" in pc
        tls.job = 1
        assert "warm" in pc             # job 1 keeps probing: refreshed
    assert pc.job_stats(1)["hits"] >= 198


def test_offline_fold_never_double_counts_retired_sidecars():
    """Regression (review finding): a worker's spans fold into
    accounts, the driver's job span retires them to the archive — the
    worker's cumulative sidecar digest for the same key must then be
    SKIPPED, not re-added as a fresh account."""
    recs = [
        {"name": "fetch.bucket", "cat": "shuffle", "ts": 1.0,
         "dur": 0.01, "job": 1, "stage": 2, "pid": 9,
         "args": {"peer": "local"}},
        {"name": "job", "cat": "sched", "ts": 0.5, "dur": 1.0,
         "job": 1, "pid": 1,
         "args": {"client": "tenant-w", "state": "done"}},
        {"name": "process.ledger", "cat": "counters", "ts": 2.0,
         "dur": 0.0, "pid": 9,
         "args": {"ledger": {"1|2|-": {"fetches": 1,
                                       "fetch_ms": 10.0}}}},
    ]
    s = ledger.fold_records(recs)
    snap = s.snapshot(now=3.0)
    total = sum(d.get("fetches", 0)
                for d in list(snap["accounts"].values())
                + list(snap["archive"].values()))
    assert total == 1, snap
    assert snap["tenants"]["tenant-w"]["fetches"] == 1


def test_offline_tenant_resolution_from_job_span():
    """The job span (emitted at job END) carries the client, so a
    spool alone resolves tenants — even though every stage span folds
    BEFORE the job span arrives."""
    recs = [
        {"name": "stage.exec", "cat": "exec", "ts": 1.0, "dur": 0.5,
         "job": 3, "stage": 1, "args": {"sig": "p"}},
        {"name": "job", "cat": "sched", "ts": 0.5, "dur": 1.2,
         "job": 3, "args": {"client": "tenant-z", "state": "done"}},
    ]
    s = ledger.fold_records(recs)
    snap = s.snapshot(now=2.0)
    assert snap["tenants"] == {"tenant-z": {"device_ms": 500.0,
                                            "stages": 1}}
