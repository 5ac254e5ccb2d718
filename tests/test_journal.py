"""Crash-consistent control plane (ISSUE 20): journaled scheduler
recovery.

The suite proves the journal's three contracts:

- WRITE-AHEAD: a completed shuffle-map stage's outputs are recorded
  (fingerprint + writer sid + locations) before the job proceeds, so a
  kill -9 anywhere later cannot lose the fact of its completion.
- REPLAY: a fresh plane (same dir — the restarted-process view) seeds
  completed stages from the journal: the resubmitted job re-registers
  surviving map outputs and re-runs NOTHING for fully-seeded stages,
  with results bit-identical to the first run.
- REFUSAL: torn tail frames are skipped (counted, never poisoning the
  load), duplicate stage records are idempotent (last wins), and a
  journal written by a NEWER schema is refused whole.

The capstone is the kill -9 leg: a subprocess controller dies at the
first reduce fetch (faults kind=kill — os._exit, no atexit), a second
subprocess replays the journal and completes the job bit-identically
with resumed_stages >= 1 and 0 recomputes.
"""

import operator
import os
import subprocess
import sys

import pytest

from dpark_tpu import journal
from dpark_tpu.utils import frame_jsonl, unframe_jsonl


@pytest.fixture(autouse=True)
def _plane_off():
    """Every test starts and ends with the journal plane disarmed."""
    journal.configure(mode="off")
    yield
    journal.configure(mode="off")


def _reduce_job(ctx):
    return sorted(ctx.parallelize([(i % 7, i) for i in range(210)], 4)
                  .reduceByKey(operator.add, 3).collect())


# ---------------------------------------------------------------------------
# the file format: torn tails, duplicates, schema refusal
# ---------------------------------------------------------------------------

def test_truncated_tail_frame_is_skipped(tmp_path):
    """A frame torn mid-write by a crash is skipped at load (counted),
    and every intact frame before it still replays."""
    d = str(tmp_path / "jnl")
    p = journal._Plane(d)
    p.append({"kind": "stage", "stage": "fp-1", "sid": 1, "nparts": 2,
              "nreduce": 3, "locs": [None, None]})
    p.append({"kind": "stage", "stage": "fp-2", "sid": 2, "nparts": 2,
              "nreduce": 3, "locs": [None, None]})
    path = p._path
    os.close(p._fd)
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(raw[:-7])                   # tear the last frame
    fresh = journal._Plane(d)
    assert fresh.lookup_stage("fp-1") is not None
    assert fresh.lookup_stage("fp-2") is None
    assert fresh.counters["skipped_frames"] == 1
    assert fresh.counters["refused_files"] == 0


def test_duplicate_stage_records_last_wins(tmp_path):
    """A stage resubmitted after a fetch failure re-journals; replay
    must see its FRESH locations, not the superseded ones."""
    d = str(tmp_path / "jnl")
    p = journal._Plane(d)
    p.append({"kind": "stage", "stage": "fp-1", "sid": 1, "nparts": 1,
              "nreduce": 1, "locs": ["file:///old"]})
    p.append({"kind": "stage", "stage": "fp-1", "sid": 5, "nparts": 1,
              "nreduce": 1, "locs": ["file:///new"]})
    fresh = journal._Plane(d)
    rec = fresh.lookup_stage("fp-1")
    assert rec["sid"] == 5 and rec["locs"] == ["file:///new"]
    assert fresh.counters["skipped_frames"] == 0


def test_newer_schema_journal_is_refused_whole(tmp_path):
    """A journal written by a NEWER schema is refused in its entirety
    — never half-interpreted — while same-schema files still load."""
    d = str(tmp_path / "jnl")
    os.makedirs(d)
    with open(os.path.join(d, "j-newer.jnl"), "wb") as f:
        f.write(frame_jsonl({"kind": "meta",
                             "schema": journal.SCHEMA + 1}))
        f.write(frame_jsonl({"kind": "stage", "stage": "fp-future",
                             "sid": 1, "nparts": 1, "nreduce": 1,
                             "locs": ["file:///x"]}))
    p = journal._Plane(d)
    p.append({"kind": "stage", "stage": "fp-now", "sid": 2,
              "nparts": 1, "nreduce": 1, "locs": ["file:///y"]})
    fresh = journal._Plane(d)
    assert fresh.lookup_stage("fp-future") is None
    assert fresh.lookup_stage("fp-now") is not None
    assert fresh.counters["refused_files"] == 1


def test_frame_round_trip_crc_rejects_corruption():
    line = frame_jsonl({"kind": "stage", "stage": "x"})
    recs, skipped = unframe_jsonl(line)
    assert recs == [{"kind": "stage", "stage": "x"}] and skipped == 0
    bad = bytearray(line)
    bad[len(bad) // 2] ^= 0xFF
    recs, skipped = unframe_jsonl(bytes(bad))
    assert recs == [] and skipped == 1


# ---------------------------------------------------------------------------
# fingerprints: restart-stable stage identity
# ---------------------------------------------------------------------------

def test_stage_fingerprint_stable_across_builds(ctx):
    """Two builds of the same DAG (fresh rdd/shuffle ids) fingerprint
    identically; a different partitioner width does not."""
    from dpark_tpu.schedule import Stage

    held = []       # a stage owns neither its rdd nor its dependency

    def stage_of(width):
        r = ctx.parallelize([(1, 2)], 2).reduceByKey(operator.add,
                                                     width)
        held.append(r)
        dep = r.dependencies[0]
        return Stage(dep.rdd, dep, [])

    a, b, c = stage_of(3), stage_of(3), stage_of(4)
    assert a.shuffle_dep.shuffle_id != b.shuffle_dep.shuffle_id
    assert journal.stage_fingerprint(a) == journal.stage_fingerprint(b)
    assert journal.stage_fingerprint(a) != journal.stage_fingerprint(c)


# ---------------------------------------------------------------------------
# replay: in-process restart simulation
# ---------------------------------------------------------------------------

def test_replay_resumes_completed_stage(ctx, tmp_path):
    """A fresh plane over the same dir (the restarted-process view)
    seeds the completed map stage: the second run resumes it — 0
    recomputes — and the result is bit-identical.  The new process
    mints a NEW shuffle id, so this also exercises the sid alias."""
    jdir = str(tmp_path / "jnl")
    journal.configure(mode="on", journal_dir=jdir)
    first = _reduce_job(ctx)
    assert ctx.scheduler.history[-1].get("resumed_stages") is None

    journal.configure(mode="on", journal_dir=jdir)   # "restart"
    second = _reduce_job(ctx)
    rec = ctx.scheduler.history[-1]
    assert second == first
    assert rec["state"] == "done"
    assert rec.get("resumed_stages") == 1
    assert rec.get("seeded_partitions") == 4
    assert rec.get("recomputes", 0) == 0
    st = journal.stats()
    assert st["journal_replays"] == 1
    assert st["recovered_stages"] == 1
    assert st["seeded_partitions"] == 4


def test_replay_recomputes_lost_outputs_by_lineage(ctx, tmp_path):
    """Map outputs deleted after the crash are holes: replay seeds the
    survivors and lineage recomputes ONLY the missing partitions."""
    jdir = str(tmp_path / "jnl")
    journal.configure(mode="on", journal_dir=jdir)
    first = _reduce_job(ctx)
    # find the journaled stage record and destroy map 0's bucket dir
    plane = journal._PLANE
    plane._ensure_loaded()
    (rec,) = plane._stages.values()
    root = rec["locs"][0][len("file://"):]
    import shutil
    shutil.rmtree(os.path.join(root, "shuffle", str(rec["sid"]), "0"))

    journal.configure(mode="on", journal_dir=jdir)
    second = _reduce_job(ctx)
    jrec = ctx.scheduler.history[-1]
    assert second == first
    assert jrec["state"] == "done"
    # 3 of 4 maps seeded; the stage was not FULLY resumed
    assert jrec.get("seeded_partitions") == 3
    assert jrec.get("resumed_stages", 0) == 0
    assert journal.stats()["recovered_stages"] == 0


def test_journal_off_is_bit_identical_and_unsampled(ctx, tmp_path):
    """The plane contract: off means no journal dir is touched and the
    result matches the on-mode run exactly."""
    jdir = str(tmp_path / "jnl")
    journal.configure(mode="on", journal_dir=jdir)
    on = _reduce_job(ctx)
    journal.configure(mode="off")
    assert journal.stats() is None
    off = _reduce_job(ctx)
    assert on == off
    assert ctx.scheduler.history[-1].get("resumed_stages") is None


def test_drain_flushes_journal(tmp_path):
    """The graceful-degradation endpoint: drain stops admission, waits
    out in-flight jobs, and flushes the journal before exit."""
    from dpark_tpu import service
    journal.configure(mode="on",
                      journal_dir=str(tmp_path / "jnl"))
    srv = service.JobServer(master="local", slots=1)
    srv.start()
    try:
        summary = srv.drain(timeout=5.0)
        assert summary["drained"] and summary["journal_flushed"]
        with pytest.raises(RuntimeError, match="draining"):
            next(iter(srv.submit(None, None)))
        assert journal.stats()["flushes"] >= 1
        srv.undrain()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# the capstone: kill -9 mid-job, restart, bit-identical completion
# ---------------------------------------------------------------------------

_CHILD = r"""
import operator, sys
from dpark_tpu import DparkContext
c = DparkContext("local")
res = sorted(c.parallelize([(i %% 7, i) for i in range(210)], 4)
             .reduceByKey(operator.add, 3).collect())
rec = c.scheduler.history[-1]
print("CHILD_RESULT %%d %%d"
      %% (sum(k * 100003 + v for k, v in res) %% (1 << 61),
         rec.get("resumed_stages") or 0))
"""


def _run_child(env, expect_kill=False):
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD % ()], env=env,
        capture_output=True, text=True, timeout=120)
    if expect_kill:
        return proc
    assert proc.returncode == 0, proc.stderr
    for line in proc.stdout.splitlines():
        if line.startswith("CHILD_RESULT "):
            _, checksum, resumed = line.split()
            return int(checksum), int(resumed)
    raise AssertionError("no CHILD_RESULT line:\n%s\n%s"
                         % (proc.stdout, proc.stderr))


def test_kill9_mid_job_restart_resumes(tmp_path):
    """kill -9 (faults kind=kill: os._exit, no atexit, no flush) at
    the first reduce fetch — after the map stage journaled — then a
    restarted controller completes the SAME job bit-identically,
    resuming the completed stage from the journal."""
    jdir = str(tmp_path / "jnl")
    workroot = str(tmp_path / "work")
    base = dict(os.environ,
                JAX_PLATFORMS="cpu",
                DPARK_JOURNAL="on",
                DPARK_JOURNAL_DIR=jdir,
                DPARK_WORK_DIR=workroot,
                DPARK_PROGRESS="0")
    base.pop("DPARK_FAULTS", None)

    # the clean expectation, computed here (reduceByKey over ints is
    # deterministic)
    agg = {}
    for i in range(210):
        agg[i % 7] = agg.get(i % 7, 0) + i
    expect = sum(k * 100003 + v
                 for k, v in sorted(agg.items())) % (1 << 61)

    victim = _run_child(
        dict(base, DPARK_FAULTS="shuffle.fetch:nth=1,kind=kill"),
        expect_kill=True)
    assert victim.returncode == 137, (victim.returncode,
                                      victim.stderr)
    assert "CHILD_RESULT" not in victim.stdout
    assert os.listdir(jdir), "victim journaled nothing"

    checksum, resumed = _run_child(base)
    assert checksum == expect
    assert resumed >= 1
