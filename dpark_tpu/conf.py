"""Configuration for dpark_tpu.

Reference parity: dpark/conf.py (module constants + optional user conf file
via the DPARK_CONF env var).  Reference mount was empty at build time; survey
cites are at file-granularity only (SURVEY.md section 2.1).

TPU additions beyond the reference: mesh shape, HBM budget knobs, and the
device-bucket padding policy used by the all_to_all shuffle.

An environment read stays here only if a test, a cell, chip_smoke.py, an
example, benchmarks/ or a tools/d* script sets or reads it, or it is a
deployment setting (path, address, credential, a server's capacity or
timeout), or it is a plane's one documented mode switch; anything else is
a constant beside the code that uses it (tests/test_repo_inventory.py).
"""

import os
import importlib.util

# ---------------------------------------------------------------------------
# Reference-parity knobs (dpark/conf.py)
# ---------------------------------------------------------------------------

MEM_PER_TASK = 200.0          # MB per task (process/mesos masters)
MAX_TASK_FAILURES = 4         # retries before a job aborts
# parent-stage resubmissions (FetchFailed lineage recovery) per stage
# before the job aborts with a chained error: a shuffle source that
# keeps failing must not loop the DAG forever (ISSUE 5 satellite)
MAX_STAGE_FAILURES = 4
SCHEDULER_STALL_TIMEOUT = 60  # s between event-queue deadlock checks; a
                              # check only aborts when NO task is in flight

# speculative re-launch of stragglers (reference: dpark/job.py): once
# SPECULATION_QUANTILE of a stage's tasks finished, any task running
# longer than SPECULATION_MULTIPLIER x the median duration gets a
# duplicate; first completion wins
SPECULATION = True
SPECULATION_QUANTILE = 0.75
SPECULATION_MULTIPLIER = 2.0
MAX_TASK_MEMORY = 15 << 10    # MB hard ceiling when escalating retries

# shuffle behaviour (the reference's `rddconf`)
SORT_SHUFFLE = False          # sort-based shuffle path instead of hash-dict
SPILL_DIR_THRESHOLD = 0.8     # fraction of MEM_PER_TASK before disk spill
SHUFFLE_CHUNK_RECORDS = 1 << 16

# workdir candidates: first writable wins (dpark: DPARK_WORK_DIR)
DPARK_WORK_DIR = os.environ.get("DPARK_WORK_DIR", "/tmp/dpark_tpu")

# compression codec for shuffle files / broadcast blocks: zlib always
# available; lz4 used when importable (reference prefers lz4).
COMPRESS = "auto"

# ---------------------------------------------------------------------------
# chaos plane + recovery knobs (dpark_tpu/faults.py — ISSUE 5)
# ---------------------------------------------------------------------------

# deterministic fault injection spec, e.g.
#   "shuffle.fetch:p=0.2,seed=7;executor.dispatch:nth=3,kind=oom"
# empty = no injection (zero hot-path cost).  See faults.py for the
# full grammar and the list of named sites.
DPARK_FAULTS = os.environ.get("DPARK_FAULTS", "")

# erasure-coded shuffle exchange (dpark_tpu/coding.py — ISSUE 6):
#   off      no parity (default; zero hot-path cost)
#   xor      4 data shards + 1 XOR parity per bucket/spill payload
#   xor(k)   same with k data shards
#   rs(k,m)  k data + m Reed-Solomon GF(2^8) parity shards
# With coding on, shuffle buckets and spill runs carry parity shards;
# the fetch side reads all n shards concurrently and DECODES from the
# fastest k — a failed or straggling fetch costs a decode, not a
# lineage recompute.  Counters surface as `decodes` in job records,
# recovery_summary(), and the bench JSON.
DPARK_SHUFFLE_CODE = os.environ.get("DPARK_SHUFFLE_CODE", "off")

# per-shard fetch attempts before a shard counts as lost (coded mode
# only; attempts past the first cycle through replica uris).  Retries
# are cheap relative to a decode failure's lineage fallback, so keep
# this >= 2 under fault injection.
SHUFFLE_SHARD_ATTEMPTS = int(os.environ.get(
    "DPARK_SHUFFLE_SHARD_ATTEMPTS", "3") or 1)

# straggler-adaptive per-exchange code selection (ISSUE 19): "1" lets
# the scheduler price (k,m) PER SHUFFLE from the adapt store's
# per-peer fetch-tail sketches and observed decode/fault rates instead
# of paying DPARK_SHUFFLE_CODE's static parity tax everywhere —
# exchanges whose recorded peers straggle (p99/p50 over
# coding.ADAPT_TAIL_RATIO) or decoded from parity before escalate to
# CODE_ADAPT_ESCALATE, exchanges whose peers are uniformly tight drop
# to uncoded.  Requires DPARK_ADAPT=on to steer; under
# DPARK_ADAPT=observe choices are logged (applied=false) and the
# static code runs, bit-identical.  The writer's self-describing frame
# geometry makes mixed per-shuffle codes safe on the wire.
CODE_ADAPT = os.environ.get("DPARK_CODE_ADAPT", "0") == "1"

# the code an escalated exchange runs (parse_code grammar); the
# no-history / insufficient-samples default stays DPARK_SHUFFLE_CODE
CODE_ADAPT_ESCALATE = os.environ.get(
    "DPARK_CODE_ADAPT_ESCALATE", "rs(4,2)")

# ---------------------------------------------------------------------------
# adaptive execution (dpark_tpu/adapt.py — ISSUE 7)
# ---------------------------------------------------------------------------

# off | observe | on.  "observe" (the CI-safe default) records
# per-(program, shape class) compute/exchange/spill ms, OOM-ladder
# outcomes, combine ratios, and skew histograms into a persistent
# store but NEVER changes a plan — bit-identical to "off".  "on"
# additionally steers four decision points (wave budget seeding,
# device-vs-object path by predicted cost, skew-widened reduce sides,
# map-side-combine pricing); every steered choice is recorded as an
# `adapt` decision in the job record and bench JSON.
DPARK_ADAPT = os.environ.get("DPARK_ADAPT", "observe")

# where the stats store lives (crc-framed JSON lines, process-safe
# append; delete the directory to reset all learned budgets/costs)
DPARK_ADAPT_DIR = os.environ.get(
    "DPARK_ADAPT_DIR", os.path.join(DPARK_WORK_DIR, "adapt"))

# the append-only store compacts down to its in-memory aggregates
# (one line per key) when the file exceeds this many bytes at load —
# unbounded growth would otherwise make every process re-read and
# crc-check an ever-longer history.  0 disables compaction.
ADAPT_STORE_MAX_BYTES = int(os.environ.get(
    "DPARK_ADAPT_STORE_MAX_BYTES", str(1 << 22)) or 0)

# mid-job re-planning at the stage boundary (ISSUE 19): "1" lets the
# scheduler re-partition a reduce side BEFORE launching it when the
# completed map stage's on-disk bucket sizes show hash-collision skew
# the plan-time guess missed (dominant-bucket byte fraction >=
# schedule.REPLAN_SKEW_FRAC) — a same-width salted re-split stage
# re-keys the buckets without recomputing any map task (resubmits ==
# recomputes == 0) and the choice lands as `replan_reason` on the job record plus an
# adapt "replan" record, so the NEXT run of the same call site salts
# its partitioner at plan time and skips the mid-job re-split.
# Requires DPARK_ADAPT=on to steer; observe mode records the would-be
# re-plan (applied=false) and launches the original reduce side.
REPLAN = os.environ.get("DPARK_REPLAN", "0") == "1"

# floor on total exchange bytes before a re-plan is considered: tiny
# exchanges re-split slower than they run
REPLAN_MIN_BYTES = int(os.environ.get(
    "DPARK_REPLAN_MIN_BYTES", "4096") or 0)

# deterministic stand-in for a device HBM ceiling (bench/test aid): a
# streamed wave budget above this many rows/device raises the same
# RESOURCE_EXHAUSTED class the degradation ladder halves on, so the
# OOM ladder and the adaptive store's learned budgets can be exercised
# on backends that report no memory limit (XLA:CPU).  0 = off.
EMULATED_WAVE_OOM_ROWS = int(os.environ.get(
    "DPARK_EMULATED_WAVE_OOM_ROWS", "0") or 0)

# ---------------------------------------------------------------------------
# resident executor service (dpark_tpu/service.py — ISSUE 9)
# ---------------------------------------------------------------------------

# When set, every DparkContext in this process attaches to ONE shared
# JobServer instead of owning a scheduler: the value is the master
# spec the server runs ("local", "tpu", "tpu:2", ...).  The server
# owns the mesh + JAXExecutor for the life of the process and
# multiplexes all contexts' jobs onto it — compiled programs and the
# HBM shuffle store amortize across jobs.  "" (the default) keeps the
# one-context-one-scheduler behavior bit-identical (one `is None`
# check per seam).  Remote clients do not use this knob: they ship
# job FUNCTIONS to a served JobServer (see service.serve /
# service.ServiceClient).
DPARK_SERVICE = os.environ.get("DPARK_SERVICE", "")

# concurrent stage-execution slots in the job server's fair
# dispatcher.  Device stages additionally serialize on the executor's
# mesh lock (two concurrently dispatched collective programs wedge
# the XLA:CPU rendezvous), so extra slots buy overlap between one
# job's device stage and another's host/object-path stage.
SERVICE_SLOTS = int(os.environ.get("DPARK_SERVICE_SLOTS", "2") or 1)

# admission control: at most this many jobs RUN concurrently; further
# submissions queue (fairness weights still apply once admitted)...
SERVICE_MAX_JOBS = int(os.environ.get("DPARK_SERVICE_MAX_JOBS",
                                      "4") or 1)

# ...and the admission queue itself is bounded: a submission that
# would make more than this many jobs wait is REFUSED with an error
# instead of growing an unbounded backlog (a resident service must
# shed load, not buffer it forever).  0 (or an empty env var) means
# UNBOUNDED — explicitly opting out of load shedding.
SERVICE_QUEUE_MAX = int(os.environ.get("DPARK_SERVICE_QUEUE_MAX",
                                       "16") or 0)

# weighted round-robin fairness: this client's jobs get this many
# stage-execution turns per cycle relative to weight-1 peers (read at
# context attach; the per-job weight rides the submission)
SERVICE_WEIGHT = int(os.environ.get("DPARK_SERVICE_WEIGHT", "1") or 1)

# compiled-program cache bound (ISSUE 9 satellite): the executor's
# per-process program cache holds at most this many entries (LRU
# eviction; hit/miss/evict counters ride /metrics and the bench
# `service` section).  A resident service compiles across many jobs
# for the life of the mesh — unbounded growth was fine for one-job
# processes, not for a server.  0 = unbounded (the pre-service
# behavior).
PROGRAM_CACHE_MAX = int(os.environ.get("DPARK_PROGRAM_CACHE_MAX",
                                       "512") or 0)

# shared-computation plane (ISSUE 18): off | mem | disk.  "off" (the
# default) costs one `is None` check at the planner's probe seam and
# is bit-identical to any cached run; "mem" serves repeated sub-plans
# (and mergeable partial aggregates) from a host-memory LRU tier;
# "disk" adds a crc-framed on-disk tier that survives restarts (any
# defect means recompute, never an error).  Entries invalidate by source
# fingerprint: v2 tabular footer stats, (path, mtime, size) for v1.
RESULT_CACHE = os.environ.get("DPARK_RESULT_CACHE", "off")

# where disk-tier result entries live (delete the directory to reset)
RESULT_CACHE_DIR = os.environ.get(
    "DPARK_RESULT_CACHE_DIR",
    os.path.join(DPARK_WORK_DIR, "resultcache"))

# memory-tier byte budget: least-recently-served entries evict past
# it, and a single result larger than the whole budget never stores.
RESULT_CACHE_BUDGET = int(os.environ.get(
    "DPARK_RESULT_CACHE_BUDGET", str(64 << 20)) or (64 << 20))

# dcn transient-connect retry: total attempts (1 = no retry) and the
# base backoff seconds (exponential with full jitter: attempt k sleeps
# uniform in [base*2^k/2, base*2^k]).  Application-level ServerError
# stays non-retryable — only transport connect errors back off.
DCN_CONNECT_ATTEMPTS = int(os.environ.get("DPARK_DCN_CONNECT_ATTEMPTS",
                                          "3") or 1)
DCN_CONNECT_BACKOFF = float(os.environ.get("DPARK_DCN_CONNECT_BACKOFF",
                                           "0.05"))

# dcn fetch deadline + whole-request retry (ISSUE 20 satellite — these
# replace the hardcoded 30s socket timeout): every dcn/bulkplane fetch
# and tracker call uses DCN_TIMEOUT_MS as its socket deadline, and a
# transport failure (connect refused, torn stream, timeout) retries up
# to DCN_RETRIES total attempts on a fresh connection with the same
# exponential-full-jitter schedule as the connect path
# (dcn.backoff_delays).  Application-level ServerError never retries.
DCN_TIMEOUT_MS = float(os.environ.get("DPARK_DCN_TIMEOUT_MS",
                                      "30000") or 30000)
DCN_RETRIES = int(os.environ.get("DPARK_DCN_RETRIES", "3") or 1)

# peer-liveness lease (ISSUE 20 tentpole b): every successful dcn/bulk
# transfer renews the serving peer's lease for this many milliseconds.
# A transport failure AFTER the lease lapsed marks the peer suspect
# (counted once per transition as `lease_expiries` on /metrics), and
# the coded fetch path fails that peer's shard attempts fast — racing
# the parity shards from live peers instead of waiting out socket
# timeouts — falling back to lineage recompute only when parity can't
# cover the loss.  A suspect peer is re-probed after the same interval
# so a recovered process rejoins without operator action.  0 disables
# liveness tracking entirely (every peer always "alive").
PEER_LEASE_MS = float(os.environ.get("DPARK_PEER_LEASE_MS",
                                     "5000") or 0)

# crash-consistent job journal (ISSUE 20 tentpole a): off | on.  "on"
# write-ahead-logs job submission, stage completion, and the
# shuffle-output registry as crc-framed JSON lines under
# DPARK_JOURNAL_DIR, so a restarted controller replays the journal and
# resumes accepted jobs from the last completed stage — re-running
# only stages whose outputs are gone (lineage recomputes the holes).
# Off (the default) costs one `is None` check per job and stage;
# results are bit-identical either way.
DPARK_JOURNAL = os.environ.get("DPARK_JOURNAL", "off")

# where journal files live; must SURVIVE a controller restart, so the
# default sits beside (not inside) the per-session workdir.  Delete
# the directory to forget every resumable job.
DPARK_JOURNAL_DIR = os.environ.get(
    "DPARK_JOURNAL_DIR",
    os.path.join(DPARK_WORK_DIR.split(",")[0].strip() or "/tmp",
                 "journal"))

# ---------------------------------------------------------------------------
# multi-controller bulk data plane (dpark_tpu/bulkplane.py — ISSUE 12)
# ---------------------------------------------------------------------------

# Route cross-process (tcp://) shuffle buckets, coded shard frames,
# broadcast chunks, and remote service results over the chunked,
# crc-framed bulk streaming channel instead of the single-frame pickled
# host bridge.  HBM-resident flat (k, v) buckets additionally serve RAW
# COLUMN bytes that assemble zero-copy into numpy views / device_put
# batches on the receiving controller.  "0" falls back to the plain
# single-frame protocol everywhere (bisection aid); a peer that does
# not speak the bulk protocol is fallen back to per request.
BULK_PLANE = os.environ.get("DPARK_BULK_PLANE", "1") != "0"

# payload bytes per bulk stream chunk frame (each frame carries its own
# crc32, so corruption costs one re-read, not a silently wrong answer)
BULK_CHUNK_BYTES = int(os.environ.get("DPARK_BULK_CHUNK_BYTES",
                                      str(1 << 20)) or (1 << 20))

# per-peer concurrency window: at most this many bulk streams in
# flight against one peer (a reduce fan-out of n coded shard fetches
# must not open n sockets to a single serving controller at once).
# 0 = unbounded.
BULK_STREAMS_PER_PEER = int(os.environ.get(
    "DPARK_BULK_STREAMS_PER_PEER", "4") or 0)

# bounded retry on bulk-channel reads (1 = no retry): a torn stream
# (peer restarting mid-transfer) or a crc-rejected frame re-reads on a
# FRESH connection with the same exponential-full-jitter backoff
# schedule the dcn connect path uses (dcn.backoff_delays — one
# implementation, two call sites).  Application-level ServerError
# stays non-retryable.
BULK_READ_ATTEMPTS = int(os.environ.get("DPARK_BULK_READ_ATTEMPTS",
                                        "3") or 1)

# ---------------------------------------------------------------------------
# TPU-native knobs (no reference analog)
# ---------------------------------------------------------------------------

# device mesh axis name used by shard_map programs
MESH_AXIS = "parts"

# per-device bucket padding granularity for the count-exchange all_to_all
# shuffle; buckets are padded up to a multiple of this so recompilation only
# happens when the padded size class changes (power-of-two size classes).
BUCKET_PAD_GRANULARITY = 1024

# max bytes of HBM a single shuffle round may use per device before the
# chunked multi-round path kicks in (the "external merge" equivalent).
SHUFFLE_HBM_BUDGET = 2 << 30

# out-of-core streaming: a monoid reduce over columnar input larger than
# this many rows per device runs in ingest->combine->exchange waves, so
# the working set in HBM is one chunk plus the combined state (the >HBM
# pipeline of SURVEY.md 7.2 item 4)
# "auto" sizes waves to device HBM at run time (stream_chunk_rows);
# assigning a number pins the wave size exactly (tests/benchmarks force
# small chunks to exercise multi-wave machinery at toy sizes)
STREAM_CHUNK_ROWS = "auto"
_STREAM_CHUNK_ROWS_FALLBACK = 4 << 20


def _hbm_bytes_limit():
    """Per-device accelerator memory; 0 on the CPU platform only (it
    reports none).  An accelerator that does not say how much memory
    it has is an error, not a CPU-sized default.  Only called once a
    backend is already live."""
    global _HBM_LIMIT_CACHE
    if _HBM_LIMIT_CACHE is None:
        import jax
        dev = jax.local_devices()[0]
        limit = 0
        if dev.platform != "cpu":
            limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
            if limit <= 0:
                raise RuntimeError(
                    "%s reports no memory_stats()['bytes_limit']; the "
                    "wave budget cannot be sized (pin "
                    "conf.STREAM_CHUNK_ROWS to run anyway)" % (dev,))
        _HBM_LIMIT_CACHE = limit
    return _HBM_LIMIT_CACHE


_HBM_LIMIT_CACHE = None


def stream_chunk_rows(row_bytes=16):
    """Effective wave size in rows per device: an explicitly assigned
    STREAM_CHUNK_ROWS wins; "auto" sizes the wave to the device's own
    HBM (each wave pays fixed dispatch and sync costs, so waves are
    sized to memory, not to a CPU-tuned constant).

    HBM accounting: without donation, raw wave bytes/device = HBM/16 —
    the wave working set (ingest + bucketized + receive + merge copies,
    ~6x) then peaks well under half of HBM.  With DONATE_BUFFERS on,
    the per-wave programs reuse their dead input buffers in place
    (ingest -> bucketized, received -> merged), dropping the multiplier
    by roughly two copies; the budget rises to HBM/12 — but the
    pipeline also holds up to STREAM_PIPELINE_DEPTH extra ingested
    waves in flight, which is why the divisor does not drop further.

    With DPARK_ADAPT=on the persistent stats store can SEED the
    budget below the derived value: the last-known-good budget
    recorded for this row-width class (e.g. by a previous run's OOM
    degradation ladder) wins over re-deriving the memory bound and
    re-walking the halving ladder (ISSUE 7).  An explicitly assigned
    STREAM_CHUNK_ROWS always bypasses both."""
    if STREAM_CHUNK_ROWS != "auto":
        return STREAM_CHUNK_ROWS
    limit = _hbm_bytes_limit()
    if not limit:
        base = _STREAM_CHUNK_ROWS_FALLBACK
    else:
        divisor = 12 if DONATE_BUFFERS else 16
        base = max(_STREAM_CHUNK_ROWS_FALLBACK,
                   limit // (divisor * max(1, row_bytes)))
    from dpark_tpu import adapt
    return adapt.steer_wave_budget(base, row_bytes)

# text-source stages bigger than this stream in waves of splits instead
# of materializing the whole encoded dataset (same out-of-core pipeline)
STREAM_TEXT_BYTES = 1 << 28

# ---------------------------------------------------------------------------
# pane-tree windowing (dpark_tpu/panes.py + dstream.py — ISSUE 10)
# ---------------------------------------------------------------------------

# slice windowed DStreams into slide-sized PANES whose partial
# aggregates persist across ticks (cached reduced RDDs; on the tpu
# master their shuffle outputs stay HBM-resident): invertible
# reduceByKeyAndWindow updates the window in O(1) panes per slide
# (prev + new pane - expired pane) regardless of the window/slide
# ratio, and non-invertible window reduces merge O(log w) cached
# dyadic tree nodes instead of re-reducing all w panes.  "0" disables
# — every windowed op then takes the pre-pane whole-window paths (the
# parity suite's reference side, and a bisection aid).  Pane mode
# needs window % slide == 0 and slide % batch == 0; misaligned
# windows keep the old paths regardless of this knob.
STREAM_PANES = os.environ.get("DPARK_STREAM_PANES", "1") != "0"

# non-invertible pane windows below this many panes skip the dyadic
# merge tree and union their panes flat each tick (the tree's extra
# cached intermediate shuffles only amortize once O(log w) beats w).
# With DPARK_ADAPT=on the planner overrides this static split-point
# choice from OBSERVED per-tick pane costs (adapt.steer_pane_mode).
STREAM_PANE_TREE_MIN = int(os.environ.get(
    "DPARK_STREAM_PANE_TREE_MIN", "8") or 0)

# default allowed event-time lateness in seconds for windowed ops that
# set an eventTime extractor without an explicit lateness= argument:
# the watermark trails the max observed event time by this much, and
# records older than the watermark drop (counted per stream).  Late
# records inside the bound patch ONLY their pane, never the window.
STREAM_ALLOWED_LATENESS = float(os.environ.get(
    "DPARK_STREAM_LATENESS", "0") or 0)

# bounded late-data buffer: at most this many late records are admitted
# per pane patch per tick — anything beyond drops (counted as
# late_dropped) so a storm of stragglers cannot grow a patch job
# without bound.  0 = unbounded.
STREAM_LATE_BUFFER_ROWS = int(os.environ.get(
    "DPARK_STREAM_LATE_BUFFER", "100000") or 0)

# ---------------------------------------------------------------------------
# overlapped wave pipeline (backend/tpu executor stream loops)
# ---------------------------------------------------------------------------

# how many waves the host runs AHEAD of the device: depth >= 1
# double-buffers device ingest (wave k+1 device_puts while wave k
# computes) and defers each wave's host readback/spill by one wave so
# D2H transfers ride behind the next wave's compute.  0 disables the
# overlap entirely (serial waves — the pre-pipeline behavior, useful
# when bisecting); values above 1 only deepen the host-side
# tokenize/ingest lookahead, at one extra ingested wave of HBM each.
STREAM_PIPELINE_DEPTH = int(os.environ.get("DPARK_PIPELINE_DEPTH",
                                           "1") or 0)

# donate dead input buffers to the per-wave jitted programs (ingest ->
# narrow/bucketize, received -> merge, batch -> concat): XLA reuses
# them in place, so a wave holds ONE copy of its working set in HBM
# instead of two.  Streamed paths only — in-core programs keep their
# inputs alive (result cache / shuffle store leaves must survive the
# call).  stream_chunk_rows raises the auto wave budget when this is
# on (see its HBM-accounting note).  "0" disables (e.g. when bisecting
# an aliasing bug).
DONATE_BUFFERS = os.environ.get("DPARK_DONATE_BUFFERS", "1") != "0"

# background spill writer for the spilled-run stream: compress+write of
# per-partition runs happens on a dedicated thread with a bounded
# queue, off the wave loop ("0" = write inline, serial).  Writer
# errors surface on the next enqueue or at end-of-stream flush.
SPILL_WRITER = os.environ.get("DPARK_SPILL_WRITER", "1") != "0"

# collective tests over the virtual CPU mesh need roughly one host CPU
# per mesh device: an 8-device all_to_all on a 2-CPU container wedges
# (XLA:CPU collectives rendezvous across intra-process threads).  The
# test harness skips mesh-marked tests when os.cpu_count() is below
# this; DPARK_MESH_TEST_DEVICES=0 forces them to run anyway.
MESH_TEST_DEVICES = int(os.environ.get("DPARK_MESH_TEST_DEVICES",
                                       "8") or 0)

# thread-pool width for text-split tokenize/encode (the C++ tokenizer
# releases the GIL, so splits tokenize truly concurrently; the reference
# runs hot loop #1 on every executor — SURVEY.md 3.1).  0 = cpu count.
INGEST_THREADS = int(os.environ.get("DPARK_INGEST_THREADS", "0") or 0)

# composite (tuple) keys on the device path: records keyed by a FLAT
# tuple of up to MAX_KEY_LEAVES numeric scalars — ((user, item), v),
# ((src, dst), w) — classify onto the array path end to end (hash
# destinations via the pair-extended phash, sort/segment/combine over
# all key columns, tuple repacked at egest).  Nested key tuples and
# non-numeric key leaves fall back; the `host-fallback-key` lint rule
# reports why.  Each extra key leaf is one more sort operand in every
# shuffle program, so keep this small (2-3 covers the (user, item) /
# (src, dst) shapes real jobs use)
MAX_KEY_LEAVES = int(os.environ.get("DPARK_MAX_KEY_LEAVES", "4") or 4)

# default dtype for device-side values
DEFAULT_DTYPE = "int32"

# narrow int64 columns to int32 on the all_to_all wire when a runtime
# min/max guard proves every valid value fits (TPUs have no native i64
# datapath: XLA emulates i64 as i32 pairs, doubling ICI bytes).  Compute
# stays i64 either way; set 0 to disable (e.g. when bisecting parity).
NARROW_EXCHANGE = os.environ.get("DPARK_NARROW_EXCHANGE", "1") != "0"

# graph-build-time rewrite of groupByKey().mapValue(provable aggregate)
# to a map-side-combining combineByKey (rdd._group_agg_rewrite): the
# classic combiner optimization, exchange volume O(distinct keys).
# "0" disables; the device SegAggOp path then serves these chains.
# FLOAT CAVEAT: the rewrite reassociates the fold — sum/mean over float
# values pre-combine map-side, so low-order bits depend on partitioning
# and combine order on EVERY master (including local), where the
# un-rewritten groupByKey summed each group's list in row order.
# Integer aggregates and min/max are exact either way.
GROUP_AGG_REWRITE = os.environ.get("DPARK_GROUP_AGG_REWRITE",
                                   "1") != "0"

# device segmented apply (SegMapOp): groupByKey().mapValues(f) with an
# arbitrary TRACEABLE per-group f (beyond the five provable aggregates)
# runs on device as a vmap over power-of-two padded group buckets.
# Admission additionally verifies f is padding-invariant (zero-pad or
# repeat-last-pad, checked on seeded samples at classification time);
# functions that need the true group length (mean-like shapes beyond
# the provable forms) keep the host path with a recorded
# fallback_reason.  "0" disables (host object path, the pre-PR
# behavior — bisection aid).
SEG_MAP = os.environ.get("DPARK_SEG_MAP", "1") != "0"

# compile-budget guard for the segmented apply: each power-of-two group
# bucket is one trace/compile of the user's per-group function, so a
# tiny input with many buckets can spend more wall time compiling than
# computing.  A stage whose estimated row count is below
# (estimated buckets x this many rows) degrades to the host loop with
# fallback_reason "seg_map compile budget".  0 disables the guard
# (every eligible stage rides; the default — compiles are cached by
# structural identity, so steady-state streams pay once).
SEG_MIN_ROWS_PER_TRACE = int(os.environ.get(
    "DPARK_SEG_MIN_ROWS_PER_TRACE", "0") or 0)

# device->host egest: int64 scalar columns at least this large are
# min/max-probed and ride the link as int32 when every valid value fits
# (D2H is the slowest link a collect() crosses; smaller columns are not
# worth the probe's round trip).  Tests shrink this to exercise the
# path at toy sizes.
EGEST_NARROW_MIN_BYTES = 8 << 20

# collect()s bigger than this log a reduce-before-collect warning
# (the reference's executor result-size limit analog, SURVEY.md
# section 2.1 executor row: oversized inline results get flagged)
EGEST_WARN_BYTES = 256 << 20

# when set, the tpu executor writes a jax.profiler trace here for the
# whole session (view with tensorboard / xprof).  NOTE: this knob was
# DPARK_TRACE_DIR before ISSUE 8; that name now belongs to the span
# trace plane's spool directory below.
XPROF_DIR = os.environ.get("DPARK_XPROF_DIR")

# ---------------------------------------------------------------------------
# trace plane (dpark_tpu/trace.py — ISSUE 8)
# ---------------------------------------------------------------------------

# off | ring | spool.  "off" (the default) costs one `is None` check
# per site and is bit-identical to any traced run; "ring" keeps spans
# in a bounded in-memory ring (served live by the web UI's
# /api/trace); "spool" additionally appends crc-framed JSON lines to
# per-process files under DPARK_TRACE_DIR — worker-process spans and
# fault/decode counters then merge back into the driver's job records,
# and tools/dtrace exports the merged Chrome trace / critical path.
DPARK_TRACE = os.environ.get("DPARK_TRACE", "off")

# where spool files live (one trace-<host>-<pid>.jsonl per process;
# delete the directory to reset)
DPARK_TRACE_DIR = os.environ.get(
    "DPARK_TRACE_DIR", os.path.join(DPARK_WORK_DIR, "trace"))

# bounded in-memory span ring per process (ring AND spool modes)
TRACE_RING_SPANS = int(os.environ.get("DPARK_TRACE_RING", "4096")
                       or 4096)

# per-process spool byte cap: span writes stop past this (counted as
# dropped); counter events always land (they are the worker-counter
# merge substrate).  0 = unbounded.
TRACE_SPOOL_MAX_BYTES = int(os.environ.get(
    "DPARK_TRACE_SPOOL_MAX_BYTES", str(32 << 20)) or 0)

# ---------------------------------------------------------------------------
# online health plane (dpark_tpu/health.py — ISSUE 14)
# ---------------------------------------------------------------------------

# off | on.  "on" (the default) installs the streaming health sink:
# every record the TRACE plane emits additionally folds into bounded
# per-site latency sketches (log2 buckets, p50/p95/p99 estimates) and
# event-rate counters — /api/health, the bench `health` section, and
# the adapt-store site-tail handoff read them.  With DPARK_TRACE=off
# nothing is emitted and the sink is inert either way; "off" removes
# even the per-record `is None` check's target (the faults/trace
# contract: off-mode job results are bit-identical to on).
DPARK_HEALTH = os.environ.get("DPARK_HEALTH", "on")

# bounded sketch registry: at most this many per-site sketches (past
# the cap, new sites fold into their base site name) — streaming
# aggregation must hold bounded memory no matter how long the process
# serves
HEALTH_MAX_SITES = int(os.environ.get("DPARK_HEALTH_MAX_SITES",
                                      "256") or 256)

# per-tenant SLO accounting (service.py — ISSUE 14): the default
# per-job latency target in ms for tenants that declare none
# explicitly (ServiceClient(..., slo_ms=) / ClientScheduler slo_ms).
# 0 = no SLO tracked for undeclared tenants.
SERVICE_SLO_MS = float(os.environ.get("DPARK_SERVICE_SLO", "0") or 0)

# ---------------------------------------------------------------------------
# resource attribution plane (dpark_tpu/ledger.py — ISSUE 15)
# ---------------------------------------------------------------------------

# off | on.  "on" (the default) installs the ledger sink as a second
# TracePlane.record consumer (the health.py contract — one `is None`
# check per record when off, on/off job results bit-identical): spans
# fold into bounded merge-associative per-(tenant, job, stage,
# program-signature) resource accounts — device wall ms, compile ms,
# mesh-lock wait ms, HBM byte-seconds, shuffle/bulk/spill bytes.
# /api/ledger, per-tenant /metrics counters, and the dtrace --ledger
# offline twin read them.  With DPARK_TRACE=off nothing is emitted and
# the ledger is inert either way.
DPARK_LEDGER = os.environ.get("DPARK_LEDGER", "on")

# bounded account registry: at most this many (job, stage, signature)
# account keys; past the cap, new keys fold into their job's coarse
# account (stage/sig dropped) so TOTALS stay honest no matter how many
# distinct programs a resident server serves.  0 = unbounded.
LEDGER_MAX_KEYS = int(os.environ.get("DPARK_LEDGER_MAX_KEYS",
                                     "512") or 0)

# concurrency sanitizer plane (dpark_tpu/locks.py — ISSUE 16): the
# named-lock registry records per-thread lock acquisition order and
# merges it into a process-wide graph, reporting lock-order cycles
# even when no deadlock fired.
#   off     no sanitizer; every named lock costs one `is None` check
#           per acquisition (the standard plane off-mode contract)
#   record  record edges; cycles() / report() surface inversions —
#           CI arms this across the whole test suite
#   strict  the acquisition that CLOSES a cycle (or self-deadlocks a
#           non-reentrant lock) raises LockOrderError pre-acquire
DPARK_LOCKCHECK = os.environ.get("DPARK_LOCKCHECK", "off")

# shard/bucket fetch result waits (lockcheck `unbounded-wait` fixes):
# a wedged peer read on a daemon fetch thread must surface as a fetch
# failure the scheduler can recover from, not park the driver forever.
# Seconds; generous — only a true wedge ever waits this long.
SHUFFLE_FETCH_WAIT_S = float(os.environ.get(
    "DPARK_SHUFFLE_FETCH_WAIT_S", "300") or 300)

# flight recorder (ISSUE 14): warning-and-above events ALWAYS land in
# a bounded in-memory ring (even with DPARK_TRACE=off); setting this
# directory additionally dumps a crc-framed snapshot (ring + health
# sketches + recovery summary + adapt decisions) there on job abort,
# stage degrade, or SIGUSR2 — post-mortem via tools/dtrace --flight.
# "" (the default) keeps the ring armed but writes nothing.
DPARK_FLIGHT_DIR = os.environ.get("DPARK_FLIGHT_DIR", "")

# the per-process dump cap (a crash loop must not fill the disk with
# snapshots)
FLIGHT_MAX_DUMPS = int(os.environ.get("DPARK_FLIGHT_MAX_DUMPS", "16")
                       or 0)

# trace-overhead-hint lint rule: warn when DPARK_TRACE=spool and a
# reduce task's estimated spool writes (one fetch span per parent map
# bucket + the task spans) exceed this — tiny-task jobs then spend
# comparable time spooling and computing
TRACE_SPAN_WRITES_PER_TASK = int(os.environ.get(
    "DPARK_TRACE_SPAN_WRITES_PER_TASK", "64") or 64)

# ---------------------------------------------------------------------------
# columnar query plane (dpark_tpu/query/ — ISSUE 13)
# ---------------------------------------------------------------------------

# Lower table/SQL DSL actions through the rule-driven query planner:
# column-pruned vectorized tabular scans (filters evaluate over column
# batches before any row tuple materializes; chunks skip via footer
# min/max stats), group-by aggregates onto the device exchange /
# SegAggOp / SegMapOp, equi-joins onto the device join, string keys
# dictionary-encoded.  "0" pins every table action to the host row
# path (the pre-plan behavior — bisection aid and the bench A/B's
# baseline side).  Operators the planner cannot PROVE equivalent keep
# the host path per query, with the reason recorded
# (`table-host-fallback` lint rule + the planner's decision log).
QUERY_PLAN = os.environ.get("DPARK_QUERY", "1") != "0"

# ---------------------------------------------------------------------------
# pre-flight plan linter (dpark_tpu/analysis/)
# ---------------------------------------------------------------------------

# off | warn | error.  Every runJob lints the submitted lineage first:
# "warn" logs each finding once per process; "error" refuses a plan
# carrying error-severity findings (e.g. monoid-multileaf — the
# round-5 silent-wrong-answer shape) with PlanLintError BEFORE any
# task launches.  The env var wins at read time (analysis.lint_mode)
# so a single run can be escalated without editing conf.
DPARK_LINT = os.environ.get("DPARK_LINT", "warn")

# plan-wide-depth rule: more chained shuffles than this on one
# uncheckpointed lineage path draws a warning (0 disables the rule)
LINT_WIDE_DEPTH = int(os.environ.get("DPARK_LINT_WIDE_DEPTH", "4"))


def load_conf(path):
    """Execute a Python conf file and overlay module-level constants.

    Reference parity: dpark/conf.py (load_conf).
    """
    spec = importlib.util.spec_from_file_location("dpark_user_conf", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    g = globals()
    for k in dir(mod):
        if k.isupper() and k in g:
            g[k] = getattr(mod, k)


_user_conf = os.environ.get("DPARK_CONF")
if _user_conf and os.path.exists(_user_conf):
    load_conf(_user_conf)
