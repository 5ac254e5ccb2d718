"""Web UI: live job/stage progress over stdlib http.server.

Reference parity: dpark/web/ (optional flask app showing stages and
progress, SURVEY.md section 2.5).  flask is not in this image, so the
same capability ships on http.server: an HTML overview at /, JSON at
/api/jobs, the merged task profile (when --profile ran) at
/api/profile, fed by the scheduler's event history.  r5 (VERDICT r4
weak #5): per-job stage DAG view, per-task drill-down (click a stage
row), profile panel.  ISSUE 8: /metrics (Prometheus text format,
job/stage/task + fault/decode/degrade/adapt counters and
phase-seconds histograms) and /api/trace?job=N (the trace plane's
span timeline; stage rows link to it).
"""

import http.server
import json
import threading
import urllib.parse

from dpark_tpu.utils.log import get_logger

logger = get_logger("web")

def _esc(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", " ")


def render_metrics(scheduler):
    """The /metrics payload (Prometheus text exposition format 0.0.4):
    job/stage/task counters, fault/decode/degrade/adapt counters, and
    phase-seconds histograms.  Built from a defensive snapshot — a
    scrape racing a mutating job record returns valid text, never an
    error (ISSUE 8 satellite)."""
    lines = []

    def metric(name, mtype, help_text, samples):
        lines.append("# HELP %s %s" % (name, help_text))
        lines.append("# TYPE %s %s" % (name, mtype))
        for labels, value in samples:
            if labels:
                lab = ",".join('%s="%s"' % (k, _esc(v))
                               for k, v in sorted(labels.items()))
                lines.append("%s{%s} %s" % (name, lab, value))
            else:
                lines.append("%s %s" % (name, value))

    try:
        snap = scheduler.metrics_snapshot()
    except Exception:
        snap = {"jobs": {}, "stages": {}, "tasks": {},
                "counters": {}, "adapt_decisions": {},
                "phases": {}, "export_seconds": 0.0,
                "jobs_running": 0}
    metric("dpark_jobs_total", "counter", "jobs by final state",
           [({"state": s}, n) for s, n in sorted(snap["jobs"].items())]
           or [({"state": "none"}, 0)])
    metric("dpark_jobs_running", "gauge", "jobs currently in flight",
           [({}, snap.get("jobs_running", 0))])
    # resident service (ISSUE 9): jobs waiting on admission, and the
    # bounded compiled-program cache's counters — the warm-submit
    # acceptance ("0 re-compiles") is asserted from these
    svc = snap.get("service") or {}
    metric("dpark_jobs_queued", "gauge",
           "jobs waiting for service admission",
           [({}, svc.get("jobs_queued", 0))])
    pc = snap.get("program_cache") or {}
    for key, help_text in (
            ("hits", "compiled-program cache hits"),
            ("misses", "compiled-program cache misses (compiles)"),
            ("evictions", "compiled-program cache LRU evictions")):
        metric("dpark_program_cache_%s_total" % key, "counter",
               help_text, [({}, pc.get(key, 0))])
    # shared-computation result cache (ISSUE 18): the planner-probe
    # counters — the two-tenant reuse acceptance ("zero scan chunks
    # on the repeated query") is asserted from these
    try:
        from dpark_tpu import resultcache
        rc = resultcache.stats() or {}
    except Exception:
        rc = {}
    for key, help_text in (
            ("hits", "sub-plan results served whole from the shared "
                     "result cache"),
            ("partial_hits", "partial-aggregate merges served from "
                             "cached partials + a residual scan"),
            ("misses", "result-cache probes that fell through to "
                       "execution"),
            ("stores", "query results stored into the result cache"),
            ("evictions", "result-cache LRU evictions past the byte "
                          "budget"),
            ("disk_loads", "result entries loaded from the disk "
                           "tier"),
            ("disk_stores", "result entries written through to the "
                            "disk tier")):
        metric("dpark_resultcache_%s_total" % key, "counter",
               help_text, [({}, rc.get(key, 0))])
    metric("dpark_resultcache_bytes", "gauge",
           "resident result-cache memory-tier bytes",
           [({}, rc.get("bytes", 0))])
    # per-tenant SLO accounting (ISSUE 14): attainment + multi-window
    # burn gauges and the monotonic violation counter, one series per
    # tenant that declared a target
    tenants = svc.get("tenants") or {}
    rows = sorted(tenants.items())
    metric("dpark_tenant_slo_attainment", "gauge",
           "fraction of a tenant's jobs inside its declared SLO",
           [({"tenant": c}, t.get("attainment", 1.0))
            for c, t in rows] or [({"tenant": "none"}, 1.0)])
    metric("dpark_tenant_slo_burn", "gauge",
           "SLO error-budget burn rate per window (1.0 = budget "
           "consumed exactly as fast as allowed)",
           [({"tenant": c, "window": w}, b)
            for c, t in rows
            for w, b in sorted((t.get("burn") or {}).items())]
           or [({"tenant": "none", "window": "none"}, 0.0)])
    metric("dpark_tenant_slo_violations_total", "counter",
           "jobs that finished outside their tenant's SLO",
           [({"tenant": c}, t.get("violations_total", 0))
            for c, t in rows] or [({"tenant": "none"}, 0)])
    # resource attribution plane (ISSUE 15): per-tenant mesh
    # consumption counters.  Monotonic by construction — accounts only
    # ever grow and HBM byte-seconds accrue at release.
    try:
        from dpark_tpu import ledger
        ltenants = ledger.tenant_totals()
    except Exception:
        ltenants = {}
    lrows = sorted(ltenants.items())
    for key, help_text in (
            ("device_seconds", "attributed device wall seconds "
                               "(mesh-lock-held stage execution)"),
            ("lock_wait_seconds", "seconds spent waiting for the "
                                  "mesh lock (contention)"),
            ("hbm_byte_seconds", "HBM shuffle-store bytes x resident "
                                 "seconds, accrued at release"),
            ("bulk_bytes", "bulk data-plane payload bytes attributed "
                           "to the tenant's jobs")):
        metric("dpark_tenant_%s_total" % key, "counter", help_text,
               [({"tenant": c}, t.get(key, 0)) for c, t in lrows]
               or [({"tenant": "none"}, 0)])
    metric("dpark_stages_total", "counter", "stages by execution kind",
           [({"kind": k}, n) for k, n in sorted(snap["stages"].items())]
           or [({"kind": "none"}, 0)])
    metric("dpark_tasks_total", "counter", "recorded task completions",
           [({"ok": str(bool(k == "ok")).lower()}, n)
            for k, n in sorted(snap["tasks"].items())])
    for key, help_text in (
            ("retries", "task retries"),
            ("resubmits", "parent-stage lineage resubmissions"),
            ("recomputes", "intact-parent recomputes"),
            ("fetch_failed", "reduce-side fetch failures"),
            ("speculated", "speculative task duplicates"),
            ("replans", "mid-job reduce-side re-plans"),
            ("resumed_stages", "stages resumed from the crash journal "
                               "instead of re-executed")):
        metric("dpark_%s_total" % key, "counter", help_text,
               [({}, snap["counters"].get(key, 0))])
    # crash-consistent control plane (ISSUE 20): journal replay and
    # peer-lease counters — the kill -9 certification asserts
    # journal_replays/recovered_stages from these, and lease_expiries
    # is the liveness layer's detection count
    try:
        from dpark_tpu import journal
        jstats = journal.stats() or {}
    except Exception:
        jstats = {}
    jcounters = jstats.get("counters") or {}
    for key, help_text in (
            ("journal_replays", "journal replay passes that seeded at "
                                "least one completed stage"),
            ("recovered_stages", "completed stages recovered from the "
                                 "journal after a restart"),
            ("seeded_partitions", "map outputs re-registered from "
                                  "journaled locations"),
            ("skipped_frames", "corrupt/truncated journal frames "
                               "skipped during replay"),
            ("refused_files", "journal files refused (newer schema "
                              "than this process understands)")):
        metric("dpark_%s_total" % key, "counter", help_text,
               [({}, jcounters.get(key, 0))])
    try:
        from dpark_tpu import dcn
        lv = dcn.liveness_stats() or {}
    except Exception:
        lv = {}
    lcounters = lv.get("counters") or {}
    for key, help_text in (
            ("lease_expiries", "peer leases that lapsed into "
                               "suspicion (liveness detections)"),
            ("fast_fails", "fetch attempts failed fast on a "
                           "suspect peer's lease")):
        metric("dpark_%s_total" % key, "counter", help_text,
               [({}, lcounters.get(key, 0))])
    metric("dpark_peers_suspect", "gauge",
           "peers currently in the lease-expired suspect window",
           [({}, len(lv.get("suspect") or ()))])
    try:
        from dpark_tpu import faults
        fstats = scheduler.recovery_summary().get("faults", {}) \
            if hasattr(scheduler, "recovery_summary") \
            else faults.stats()
    except Exception:
        fstats = {}
    metric("dpark_faults_injected_total", "counter",
           "chaos-plane firings by site",
           [({"site": s}, st.get("fired", 0))
            for s, st in sorted(fstats.items())]
           or [({"site": "none"}, 0)])
    try:
        from dpark_tpu import coding
        dstats = coding.stats()
    except Exception:
        dstats = {}
    metric("dpark_decodes_total", "counter",
           "erasure-decode outcomes",
           [({"kind": k}, v) for k, v in sorted(dstats.items())
            if isinstance(v, int)
            and k not in ("parity_bytes",)]
           or [({"kind": "none"}, 0)])
    # per-peer decode attribution (ISSUE 19): which serving peer's
    # shards the policy repaired / raced / failed on — the evidence
    # behind a per-exchange escalation
    metric("dpark_decodes_by_peer_total", "counter",
           "erasure-decode outcomes by serving peer",
           [({"kind": k, "peer": p}, v)
            for p, counts in sorted((dstats.get("per_peer")
                                     or {}).items())
            for k, v in sorted(counts.items())]
           or [({"kind": "none", "peer": "none"}, 0)])
    metric("dpark_parity_bytes_total", "counter",
           "erasure-parity bytes written to shuffle buckets",
           [({}, int(dstats.get("parity_bytes", 0) or 0))])
    metric("dpark_adapt_decisions_total", "counter",
           "cost-model decisions (applied=steered)",
           [({"applied": "true"},
             snap["adapt_decisions"].get("applied", 0)),
            ({"applied": "false"},
             snap["adapt_decisions"].get("logged", 0)
             - snap["adapt_decisions"].get("applied", 0))])
    try:
        from dpark_tpu import trace as trace_mod
        emitted, dropped = trace_mod.counts()
        tmode = trace_mod.mode()
    except Exception:
        emitted = dropped = 0
        tmode = "off"
    metric("dpark_trace_spans_total", "counter",
           "trace spans emitted (mode label = DPARK_TRACE)",
           [({"mode": tmode}, emitted)])
    metric("dpark_trace_spans_dropped_total", "counter",
           "trace spans dropped (spool cap)", [({}, dropped)])
    # the host-bridge export total is cumulative wall time, not a
    # per-stage observation — a counter, so rate() works on it
    metric("dpark_export_seconds_total", "counter",
           "cumulative host-bridge export wall seconds",
           [({}, round(float(snap.get("export_seconds", 0.0)), 6))])
    # multi-controller bulk data plane (ISSUE 12): per-peer byte
    # counters both directions, stream totals, and the live
    # active-stream gauge
    try:
        from dpark_tpu import bulkplane
        bstats = bulkplane.stats()
    except Exception:
        bstats = {"sent": {}, "received": {}, "streams": 0,
                  "active": 0, "retries": 0, "corrupt_frames": 0,
                  "torn_streams": 0}
    metric("dpark_bulk_bytes_total", "counter",
           "bulk data-plane payload bytes by peer and direction",
           [({"peer": p, "direction": "received"}, v)
            for p, v in sorted(bstats["received"].items())]
           + [({"peer": p, "direction": "sent"}, v)
              for p, v in sorted(bstats["sent"].items())]
           or [({"peer": "none", "direction": "none"}, 0)])
    metric("dpark_bulk_streams_total", "counter",
           "completed bulk fetch streams", [({}, bstats["streams"])])
    metric("dpark_bulk_streams_active", "gauge",
           "bulk fetch streams currently in flight",
           [({}, bstats["active"])])
    for key, help_text in (
            ("retries", "bulk reads retried after a torn stream or "
                        "rejected frame"),
            ("corrupt_frames", "bulk frames rejected by crc"),
            ("torn_streams", "bulk streams cut mid-transfer")):
        metric("dpark_bulk_%s_total" % key, "counter", help_text,
               [({}, bstats[key])])
    # pane-plane stream gauges (ISSUE 10): live per-windowed-stream
    # state from the panes registry — resident pane partials, merge
    # activity, watermark lag, and late-record accounting
    try:
        from dpark_tpu import panes as panes_mod
        sstats = panes_mod.stream_stats()
    except Exception:
        sstats = {}
    rows = sorted(sstats.items())
    metric("dpark_stream_panes", "gauge",
           "resident pane partial aggregates per windowed stream",
           [({"stream": s}, st.get("panes", 0)) for s, st in rows]
           or [({"stream": "none"}, 0)])
    metric("dpark_stream_pane_merges_total", "counter",
           "pane merge-tree nodes built per windowed stream",
           [({"stream": s}, st.get("node_builds", 0))
            for s, st in rows] or [({"stream": "none"}, 0)])
    metric("dpark_stream_watermark_lag_seconds", "gauge",
           "processing-time distance back to the event-time watermark",
           [({"stream": s}, round(st["watermark_lag_s"], 6))
            for s, st in rows
            if st.get("watermark_lag_s") is not None]
           or [({"stream": "none"}, 0)])
    metric("dpark_stream_late_dropped_total", "counter",
           "late records dropped below the watermark / buffer bound",
           [({"stream": s}, st.get("late_dropped", 0))
            for s, st in rows] or [({"stream": "none"}, 0)])
    metric("dpark_stream_late_patched_rows_total", "counter",
           "admitted late records folded into pane patches",
           [({"stream": s}, st.get("late_patched_rows", 0))
            for s, st in rows] or [({"stream": "none"}, 0)])
    # phase-seconds histograms: one observation per streamed stage per
    # phase, pre-folded (with the trimmed-history archive) by
    # metrics_snapshot so the series stay monotonic
    from dpark_tpu.schedule import PHASE_BUCKETS
    lines.append("# HELP dpark_phase_seconds per-stage phase wall "
                 "seconds")
    lines.append("# TYPE dpark_phase_seconds histogram")
    phases = snap.get("phases", {})
    for phase in sorted(phases):
        h = phases[phase]
        acc = 0
        for i, le in enumerate(PHASE_BUCKETS):
            acc += h["buckets"][i]
            lines.append(
                'dpark_phase_seconds_bucket{phase="%s",le="%s"} %d'
                % (phase, le, acc))
        lines.append(
            'dpark_phase_seconds_bucket{phase="%s",le="+Inf"} %d'
            % (phase, h["count"]))
        lines.append('dpark_phase_seconds_sum{phase="%s"} %s'
                     % (phase, round(h["sum"], 6)))
        lines.append('dpark_phase_seconds_count{phase="%s"} %d'
                     % (phase, h["count"]))
    return "\n".join(lines) + "\n"

_PAGE = """<!doctype html>
<html><head><meta charset="utf-8"><title>dpark_tpu</title>
<style>
 body { font-family: monospace; margin: 2em; }
 table { border-collapse: collapse; margin-bottom: 1em; }
 td, th { border: 1px solid #999; padding: 4px 10px; text-align: left; }
 .done { color: #2a2; } .run { color: #d80; } .fail { color: #c22; }
 .dag { white-space: pre; background: #f6f6f6; padding: 8px;
        display: inline-block; margin: 2px 0 10px; }
 .tasks { margin-left: 2em; }
 .stage { cursor: pointer; }
 pre { background: #f6f6f6; padding: 8px; }
</style></head>
<body>
<h2>dpark_tpu jobs</h2>
<table id="t"><tr><th>job</th><th>scope</th><th>parts</th>
<th>finished</th><th>stages</th><th>seconds</th><th>state</th>
<th>client</th><th>queue ms</th><th>SLO (attain %)</th>
<th>cache (hit/miss)</th>
<th>recovery (resubmit/recompute/retry)</th>
<th>decodes (repair/straggler/fail)</th>
<th>adapt (steered/logged)</th></tr></table>
<h2>stages <small>(click a row for its tasks; DAG per job below)</small></h2>
<table id="s"><tr><th>job</th><th>stage</th><th>rdd</th>
<th>parts</th><th>kind</th><th>seconds</th><th>device run s</th>
<th>HBM bytes</th><th>wire bytes</th><th>remote fetch B</th>
<th>pad eff</th>
<th>waves</th><th>idle %</th><th>pipeline ms (in/cmp/xchg/spill)</th>
<th>decodes</th>
<th>fetch p99 ms</th>
<th>stream</th>
<th>fallback / degrade</th>
</tr></table>
<h2>resource ledger <small>(per-tenant mesh attribution)</small></h2>
<div id="util" style="width:480px;height:18px;display:flex;
 border:1px solid #999;margin-bottom:6px"></div>
<div id="utiltxt" style="margin-bottom:8px"></div>
<table id="l"><tr><th>tenant</th><th>device s</th>
<th>lock wait s</th><th>HBM byte-s</th><th>bulk bytes</th>
<th>spill bytes</th><th>fetches</th><th>compiles (ms)</th>
<th>waves</th></tr></table>
<h2>streams <small>(pane plane: windowed DStreams)</small></h2>
<table id="w"><tr><th>stream</th><th>type</th><th>mode</th>
<th>window/slide</th><th>panes</th><th>nodes (built)</th>
<th>watermark lag s</th><th>late rows (patched/dropped)</th>
<th>patches</th><th>ticks</th></tr></table>
<div id="dags"></div>
<h2>profile</h2>
<pre id="prof">(run with --profile)</pre>
<script>
const open = new Set();
function dagText(j) {
  // topological-ish text DAG: each stage with its parents as edges
  const lines = ['job ' + j.id + '  (' + (j.scope || '') + ')'];
  for (const st of (j.stage_info || [])) {
    const par = (st.parents && st.parents.length)
      ? st.parents.map(p => 'stage ' + p).join(', ') : 'source';
    lines.push('  ' + par + '  ->  stage ' + st.id +
               '  [' + (st.rdd || '?') + ', ' + (st.kind || '?') + ']');
  }
  return lines.join('\\n');
}
function taskRows(st) {
  const ts = st.tasks || [];
  if (!ts.length) return '(no per-task records)';
  let h = '<table><tr><th>part</th><th>seconds</th><th>host</th>' +
          '<th>ok</th></tr>';
  for (const t of ts)
    h += '<tr class="' + (t.ok ? 'done' : 'fail') + '"><td>' + t.p +
         '</td><td>' + t.s + '</td><td>' + (t.host || '') +
         '</td><td>' + t.ok + '</td></tr>';
  return h + '</table>';
}
async function tick() {
  // health registry feed (ISSUE 14): per-stage fetch p99s, tenant SLO
  // attainment/burn — one defensive snapshot per tick
  let hd = {};
  try { hd = await (await fetch('/api/health')).json(); }
  catch (e) { hd = {}; }
  const r = await fetch('/api/jobs'); const jobs = await r.json();
  const t = document.getElementById('t');
  while (t.rows.length > 1) t.deleteRow(1);
  const s = document.getElementById('s');
  while (s.rows.length > 1) s.deleteRow(1);
  const dags = document.getElementById('dags');
  dags.innerHTML = '';
  for (const j of jobs) {
    const row = t.insertRow();
    // lineage-recovery accounting (ISSUE 5): FetchFailed parent
    // resubmits / intact-parent recomputes / task retries per job
    const rec = (j.resubmits || 0) + '/' + (j.recomputes || 0) + '/' +
                (j.retries || 0);
    // coded-shuffle decode accounting (ISSUE 6): parity repairs /
    // straggler wins / failed decodes attributed to this job, with
    // the active code mode when one is configured
    const dj = j.decodes || {};
    const dec = dj.mode
      ? (dj.repair || 0) + '/' + (dj.straggler_win || 0) + '/' +
        (dj.decode_failures || 0) + ' [' + dj.mode + ']' : '';
    // adaptive-execution decisions (ISSUE 7): cost-model choices taken
    // during this job — applied steers vs observe-mode would-bes, with
    // the mode; hover a stage's why column for the per-stage reason
    const aj = j.adapt || {};
    const ads = aj.decisions || [];
    const adp = aj.mode
      ? ads.filter(d => d.applied).length + '/' + ads.length +
        ' [' + aj.mode + ']' : '';
    // resident service (ISSUE 9): submitting tenant, admission/queue
    // wait, and the job's compiled-program cache delta (a warm
    // re-submission shows hits/0 — zero compiles)
    const pc = j.program_cache || {};
    const cache = pc.hits !== undefined
      ? pc.hits + '/' + pc.misses : '';
    const qw = j.queue_wait_ms !== undefined ? j.queue_wait_ms : '';
    // per-tenant SLO column (ISSUE 14): this job's latency vs its
    // tenant's target, plus the tenant's lifetime attainment from
    // the health registry — red when the tenant is burning budget
    const ten = ((hd.tenants || {})[j.client]) || null;
    const burning = ten &&
      Math.max(...Object.values(ten.burn || {0: 0})) >= 1.0;
    let slo = '';
    if (j.slo)
      slo = j.slo.latency_ms + '/' + j.slo.slo_ms + 'ms' +
            (j.slo.ok ? '' : ' VIOLATED');
    if (ten)
      slo += (slo ? ' ' : '') +
             '(' + (100 * ten.attainment).toFixed(1) + '%)';
    for (const v of [j.id, j.scope, j.parts, j.finished, j.stages,
                     j.seconds, j.state, j.client || '', qw, slo,
                     cache, rec, dec, adp])
      row.insertCell().textContent = v;
    if (slo)
      row.cells[9].className =
        burning || (j.slo && !j.slo.ok) ? 'fail' : 'done';
    row.className = j.state === 'done' ? 'done' : 'run';
    const d = document.createElement('div');
    d.className = 'dag'; d.textContent = dagText(j);
    dags.appendChild(d); dags.appendChild(document.createElement('br'));
    for (const st of (j.stage_info || [])) {
      const sr = s.insertRow();
      // overlapped wave pipeline (streamed stages): waves, device-idle
      // fraction, and the per-stage ingest/compute/exchange/spill ms —
      // live while the stream runs; the idle-percent drop IS the overlap
      const p = st.pipeline || {};
      const pms = p.waves ? (p.ingest_ms + '/' + p.compute_ms + '/' +
                             p.exchange_ms + '/' + p.spill_ms) : '';
      const idle = p.waves ? (100 * p.device_idle_frac).toFixed(1) : '';
      // why the stage left (or nearly left) the array path: the
      // analyze-time fallback_reason, the runtime degrade_reason, or
      // the cost model's adapt_reason (ISSUE 7: predicted, not
      // assumed, admission)
      const why = st.fallback_reason || st.degrade_reason ||
        st.adapt_reason || '';
      // per-stage decode deltas: activity against THIS stage's map
      // outputs (the parent whose buckets were decoded from parity)
      const ds = st.decodes || {};
      const sdec = Object.keys(ds).length
        ? (ds.repair || 0) + '/' + (ds.straggler_win || 0) + '/' +
          (ds.decode_failures || 0) : '';
      // pane-plane attribution (ISSUE 10): which stream + role
      // (pane-build / tree-merge / late-patch / window-emit) this
      // stage served, with the pane index when one applies
      const sw = st.stream || {};
      const srole = sw.stream
        ? sw.stream + ' ' + (sw.role || '') +
          (sw.pane !== undefined ? ' #' + sw.pane : '') : '';
      // cross-controller bytes this stage fetched over the bulk data
      // plane (ISSUE 12) — nonzero only when a reduce read a remote
      // peer's map outputs
      // per-stage fetch p99 from the health registry's streaming
      // sketches (ISSUE 14) — live while the stage fetches
      const sf = ((hd.stage_fetch || {})[j.id + ':' + st.id]) || {};
      const fp99 = sf.p99_ms !== undefined ? sf.p99_ms : '';
      for (const v of [j.id, st.id, st.rdd, st.parts, st.kind,
                       st.seconds, st.run_seconds, st.hbm_bytes,
                       st.wire_bytes, st.remote_fetch_bytes,
                       st.pad_efficiency,
                       p.waves, idle, pms, sdec, fp99, srole, why])
        sr.insertCell().textContent = v === undefined ? '' : v;
      // span timeline link (ISSUE 8): the stage's job timeline from
      // the trace plane ring/spool via /api/trace
      sr.cells[1].innerHTML = '<a href="/api/trace?job=' + j.id +
        '" target="_blank">' + st.id + '</a>';
      sr.className = 'stage ' + (st.seconds === null ? 'run' : 'done');
      const key = j.id + ':' + st.id;
      sr.onclick = () => {
        if (open.has(key)) open.delete(key); else open.add(key);
        tick();
      };
      if (open.has(key)) {
        const dr = s.insertRow();
        const c = dr.insertCell(); c.colSpan = 18;
        c.className = 'tasks'; c.innerHTML = taskRows(st);
      }
    }
  }
  // resource ledger (ISSUE 15): per-tenant attribution table + the
  // mesh busy/idle/contended utilization bar
  try {
    const lr = await fetch('/api/ledger'); const led = await lr.json();
    const lt = document.getElementById('l');
    while (lt.rows.length > 1) lt.deleteRow(1);
    const tenants = led.tenants || {};
    for (const name of Object.keys(tenants).sort()) {
      const a = tenants[name];
      const row = lt.insertRow();
      for (const v of [name, a.device_seconds, a.lock_wait_seconds,
                       a.hbm_byte_seconds, a.bulk_bytes,
                       a.spill_bytes, a.fetches,
                       a.compiles + ' (' + a.compile_ms + ')',
                       a.waves])
        row.insertCell().textContent = v === undefined ? '' : v;
    }
    const u = led.utilization || {};
    const bar = document.getElementById('util');
    bar.innerHTML = '';
    for (const [frac, color, label] of
         [[u.busy_frac, '#2a2', 'busy'],
          [u.contended_frac, '#c22', 'contended'],
          [u.idle_frac, '#ddd', 'idle']]) {
      const seg = document.createElement('div');
      seg.style.width = (100 * (frac || 0)) + '%';
      seg.style.background = color;
      seg.title = label + ' ' + (100 * (frac || 0)).toFixed(1) + '%';
      bar.appendChild(seg);
    }
    const cons = led.conservation || {};
    document.getElementById('utiltxt').textContent =
      'mesh busy ' + (100 * (u.busy_frac || 0)).toFixed(1) +
      '% / contended ' + (100 * (u.contended_frac || 0)).toFixed(1) +
      '% / idle ' + (100 * (u.idle_frac || 0)).toFixed(1) +
      '%  |  conservation: ' +
      (cons.ratio === null || cons.ratio === undefined
        ? 'n/a' : (100 * cons.ratio).toFixed(1) +
          '% of busy time attributed');
  } catch (e) {}
  // pane-plane streams (ISSUE 10): live pane counts, watermark lag,
  // late-record accounting per windowed stream
  const wr = await fetch('/api/streams'); const streams = await wr.json();
  const w = document.getElementById('w');
  while (w.rows.length > 1) w.deleteRow(1);
  for (const sid of Object.keys(streams).sort()) {
    const st = streams[sid];
    const row = w.insertRow();
    const lag = st.watermark_lag_s === null ||
                st.watermark_lag_s === undefined
      ? '' : st.watermark_lag_s.toFixed(3);
    for (const v of [sid, st.type, st.mode,
                     st.window + '/' + st.slide, st.panes,
                     st.nodes + ' (' + st.node_builds + ')', lag,
                     st.late_patched_rows + '/' + st.late_dropped,
                     st.late_patches, st.ticks])
      row.insertCell().textContent = v === undefined ? '' : v;
  }
  const pr = await fetch('/api/profile');
  document.getElementById('prof').textContent = await pr.text();
}
setInterval(tick, 1000); tick();
</script></body></html>"""


def start_ui(scheduler, host="127.0.0.1", port=0):
    """Serve the scheduler's job history; returns (server, url)."""

    class Handler(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            if self.path.startswith("/api/jobs"):
                body = json.dumps(
                    list(getattr(scheduler, "history", []))).encode()
                ctype = "application/json"
            elif self.path.startswith("/metrics"):
                # Prometheus text exposition; never throws on a job
                # mid-mutation (defensive snapshot under the
                # scheduler lock)
                try:
                    body = render_metrics(scheduler).encode()
                except Exception as e:
                    body = ("# metrics unavailable: %s\n"
                            % e).encode()
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            elif self.path.startswith("/api/trace"):
                # span timeline (trace plane, ISSUE 8): ?job=N filters
                # to one job; spool mode merges worker-process spans
                from dpark_tpu import trace as trace_mod
                q = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query)
                job = None
                try:
                    job = int(q["job"][0])
                except (KeyError, ValueError, IndexError):
                    pass
                try:
                    recs = trace_mod.collected(job=job)
                except Exception:
                    recs = []
                body = json.dumps(
                    {"mode": trace_mod.mode(), "job": job,
                     "spans": recs}).encode()
                ctype = "application/json"
            elif self.path.startswith("/api/health"):
                # online health plane (ISSUE 14): graded subsystems
                # with evidence, per-site tail summaries, per-tenant
                # SLO stats, per-stage fetch p99s — built from
                # defensive snapshots under the registry locks (same
                # discipline as /metrics: a scrape racing a running
                # job returns valid JSON, never an error)
                try:
                    from dpark_tpu import health as health_mod
                    body = json.dumps(
                        health_mod.api_health(scheduler)).encode()
                except Exception as e:
                    body = json.dumps(
                        {"mode": "error", "error": str(e)}).encode()
                ctype = "application/json"
            elif self.path.startswith("/api/ledger"):
                # resource attribution plane (ISSUE 15): per-tenant
                # accounts, the mesh utilization split, and the
                # conservation check — defensive snapshots, never an
                # error
                try:
                    from dpark_tpu import ledger as ledger_mod
                    body = json.dumps(
                        ledger_mod.api_ledger(scheduler)).encode()
                except Exception as e:
                    body = json.dumps(
                        {"mode": "error", "error": str(e)}).encode()
                ctype = "application/json"
            elif self.path.startswith("/api/streams"):
                # pane-plane live stats (ISSUE 10): one row per
                # windowed stream from the panes registry
                try:
                    from dpark_tpu import panes as panes_mod
                    body = json.dumps(panes_mod.stream_stats()).encode()
                except Exception:
                    body = b"{}"
                ctype = "application/json"
            elif self.path.startswith("/api/profile"):
                prof = getattr(scheduler, "profile", None)
                body = (prof.summary() if prof is not None
                        else "(run with --profile)").encode()
                ctype = "text/plain; charset=utf-8"
            else:
                body = _PAGE.encode()
                ctype = "text/html; charset=utf-8"
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    server = http.server.ThreadingHTTPServer((host, port), Handler)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    url = "http://%s:%d/" % server.server_address
    logger.info("web ui at %s", url)
    return server, url
