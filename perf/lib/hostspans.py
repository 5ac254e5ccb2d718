"""What the host did inside a job, from the program's own spans: `plan`,
`launch`, `eager`, `readback`, `egest`, `ingest` and `hbm.spill`
(dpark_tpu/trace.py lists where each is emitted).  The span metrics sum a
job's spans on the ring's clock; the idle metrics lay a profiled job's
spans over the gaps of the device trace, shifted onto the trace's clock
through the job's TraceAnnotation as runner.host_segments shifts
`stage.exec`.

A program from before these spans has none of them: every reader then
returns None, and a cell that merely ran none of one kind (no spill under
the HBM budget, no egest in a count()) reports 0 for it.
"""

from perf.lib import stats

SPANS = ("plan", "launch", "eager", "readback", "egest", "ingest",
         "hbm.spill")
# a `readback` that starts inside one of these is that span's time
OUTER = ("hbm.spill", "egest", "plan")
# the idle metric that reports the idle time under each span: the device
# waits for work while the host plans, ingests, dispatches eager
# operations or launches a program, and all of that is `launch`
PART = {"hbm.spill": "spill", "egest": "readback", "readback": "readback",
        "launch": "launch", "eager": "launch", "plan": "launch",
        "ingest": "launch"}
UNATTRIBUTED = "unattributed"
PARTS = ("spill", "readback", "launch", UNATTRIBUTED)


def host_spans(job):
    return [s for s in job.get("spans", ()) if s["name"] in SPANS]


def instrumented(obs):
    """Does this program emit the spans at all?"""
    return any(host_spans(j) for j in obs["jobs"] + obs["profiled_jobs"])


def own_spans(job, name):
    """The job's spans called `name` that do not start inside an OUTER
    span of another name (spans of one thread nest, so starting inside
    is being inside)."""
    spans = host_spans(job)
    outer = [(s["ts"], s["ts"] + s["dur"]) for s in spans
             if s["name"] in OUTER and s["name"] != name]
    return [s for s in spans if s["name"] == name
            and not any(a <= s["ts"] < b for a, b in outer)]


def span_ms(obs, name, stat=stats.median):
    """`stat` over the window's traced jobs of each job's summed `name`
    spans, in ms."""
    jobs = [j for j in obs["jobs"] if "spans" in j]
    if not jobs or not instrumented(obs):
        return None
    return stat([sum(s["dur"] for s in own_spans(j, name)) * 1e3
                 for j in jobs])


def per_job_count(obs, counter):
    """The window's delta of one of the executor's counters, per job."""
    total = obs["counters"].get(counter)
    if total is None or not obs["jobs"]:
        return None
    return total / len(obs["jobs"])


def describe(span):
    args = span.get("args") or {}
    detail = args.get("program") or args.get("site")
    return "%s %s" % (span["name"], detail) if detail else span["name"]


def segments(job, mark):
    """The job's host spans on the trace's clock, clipped to the job's
    annotation, as non-overlapping (start_ns, end_ns, name, description)
    in order of start, the outermost span winning."""
    lo, hi = mark["start_ns"], mark["end_ns"]
    shift = lo - job["t0_wall"] * 1e9
    out, at = [], lo
    for s in sorted(host_spans(job), key=lambda s: (s["ts"], -s["dur"])):
        a = max(at, s["ts"] * 1e9 + shift)
        b = min(hi, (s["ts"] + s["dur"]) * 1e9 + shift)
        if b <= a:
            continue                    # nested, or outside the job
        out.append((a, b, s["name"], describe(s)))
        at = b
    return out


def profiled(obs):
    """(job, its annotation) for each profiled job the trace marked."""
    marks = {j["index"]: j for j in obs["profile"]["jobs"]}
    return [(j, marks[j["index"]]) for j in obs["profiled_jobs"]
            if j["index"] in marks]


def idle_by_part(obs):
    """Per profiled job: ({part: idle ns}, [(ns, after, before)] of its
    unattributed pieces with the spans on either side).  The parts of a
    job add up to the device's idle time inside its annotation.  None
    without a device trace or without the spans."""
    profile = obs.get("profile")
    if not profile or not instrumented(obs):
        return None
    out = []
    for job, mark in profiled(obs):
        lo, hi = mark["start_ns"], mark["end_ns"]
        segs = segments(job, mark)
        parts = dict.fromkeys(PARTS, 0)
        loose = []
        i = 0
        for gs, ge in profile["gaps_ns"]:
            gs, ge = max(gs, lo), min(ge, hi)
            if ge <= gs:
                continue
            while i < len(segs) and segs[i][1] <= gs:
                i += 1
            at, j = gs, i
            while at < ge:
                nxt = segs[j] if j < len(segs) else None
                free_to = min(ge, nxt[0]) if nxt else ge
                if free_to > at:
                    parts[UNATTRIBUTED] += free_to - at
                    loose.append((free_to - at,
                                  segs[j - 1][3] if j else "job start",
                                  nxt[3] if nxt else "job end"))
                    at = free_to
                if nxt is None or at >= ge:
                    break
                end = min(ge, nxt[1])
                parts[PART[nxt[2]]] += end - at
                at = end
                if nxt[1] <= ge:
                    j += 1
        out.append((parts, loose))
    return out or None


def idle_ms(obs, part):
    """Median over the profiled jobs of the device's idle ms under
    `part`."""
    jobs = idle_by_part(obs)
    if jobs is None:
        return None
    return stats.median(parts[part] / 1e6 for parts, _ in jobs)


def log_unattributed(obs, top=6):
    """One line on the run's log, as job_roofline's: between which spans
    the longest idle pieces under no span lie."""
    jobs = idle_by_part(obs)
    if jobs is None:
        return
    where = {}
    for _, loose in jobs:
        for ns, after, before in loose:
            key = "after %s, before %s" % (after, before)
            where[key] = where.get(key, 0) + ns
    ranked = sorted(where.items(), key=lambda kv: -kv[1])[:top]
    print("[idle] unattributed, ms over %d profiled jobs: %s"
          % (len(jobs), "; ".join("%s: %.3f" % (k, ns / 1e6)
                                  for k, ns in ranked) or "none"),
          flush=True)
