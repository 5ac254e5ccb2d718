"""A job's wall, every millisecond under a span: self times.

The program's spans of one job nest on the driver's thread: `preflight`,
then `job.begin`, then `job` around one `stage.run` a stage (each around
`plan` and `stage.exec`, which is around `launch`, `eager`, `readback`,
`egest`, `ingest`, `join`, `sort.sample`, `hbm.spill`), then `job.finish`
(dpark_tpu/trace.py lists where each is emitted).  `lay` makes the tree
by containment and cuts the runner's wall of the job into pieces, each
owned by the innermost span over it, or by none.  A span's self time is
what it owns: its duration minus the part its children cover.  The
pieces of a job add up to its wall exactly.

The `*_job_ms` readers take one number a job and the median over the
window's traced jobs, as hostspans.span_ms does.  The three idle readers
put the same pieces on the device trace's clock, through the job's
TraceAnnotation as hostspans.segments does, and split what
idle_unattributed_job_ms counts by who owned the host meanwhile.

A program from before `stage.run` has no tree to lay: every reader then
returns None.  A cell that merely ran none of a kind reports 0 for it.
"""

from perf.lib import hostspans, stats

DRIVER = ("preflight", "job.begin", "job", "stage.run", "job.finish")
# after-the-fact records that overlap the tree instead of nesting in it
# (`stage` and `task` run from submission to the completion event,
# `phase.*`, `wave`, `mesh.lock`) stay out, as do instant events
TREE = frozenset(DRIVER + hostspans.SPANS + (
    "stage.exec", "join", "sort.sample", "store.release", "adapt.path",
    "result.rows"))
OUTSIDE = "outside"
# the `[host]` line's terms, in order; a span of another name (`ingest`,
# `join`, `sort.sample`, `hbm.spill`, a `store.release` of an eviction,
# and stage.run's children `adapt.path` and `result.rows`) adds a term
# of its own name behind them
COLUMNS = (OUTSIDE, "preflight", "begin", "loop", "stage self", "plan",
           "exec self", "launch", "eager", "egest rows", "read copy",
           "device wait", "finish")
SELF_COLUMN = {"preflight": "preflight", "job": "loop",
               "stage.run": "stage self", "stage.exec": "exec self",
               "launch": "launch", "eager": "eager", "egest": "egest rows"}
# a piece anywhere under one of these is that span's, whole
WHOLE_COLUMN = {"job.begin": "begin", "job.finish": "finish",
                "plan": "plan"}
IDLE_PARTS = ("driver", "exec_self", "unspanned")
# the ring rounds a start and a duration to the microsecond each, so a
# span can seem to end this long after the sibling behind it starts
ROUNDING = 2e-6


def tree_spans(job):
    """The job's spans that nest: known names, a duration, and the
    thread of its `job` span (a bridge thread's reads lie across it)."""
    spans = [s for s in job.get("spans", ())
             if s["name"] in TREE and s["dur"] > 0]
    tids = {s.get("tid") for s in spans if s["name"] in DRIVER}
    if len(tids) == 1:
        spans = [s for s in spans if s.get("tid") in tids]
    return spans


def instrumented(obs):
    """Does this program emit the driver's spans at all?"""
    return any(s["name"] == "stage.run"
               for j in obs["jobs"] + obs["profiled_jobs"]
               for s in j.get("spans", ()))


def lay(spans, t0, length):
    """The tree of `spans` over the `length` seconds from `t0` on the
    ring's clock.  Returns (order, parent, pieces): the spans that reach
    into the interval, outermost first where two start together; each
    one's parent as an index into `order`, or None; and (start, end,
    owner) pieces, in seconds after `t0`, that cut [0, length) without a
    gap, `owner` the index of the innermost span over the piece or None.
    A span is clipped to the interval and to its parent, and one that
    starts within ROUNDING of another's end is its sibling."""
    order, parent, pieces = [], [], []
    stack = []                          # (end, index), innermost last
    at = 0.0

    def advance(to):
        nonlocal at
        if to > at:
            pieces.append((at, to, stack[-1][1] if stack else None))
            at = to

    for s in sorted(spans, key=lambda s: (s["ts"], -s["dur"])):
        a = max(0.0, s["ts"] - t0)
        b = min(length, s["ts"] - t0 + s["dur"])
        if b <= a:
            continue
        while stack and stack[-1][0] <= a + ROUNDING:
            advance(stack[-1][0])
            stack.pop()
        advance(a)
        if stack:
            b = min(b, stack[-1][0])
        order.append(s)
        parent.append(stack[-1][1] if stack else None)
        stack.append((b, len(order) - 1))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    advance(length)
    return order, parent, pieces


def lay_job(job):
    return lay(tree_spans(job), job["t0_wall"], job["wall_s"])


def lineage(order, parent, i):
    """Names from span `i` up to its root."""
    names = []
    while i is not None:
        names.append(order[i]["name"])
        i = parent[i]
    return names


def self_s(job):
    """{span name: seconds the job's spans of that name own} and under
    OUTSIDE the seconds of the wall no span covers."""
    order, _, pieces = lay_job(job)
    out = {OUTSIDE: 0.0}
    for a, b, i in pieces:
        name = OUTSIDE if i is None else order[i]["name"]
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def whole_s(job, name):
    """Seconds under the job's spans called `name`, children and all."""
    order, parent, pieces = lay_job(job)
    return sum(b - a for a, b, i in pieces
               if i is not None and name in lineage(order, parent, i))


def wait_s(job):
    """Seconds the job's `readback` spans say they waited for the
    device."""
    return sum((s.get("args") or {}).get("wait_s") or 0.0
               for s in tree_spans(job) if s["name"] == "readback")


def columns(job):
    """The job's wall as the `[host]` line's terms: {term: seconds}, in
    the line's order, adding up to `wall_s`."""
    order, parent, pieces = lay_job(job)
    owned = [0.0] * len(order)
    out = dict.fromkeys(COLUMNS, 0.0)
    for a, b, i in pieces:
        if i is None:
            out[OUTSIDE] += b - a
        else:
            owned[i] += b - a
    for i, s in enumerate(order):
        names = lineage(order, parent, i)
        whole = [WHOLE_COLUMN[n] for n in names if n in WHOLE_COLUMN]
        if whole:
            out[whole[-1]] += owned[i]
        elif s["name"] == "readback":
            waited = min(owned[i], (s.get("args") or {}).get("wait_s") or 0)
            out["device wait"] += waited
            out["read copy"] += owned[i] - waited
        else:
            term = SELF_COLUMN.get(s["name"], s["name"])
            out[term] = out.get(term, 0.0) + owned[i]
    return out


def traced_jobs(obs):
    return [j for j in obs["jobs"] if "spans" in j]


def job_ms(obs, per_job):
    """Median over the window's traced jobs of `per_job(job)` seconds,
    in ms; None from a program without the driver's spans."""
    jobs = traced_jobs(obs)
    if not jobs or not instrumented(obs):
        return None
    return stats.median(per_job(j) * 1e3 for j in jobs)


def self_ms(obs, name):
    return job_ms(obs, lambda j: self_s(j).get(name, 0.0))


def whole_ms(obs, name):
    return job_ms(obs, lambda j: whole_s(j, name))


# what a stage.run is known to hold; what else nests in it is `stray_s`
STAGE_RUN_HOLDS = frozenset(("plan", "stage.exec", "adapt.path",
                             "result.rows"))


def stray_s(job):
    """Seconds under a `stage.run` and outside its `plan`, `stage.exec`,
    `adapt.path` and `result.rows`: the read of the scheduler's row
    accounting (`exchange.real_rows`), a join on the object path.  The
    job's wall minus its `stage.exec` spans is the driver's seven parts,
    `plan`, the two children of `stage.run`, and this."""
    order, parent, pieces = lay_job(job)
    total = 0.0
    for a, b, i in pieces:
        names = lineage(order, parent, i)
        if "stage.run" in names[1:] and not STAGE_RUN_HOLDS & set(names):
            total += b - a
    return total


def format_columns(job):
    cols = columns(job)
    return "job %d wall %.3f ms = %s" % (
        job["index"], job["wall_s"] * 1e3,
        " + ".join("%s %.3f" % (k, v * 1e3) for k, v in cols.items()
                   if k in COLUMNS or v))


def log_host(obs):
    """One line on the run's log, as `[idle]`: the wall of the window's
    median job and of its slowest, term by term."""
    jobs = sorted(traced_jobs(obs), key=lambda j: j["wall_s"])
    if not jobs or not instrumented(obs):
        return
    stray = sorted(stray_s(j) for j in jobs)
    print("[host] median %s; slowest %s; reads under stage.run outside "
          "stage.exec: median %.3f, at most %.3f ms a job over %d jobs"
          % (format_columns(jobs[len(jobs) // 2]), format_columns(jobs[-1]),
             stray[len(stray) // 2] * 1e3, stray[-1] * 1e3, len(jobs)),
          flush=True)


def unattributed(job, mark, gaps_ns):
    """The trace's gaps inside the job's annotation that lie under none
    of hostspans' seven spans, as (start_ns, end_ns): the pieces
    idle_unattributed_job_ms adds up."""
    lo, hi = mark["start_ns"], mark["end_ns"]
    segs = hostspans.segments(job, mark)
    out = []
    for gs, ge in gaps_ns:
        at, ge = max(gs, lo), min(ge, hi)
        for a, b, _, _ in segs:
            if at >= ge:
                break
            if b <= at:
                continue
            if a > at:
                out.append((at, min(a, ge)))
            at = max(at, b)
        if at < ge:
            out.append((at, ge))
    return out


def idle_parts(obs):
    """Per profiled job {part: ns}: its unattributed idle time split into
    `driver` (under a driver span and outside every `stage.exec`),
    `exec_self` (inside a `stage.exec`: its own self time, and that of
    `join` and `sort.sample`, which the seven spans do not cover either)
    and `unspanned` (under no span).  None without a device trace or
    without the driver's spans."""
    profile = obs.get("profile")
    if not profile or not instrumented(obs):
        return None
    out = []
    for job, mark in hostspans.profiled(obs):
        lo, hi = mark["start_ns"], mark["end_ns"]
        order, parent, pieces = lay(tree_spans(job), job["t0_wall"],
                                    (hi - lo) / 1e9)
        parts = dict.fromkeys(IDLE_PARTS, 0.0)
        for gs, ge in unattributed(job, mark, profile["gaps_ns"]):
            for k, (a, b, i) in enumerate(pieces):
                # the last piece ends where the annotation does
                a = lo + a * 1e9
                b = lo + b * 1e9 if k + 1 < len(pieces) else hi
                over = min(b, ge) - max(a, gs)
                if over <= 0:
                    continue
                if i is None:
                    parts["unspanned"] += over
                elif "stage.exec" in lineage(order, parent, i):
                    parts["exec_self"] += over
                else:
                    parts["driver"] += over
        out.append(parts)
    return out or None


def idle_ms(obs, part):
    jobs = idle_parts(obs)
    if jobs is None:
        return None
    return stats.median(parts[part] / 1e6 for parts in jobs)
