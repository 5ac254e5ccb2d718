"""From a jax.profiler trace (.xplane.pb) to device busy time, per-job
device time, collective time, the operations that took most time and the
idle gaps.  Reads with jax.profiler.ProfileData and nothing else.

On a TPU every chip is a plane `/device:TPU:<n>`.  Its line `XLA Ops`
holds one event per executed HLO operation (nested where an operation
such as a `while` contains others), named by the operation's whole HLO
text; `XLA Modules` holds one event per executed program
(`jit_per_device(<fingerprint>)`); `Async XLA Ops` holds the spans of
asynchronous copies and collectives from start to done.  All are on the
clock the host lines use.
The runner marks each job with a TraceAnnotation named JOB_MARK on the
host; a job's device time is the union of the operation intervals that
fall inside its annotation.  The caller says which platform the run was
on.  A "tpu" run is reduced from its `/device:TPU` planes alone, and one
whose trace holds none gives None: nothing else ever stands in for the
device's clock.  A rehearsal ("cpu") has no device plane: there the
executed operations are the host-line events that carry an `hlo_op`
stat, and what is made from them never goes under a device metric's name
(the runner reports it under `rehearsal_only`).
"""

import re

JOB_MARK = "perf.job"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_RE = re.compile(r"all-?to-?all|all_to_all", re.I)


def is_collective(hlo_text):
    """Is this operation itself an all-to-all (and not one that merely
    takes an all-to-all's result as an operand)?"""
    return bool(COLLECTIVE_RE.search(hlo_text.split(" = ")[0]))


def short_op(hlo_text):
    """`%fusion.66 = u32[1048576]{0:T(1024)S(1)} fusion(u32[...] %p, ...),
    kind=kCustom, calls=...` -> `fusion.66 fusion:kCustom u32[1048576]`."""
    m = re.match(r"%?(\S+) = \(?(\w+\[[\d,]*\])?(.*)", hlo_text)
    if not m:
        return hlo_text[:80]
    name, result, rest = m.groups()
    opcode = re.search(r"(?:^|[\s})])([a-z][\w\-]*)\(", rest)
    kind = re.search(r"kind=(\w+)", rest)
    out = name
    if opcode:
        out += " " + opcode.group(1) + (":" + kind.group(1) if kind else "")
    if result:
        out += " " + result
    return out[:120]


def short_module(name):
    """`jit_per_device(16263818937124394898)` -> `jit_per_device#4898`
    (programs share names; the fingerprint's tail tells them apart)."""
    m = re.match(r"(.*)\((\d+)\)$", name)
    return "%s#%s" % (m.group(1), m.group(2)[-4:]) if m else name[:60]


def _stats(event):
    try:
        return dict(event.stats)
    except Exception:
        return {}


def read_events(path, platform):
    """{"jobs": [(index, start_ns, end_ns)], "devices": {name:
    [(start_ns, end_ns, op_name)]}, "collectives": {name: [...]}} from
    one .xplane.pb.  An operation's name is `<program>/<short op>`;
    "collectives" are the all-to-all spans of both operation lines."""
    import bisect
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    jobs, devices, cpu_ops, collectives = [], {}, {}, {}
    for plane in data.planes:
        on_device = plane.name.startswith("/device:TPU")
        if on_device:
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
            modules = sorted(lines.get(MODULES_LINE, ()))
            starts = [m[0] for m in modules]
            ops = []
            for s, e, name in lines.get(OPS_LINE, ()):
                i = bisect.bisect_right(starts, s) - 1
                inside = i >= 0 and s < modules[i][1]
                prog = short_module(modules[i][2]) if inside else "?"
                ops.append((s, e, prog + "/" + short_op(name)))
            devices[plane.name] = ops
            collectives[plane.name] = [
                ev for key in (OPS_LINE, ASYNC_LINE)
                for ev in lines.get(key, ()) if is_collective(ev[2])]
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == JOB_MARK:
                    jobs.append((int(_stats(e).get("index", len(jobs))),
                                 e.start_ns, e.start_ns + e.duration_ns))
                elif not plane.name.startswith("/device:"):
                    st = _stats(e)
                    if "hlo_op" in st:
                        cpu_ops.setdefault(
                            "cpu:%s" % st.get("device_ordinal", 0),
                            []).append((e.start_ns,
                                        e.start_ns + e.duration_ns, e.name))
    if platform != "tpu":
        devices = cpu_ops
        collectives = {name: [ev for ev in evs if is_collective(ev[2])]
                       for name, evs in cpu_ops.items()}
    jobs.sort(key=lambda j: j[1])
    for evs in devices.values():
        evs.sort()
    return {"jobs": jobs, "devices": devices, "collectives": collectives}


def union(intervals, lo=None, hi=None):
    """Merged, sorted, non-overlapping [start, end) pieces of `intervals`
    (any order, may nest), clipped to [lo, hi)."""
    out = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(pieces):
    return sum(e - s for s, e in pieces)


def self_times(events):
    """name -> ns during which that operation was the innermost one
    running (an enclosing `while` is not charged its body's time)."""
    out = {}
    stack = []          # [end, name, start, ns covered by children]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, child = stack.pop()
            out[name] = out.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][3] += end - start

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack and e > stack[-1][0]:
            e = stack[-1][0]        # clip a child that overruns its parent
        stack.append([e, name, s, 0])
    close(float("inf"))
    return out


def complement(pieces, lo, hi):
    gaps, at = [], lo
    for s, e in pieces:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def reduce(path, platform, top=10):
    """The trace as numbers (seconds), over the window from the first
    job's start to the last job's end:

    window_s, busy_s (union of device-op intervals, averaged over the
    devices), per job device_s and collective_s (likewise averaged),
    device_ops (the `top` operations by self time, summed over devices),
    gaps_ns (where no device ran anything), n_devices.  None where the
    trace holds no job mark or no operation of `platform`'s devices."""
    ev = read_events(path, platform)
    jobs, devices = ev["jobs"], ev["devices"]
    if not jobs or not devices:
        return None
    lo, hi = jobs[0][1], max(j[2] for j in jobs)
    ndev = len(devices)
    busy, ops, all_busy = 0.0, {}, []
    per_job = [{"index": j[0], "start_ns": j[1], "end_ns": j[2],
                "device_s": 0.0, "collective_s": 0.0} for j in jobs]
    for plane, evs in devices.items():
        inside = [e for e in evs if e[1] > lo and e[0] < hi]
        pieces = union(inside, lo, hi)
        all_busy.extend(pieces)
        busy += total(pieces) / 1e9
        for name, ns in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9
        coll = ev["collectives"].get(plane, ())
        for rec in per_job:
            s, e = rec["start_ns"], rec["end_ns"]
            rec["device_s"] += total(union(inside, s, e)) / 1e9 / ndev
            rec["collective_s"] += total(union(coll, s, e)) / 1e9 / ndev
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / ndev,
            "n_devices": ndev, "jobs": per_job,
            "device_ops": [[n, s] for n, s in top_ops],
            "gaps_ns": complement(union(all_busy), lo, hi)}


def label_gaps(gaps_ns, segments, default, top=10):
    """Idle seconds by what the host was doing.  `segments` are
    non-overlapping (start_ns, end_ns, label) host intervals; time in no
    segment is `default`.  Returns [[label, seconds]], longest first."""
    segs = sorted(segments)
    out = {}
    i = 0
    for gs, ge in gaps_ns:
        at = gs
        while i < len(segs) and segs[i][1] <= gs:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < ge:
            s, e, label = segs[j]
            if s > at:
                out[default] = out.get(default, 0) + (s - at)
            lo, hi = max(s, at), min(e, ge)
            if hi > lo:
                out[label] = out.get(label, 0) + (hi - lo)
            at = max(at, hi)
            j += 1
        if ge > at:
            out[default] = out.get(default, 0) + (ge - at)
    ranked = sorted(out.items(), key=lambda kv: -kv[1])[:top]
    return [[label, ns / 1e9] for label, ns in ranked]
