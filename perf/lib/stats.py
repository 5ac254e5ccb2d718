"""Small statistics the metric readers share."""

import statistics


def median(values):
    values = list(values)
    return statistics.median(values) if values else None


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-len(s) * q // 100) - 1))]


def stage_exec_s(job):
    """Sum of a job's `stage.exec` ring spans, or None without spans."""
    spans = [s for s in job.get("spans", ()) if s["name"] == "stage.exec"]
    return sum(s["dur"] for s in spans) if spans else None


def device_s_by_index(obs):
    """index -> device seconds of each profiled job, {} without a trace."""
    if not obs.get("profile"):
        return {}
    return {j["index"]: j for j in obs["profile"]["jobs"]}
