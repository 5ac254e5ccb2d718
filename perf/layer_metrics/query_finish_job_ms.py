"""driver: a job's `query.finish` ring spans (`query/planner.py:
PlannedQuery._run`, after the job's action has returned: key words back
to bytes, averages divided, HAVING / projections / ORDER BY over the
result rows; stamped with the id of the job it finishes), summed, median
over the window's traced jobs, in ms.  A program without the span reports
nothing."""

from perf.lib import stats


def read(obs):
    ends = [[s["dur"] for s in j["spans"] if s["name"] == "query.finish"]
            for j in obs["jobs"] if "spans" in j]
    if not any(ends):
        return None
    return stats.median(sum(durs) * 1e3 for durs in ends)
