"""driver: a job's `query.plan` ring spans (`query/planner.py:
plan_query`, one a table action: the logical tree's rules from shape to
the result-cache probe; measured before the job's id exists, kept on the
PlannedQuery and emitted by `PlannedQuery._job` under the id of the job
its action opened), summed, median
over the window's traced jobs, in ms.  The text's parse into the table
calls (`table.execute`) is not in it: that lies outside every span, in
`driver_outside_job_ms`.  A program without the span reports nothing."""

from perf.lib import stats


def read(obs):
    plans = [[s["dur"] for s in j["spans"] if s["name"] == "query.plan"]
             for j in obs["jobs"] if "spans" in j]
    if not any(plans):
        return None
    return stats.median(sum(durs) * 1e3 for durs in plans)
