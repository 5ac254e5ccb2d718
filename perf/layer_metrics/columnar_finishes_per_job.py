"""driver: grouped finishes a planned query ran over numpy columns, per job
of the window (the executor's `query_finishes_columnar`: a bare `+= 1` in
`JAXExecutor.note_columnar_finish`, which `query/planner.py:
PlannedQuery._run` calls after its `query.finish` span where the plan chose
the finish over columns; window delta / jobs): 1 in `tpch.q1` and
`tpch.q3`, 0 where a query's finish went row by row.  A program without the
counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "query_finishes_columnar")
