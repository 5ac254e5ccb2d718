"""stage programs: joins a planned query lowered with every side a table
resident on the device, per job of the window (the executor's
`query_joins_device`: a bare `+=` in `JAXExecutor.note_device_joins`,
which `query/planner.py: PlannedQuery._job` calls once its job has run;
window delta / jobs): 2 in `tpch.q3`, 0 where a side went back to a
driver scan or the query to the host row chain.  A program without the
counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "query_joins_device")
