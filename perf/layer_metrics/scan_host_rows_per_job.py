"""driver: rows the query plane's Scans evaluated on the driver in numpy
per job of the window (the executor's `scan_rows_host`: `query/planner.py:
_ScanSeg.run` reports them to `JAXExecutor.note_host_scan`, a bare `+=`;
window delta / jobs): 0 over a table
resident on the device; the guard that its scan has not gone back to the
host.  A program without the counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "scan_rows_host")
