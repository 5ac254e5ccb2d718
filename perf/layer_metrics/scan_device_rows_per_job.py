"""stage programs: rows of tables resident on the device that stage
programs were launched over, per job of the window (the executor's
`scan_rows_device`: a bare `+=` in `JAXExecutor._source_outs` after the
narrow program over a cached batch that a table was made of has been
launched, by the row count read when the table was made; window delta /
jobs): the rows of one partition where the query plane's scan, filter and
projection run inside the stage program, 0 where the stage went to Python
rows or the scan back to the driver.  A program without the counter
reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "scan_rows_device")
