"""executor host side: rows the bounds samples of sortByKey brought to
the host per job (the executor's `sort_sample_rows`, window delta /
jobs): sampleSize = 2,000 when the sample is sliced on the device, the
table's rows when every row is egested for it.  A program without the
counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "sort_sample_rows")
