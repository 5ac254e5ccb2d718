"""executor host side: sum of a job's `hbm.spill` ring spans, mean over the
window's jobs: the statistic of `spill_job_ms`, which times the same routine
from outside, so the two can be held against each other.  0 in a cell that
runs under the HBM budget."""

import statistics

from perf.lib import hostspans


def read(obs):
    return hostspans.span_ms(obs, "hbm.spill", stat=statistics.fmean)
