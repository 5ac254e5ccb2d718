"""device: idle time inside a profiled job's annotation that falls under an
`hbm.spill` span (profiler trace gaps over the job's ring spans), median
over the profiled jobs."""

from perf.lib import hostspans


def read(obs):
    return hostspans.idle_ms(obs, "spill")
