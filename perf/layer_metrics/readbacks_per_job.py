"""executor host side: blocking device-to-host reads per job of the window
(the executor's `host_reads` counter: calls of `layout.host_read`; a count
that repeats exactly)."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "host_reads")
