"""stage programs: supersteps of device Pregel per job of the window (the
executor's `pregel_supersteps`, a bare `+= 1` a superstep in
`DevicePregel.run`, window delta / jobs): PageRank of k iterations runs
k + 1 (superstep 0 sends the initial ranks, the last sends nothing).  A
program without the counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "pregel_supersteps")
