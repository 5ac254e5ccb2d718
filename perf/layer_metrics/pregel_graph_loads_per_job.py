"""executor host side: graphs partitioned on the host and put on the
devices per job of the window (the executor's `pregel_graph_loads`, a bare
`+= 1` in `DeviceGraph.__init__`, window delta / jobs): 0 where the graphs
stay resident, 1 where every run loads its graph again.  A program without
the counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "pregel_graph_loads")
