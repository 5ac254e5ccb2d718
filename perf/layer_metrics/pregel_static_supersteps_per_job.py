"""stage programs: supersteps of device Pregel per job of the window whose
messages were combined and delivered over the order the graph's load left
the arcs in (the executor's `pregel_static_supersteps`, a bare `+= 1` in
`DevicePregel.run` where the gen program is the one-device one: a segmented
scan over destination-ordered arcs and a read at a fixed index, no sort, no
search, no exchange).  Equal to `pregel_supersteps_per_job` on one chip, 0
across chips.  A program without the counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "pregel_static_supersteps")
