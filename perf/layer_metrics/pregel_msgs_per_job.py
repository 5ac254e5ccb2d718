"""stage programs: messages of device Pregel per job of the window (the
executor's `pregel_messages`: what the gen program reported each superstep,
the arcs whose source sent, before the per-destination combine; the host
loop reads it every superstep anyway; window delta / jobs): iterations x
arcs for PageRank.  A program without the counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "pregel_messages")
