"""driver: a job's wall on the runner's clock minus the union of all its
ring spans: building the RDD chain, the action's own Python over the
returned rows, the generator's hand-off; median over the window's jobs.
The run's log gets the `[host]` line here: the wall of the window's median
job and of its slowest, term by term."""

from perf.lib import selftime


def read(obs):
    selftime.log_host(obs)
    return selftime.self_ms(obs, selftime.OUTSIDE)
