"""device: of the idle time `idle_unattributed_job_ms` counts, the part
under no span of the program at all (inside the runner's annotation:
building the RDD chain, the generator's hand-off, the action's own
Python), median over the profiled jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.idle_ms(obs, "unspanned")
