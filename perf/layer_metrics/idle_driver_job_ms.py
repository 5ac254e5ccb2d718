"""device: of the idle time `idle_unattributed_job_ms` counts, the part
under a driver span (`preflight`, `job.begin`, `job`, `stage.run`,
`job.finish`) and outside every `stage.exec`, median over the profiled
jobs.  With idle_exec_self_ and idle_unspanned_job_ms it adds up, per
profiled job, to that job's idle_unattributed_job_ms."""

from perf.lib import selftime


def read(obs):
    return selftime.idle_ms(obs, "driver")
