"""driver: a job's `job.finish` ring spans (the way out of a job: the
finalizers, `_job_finished`, and a `store.release` its drain ran), summed,
median over the window's jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.whole_ms(obs, "job.finish")
