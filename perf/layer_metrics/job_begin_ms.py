"""driver: a job's `job.begin` ring spans (the way into a job:
`new_stage`'s DAG walk, `_new_job_record`, `_job_started`, and the
`store.release` of the stores the job before left), summed, median over
the window's jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.whole_ms(obs, "job.begin")
