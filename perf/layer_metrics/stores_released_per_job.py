"""executor host side: shuffle stores dropped because their ShuffleDependency
died, per job of the window (the executor's `stores_released` counter, added
in the scheduler's drain, `TPUScheduler._shuffle_unreachable`; a count that
repeats exactly: each job frees what the job before it left)."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "stores_released")
