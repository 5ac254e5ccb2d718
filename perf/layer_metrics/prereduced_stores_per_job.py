"""stage programs: in-core shuffle stores registered `pre_reduced` per job of
the window (the executor's `stores_pre_reduced` counter, a bare `+= 1` in
`JAXExecutor._finish_stage` where a one-device mesh's combining write is
marked: the stage that reads such a store runs no exchange and no reduce
program).  1 in a one-chip cell whose job ends in one combining shuffle, 0
on four chips and for writes that combine nothing.  A program without the
counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "stores_pre_reduced")
