"""executor host side: calls of a compiled stage program per job of the
window (the executor's `program_launches` counter, `JAXExecutor._launch`; a
count that repeats exactly)."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "program_launches")
