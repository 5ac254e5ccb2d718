"""executor host side: a job's `store.release` ring spans (one drain of
dead shuffles that freed at least one HBM store), summed, median over the
window's jobs.  It lies inside `job_begin_ms` or `job_finish_ms`, whichever
drain ran it."""

from perf.lib import selftime


def read(obs):
    return selftime.whole_ms(obs, "store.release")
