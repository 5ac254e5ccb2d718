"""driver: a job's `preflight` ring spans (`analysis.preflight` in
`DparkContext.runJob`: the lint's lineage walk and AST pass over every
user function, before the scheduler sees the job), summed, median over
the window's jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.whole_ms(obs, "preflight")
