"""stage programs: filters folded into a shuffle write's validity mask per job
of the window (the executor's `filters_masked`: a bare `+=` of the number of
filters a plan folded, `fuse.masked_filters`, at each launch of its narrow,
reduce or spilled-run map program; window delta / jobs).  A filter in the
trailing run of filters and maps ahead of a shuffle write ANDs its predicate
into the write's mask and packs nothing (`fuse.run_ops`): 1 where a job's one
write follows one such filter (`tpch.q1`, the visits side of
`uservisits.join`), 0 in a cell with no filter or whose filter feeds a prefix
(a result stage: `sort.4chip`).  A program without the counter reports
nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "filters_masked")
