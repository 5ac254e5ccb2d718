"""exchange: time in `all-to-all` operations inside a job (profiler
trace, union per chip, averaged over the chips), median over the profiled
jobs."""

from perf.lib import stats


def read(obs):
    dev = stats.device_s_by_index(obs)
    return stats.median(j["collective_s"] * 1e3 for j in dev.values())
