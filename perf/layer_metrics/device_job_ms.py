"""stage programs: union of the device-operation intervals inside a job's
TraceAnnotation (profiler trace, averaged over the chips), median over
the profiled jobs."""

from perf.lib import stats


def read(obs):
    dev = stats.device_s_by_index(obs)
    return stats.median(j["device_s"] * 1e3 for j in dev.values())
