"""executor host side: sum of a job's `stage.exec` ring spans minus the
device time the profiler saw inside that job, median over the profiled
jobs."""

from perf.lib import stats


def read(obs):
    dev = stats.device_s_by_index(obs)
    vals = [(stats.stage_exec_s(j) - dev[j["index"]]["device_s"]) * 1e3
            for j in obs["profiled_jobs"]
            if j["index"] in dev and stats.stage_exec_s(j) is not None]
    return stats.median(vals)
