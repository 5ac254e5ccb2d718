"""driver: a job's wall on the caller's clock minus the sum of its
`stage.exec` ring spans (planning, scheduling, rows to host), median over
the window's jobs."""

from perf.lib import stats


def read(obs):
    staged = ((j["wall_s"], stats.stage_exec_s(j)) for j in obs["jobs"])
    return stats.median((wall - exec_s) * 1e3 for wall, exec_s in staged
                        if exec_s is not None)
