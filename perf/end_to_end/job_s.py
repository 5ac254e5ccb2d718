"""Median wall of one job: from building the RDD chain to the return of
its action, the answer in host memory."""

from perf.lib import stats


def read(obs):
    return stats.median(j["wall_s"] for j in obs["jobs"])
