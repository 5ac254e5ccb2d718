"""driver: 95th percentile of the window's job walls; only where the
window holds at least 200 jobs (ten samples beyond the percentile)."""

from perf.lib import stats


def read(obs):
    walls = [j["wall_s"] * 1e3 for j in obs["jobs"]]
    return stats.percentile(walls, 95) if len(walls) >= 200 else None
