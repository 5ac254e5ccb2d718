"""device: 1 - busy union / window over the profiled jobs (profiler
trace, averaged over the chips)."""


def read(obs):
    p = obs.get("profile")
    if not p or not p["window_s"] > 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
