"""executor host side: milliseconds per job the executor spent spilling
dead shuffle stores to disk (SHUFFLE_HBM_BUDGET eviction), on the runner's
clock around `_spill_shuffle_to_disk` (runner.SpillWatch): the window's
total over its jobs.  0 in a cell that runs under the budget.  Where the
program has lost that routine and still evicts, the runner stops the run
instead of reporting a 0 it cannot vouch for."""


def read(obs):
    jobs = obs["jobs"]
    if not jobs:
        return None
    return sum(j["spill_s"] for j in jobs) * 1e3 / len(jobs)
