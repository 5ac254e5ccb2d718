"""The one traffic generator.  A traffic mix is a JSON file of parameters
(perf/traffic/<name>.json); nothing about a mix lives in code:

    loop                 "closed": the client sends its next job when the
                         last one has returned and been checked.
                         "open": jobs arrive at `rate_per_s` whether or
                         not the last has returned, and wait in one queue;
                         each job then also carries `latency_s` (arrival
                         to return) and `late_s` (arrival to start)
    rate_per_s           open loop: arrivals a second, fixed in the file
    arrivals             open loop: "poisson" (exponential gaps from the
                         seed, the default) or "fixed" (even gaps)
    clients              1: jobs run one at a time in one thread
    rows_per_job         input rows of one job = rows of one partition
    resident_partitions  day partitions kept in HBM
    draw                 how the partition of each job is drawn from the
                         seed: "uniform" (with replacement), "zipf" (rank
                         r with weight r ** -draw_exponent, the ranks
                         dealt to partitions by the seed; hot and cold
                         partitions) or "round_robin" (a scan from a
                         seeded start)
    draw_exponent        the exponent of "zipf" (default 1.0)
    jobs                 the mix: [{"query", "action", "weight",
                         optional "setup_actions"}]; "query" and the
                         actions are names the configuration's job module
                         knows
    warmup_jobs          jobs run (and checked) in set-up, per entry of
                         the mix, so that the window finds every program
                         compiled and the executor in its steady state
    profile_jobs         jobs of a traced run that the jax profiler covers
"""

import numpy as np

LOOPS = ("closed", "open")
ARRIVALS = ("poisson", "fixed")
DRAWS = ("uniform", "zipf", "round_robin")


def validate(params):
    if params.get("loop") not in LOOPS:
        raise ValueError("traffic loop must be one of %s" % (LOOPS,))
    if int(params.get("clients", 0)) != 1:
        raise ValueError("jobs run one at a time: clients must be 1")
    if params["loop"] == "open":
        if not float(params.get("rate_per_s", 0)) > 0:
            raise ValueError("an open loop needs a positive rate_per_s")
        if params.get("arrivals", "poisson") not in ARRIVALS:
            raise ValueError("traffic arrivals must be one of %s"
                             % (ARRIVALS,))
    if not float(params.get("draw_exponent", 1.0)) > 0:
        raise ValueError("traffic draw_exponent must be positive")
    if params.get("draw") not in DRAWS:
        raise ValueError("traffic draw must be one of %s" % (DRAWS,))
    for key in ("rows_per_job", "resident_partitions", "warmup_jobs",
                "profile_jobs"):
        if int(params.get(key, 0)) < 1:
            raise ValueError("traffic %s must be a positive integer" % key)
    if not params.get("jobs"):
        raise ValueError("traffic needs at least one entry under jobs")
    for entry in params["jobs"]:
        if not entry.get("query") or not entry.get("action") \
                or not float(entry.get("weight", 0)) > 0:
            raise ValueError("a jobs entry needs query, action and a "
                             "positive weight: %r" % (entry,))


def arrivals(params, seed):
    """Open loop: an endless stream of arrival times, in seconds from the
    window's start, drawn from the seed.  None for a closed loop."""
    if params["loop"] != "open":
        return None
    rate = float(params["rate_per_s"])
    fixed = params.get("arrivals", "poisson") == "fixed"
    rng = np.random.default_rng([seed, 0xA221FE])

    def times():
        at = 0.0
        while True:
            gaps = np.full(256, 1.0 / rate) if fixed \
                else rng.exponential(1.0 / rate, size=256)
            for gap in gaps:
                at += float(gap)
                yield at
    return times()


def _partitions(params, rng, n_partitions):
    """Endless batches of partition numbers, as `draw` says."""
    draw = params["draw"]
    if draw == "zipf":
        ranks = np.arange(1, n_partitions + 1, dtype=np.float64)
        p = ranks ** -float(params.get("draw_exponent", 1.0))
        p = (p / p.sum())[rng.permutation(n_partitions)]
    elif draw == "round_robin":
        at = int(rng.integers(0, n_partitions))
    while True:
        if draw == "uniform":
            yield rng.integers(0, n_partitions, size=256)
        elif draw == "zipf":
            yield rng.choice(n_partitions, size=256, p=p)
        else:
            yield (at + np.arange(256)) % n_partitions
            at = (at + 256) % n_partitions


def schedule(params, seed, n_partitions):
    """An endless stream of (jobs entry, partition) drawn from the seed."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    entries = params["jobs"]
    weights = np.array([float(e["weight"]) for e in entries])
    weights = weights / weights.sum()
    batches = _partitions(params, rng, n_partitions)
    while True:
        picks = rng.choice(len(entries), size=256, p=weights)
        for e, p in zip(picks, next(batches)):
            yield entries[int(e)], int(p)
