"""stage programs: sum of a job's `join` ring spans (the device join from
its two exchanges to the expanded batch: `JAXExecutor.device_join_batch`,
enclosing its `launch` spans and the `join.totals` read, so the wait for
the count program is in it), median over the window's jobs.  A program
without the span reports nothing."""

from perf.lib import stats


def read(obs):
    joins = [[s["dur"] for s in j["spans"] if s["name"] == "join"]
             for j in obs["jobs"] if "spans" in j]
    if not any(joins):
        return None
    return stats.median(sum(durs) * 1e3 for durs in joins)
