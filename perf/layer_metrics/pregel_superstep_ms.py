"""stage programs: the median `pregel.superstep` ring span (one a
superstep of device Pregel, inside the job's `stage.exec`: the exchange's
launches, the step program's launch, the read of the active count, the gen
program's launch and the read of the message count;
`dpark_tpu/backend/tpu/bagel.py: DevicePregel.run`), a job's spans reduced
to their median, then the median over the window's traced jobs, in ms.  A
program without the span reports nothing."""

from perf.lib import stats


def read(obs):
    steps = [[s["dur"] for s in j["spans"] if s["name"] == "pregel.superstep"]
             for j in obs["jobs"] if "spans" in j]
    if not any(steps):
        return None
    return stats.median(stats.median(durs) * 1e3 for durs in steps if durs)
