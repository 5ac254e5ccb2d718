"""executor host side: sum of a job's `sort.sample` ring spans (the read
of sortByKey's bounds sample: `JAXExecutor._sample_keys`, around the
egest of the first keys of every split, inside the sample job's
`stage.exec`), median over the window's jobs.  A program without the span
reports nothing."""

from perf.lib import stats


def read(obs):
    samples = [[s["dur"] for s in j["spans"] if s["name"] == "sort.sample"]
               for j in obs["jobs"] if "spans" in j]
    if not any(samples):
        return None
    return stats.median(sum(durs) * 1e3 for durs in samples)
