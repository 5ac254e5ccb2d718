"""kernels: the least time the chip could take for the job (the larger of
least HBM bytes over peak HBM bandwidth and least ICI bytes over peak ICI
bandwidth, from perf/lib/least_bytes.py and perf/lib/peaks.json) over the
device time the job took, in percent.  The issue's `hbm_roofline_pct`.
The bounding term is printed on the run's log."""

from perf.lib import least_bytes, stats


def read(obs):
    dev = stats.device_s_by_index(obs)
    if not dev or not obs.get("peaks"):
        return None
    shares, terms = [], set()
    for job in obs["profiled_jobs"]:
        device_s = dev.get(job["index"], {}).get("device_s", 0.0)
        if not device_s > 0:
            continue
        seconds, bound = least_bytes.least_seconds(
            obs["least"][job["query"]], obs["peaks"])
        shares.append(100.0 * seconds / device_s)
        terms.add(bound)
    if shares:
        print("[roofline] bounded by %s" % "/".join(sorted(terms)),
              flush=True)
    return stats.median(shares)
