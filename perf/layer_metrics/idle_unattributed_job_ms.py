"""device: idle time inside a profiled job's annotation under none of the
program's `plan`, `launch`, `eager`, `readback`, `egest`, `ingest` and
`hbm.spill` spans, median over the profiled jobs.  With idle_spill_,
idle_readback_ and idle_launch_job_ms it adds up, per profiled job, to the
device's idle time inside the job.  The run's log names the spans on
either side of the longest pieces."""

from perf.lib import hostspans


def read(obs):
    hostspans.log_unattributed(obs)
    return hostspans.idle_ms(obs, hostspans.UNATTRIBUTED)
