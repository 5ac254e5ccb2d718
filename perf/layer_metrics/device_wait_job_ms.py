"""executor host side: the `wait_s` of a job's `readback` ring spans (how
long `layout.host_read` blocked until the device had produced the value,
before the copy), summed, median over the window's jobs.  It moves with
`device_job_ms` by design; `readback_job_ms + egest_job_ms` minus this is
the host's part of the reads."""

from perf.lib import selftime


def read(obs):
    return selftime.job_ms(obs, selftime.wait_s)
