"""device: idle time inside a profiled job's annotation that falls under a
`readback` or `egest` span (the host waiting for the device's last value,
then copying and building rows while the device has nothing queued), median
over the profiled jobs."""

from perf.lib import hostspans


def read(obs):
    return hostspans.idle_ms(obs, "readback")
