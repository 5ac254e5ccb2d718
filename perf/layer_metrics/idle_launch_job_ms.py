"""device: idle time inside a profiled job's annotation while the host is
getting the next work to it: under a `launch` span, or under `plan`,
`ingest` or `eager` (planning the stage, host numpy to device, eager jnp
operations outside a compiled program), median over the profiled jobs."""

from perf.lib import hostspans


def read(obs):
    return hostspans.idle_ms(obs, "launch")
