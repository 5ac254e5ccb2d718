"""executor host side: sum of a job's `egest` ring spans (the result's
readbacks plus building Python rows), median over the window's jobs."""

from perf.lib import hostspans


def read(obs):
    return hostspans.span_ms(obs, "egest")
