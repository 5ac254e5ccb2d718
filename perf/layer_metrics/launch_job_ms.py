"""executor host side: sum of a job's `launch` ring spans (the host's side
of calling each compiled stage program; not device time, and not the
`eager` spans of operations outside a compiled program), median over the
window's jobs."""

from perf.lib import hostspans


def read(obs):
    return hostspans.span_ms(obs, "launch")
