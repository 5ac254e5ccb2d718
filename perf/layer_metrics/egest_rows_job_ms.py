"""executor host side: self time of a job's `egest` ring spans (its
`readback`s taken out: Python rows, `tolist`, `zip`, byte strings
rebuilt), summed, median over the window's jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.self_ms(obs, "egest")
