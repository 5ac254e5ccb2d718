"""executor host side: sum of a job's `readback` ring spans that lie outside
`egest`, `hbm.spill` and `plan` (the host blocked on a control value:
counts, min/max, totals), median over the window's jobs."""

from perf.lib import hostspans


def read(obs):
    return hostspans.span_ms(obs, "readback")
