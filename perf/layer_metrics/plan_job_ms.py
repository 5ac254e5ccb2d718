"""driver: sum of a job's `plan` ring spans (`fuse.analyze_stage`, once a
stage), median over the window's jobs."""

from perf.lib import hostspans


def read(obs):
    return hostspans.span_ms(obs, "plan")
