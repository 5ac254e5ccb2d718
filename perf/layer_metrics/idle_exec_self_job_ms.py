"""device: of the idle time `idle_unattributed_job_ms` counts, the part
inside a `stage.exec` (under none of its `launch`, `eager`, `readback`,
`egest`, `ingest` and `hbm.spill`: its self time, and that of `join` and
`sort.sample`), median over the profiled jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.idle_ms(obs, "exec_self")
