"""executor host side: self time of a job's `stage.exec` ring spans (what
lies under none of `launch`, `eager`, `readback`, `egest`, `ingest`,
`join`, `sort.sample`, `hbm.spill`: the source look-up, the store's
registration, eviction, the identity exchange), summed, median over the
window's jobs."""

from perf.lib import selftime


def read(obs):
    return selftime.self_ms(obs, "stage.exec")
