"""executor host side: rows whose byte strings crossed between their host
form and their device words, per job of the window (the executor's
`bytes_rows_packed` + `bytes_rows_unpacked` counters: `layout._pack_parts`
at ingest, `layout.host_columns` at egest and the export bridge).  A job
over resident tables that only counts reads 0: strings live and die on the
device; anything else means string keys took a way through the host.  A
program without the counters reports nothing."""

from perf.lib import hostspans


def read(obs):
    packed = hostspans.per_job_count(obs, "bytes_rows_packed")
    unpacked = hostspans.per_job_count(obs, "bytes_rows_unpacked")
    if packed is None or unpacked is None:
        return None
    return packed + unpacked
