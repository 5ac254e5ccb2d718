"""executor host side: byte-string rows packed into device words or
rebuilt as host `bytes` per job of a cell that JOINS on a byte string (the
executor's `bytes_rows_packed` + `bytes_rows_unpacked`, window delta /
jobs).  A job over resident tables that returns one row reads 1: the
answer's sourceIP; more means the join's keys or values took a way through
the host.  A program without the counters reports nothing."""

from perf.lib import hostspans


def read(obs):
    packed = hostspans.per_job_count(obs, "bytes_rows_packed")
    unpacked = hostspans.per_job_count(obs, "bytes_rows_unpacked")
    if packed is None or unpacked is None:
        return None
    return packed + unpacked
