"""stage programs: in-core shuffle stores registered `key_ordered` per job
of the window (the executor's `stores_key_ordered` counter, a bare `+= 1`
in `JAXExecutor._finish_stage` where a one-device mesh's write that
combines nothing, partitioned by hash over number keys, sorted its rows by
key: the join's gather over such a store launches no program).  4 in
`tpch.q3` (customer, orders, lineitem and join 1's re-keyed output), 2 in
`join.resident`, 0 in `uservisits.join`, whose byte-string key keeps the
destination sort.  A program without the counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "stores_key_ordered")
