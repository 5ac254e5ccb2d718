"""stage programs: launches of a map program whose combining write packs to a
proven key bound, per job of the window (the executor's `combines_bounded`: a
bare `+= 1` at each launch of a narrow program whose plan carries the query
planner's key bound, `planner._key_bound`, and whose bound's capacity class is
below the program's input capacity, `JAXExecutor._pack_cap`; window delta /
jobs).  Such a write sorts only its keep flags and gathers the first slots of
that order: its store leaves the program at the bound's class and not the
input's.  1 in `tpch.q1` (two CHAR(1) keys, 18 x 10 values: 256 slots of
8,388,608), 0 in every cell whose chain is RDD code.  A program without the
counter reports nothing."""

from perf.lib import hostspans


def read(obs):
    return hostspans.per_job_count(obs, "combines_bounded")
