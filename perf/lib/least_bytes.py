"""The least traffic a job's algorithm must move, from its shapes alone.

These are the yardstick of `job_roofline`: what any implementation of the
same query has to read and write once, not what today's programs move.
All sizes are per chip; `rows` is the chip's share of the job's input.
"""


def keyed_aggregate(rows, row_bytes, groups, ndev):
    """GROUP BY key, SUM(value) over `rows` input rows a chip into at most
    `groups` groups a chip.

    HBM: every input row is read once and every group written once.
    ICI (ndev > 1): after the best possible map-side combine a chip holds
    min(rows, groups * ndev) partial rows, of which (ndev-1)/ndev belong
    to another chip and have to cross once.
    """
    hbm = rows * row_bytes + groups * row_bytes
    ici = 0
    if ndev > 1:
        ici = min(rows, groups * ndev) * row_bytes * (ndev - 1) / ndev
    return {"hbm_bytes": float(hbm), "ici_bytes": float(ici)}


def join_aggregate(fact_rows, dim_rows, row_bytes, groups, ndev):
    """fact JOIN dim ON key, then GROUP BY f(key), SUM: both tables are
    read once, every group is written once.  Across chips the smaller
    side (the dimension) crosses once to every other chip."""
    hbm = (fact_rows + dim_rows) * row_bytes + groups * row_bytes
    ici = dim_rows * row_bytes * (ndev - 1) if ndev > 1 else 0
    return {"hbm_bytes": float(hbm), "ici_bytes": float(ici)}


def least_seconds(least, peaks):
    """(seconds, the term that bounds) on a chip with these peaks."""
    terms = {"hbm": least.get("hbm_bytes", 0.0) / peaks["hbm_bytes_per_s"],
             "ici": least.get("ici_bytes", 0.0) * 8
             / peaks["ici_bits_per_s"]}
    bound = max(terms, key=terms.get)
    return terms[bound], bound
