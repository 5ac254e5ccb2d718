"""Comparisons that decide `correct` (copied from chip_smoke.py, PR 21,
and widened): numpy only, no dpark_tpu."""

import numpy as np

# np.bincount sums its weights in float64, which is exact for integers
# below 2**53; the references assert their worst case stays under it
EXACT_F64 = 1 << 53


def rows_to_arrays(rows):
    """A list of (key, value) rows as two int64 arrays."""
    k = np.fromiter((r[0] for r in rows), np.int64, len(rows))
    v = np.fromiter((r[1] for r in rows), np.int64, len(rows))
    return k, v


def keyed_sums(keys, vals, n_groups):
    """Reference GROUP BY key, SUM(val) over a dense key domain
    [0, n_groups): (present keys ascending, their exact int64 sums)."""
    if len(keys) and int(vals.max()) * len(keys) >= EXACT_F64:
        raise OverflowError("sums may pass 2**53: bincount would round")
    hits = np.bincount(keys, minlength=n_groups)
    sums = np.bincount(keys, weights=vals, minlength=n_groups)
    present = np.flatnonzero(hits)
    return present.astype(np.int64), sums[present].astype(np.int64)


def same_keyed_sums(rows, expected):
    """Do the (key, sum) rows a job returned equal the reference, every
    key once, in any order?"""
    exp_k, exp_v = expected
    if len(rows) != len(exp_k):
        return False
    k, v = rows_to_arrays(rows)
    order = np.argsort(k, kind="stable")
    return bool(np.array_equal(k[order], exp_k)
                and np.array_equal(v[order], exp_v))
