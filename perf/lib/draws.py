"""Seeded draws of a configuration's columns.  A configuration names each
distribution in its file; one the benchmark does not implement is an
error, never a silent default."""

import numpy as np


def integers(rng, dist, n):
    """`n` int64 values of `dist`: {"kind": "uniform", "bits": b} draws
    from [0, 2**b), {"kind": "uniform", "low": l, "high": h} from
    [l, h)."""
    if not isinstance(dist, dict) or dist.get("kind") != "uniform":
        raise ValueError("distribution %r is not implemented (known: "
                         "uniform)" % (dist,))
    if "bits" in dist:
        low, high = 0, 1 << int(dist["bits"])
    else:
        low, high = int(dist["low"]), int(dist["high"])
    return rng.integers(low, high, n, dtype=np.int64)
