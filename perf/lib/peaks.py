"""Published peaks of one chip, keyed by jax's `device_kind`."""

import json
import os


def for_device(kind):
    """The peaks of `kind`.  An unknown device is an error, never a
    default: a roofline share against made-up peaks means nothing."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError("no published peaks for device kind %r in "
                       "perf/lib/peaks.json (known: %s)"
                       % (kind, sorted(table)))
    return table[kind]
