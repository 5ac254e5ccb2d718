"""dpark_tpu — a TPU-native distributed dataset framework with the
capabilities of douban/dpark.

Same semantic contract as the reference (lazy partitioned RDDs, DAG
scheduler cutting stages at shuffle boundaries, local/process masters) with
a TPU master where stages compile to jitted SPMD programs over a jax device
mesh and shuffles run as ICI collectives (see SURVEY.md and backend/tpu/).
"""

from dpark_tpu.utils import apply_platform_override

# honor DPARK_TPU_PLATFORM for EVERY master before any jax backend
# init: user code may call jnp on the local/process masters too, and
# their first jnp call initialises whatever platform jax defaults to.
# No-op unless the env var is set.
apply_platform_override()

from dpark_tpu.context import DparkContext, optParser, parse_options
from dpark_tpu.rdd import Columns

__version__ = "0.1.0"

_default_ctx = None


def _ctx():
    global _default_ctx
    if _default_ctx is None:
        _default_ctx = DparkContext()
    return _default_ctx


def parallelize(seq, numSlices=None):
    return _ctx().parallelize(seq, numSlices)


def makeRDD(seq, numSlices=None):
    return _ctx().makeRDD(seq, numSlices)


def textFile(path, **kw):
    return _ctx().textFile(path, **kw)


def accumulator(init=0, param=None):
    return _ctx().accumulator(init, param)


def broadcast(value):
    return _ctx().broadcast(value)


__all__ = ["DparkContext", "optParser", "parse_options", "parallelize",
           "makeRDD", "textFile", "accumulator", "broadcast"]
