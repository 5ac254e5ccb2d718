"""Columnar array-partition layout for the TPU backend.

The object path (dpark/rdd.py generators) represents a partition as a Python
iterator of records.  The array path represents a *stage's worth* of
partitions as a struct-of-arrays batch sharded over the device mesh:

  * a record is a JAX pytree (e.g. ``(k, v)`` or a bare scalar);
  * each pytree leaf becomes one column array of shape ``(ndev, cap)``
    (+ trailing dims), sharded ``P('parts', None)`` so device d holds
    logical partition d;
  * ``counts`` (shape ``(ndev,)``) gives the number of valid rows per
    device; rows past the count are padding.

This is the TPU-native replacement for the reference's pickled partition
streams (dpark/shuffle.py file buckets): data never leaves HBM between
stages.  Reference parity anchor: SURVEY.md section 7.0 "array partitions".
"""

import math
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dpark_tpu import conf
from dpark_tpu import trace

AXIS = conf.MESH_AXIS
# int64 sentinel: keys must be < 2**63 - 1; ingest() rejects the sentinel
# value itself (-> host fallback) so no real key can collide with padding
KEY_SENTINEL = np.int64(2 ** 63 - 1)


def make_mesh(devices=None):
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (AXIS,))


# ---------------------------------------------------------------------
# multi-controller SPMD support (SURVEY.md section 2.5): when the mesh
# spans jax processes (mrun + jax.distributed), every rank runs the
# same driver program; host->device and device->host crossings go
# through these two helpers so the same scheduler code works unchanged
# on one process or many.
# ---------------------------------------------------------------------
def put_sharded(arr, sharding):
    """numpy -> sharded jax.Array.  Fully-addressable shardings take
    the direct device_put; process-spanning shardings build the global
    array from each rank's addressable shards (every rank holds the
    same full host array, so any index slice is available locally)."""
    if sharding.is_fully_addressable:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


_REPLICATORS = {}

# calls of host_read in this process, traced or not: every blocking
# control read of the array path goes through it, so a job's delta is
# its number of host<->device round trips (JAXExecutor.host_reads).
# A bare `+= 1`: exact while one thread runs jobs; the export server's
# threads read too, and a racing add can lose a count
HOST_READS = 0
# rows whose fixed-width byte strings were packed into device words at
# ingest / rebuilt as host bytes at a host exit (bare `+=`, like
# HOST_READS; JAXExecutor.bytes_rows_packed / bytes_rows_unpacked)
BYTES_ROWS_PACKED = 0
BYTES_ROWS_UNPACKED = 0
# widest S<w> column that is a device leaf: 16 words, room for the
# 100-byte URLs of the field's web-log tables.  A string's own limit
# (conf.MAX_KEY_LEAVES bounds tuple keys): past a few words a row no
# longer rides a sort as one operand a word (collectives._lex_sort),
# so a word costs run time and not minutes of compile
BYTES_WIDTH_MAX = 128


class ByteStr:
    """A fixed-width byte string on the array path (a numpy ``S<w>``
    column): `width` NUL-padded bytes held as ceil(width / 8) int64
    words, big-endian, so that a word compares as its bytes do (for
    ASCII the signed word order is byte order) and a key of X bytes is
    ceil(X / 8) ordinary int key columns.  A registered pytree node:
    the words are the leaves, the width rides in the treedef.

    Inside a traced user function it stands in for the `bytes` object
    the local master passes: a static slice ``s[a:b]`` is the bytes
    slice (a string shorter than the slice is the whole string:
    trailing NULs are padding, as in numpy), ``s[i]`` is the byte as an
    int, ``==``/``!=`` compare with `bytes` or another ByteStr.
    Anything else (len, iteration, ordering, negative or traced
    indices: all depend on the string's true length or order) raises
    and keeps the host path."""

    __slots__ = ("width", "words")
    __hash__ = None

    def __init__(self, width, words):
        self.width = int(width)
        self.words = tuple(words)

    def __repr__(self):
        return "ByteStr[%d]%r" % (self.width, self.words)

    def _byte(self, i):
        return (self.words[i // 8] >> (8 * (7 - i % 8))) & 0xFF

    def __getitem__(self, i):
        if isinstance(i, slice):
            if i.step not in (None, 1):
                raise TypeError("ByteStr slices have step 1")
            lo = 0 if i.start is None else i.start
            hi = self.width if i.stop is None else i.stop
            if not all(isinstance(x, int) and x >= 0 for x in (lo, hi)):
                raise TypeError("ByteStr slice bounds must be static "
                                "non-negative ints")
            hi = min(hi, self.width)
            return self._window(min(lo, hi), hi)
        if isinstance(i, bool) or not isinstance(i, int):
            raise TypeError("ByteStr index must be a static int")
        if i < 0:
            raise TypeError("a negative ByteStr index counts from the "
                            "row's own end; host path")
        if i >= self.width:
            raise IndexError("ByteStr index out of range")
        return self._byte(i)

    def _window(self, lo, hi):
        """Bytes [lo, hi) as a ByteStr of width hi - lo."""
        width = hi - lo
        shift = 8 * (lo % 8)
        words = []
        for j in range(-(-width // 8)):
            a = lo // 8 + j
            w = self.words[a]
            if shift:
                w = w << shift
                if a + 1 < len(self.words):
                    # logical right shift of the next word's top bytes
                    w = w | ((self.words[a + 1] >> (64 - shift))
                             & ((1 << shift) - 1))
            words.append(w)
        tail = width % 8
        if tail:
            words[-1] = words[-1] & -(1 << (8 * (8 - tail)))
        return ByteStr(width, words)

    def _same(self, other):
        if isinstance(other, bytes):
            if len(other) > self.width or other.endswith(b"\0"):
                # host strings are at most `width` long and carry no
                # trailing NUL: never equal
                return jnp.zeros((), bool)
            other = ByteStr(self.width, pack_bytes(
                np.array([other], "S%d" % max(self.width, 1)))[0])
        if not isinstance(other, ByteStr):
            return None
        n = max(len(self.words), len(other.words))
        a = self.words + (0,) * (n - len(self.words))
        b = other.words + (0,) * (n - len(other.words))
        same = jnp.ones((), bool)
        for x, y in zip(a, b):
            same = same & (x == y)
        return same

    def __eq__(self, other):
        same = self._same(other)
        return NotImplemented if same is None else same

    def __ne__(self, other):
        same = self._same(other)
        return NotImplemented if same is None else ~same

    def __len__(self):
        raise TypeError("len() of a device byte string depends on the "
                        "row; host path")

    def __iter__(self):
        raise TypeError("a device byte string does not iterate; host "
                        "path")


jax.tree_util.register_pytree_node(
    ByteStr, lambda s: (s.words, s.width),
    lambda width, words: ByteStr(width, words))


def bytes_words(width):
    return -(-int(width) // 8)


def pack_bytes(col):
    """numpy S<w> array (n,) -> (n, ceil(w / 8)) int64 big-endian
    words, NUL-padded."""
    col = np.ascontiguousarray(col)
    w = col.dtype.itemsize
    nw = bytes_words(w)
    buf = np.zeros((len(col), nw * 8), np.uint8)
    buf[:, :w] = col.view(np.uint8).reshape(len(col), w)
    return buf.view(">i8").astype(np.int64)


def unpack_bytes(words, width):
    """Word columns (each of shape `shape`, any int dtype) -> S<width>
    array of that shape (numpy strips the NUL padding at .tolist())."""
    stacked = np.stack([np.asarray(w) for w in words],
                       axis=-1).astype(">i8")
    raw = np.ascontiguousarray(
        stacked.view(np.uint8)[..., :width])
    return raw.view("S%d" % width).reshape(stacked.shape[:-1])


def _is_bytestr(x):
    return isinstance(x, ByteStr)


def column_groups(treedef, nleaves):
    """(host treedef, groups) when the record holds ByteStr nodes, else
    None.  The host sees each ByteStr as ONE column (an S<w> array, a
    `bytes` object in a row): `groups` lists, per host column, a leaf
    index or a ByteStr whose words are leaf indices; the host treedef
    has a plain leaf where the node was."""
    if "ByteStr" not in str(treedef):
        return None
    sample = jax.tree_util.tree_unflatten(treedef, list(range(nleaves)))
    groups, outer = jax.tree_util.tree_flatten(sample,
                                               is_leaf=_is_bytestr)
    return outer, groups


def host_columns(treedef, cols, rows=None):
    """(treedef, numpy columns) as a host exit sees them: the word
    columns of every ByteStr node become one S<w> column.  Unchanged
    when the record holds no byte string.  BYTES_ROWS_UNPACKED counts
    `rows` (the valid rows, where the columns carry padding)."""
    global BYTES_ROWS_UNPACKED
    found = column_groups(treedef, len(cols))
    if found is None:
        return treedef, cols
    outer, groups = found
    if rows is None:
        rows = len(cols[0])
    BYTES_ROWS_UNPACKED += rows
    out = [unpack_bytes([cols[i] for i in g.words], g.width)
           if _is_bytestr(g) else cols[g] for g in groups]
    return outer, out


def _pack_parts(partitions, treedef, specs):
    """Columnar partitions whose S<w> columns become the word columns
    of their ByteStr nodes (so that every part carries one column a
    leaf).  BYTES_ROWS_PACKED counts the rows.
    Byte strings reach the device from Columns only: the width is the
    column's, which rows of `bytes` objects do not have."""
    global BYTES_ROWS_PACKED
    found = column_groups(treedef, len(specs))
    if found is None:
        return partitions
    from dpark_tpu.rdd import _ColumnarSlice
    _, groups = found
    rows = 0
    out = []
    for part in partitions:
        cols = getattr(part, "columns", None)
        if not len(part) or (cols is not None
                             and len(cols) == len(specs)
                             and not any(np.asarray(c).dtype.kind
                                         == "S" for c in cols)):
            out.append(part)        # empty, or already word columns
            continue
        if cols is None or len(cols) != len(groups):
            raise ValueError("byte-string records reach the device "
                             "as Columns; taking the host path")
        leaf_cols = [None] * len(specs)
        for g, c in zip(groups, cols):
            if not _is_bytestr(g):
                leaf_cols[g] = c
                continue
            c = np.asarray(c)
            if c.dtype.kind != "S" or c.dtype.itemsize != g.width:
                raise ValueError("column dtype %s where S%d was "
                                 "planned; taking the host path"
                                 % (c.dtype, g.width))
            packed = pack_bytes(c)
            for j, li in enumerate(g.words):
                leaf_cols[li] = packed[:, j]
            rows += len(c)
        out.append(_ColumnarSlice(leaf_cols))
    BYTES_ROWS_PACKED += rows
    return out


def host_read(x, site=""):
    """device -> host numpy for metric/sizing readbacks: the array
    path's one blocking read.  `x` is an array, or a list of arrays
    fetched in one transfer (a list of numpy arrays comes back).
    `site` is the caller's short literal: it names the `readback` span
    that times the wait for the device plus the copy when the trace
    plane is on.  The span says how long it waited (`wait_s`: until
    the device had produced the value); the rest of it is the copy."""
    global HOST_READS
    HOST_READS += 1
    plane = trace._PLANE
    if plane is not None:
        leaves = x if isinstance(x, list) else (x,)
        with trace.span("readback", "exec", site=site, bytes=sum(
                int(getattr(a, "nbytes", 0)) for a in leaves)) as sp:
            t0 = time.time()
            jax.block_until_ready(x)
            sp.args["wait_s"] = round(time.time() - t0, 6)
            return _to_host(x)
    return _to_host(x)


def _to_host(x):
    """A global array whose shards live on other processes cannot be
    device_get directly; replicate it across the mesh first (one
    all_gather) — every rank then reads the SAME value, which also
    keeps multi-rank scheduler decisions (slot sizing, round counts)
    deterministic."""
    if isinstance(x, list):
        if all(getattr(a, "is_fully_addressable", True) for a in x):
            return [np.asarray(a) for a in jax.device_get(x)]
        return [_to_host(a) for a in x]
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(jax.device_get(x))
    mesh = x.sharding.mesh           # Mesh is hashable — key by value,
    fn = _REPLICATORS.get(mesh)      # not id() (ids recycle); bound the
    if fn is None:                   # cache so executor churn can't pin
        if len(_REPLICATORS) >= 8:   # dead meshes forever
            _REPLICATORS.pop(next(iter(_REPLICATORS)))
        fn = jax.jit(lambda a: a,
                     out_shardings=NamedSharding(mesh, P()))
        _REPLICATORS[mesh] = fn
    return np.asarray(jax.device_get(fn(x)))


def round_capacity(n):
    """Pad capacities to power-of-two size classes so recompilation only
    happens when the class changes (SURVEY.md 7.2 item 5)."""
    return max(8, 1 << math.ceil(math.log2(max(n, 1))))


def round_capacity_fine(n):
    """Pad to 1/16th-octave size classes (16 classes per power of two):
    worst-case padding drops from 2x to 6.25%.  Used for exchange SLOT
    sizing, where power-of-two rounding halves wire efficiency at
    uniform key loads (a slot just past a power of two pads to the
    next); capacity classes for compiled stage programs stay
    power-of-two."""
    n = max(n, 1)
    if n <= 128:
        return round_capacity(n)
    k = (n - 1).bit_length() - 1          # n in (2^k, 2^(k+1)]
    step = 1 << (k - 4)                   # 16 classes per octave
    return -(-n // step) * step


class Batch:
    """A sharded struct-of-arrays batch: one stage's partitions in HBM."""

    def __init__(self, treedef, cols, counts):
        self.treedef = treedef          # record pytree structure
        self.cols = list(cols)          # leaf arrays, each (ndev, cap, ...)
        self.counts = counts            # (ndev,) int32
        self.ndev = cols[0].shape[0]
        self.cap = cols[0].shape[1]


def record_spec(sample):
    """(treedef, leaf dtypes/shapes) for a sample record."""
    leaves, treedef = jax.tree_util.tree_flatten(sample)
    # a 0-d S<w> array stands for a fixed-width byte-string COLUMN
    # (fuse._sample_record): it becomes a ByteStr node of int64 words.
    # A bare `bytes` object has no column width, and a column over the
    # limit keeps its S dtype: both are what every caller declines
    columns = [isinstance(l, np.ndarray) and l.dtype.kind == "S"
               and 0 < l.dtype.itemsize <= BYTES_WIDTH_MAX for l in leaves]
    if any(columns):
        leaves, treedef = jax.tree_util.tree_flatten(
            jax.tree_util.tree_unflatten(treedef, [
                ByteStr(l.dtype.itemsize,
                        [np.int64(0)] * bytes_words(l.dtype.itemsize))
                if is_col else l for l, is_col in zip(leaves, columns)]))
    specs = []
    for leaf in leaves:
        arr = np.asarray(leaf)
        dt = arr.dtype
        if dt == np.float64:
            # device path computes in float32 (TPU-native); parity tests
            # use allclose for float reductions (SURVEY.md 7.2 item 6)
            dt = np.dtype(np.float32)
        elif np.issubdtype(dt, np.integer):
            # int64 so counting/summing workloads cannot silently wrap —
            # exact parity with the local master's Python ints up to 2**63
            dt = np.dtype(np.int64)
        elif dt == np.bool_:
            dt = np.dtype(np.bool_)
        specs.append((dt, arr.shape))
    return treedef, specs


def ingest(mesh, partitions, treedef, specs, key_leaf=None,
           cap_floor=0):
    """Host rows -> sharded Batch.

    `partitions`: list (len == mesh size) of lists of records.  Each record
    must match `treedef`/`specs`.  When `key_leaf` is given, that leaf is
    checked against KEY_SENTINEL (raises ValueError -> host fallback).
    `cap_floor` pins the capacity class from below — stream loops pass
    their running max so a smaller tail wave reuses the compiled
    programs of earlier waves instead of compiling a new size class.
    """
    ndev = mesh.devices.size
    assert len(partitions) == ndev, (len(partitions), ndev)
    partitions = _pack_parts(partitions, treedef, specs)
    counts = np.array([len(p) for p in partitions], dtype=np.int32)
    cap = max(round_capacity(int(counts.max()) if len(counts) else 1),
              cap_floor)
    # host->device wire narrowing: int64 scalar leaves whose values
    # provably fit int32 cross H2D at i32 (half the bytes of the
    # largest transfer a job makes);
    # the stage program widens back to the spec dtype at entry, so
    # compute semantics are unchanged.  Columnar partitions only (the
    # big-data path, where the min/max scan is one vectorized pass).
    from dpark_tpu import conf as _conf
    tight = [None] * len(specs)
    col_stats = {}
    all_columnar = _conf.NARROW_EXCHANGE and any(
        len(p) for p in partitions) and all(
        getattr(p, "columns", None) is not None
        and len(p.columns) == len(specs)
        for p in partitions if len(p))
    if all_columnar:
        i32 = np.iinfo(np.int32)
        for li, (dt, shape) in enumerate(specs):
            if np.dtype(dt) == np.int64 and shape == ():
                los, his = [], []
                for p in partitions:
                    if len(p):
                        c = np.asarray(p.columns[li])
                        if c.size:
                            los.append(int(c.min()))
                            his.append(int(c.max()))
                lo = min(los) if los else 0
                hi = max(his) if his else 0
                col_stats[li] = (lo, hi)
                if lo >= i32.min and hi <= i32.max:
                    tight[li] = np.dtype(np.int32)
    cols = []
    for li, (dt, shape) in enumerate(specs):
        col = np.zeros((ndev, cap) + shape, dtype=tight[li] or dt)
        cols.append(col)
    flat_scalars = all(shape == () for _, shape in specs)
    for d, part in enumerate(partitions):
        if not part:
            continue
        part_cols = getattr(part, "columns", None)
        if part_cols is not None and len(part_cols) == len(specs):
            # columnar parallelize: memcpy + cast, no row objects
            for li, (dt, shape) in enumerate(specs):
                cols[li][d, :counts[d]] = part_cols[li].astype(
                    dt, copy=False)
            continue
        if flat_scalars and len(specs) > 1 and isinstance(part[0], tuple) \
                and len(part[0]) == len(specs):
            # fast path: rows are flat tuples of scalars -> one 2D array
            mat = np.asarray(part)
            for li, (dt, shape) in enumerate(specs):
                cols[li][d, :counts[d]] = mat[:, li].astype(dt)
            continue
        if flat_scalars and len(specs) == 1:
            cols[0][d, :counts[d]] = np.asarray(part, dtype=specs[0][0])
            continue
        # general path: flatten rows to leaves column-wise
        leaf_lists = [[] for _ in specs]
        for rec in part:
            leaves = jax.tree_util.tree_leaves(rec)
            for li, leaf in enumerate(leaves):
                leaf_lists[li].append(leaf)
        for li, (dt, shape) in enumerate(specs):
            cols[li][d, :counts[d]] = np.asarray(leaf_lists[li], dtype=dt)
    if key_leaf is not None and cols[key_leaf].size:
        kc = cols[key_leaf]
        if np.issubdtype(kc.dtype, np.floating):
            if np.isinf(kc).any() or np.isnan(kc).any():
                raise ValueError("inf/nan float key collides with device "
                                 "padding; taking the host path")
        else:
            # sentinel check against the SPEC dtype (a narrowed i32
            # column can never hold the i64 sentinel; reuse the fit
            # scan's max instead of rescanning)
            hi = (col_stats[key_leaf][1] if key_leaf in col_stats
                  else int(kc.max()))
            if hi == int(np.iinfo(np.dtype(specs[key_leaf][0])).max):
                raise ValueError("key equal to the device sentinel; "
                                 "taking the host path")
    sharding = NamedSharding(mesh, P(AXIS))
    dev_cols = [put_sharded(c, sharding) for c in cols]
    dev_counts = put_sharded(counts, NamedSharding(mesh, P(AXIS)))
    return Batch(treedef, dev_cols, dev_counts)


@jax.jit
def _masked_minmax(c, counts):
    """(min, max) over the VALID rows of a (ndev, cap) column (padding
    content — e.g. the int64 key sentinel — must not block narrowing)."""
    valid = jnp.arange(c.shape[1])[None, :] < counts[:, None]
    lo = jnp.min(jnp.where(valid, c, jnp.iinfo(c.dtype).max))
    hi = jnp.max(jnp.where(valid, c, jnp.iinfo(c.dtype).min))
    return jnp.stack([lo, hi])


@jax.jit
def _masked_ascending(c, counts):
    """A device's row of (strictly increasing?, first, last) over the
    VALID rows of a (ndev, cap) int column: (ndev, 3) of its dtype, the
    flag 1 or 0.  With the order of the devices it shows a column
    unique without a sort (JAXExecutor._table_stats)."""
    valid = jnp.arange(c.shape[1])[None, :] < counts[:, None]
    rising = jnp.all(jnp.where(valid[:, 1:], c[:, 1:] > c[:, :-1], True),
                     axis=1)
    last = jnp.take_along_axis(
        c, jnp.maximum(counts - 1, 0)[:, None].astype(jnp.int32), axis=1)
    return jnp.stack([rising.astype(c.dtype), c[:, 0], last[:, 0]], axis=1)


@jax.jit
def _cast_i32(c):
    return c.astype(jnp.int32)


def _egest_read(c, dev_counts):
    """One column device->host, narrowed to int32 on the wire when the
    column is large and every valid value fits: D2H is the slowest
    link a collect() crosses, and an int64 result that fits int32
    moves half the bytes.  Row lists are built via
    .tolist() downstream, so the narrowed dtype is invisible to
    callers; padding may wrap in the cast — no caller reads past the
    per-device counts."""
    if (conf.NARROW_EXCHANGE and c.ndim == 2
            and c.dtype == jnp.int64
            and int(c.nbytes) >= conf.EGEST_NARROW_MIN_BYTES):
        lo, hi = host_read(_masked_minmax(c, dev_counts),
                           site="egest.minmax")
        i32 = np.iinfo(np.int32)
        if lo >= i32.min and hi <= i32.max:
            return host_read(_cast_i32(c), site="egest.col")
    return host_read(c, site="egest.col")


def egest(batch):
    """Sharded Batch -> list of per-partition row lists (host).
    Multi-controller meshes replicate through host_read, so every rank
    egests the same full result set.  With the trace plane on, one
    `egest` span: the result's readbacks (nested `readback` spans) plus
    building the Python rows."""
    plane = trace._PLANE
    if plane is not None:
        with trace.span("egest", "exec") as sp:
            out = _egest_rows(batch, sp.args)
            sp.args["rows"] = sum(len(rows) for rows in out)
            return out
    return _egest_rows(batch)


# an egest whose every partition holds at most capacity >> this many
# rows (a selective filter's result: 1 row in 1,024 or fewer) reads
# that prefix of each column, not the capacity: a sorted 1-in-4,096
# sample of 2M rows of 112 B a chip was 1.0 GB of padding across four
# chips, 7.6 s of a 7.4 s job (PR 33's chip run).  ONE class a
# capacity, so the slice programs are as many as the stage's own
_EGEST_HEAD_SHIFT = 10


def _head(c, n):
    return c[:, :n]


_head = jax.jit(_head, static_argnums=1)


def _egest_rows(batch, span_args=None):
    counts = host_read(batch.counts, site="egest.counts")
    cols = batch.cols
    head = batch.cap >> _EGEST_HEAD_SHIFT
    if head and int(counts.max(initial=0)) <= head:
        cols = [_head(c, head) for c in cols]
    total = sum(int(c.nbytes) for c in cols)
    if span_args is not None:
        span_args["bytes"] = total      # what is read: the prefix's
    if total >= conf.EGEST_WARN_BYTES:
        from dpark_tpu.utils.log import get_logger
        get_logger("layout").warning(
            "egesting %.1f MB of device results to the host, one "
            "Python row object per record — prefer reducing on "
            "device (reduceByKey/aggregate) before collect(), or "
            "saveAs* sinks", total / (1 << 20))
    host_cols = [_egest_read(c, batch.counts) for c in cols]
    # byte strings leave as one S<w> column each (bytes in the rows)
    treedef, host_cols = host_columns(batch.treedef, host_cols,
                                      rows=int(counts.sum()))
    # fast paths: scalar records, and arbitrarily-nested TUPLE records
    # (e.g. join's (k, (a, b))) rebuild with zips instead of a per-row
    # tree_unflatten
    sample = jax.tree_util.tree_unflatten(
        treedef, list(range(len(host_cols))))
    all_2d = all(c.ndim == 2 for c in host_cols)

    def _tuple_only(struct):
        if isinstance(struct, int):
            return True
        return (isinstance(struct, tuple)
                and all(_tuple_only(x) for x in struct))

    def _zip_build(struct, lists):
        if isinstance(struct, int):
            return lists[struct]
        parts = [_zip_build(x, lists) for x in struct]
        return list(zip(*parts))

    zipable = all_2d and _tuple_only(sample)
    bare_scalar = (len(host_cols) == 1 and sample == 0 and all_2d)
    out = []
    for d in range(batch.ndev):
        n = int(counts[d])
        rows = []
        if n:
            if bare_scalar:
                rows = host_cols[0][d, :n].tolist()
            elif zipable:
                rows = _zip_build(
                    sample, [c[d, :n].tolist() for c in host_cols])
            else:
                per_leaf = [c[d, :n].tolist() for c in host_cols]
                for i in range(n):
                    rows.append(jax.tree_util.tree_unflatten(
                        treedef, [pl[i] for pl in per_leaf]))
        out.append(rows)
    return out


def key_width(treedef, specs, kinds="i"):
    """Number of leading KEY COLUMNS of a ``(key, value...)`` record.

    The key is leaf 0 when it is a scalar, or leaves 0..n-1 when it is
    a FLAT tuple of n scalars (``((k1, ..., kn), v)`` — the composite
    keys real dpark jobs use: ``((user, item), v)``, ``((src, dst),
    w)``).  Every key leaf must be a scalar whose dtype kind is in
    `kinds` ("i" for hash shuffles — portable_hash semantics are only
    reproduced on device for ints — "if" for range repartitioning).
    Nested key pytrees or tuples of >conf.MAX_KEY_LEAVES columns return
    None (host fallback)."""
    from dpark_tpu import conf
    if not specs:
        return None
    sample = jax.tree_util.tree_unflatten(
        treedef, list(range(len(specs))))
    if not (isinstance(sample, tuple) and len(sample) >= 2):
        return None
    key = sample[0]
    if isinstance(key, ByteStr):
        # a fixed-width byte string: its words are the key columns
        # (as many as BYTES_WIDTH_MAX allows: record_spec's limit)
        nk = len(key.words)
        if key.words != tuple(range(nk)):
            return None
    elif isinstance(key, int) and key == 0:
        nk = 1
    elif (isinstance(key, tuple)
          and 2 <= len(key) <= conf.MAX_KEY_LEAVES
          and all(isinstance(key[i], int) and key[i] == i
                  for i in range(len(key)))):
        nk = len(key)
    else:
        return None
    for dt, shape in specs[:nk]:
        if shape != () or dt.kind not in kinds:
            return None
    return nk


def bytes_key_width(treedef, nleaves):
    """Byte width of the key when it is a ByteStr, else None."""
    if "ByteStr" not in str(treedef):
        return None
    sample = jax.tree_util.tree_unflatten(treedef, list(range(nleaves)))
    if isinstance(sample, tuple) and len(sample) >= 2 \
            and isinstance(sample[0], ByteStr):
        return sample[0].width
    return None


def key_leaf_index(treedef, specs):
    """Back-compat shim: 0 when the record has a device-hashable key
    (scalar int leaf 0 — see key_width for the composite-key form),
    else None."""
    return 0 if key_width(treedef, specs, kinds="i") == 1 else None
