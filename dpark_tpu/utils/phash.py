"""Portable, cross-process, host/device-consistent hashing.

Reference parity: dpark/portable_hash.pyx (Cython) — a deterministic hash for
str/bytes/tuple/int/None used by HashPartitioner so partition assignment is
stable across interpreter processes (SURVEY.md section 2.6 item 1).

TPU-native twist: the same integer mix (murmur3 fmix32) is implemented three
ways and cross-checked by tests/test_phash.py:

  * pure Python  (`portable_hash`)      — host path, arbitrary objects
  * jax.numpy    (`phash_device`)       — device path, int32 key columns
  * C++          (dpark_tpu/native)     — bulk host path (ctypes), optional

For an int32 key k the partition is  fmix32(u32(k) ^ u32(k >> 31)) % n  on
every path, so a shuffle planned on host lands where device code expects.

COMPOSITE (tuple) keys reuse portable_hash's own tuple recipe —
  h = 0x345678; for item: h = (h ^ hash(item)) * 0x9E3779B1; fmix32(h ^ n)
— as a columnar combine over the per-column int hashes (`phash_np_cols`
/ `phash_device_cols` / C++ `phash_i64_cols`), so a ((u, i), v) record
hash-routes to the same partition on the host object path, the jnp
device path, and the bulk C++ path bit-for-bit.
"""

import struct

_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_FNV_OFFSET = 0x811C9DC5
_FNV_PRIME = 0x01000193
_MASK = 0xFFFFFFFF
TUPLE_SEED = 0x345678
TUPLE_MULT = 0x9E3779B1
_INF = float("inf")
_NINF = float("-inf")


def fmix32(h):
    """murmur3 finalizer on a uint32 (pure Python)."""
    h &= _MASK
    h ^= h >> 16
    h = (h * _M1) & _MASK
    h ^= h >> 13
    h = (h * _M2) & _MASK
    h ^= h >> 16
    return h


def _hash_int(x):
    lo = x & _MASK
    hi = (x >> 32) & _MASK
    return fmix32(lo ^ hi)


def _hash_bytes(b):
    h = _FNV_OFFSET
    for c in b:
        h = ((h ^ c) * _FNV_PRIME) & _MASK
    return fmix32(h)


def portable_hash(obj):
    """Deterministic uint32 hash, stable across processes and Python runs."""
    if obj is None:
        return 0x7F5F
    t = type(obj)
    if t is bool:
        return _hash_int(int(obj))
    if t is int:
        return _hash_int(obj)
    if t is float:
        # NaN/inf first: int(obj) raises on them (== int(obj) crashed
        # any NaN-keyed partition before this guard)
        if obj != obj or obj == _INF or obj == _NINF:
            return _hash_bytes(struct.pack("<d", obj))
        if obj == int(obj) and abs(obj) < 2 ** 62:
            return _hash_int(int(obj))     # hash(1.0) == hash(1)
        return _hash_bytes(struct.pack("<d", obj))
    if t is str:
        return _hash_bytes(obj.encode("utf-8"))
    if t is bytes:
        return _hash_bytes(obj)
    if t is tuple:
        h = TUPLE_SEED
        for item in obj:
            h = ((h ^ portable_hash(item)) * TUPLE_MULT) & _MASK
        return fmix32(h ^ len(obj))
    # subclasses and numpy scalars hash AS THEIR VALUE: dict/partition
    # semantics treat np.str_('w') == 'w' and np.int64(3) == 3 as the
    # same key, so the partitioner must agree — the exact-type pickle
    # fallback silently routed equal keys to different partitions
    # (found by the query parity fuzzer joining a tabular string
    # column against parallelize'd python strs)
    if isinstance(obj, str):
        return _hash_bytes(obj.encode("utf-8"))
    if isinstance(obj, bytes):
        return _hash_bytes(bytes(obj))
    if isinstance(obj, bool):
        return _hash_int(int(obj))
    if isinstance(obj, int):
        return _hash_int(int(obj))
    try:
        import numpy as _np
        if isinstance(obj, _np.bool_):
            return _hash_int(int(obj))
        if isinstance(obj, _np.integer):
            return _hash_int(int(obj))
        if isinstance(obj, _np.floating):
            return portable_hash(float(obj))
    except ImportError:
        pass
    if isinstance(obj, float):
        return portable_hash(float(obj))
    # fallback: structural hash via pickled bytes (deterministic for the
    # value types that reach partitioners in practice)
    import pickle
    return _hash_bytes(pickle.dumps(obj, 4))


def phash_np(keys):
    """NumPy twin of phash_device: bulk host-side hashing of an int array
    -> uint32 array, bit-identical to portable_hash/phash_device.  Used
    for host-side vertex partitioning (device Bagel setup) so state lands
    on the device that hash-routed messages will reach."""
    import numpy as np
    keys = np.asarray(keys)
    if keys.dtype == np.int64:
        lo = (keys & np.int64(0xFFFFFFFF)).astype(np.uint32)
        hi = ((keys >> 32) & np.int64(0xFFFFFFFF)).astype(np.uint32)
    else:
        k = keys.astype(np.int32)
        lo = k.astype(np.uint32)
        hi = (k >> 31).astype(np.uint32)       # 0 or 0xFFFFFFFF
    h = lo ^ hi
    h ^= h >> 16
    h = h * np.uint32(_M1)
    h ^= h >> 13
    h = h * np.uint32(_M2)
    h ^= h >> 16
    return h


def phash_device(keys):
    """Device-side portable hash of an int array -> uint32 array.

    Bit-exactly matches `portable_hash` for any int64 value: the host path
    computes lo = x & 0xFFFFFFFF, hi = (x >> 32) & 0xFFFFFFFF, and
    fmix32(lo ^ hi).  For int32 inputs the hi word is the sign extension,
    reproduced with an arithmetic shift.  Host/device agreement is what
    makes partition assignment identical across masters (lookup,
    partitionBy co-location).
    """
    import jax.numpy as jnp
    if keys.dtype == jnp.int64:
        lo = (keys & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = ((keys >> 32) & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32)
    else:
        k = keys.astype(jnp.int32)
        lo = k.astype(jnp.uint32)
        hi = (k >> 31).astype(jnp.uint32)      # 0 or 0xFFFFFFFF
    h = lo ^ hi
    h ^= h >> 16
    h = h * jnp.uint32(_M1)
    h ^= h >> 13
    h = h * jnp.uint32(_M2)
    h ^= h >> 16
    return h


def _fmix32_np(h):
    import numpy as np
    h = h.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h = h * np.uint32(_M1)
    h ^= h >> np.uint32(13)
    h = h * np.uint32(_M2)
    h ^= h >> np.uint32(16)
    return h


def phash_np_cols(cols):
    """Composite (tuple-key) hash of N int column arrays -> uint32
    array, bit-identical to ``portable_hash((k1, ..., kn))`` per row
    when every element is a Python int.  The per-column hash is the
    scalar `phash_np`; columns combine with the tuple recipe."""
    import numpy as np
    cols = list(cols)
    if len(cols) == 1:
        return phash_np(cols[0])
    h = np.full(np.asarray(cols[0]).shape, TUPLE_SEED, np.uint32)
    for c in cols:
        h = (h ^ phash_np(c)) * np.uint32(TUPLE_MULT)
    return _fmix32_np(h ^ np.uint32(len(cols)))


def phash_device_cols(cols):
    """Device twin of phash_np_cols: composite hash over int key
    COLUMNS, matching portable_hash(tuple) bit-for-bit — multi-column
    shuffle destinations agree across the pure-Python host partitioner,
    the jnp exchange, and the C++ bulk path (phash_i64_cols)."""
    import jax.numpy as jnp
    cols = list(cols)
    if len(cols) == 1:
        return phash_device(cols[0])
    h = jnp.full(cols[0].shape, TUPLE_SEED, jnp.uint32)
    for c in cols:
        h = (h ^ phash_device(c)) * jnp.uint32(TUPLE_MULT)
    h = h ^ jnp.uint32(len(cols))
    h ^= h >> 16
    h = h * jnp.uint32(_M1)
    h ^= h >> 13
    h = h * jnp.uint32(_M2)
    h ^= h >> 16
    return h


def phash_device_bytes(word_cols, width):
    """Device twin of portable_hash(bytes) for a fixed-width byte-string
    key held as big-endian int64 WORD columns (backend/tpu/layout.py
    ByteStr): FNV-1a over the bytes up to the last non-NUL one (the
    host hashes the NUL-stripped `bytes` object), then fmix32 —
    bit-identical, so byte-keyed shuffles land where the host
    HashPartitioner (lookup, co-partitioned joins) expects."""
    import jax.numpy as jnp
    octets = [((word_cols[i // 8] >> (8 * (7 - i % 8))) & 0xFF)
              .astype(jnp.uint32) for i in range(width)]
    length = jnp.zeros(word_cols[0].shape, jnp.int32)
    for i, b in enumerate(octets):
        length = jnp.where(b != 0, i + 1, length)
    h = jnp.full(word_cols[0].shape, _FNV_OFFSET, jnp.uint32)
    for i, b in enumerate(octets):
        h = jnp.where(i < length, (h ^ b) * jnp.uint32(_FNV_PRIME), h)
    h ^= h >> 16
    h = h * jnp.uint32(_M1)
    h ^= h >> 13
    h = h * jnp.uint32(_M2)
    h ^= h >> 16
    return h
