"""Device-side shuffle primitives: bucketize, exchange, segmented reduce.

This is the TPU-native replacement for the reference's shuffle data plane
(dpark/shuffle.py write/fetch/merge + dpark/task.py ShuffleMapTask bucket
loop, SURVEY.md section 3.1 hot loops #2/#3):

  host hash+dict-combine  ->  phash_device + sort by destination
  bucket files + HTTP     ->  lax.all_to_all over ICI, count-exchange first
  dict/heap merge         ->  sort by key + segmented associative reduce

All functions here operate on ONE device's block inside shard_map (leading
mesh dim already squeezed).  Raggedness is handled with padded slots and a
multi-round overflow loop (the "external merge" equivalent, SURVEY.md 5.7):
each round every device sends at most `slot` records per destination; the
psum'd overflow tells the host loop whether another round is needed.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from dpark_tpu.utils.phash import (
    phash_device, phash_device_bytes, phash_device_cols)

def _sentinel(dtype):
    """Max value of the key dtype — padding rows sort last.  ingest()
    rejects int keys equal to this value (host fallback); float keys use
    +inf (real +inf keys are a documented range-sort limitation)."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def hash_dst(key, n_dst, valid, r=None):
    """Destination partition by portable hash (HashPartitioner).

    `r` is the logical partition count (<= n_dst, the mesh size): dst in
    [0, r), padding rows get the sentinel bucket n_dst; devices >= r
    simply receive nothing."""
    r = n_dst if r is None else r
    dst = (phash_device(key) % jnp.uint32(r)).astype(jnp.int32)
    return jnp.where(valid, dst, n_dst)


def hash_dst_cols(key_cols, n_dst, valid, r=None, bytes_width=None):
    """hash_dst over a COMPOSITE key (one or more key columns): the
    destination is the pair-extended portable hash over all columns —
    bit-identical to host HashPartitioner.get_partition((k1, ..., kn))
    — so tuple-keyed shuffles land where the host partitioner (lookup,
    co-partitioned joins) expects.  With `bytes_width` the columns are
    the big-endian words of ONE fixed-width byte-string key
    (layout.ByteStr) and the hash is the host's own of the `bytes`
    object: rows land where get_partition(b"...") says."""
    r = n_dst if r is None else r
    if bytes_width is not None:
        h = phash_device_bytes(list(key_cols), bytes_width)
    else:
        h = phash_device_cols(list(key_cols))
    dst = (h % jnp.uint32(r)).astype(jnp.int32)
    return jnp.where(valid, dst, n_dst)


def key_hash64(key_cols, valid):
    """One int64 word for a key of several int64 columns (the words of
    a byte-string key): a multiply-xorshift chain over the columns,
    below the padding sentinel on valid rows and equal to it on the
    rest.  Equal keys hash alike; keys that differ may too, so whoever
    matches by it compares the columns afterwards (the device join)."""
    h = jnp.full(key_cols[0].shape, 0x9E3779B97F4A7C15, jnp.uint64)
    for c in key_cols:
        h = (h ^ c.astype(jnp.uint64)) * jnp.uint64(0xBF58476D1CE4E5B9)
        h = h ^ (h >> 32)
    h = h * jnp.uint64(0x94D049BB133111EB)
    h = (h ^ (h >> 29)) >> 1
    sent = _sentinel(jnp.int64)
    return jnp.where(valid, jnp.minimum(h.astype(jnp.int64), sent - 1),
                     sent)


def range_dst(key, bounds, ascending, n_dst, valid, r=None):
    """Destination partition by sorted bounds (RangePartitioner): the
    device twin of host bisect_left over the sampled bounds."""
    r = n_dst if r is None else r
    idx = jnp.searchsorted(bounds, key, side="left").astype(jnp.int32)
    dst = idx if ascending else (r - 1 - idx)
    return jnp.where(valid, dst, n_dst)


def _lex_less_cols(a_cols, b_cols):
    """Row-wise lexicographic a < b over parallel column lists (the
    device twin of Python tuple comparison)."""
    lt = a_cols[0] < b_cols[0]
    eq = a_cols[0] == b_cols[0]
    for a, b in zip(a_cols[1:], b_cols[1:]):
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return lt


def lex_searchsorted(sorted_cols, query_cols, side="left"):
    """Multi-column searchsorted: for each query row (one value per
    column), the insertion index into rows of `sorted_cols` (sorted
    lexicographically ascending).  jnp.searchsorted has no multi-key
    form, so this runs a vectorized binary search — ceil(log2(m+1))
    fixed steps of a row-wise lexicographic compare; every query
    resolves in one fused program, no per-row host work."""
    m = int(sorted_cols[0].shape[0])
    nq = query_cols[0].shape[0]
    lo = jnp.zeros((nq,), jnp.int32)
    hi = jnp.full((nq,), m, jnp.int32)
    for _ in range(max(1, m.bit_length())):
        active = lo < hi
        mid = (lo + hi) >> 1
        safe = jnp.clip(mid, 0, max(m - 1, 0))
        mid_cols = [c[safe] for c in sorted_cols]
        if side == "left":
            pred = _lex_less_cols(mid_cols, query_cols)
        else:
            pred = ~_lex_less_cols(query_cols, mid_cols)
        lo = jnp.where(active & pred, mid + 1, lo)
        hi = jnp.where(active & ~pred, mid, hi)
    return lo


# flipping a word's top bit turns its unsigned order into the signed one
_TOP_BIT = np.int64(-2 ** 63)


def bytes_order(words):
    """The ordering view of a byte-string key's words (layout.ByteStr:
    big-endian int64 words, word 0 already the sentinel on padding
    rows): int64 columns whose SIGNED lexicographic order is memcmp's
    over the bytes, unsigned, and in which the sentinel is still the
    largest word 0, so padding sorts last.  A word's unsigned order is
    the signed order of the word with its top bit flipped; word 0
    steps over the sentinel's place (bytes 7f ff ff ff ff ff ff ff,
    which ingest keeps out of every valid key): the words above it
    move down one."""
    w0 = words[0]
    v0 = jnp.where(w0 == _sentinel(w0.dtype), w0,
                   (w0 ^ _TOP_BIT) - (w0 < 0).astype(w0.dtype))
    return [v0] + [w ^ _TOP_BIT for w in words[1:]]


def sort_by_key(cols, nk, unsigned=False):
    """_lex_sort of rows by their first nk columns (column 0 carries
    the sentinel on padding rows, which sort last); `unsigned` when
    those columns are the words of ONE byte-string key, which orders
    as its bytes do.

    The byte-string form sorts a word at a time, least significant
    first: a stable sort of (the word's ordering view, the running
    permutation) a word, the next word gathered through the
    permutation, and the rows behind the last one as whole rows.  It
    buys compile time with run time: at 2,359,296 rows of a two-word
    key the permutation alone takes 24.6 ms and compiles in 51 s on
    the chip's host, ONE sort with both 64-bit words as keys 11.5 ms
    and 103 s (PR 33's chip run; 337 s against 120 s for the whole
    reduce program on this PR's sandbox, six compiles at a time); a
    word more costs this form a 3-word sort and a column gather.  Its
    callers are the two places that need the ORDER (SortOp, and the
    no-combine reduce when SortOp takes its order); where equal keys
    only have to be adjacent the signed words do."""
    cols = list(cols)
    if not unsigned:
        return list(_lex_sort(tuple(cols), nk))
    order = lax.iota(jnp.int32, cols[0].shape[0])
    for i, v in enumerate(reversed(bytes_order(cols[:nk]))):
        if i:
            (v,) = take_rows([v], order)
        _, order = lax.sort((v, order), num_keys=1, is_stable=True)
    return gather_rows(cols, order)


def range_dst_cols(key_cols, bounds_cols, ascending, n_dst, valid,
                   r=None, unsigned=False):
    """range_dst over a COMPOSITE key: bisect_left of each (k1, ..., kn)
    row into the sampled tuple bounds, compared lexicographically —
    exactly host RangePartitioner.get_partition on tuple keys.  With
    `unsigned` the columns are the words of ONE byte-string key and of
    `bytes` bounds, compared as bytes compare.

    The bounds are sorted, so a row's place among them (bisect_left) is
    the number of bounds below it: a compare and a sum a bound while
    the loop is short (_bucket_counts' other form), the binary search
    of gathers past that."""
    key_cols = list(key_cols)
    if len(key_cols) == 1 and bounds_cols[0].ndim <= 1 and not unsigned:
        return range_dst(key_cols[0], bounds_cols[0], ascending, n_dst,
                         valid, r=r)
    r = n_dst if r is None else r
    bounds_cols = list(bounds_cols)
    if unsigned:
        key_cols = [c ^ _TOP_BIT for c in key_cols]
        bounds_cols = [c ^ _TOP_BIT for c in bounds_cols]
    nb = int(bounds_cols[0].shape[0])
    if nb <= _DST_LOOP_MAX:
        idx = jnp.zeros(key_cols[0].shape, jnp.int32)
        for b in range(nb):
            idx = idx + _lex_less_cols(
                [c[b] for c in bounds_cols], key_cols).astype(jnp.int32)
    else:
        idx = lex_searchsorted(bounds_cols, key_cols,
                               side="left").astype(jnp.int32)
    dst = idx if ascending else (r - 1 - idx)
    return jnp.where(valid, dst, n_dst)


def _lex_sort(ops, num_keys):
    """Stable lexicographic sort of `ops` by its first num_keys operands
    (rank 1, most significant first); every other operand moves with
    its row.

    ONE multi-operand lax.sort carries the rows.  XLA's sort takes only
    operands of the keys' shape, so a leaf of rank > 1 is gathered
    through an i32 iota that rides the same sort; records of scalar
    leaves lower no gather at all.

    Timed alone on the v5e against the form this replaced (a 2-operand
    (key, iota) sort per key column, the permutations composed, every
    operand gathered through the result), PR 29's chip runs at 2M rows:
    the sort carrying 2 / 4 / 6 / 8 32-bit words (an int64 column is
    two) 5.1 / 7.3-7.8 / 9.9-10.3 / 13.0-13.1 ms, the gathers 50-77 /
    84-148 / 117-258 / 150-356; the map-side combine's order sort
    (dst, key | value, int64) 9.0 against 133.8.  1.3 ms a word,
    linear, int32 and int64 leading key alike, so the payloads ride in
    one group.  The compiler pays instead: 16-25 s for the 2-word sort
    and about 10 s more for every further word, at 65,536 rows as at
    2M (the replaced form: 8-37 s)."""
    ops = tuple(ops)
    if num_keys == 0:           # no key: nothing to sort by
        return ops
    rank1 = [o for o in ops if o.ndim == 1]
    # a wide row: only the keys ride the sort, the rest of the row
    # follows behind the iota as whole rows, however few its words (a
    # gather costs a column what it costs a row of 16 words)
    rows = sum(_words(o) for o in rank1) > _CARRIED_WORDS
    behind = rows or any(o.ndim > 1 for o in ops)
    carried = rank1[:num_keys] if rows else rank1
    if behind:
        carried = carried + [lax.iota(jnp.int32, ops[0].shape[0])]
    carried = lax.sort(carried, num_keys=num_keys, is_stable=True)
    if not behind:
        return tuple(carried)
    *flat, order = carried
    if rows:
        flat += _take_whole_rows(rank1[num_keys:], order)
    flat = iter(flat)
    return tuple(o[order] if o.ndim > 1 else next(flat) for o in ops)


# 32-bit words that one lax.sort carries as operands of their own, or
# that are gathered a column at a time.  Every sort and join of the
# benchmark's int-pair and two-word-key cells stays under it (7 at
# most); past it a row moves as rows of one u32[n, words] array: the
# TPU's compiler takes about 10 s for every word a sort carries (a
# 100-byte string is 26), and a gather costs a ROW what it costs a word
_CARRIED_WORDS = 8
# words of a row that one gather moves: on the v5e a gather of rows of
# u32[n, 31] takes 39.7 ms at 1M rows, of int64[n, 15] (two planes of
# 15 words) 14.7, of u32[n, 10] under 8 (PR 31's chip table)
_ROW_WORDS = 16


def _words(col):
    return max(1, col.dtype.itemsize // 4)


def _stack_rows(cols):
    """Rank-1 columns -> u32[n, words] (an 8-byte column is two)."""
    parts = []
    for c in cols:
        if c.dtype.itemsize == 8:
            parts.append(lax.bitcast_convert_type(c, jnp.uint32))
        elif c.dtype.itemsize == 4:
            parts.append(lax.bitcast_convert_type(c, jnp.uint32)[:, None])
        else:
            parts.append(c.astype(jnp.uint32)[:, None])
    return jnp.concatenate(parts, axis=1)


def _unstack_rows(mat, like):
    out, at = [], 0
    for c in like:
        if c.dtype.itemsize == 8:
            out.append(lax.bitcast_convert_type(mat[:, at:at + 2], c.dtype))
        elif c.dtype.itemsize == 4:
            out.append(lax.bitcast_convert_type(mat[:, at], c.dtype))
        else:
            out.append(mat[:, at].astype(c.dtype))
        at += _words(c)
    return out


def take_rows(cols, idx):
    """Rows `idx` of parallel columns (an index may repeat: the join's
    expansion), as whole rows: one gather moves 16 words for what one
    word costs, and an int64 column alone is two (its planes).  A
    gather a column only for a few columns that are not whole words
    (narrower than 4 bytes, or of rank > 1)."""
    cols = list(cols)
    if sum(_words(c) for c in cols) <= _CARRIED_WORDS and not all(
            c.ndim == 1 and c.dtype.itemsize >= 4 for c in cols):
        return [c[idx] for c in cols]
    return _take_whole_rows(cols, idx)


def gather_rows(cols, idx):
    """take_rows for records that may hold leaves of rank > 1: those
    are gathered a leaf at a time, the rank-1 columns as whole rows."""
    moved = iter(take_rows([c for c in cols if c.ndim == 1], idx))
    return [next(moved) if c.ndim == 1 else c[idx] for c in cols]


def _take_whole_rows(cols, idx):
    """take_rows through one u32[n, words] array, _ROW_WORDS words a
    gather."""
    if not cols:
        return []
    mat = _stack_rows(cols)
    return _unstack_rows(jnp.concatenate(
        [mat[:, at:at + _ROW_WORDS][idx]
         for at in range(0, mat.shape[1], _ROW_WORDS)], axis=1), cols)


def _bcast(flag, leaf):
    """Broadcast a (n,) bool against a (n, ...) leaf."""
    extra = leaf.ndim - flag.ndim
    return flag.reshape(flag.shape + (1,) * extra)


def compact(leaves, mask):
    """Move rows where mask is True to the front (stable: one _lex_sort
    by the inverted mask, the leaves riding it); returns (leaves,
    new_count)."""
    sorted_ops = _lex_sort((~mask,) + tuple(leaves), 1)
    return list(sorted_ops[1:]), jnp.sum(mask).astype(jnp.int32)


def join_ranges(a_cols, b_cols, a, b):
    """The device join's matching: for every row of side A its range
    of equal keys in side B, as (lo, per): B rows lo .. lo + per - 1
    (numpy.searchsorted(B, A, "left") and right - left; per is 0 past
    A's `a` valid rows).  Both sides are key-sorted over their valid
    prefix, any number of key columns.  Only key column 0 takes the
    sentinel: invalid rows sort last on it, and no valid key carries it.

    ONE stable _lex_sort of B's keys followed by A's merges the sides:
    rows of B come before the rows of A they equal, so at a row of A
    every B row counted so far is <= its key, and those counted before
    its run of equal keys began are < it.  A's rows keep their order
    through a stable merge, so compact's pack sort brings the ranges
    back to A's rows: no search, no scatter, no inverse permutation.

    Alone on the v5e (PR 32's chip table), 262,144 int64 rows in
    16,384 / 1,048,576 in 1,048,576: 2.2 / 9.8 ms, where
    jnp.searchsorted left and right take 114.8 / 1,503.9 by their
    default binary search and 6.8 / 38.8 by method="sort" (two
    argsorts and two permutation scatters a call)."""
    a_cols, b_cols = list(a_cols), list(b_cols)
    cap_a, cap_b = a_cols[0].shape[0], b_cols[0].shape[0]
    sent = _sentinel(a_cols[0].dtype)
    a_cols[0] = jnp.where(jnp.arange(cap_a) < a, a_cols[0], sent)
    b_cols[0] = jnp.where(jnp.arange(cap_b) < b, b_cols[0], sent)
    keys = [jnp.concatenate([y, x]) for x, y in zip(a_cols, b_cols)]
    *keys, of_a = _lex_sort(
        keys + [jnp.arange(cap_b + cap_a) >= cap_b], len(keys))
    seen_b = jnp.cumsum(~of_a, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), _changed_adjacent(keys)])
    lo = _segment_first(starts, seen_b - ~of_a)
    (lo, per), _ = compact([lo, seen_b - lo], of_a)
    return lo[:cap_a], jnp.where(jnp.arange(cap_a) < a, per[:cap_a], 0)


def join_slots(lo, per, cap_out, cap_b):
    """The device join's expansion: for every output slot t its row of
    A and its row of B, numpy.repeat(arange(cap_a), per) and lo[i] + t
    - offs[i] (offs: the exclusive prefix sum of per), from the ranges
    join_ranges found; slots past sum(per) hold rows in range and
    nothing else.

    By the same merge: A's offsets, then the slots, through one stable
    _lex_sort.  A slot follows the rows of A that begin at or before it,
    so its position says how many they are, and the last of them is
    its row (a row of no match shares its offset with the next and
    sorts before it); what it needs of that row (lo - offs) rides the
    sort and reaches it along the sorted order, with no gather.

    Alone on the v5e (PR 32's chip table), 131,072 slots over 262,144
    rows / 1,048,576 over 1,048,576: 2.3 / 9.5 ms; a binary search of
    the slots in the offsets and two gathers 38.7 / 675.1, the search
    by method="sort" and one stacked gather 6.0 / 28.6, this merge
    with a gather of lo - offs in place of the carried word 3.1 /
    15.5."""
    cap_a = per.shape[0]
    offs = jnp.cumsum(per) - per
    slots = jnp.arange(cap_out, dtype=offs.dtype)
    at, of_a, shift = _lex_sort(
        (jnp.concatenate([offs, slots]),
         jnp.arange(cap_a + cap_out) < cap_a,
         jnp.concatenate([lo - offs, jnp.zeros_like(slots)])), 1)
    shift = _segment_first(of_a, shift)
    i = jnp.arange(cap_a + cap_out, dtype=at.dtype) - at - 1
    (i, bi), _ = compact([i, shift + at], ~of_a)
    return i[:cap_out], jnp.clip(bi[:cap_out], 0, cap_b - 1)


_DST_LOOP_MAX = 16      # destinations a per-bucket Python loop serves


def _bucket_counts(d, nb):
    """Rows of `d` in each bucket 0..nb-1, with no scatter: a compare
    and a sum per bucket while the loop is short, boundaries of the
    column (which must then be SORTED) past that.  jnp.bincount is a
    scatter-add of ones, which the v5e runs a row at a time: 127 ms
    for 2M rows into three bins, where these take 0.6 (PR 27's chip
    runs; ledger PR 26, `fusion.14 u32[n_dst + 1]`: 125 ms a job)."""
    if nb <= _DST_LOOP_MAX + 1:             # + the sentinel bucket
        return jnp.stack([jnp.sum(d == b, dtype=jnp.int32)
                          for b in range(nb)])
    edges = jnp.searchsorted(d, jnp.arange(nb + 1, dtype=d.dtype))
    return jnp.diff(edges).astype(jnp.int32)


def bucketize(key, leaves, valid, n_dst, dst=None, r=None):
    """Sort one device's rows by destination partition: the stable
    _lex_sort by `dst` alone, the leaves riding it.

    Returns (sorted_leaves, counts[n_dst], offsets[n_dst]).  Invalid rows
    (`valid` False: past the count, or failed a filter folded into the
    write) sort into a sentinel bucket past the end.
    """
    if dst is None:
        dst = hash_dst(key, n_dst, valid, r)
    sorted_ops = _lex_sort((dst,) + tuple(leaves), 1)
    counts = _bucket_counts(sorted_ops[0], n_dst + 1)[:n_dst]
    return list(sorted_ops[1:]), counts, jnp.cumsum(counts) - counts


def bucketize_by_key(leaves, valid, nk):
    """bucketize on ONE device, followed by what the no-combine reader
    does with the store: its rows key-sorted, valid first.  Every valid
    row's destination is 0, so sorting by it only packs; the reader then
    sorts the packed rows by their nk key columns with column 0 the
    sentinel past the count.  ONE stable _lex_sort of the same operands
    by the keys, column 0 the sentinel where `valid` is False, leaves
    the same valid prefix: the valid rows, stably by key, in input
    order, and padding after them.  It adds no operand, so a record of
    8 words stays carried.

    A valid row whose column 0 equals the sentinel (a hash write's keys
    are integers: the dtype's maximum) would sort among the padding:
    where one exists, lax.cond takes the two sorts instead, dst pack
    then key sort, bit for bit what the store and its reader gave.

    Returns (sorted_leaves, counts[1], offsets[1]) as bucketize does."""
    leaves = list(leaves)
    k0 = leaves[0]
    sent = _sentinel(k0.dtype)

    def by_key():
        return list(_lex_sort(
            tuple([jnp.where(valid, k0, sent)] + leaves[1:]), nk))

    def by_dst():
        packed, counts, _ = bucketize(
            k0, leaves, valid, 1, dst=(~valid).astype(jnp.int32))
        packed[0] = jnp.where(jnp.arange(k0.shape[0]) < counts[0],
                              packed[0], sent)
        return sort_by_key(packed, nk)

    out = lax.cond(jnp.any(valid & (k0 == sent)), by_dst, by_key)
    counts = jnp.sum(valid, dtype=jnp.int32).reshape(1)
    return out, counts, jnp.zeros((1,), jnp.int32)


def exchange_round(axis, leaves, offsets, counts, sent, slot,
                   narrow=None):
    """One all_to_all round: send up to `slot` records to each destination.

    leaves: destination-sorted rows (cap, ...); offsets/counts/sent: (R,).
    `narrow`: optional per-leaf wire dtype (or None) — leaves proven to
    fit ride the collective narrowed (e.g. int64 -> int32: TPUs have no
    native 64-bit integer datapath, XLA emulates i64 as i32 pairs, so an
    i64 exchange moves 2x the ICI bytes; the executor's runtime min/max
    guard decides per exchange).  The cast happens right around the
    collective — callers always see the original dtypes.
    Returns (recv_leaves (R, slot, ...), recv_cnt (R,), new_sent,
    overflow_scalar) where overflow is the psum of still-unsent records
    across all devices — 0 means the exchange is complete.

    A SEND BLOCK is one destination's next `slot` rows.  bucketize has
    sorted the leaves by destination, so the rows still owed to
    destination r are ONE contiguous run that starts at offsets[r] +
    sent[r]: the block is a slice on axis 0 (a leaf of any rank), its
    rows past sendable[r] zeroed, and the R blocks stack to (R, slot,
    ...).  No index a row is computed and nothing is gathered.

    THE CLAMP: lax.dynamic_slice moves a START whose slice would run
    past the end back until it fits, and would hand over rows that
    begin earlier.  offsets + counts <= cap says only that the
    SENDABLE rows exist, not the whole block (the last destination's
    block nearly always runs past cap, and any block may when slot >=
    cap), so the leaf is padded by `slot` rows behind and no start ever
    clamps.  The compiler fuses the pad into the slices (one pass a
    32-bit plane; no copy, nothing for a donated leaf to lose).

    Alone on the v5e (PR 34's chip table; cap 2,097,152, R 4, slot
    557,056 / 589,824, ms a call with the host's dispatch; the compile
    beside it): one int64 leaf 0.73 / 0.70 (2-4 s), one int32 leaf
    0.69 / 0.76 (1 s), fourteen int64 leaves (a gensort record) 3.4 /
    3.7 (1 s); a gather of every slot row (leaf[offsets[:, None] +
    sent[:, None] + arange(slot)], what this replaced) 67.3 / 71.2,
    16.7 / 17.9 and 449 / 513; the slices under vmap (a while loop
    over R with a materialised pad) 1.0 / 0.9, 0.75 / 0.79 and 7.4 /
    7.7; the leaves' words stacked to u32[cap, W] and gathered as
    whole rows 10.8 / 11.3, 16.8 / 17.8 and 130 / 143 (11-17 s).
    """
    n_dst = counts.shape[0]
    sendable = jnp.minimum(counts - sent, slot).astype(jnp.int32)
    start = offsets + sent
    mask = jnp.arange(slot)[None, :] < sendable[:, None]       # (R, slot)
    send = []
    for li, leaf in enumerate(leaves):
        padded = jnp.concatenate(
            [leaf, jnp.zeros((slot,) + leaf.shape[1:], leaf.dtype)])
        g = jnp.stack([lax.dynamic_slice_in_dim(padded, start[r], slot)
                       for r in range(n_dst)])                 # (R, slot, ..)
        g = jnp.where(_bcast(mask, g), g, jnp.zeros((), g.dtype))
        if narrow is not None and narrow[li] is not None:
            g = g.astype(narrow[li])
        send.append(g)
    recv = _grouped_all_to_all(send, axis)
    for li, leaf in enumerate(leaves):
        if narrow is not None and narrow[li] is not None:
            recv[li] = recv[li].astype(leaf.dtype)
    recv_cnt = lax.all_to_all(sendable, axis, 0, 0, tiled=True)
    new_sent = sent + sendable
    overflow = lax.psum(jnp.sum(counts - new_sent), axis)
    return recv, recv_cnt, new_sent, overflow


def _grouped_all_to_all(buffers, axis):
    """Exchange the per-destination buffers with as few collectives as
    possible: scalar leaves of the same dtype stack into one all_to_all
    (one ICI launch instead of one per column)."""
    groups = {}
    for i, g in enumerate(buffers):
        key = (str(g.dtype), g.shape) if g.ndim == 2 else ("solo%d" % i,)
        groups.setdefault(key, []).append(i)
    out = [None] * len(buffers)
    for key, idxs in groups.items():
        if len(idxs) == 1 or key[0].startswith("solo"):
            for i in idxs:
                out[i] = lax.all_to_all(buffers[i], axis, 0, 0, tiled=True)
            continue
        packed = jnp.stack([buffers[i] for i in idxs], axis=-1)
        exchanged = lax.all_to_all(packed, axis, 0, 0, tiled=True)
        for pos, i in enumerate(idxs):
            out[i] = exchanged[..., pos]
    return out


def flatten_received(recv_rounds, cnt_rounds, key_index=0):
    """Concatenate per-round receive buffers (lists of (R, slot, ...)) into
    flat row arrays with a validity mask; invalid keys get the sentinel.

    Returns (leaves, valid_mask) with leading dim rounds*R*slot.
    """
    nleaves = len(recv_rounds[0])
    flat = []
    for li in range(nleaves):
        parts = [r[li].reshape((-1,) + r[li].shape[2:]) for r in recv_rounds]
        flat.append(jnp.concatenate(parts, axis=0))
    # rebuild validity masks per round from the exchanged counts
    masks = []
    for r, cnt in zip(recv_rounds, cnt_rounds):
        slot = r[0].shape[1]
        j = jnp.arange(slot)
        m = (j[None, :] < cnt[:, None]).reshape(-1)
        masks.append(m)
    mask = jnp.concatenate(masks, axis=0)
    flat[key_index] = jnp.where(
        mask, flat[key_index], _sentinel(flat[key_index].dtype))
    return flat, mask


def _segment_first(starts, col):
    """Every row's value of `col` at the row that starts its segment."""
    return segmented_combine(starts, [col], lambda first, later: first)[0]


def segmented_combine(starts, val_leaves, merge_leaves):
    """Inclusive segmented scan: scanned[i] = reduction of values from the
    segment start through i.  starts: (m,) bool segment-start flags,
    starts[0] set.  log2(m) shift-and-merge steps, each an elementwise
    pass over contiguous slices (rows nearer the front than a step's
    distance are flagged by then, so what the shift wraps in is never
    merged).  lax.associative_scan's strided halving took the v5e's
    compiler 235-250 s for one 2M-row scan and ran in 4.9-7.6 ms; these
    steps compile in 2 s and run in 0.9-1.3 ms (PR 27's chip runs)."""
    vs, flags, d = list(val_leaves), starts, 1

    def shift(x):
        return jnp.concatenate([x[:d], x[:-d]])

    while d < starts.shape[0]:
        merged = merge_leaves([shift(v) for v in vs], vs)
        vs = [jnp.where(_bcast(flags, v), v, mg)
              for v, mg in zip(vs, merged)]
        flags = flags | shift(flags)
        d *= 2
    return vs


def bucketize_combine(key, val_leaves, n, n_dst, merge_leaves,
                      monoid=None, dst=None, r=None):
    """Map-side pre-combine (the classic combiner optimization): sort one
    device's rows by (destination, key), merge equal keys within each
    destination run, compact.  Cuts exchange volume to O(#distinct keys per
    device per destination) — decisive for low-cardinality reduceByKey.

    Returns (key', val_leaves', counts[n_dst], offsets[n_dst]) where rows
    are destination-sorted and combined.
    """
    ks, vv, counts, offsets = bucketize_combine_keys(
        [key], val_leaves, jnp.arange(key.shape[0]) < n, n_dst,
        merge_leaves, monoid=monoid, dst=dst, r=r)
    return ks[0], vv, counts, offsets


def bucketize_combine_keys(key_cols, val_leaves, valid, n_dst,
                           merge_leaves, monoid=None, dst=None, r=None,
                           out_cap=None):
    """bucketize_combine over a COMPOSITE key: sort one device's rows by
    (destination, k1, ..., kn), merge rows equal in EVERY key column,
    compact.  Returns (key_cols', vals', counts, offsets).  Only key
    column 0 carries the sentinel on invalid rows (`valid` False) —
    invalid rows sort into the sentinel bucket and are dropped by the
    keep mask, so the other columns never need guarding.  `out_cap`:
    _bucketize_combine_cols' proven bound on the rows the combine
    keeps."""
    key_cols = list(key_cols)
    if dst is None:
        dst = hash_dst_cols(key_cols, n_dst, valid, r)
    ks = [jnp.where(valid, key_cols[0], _sentinel(key_cols[0].dtype))]
    ks += key_cols[1:]
    # composite keys across devices: one hash column to order by instead
    # of n key columns (the reduce side re-sorts by the true key columns;
    # see _bucketize_combine_cols on why adjacency is sufficient there).
    # With ONE destination there is no reduce side to mend a group that
    # a hash collision split: the true key columns order the rows, the
    # combine is exact, and the executor registers the store pre_reduced.
    # A write packed to a bound orders by the true columns too: a split
    # group would be a kept row the bound does not count
    order_col = (phash_device_cols(key_cols)
                 if len(key_cols) > 1 and n_dst > 1 and out_cap is None
                 else None)
    return _bucketize_combine_cols(dst, ks, val_leaves, n_dst,
                                   merge_leaves, monoid,
                                   order_col=order_col, out_cap=out_cap)


def _changed_adjacent(cols):
    """(m-1,) bool: any of the key columns differs from its neighbor."""
    changed = cols[0][1:] != cols[0][:-1]
    for c in cols[1:]:
        changed = changed | (c[1:] != c[:-1])
    return changed


_MONOID_OPS = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum,
               "mul": jnp.multiply}


def _segment_merge(key_cols, val_leaves, keep_valid, merge_leaves,
                   monoid):
    """Shared segment-combine core over rows sorted by `key_cols`:
    merge values of adjacent rows equal in ALL key columns, keeping the
    LAST row of each segment (keep_valid(row_flags) restricts which
    rows qualify), where a scan along the sorted rows leaves the
    segment's total: of the monoid's own operation if the merge is
    classified, else of the traced user function.  No scatter and no
    gather: the segment_sum + gather-back this replaces ("one scatter
    instead of the log-n scan", a choice made on XLA:CPU) cost the v5e
    68 ns a row inside the stage programs (ledger PR 26) and 207 ms
    alone for 2M int64 rows, where the scan takes 1.2 (PR 27's chip
    runs).

    Returns (keep_mask, reduced_val_leaves), both row-aligned with the
    input order — callers compact kept rows to the front with their
    own pack sort and derive counts from the mask."""
    changed = _changed_adjacent(key_cols)
    starts = jnp.concatenate([jnp.ones((1,), bool), changed])
    is_last = jnp.concatenate([changed, jnp.ones((1,), bool)])
    if monoid is not None:
        op = _MONOID_OPS[monoid]

        def merge_leaves(a, b):
            return [op(x, y) for x, y in zip(a, b)]
    reduced = segmented_combine(starts, val_leaves, merge_leaves)
    return keep_valid(is_last), reduced


def _kept_counts(d, keep, n_dst):
    """Kept rows of each destination 0..n_dst-1 over a `d` sorted by
    destination, in the rows' own order: _bucket_counts with no pack
    sort ahead of it."""
    if n_dst <= _DST_LOOP_MAX:
        return _bucket_counts(jnp.where(keep, d, n_dst), n_dst + 1)[:n_dst]
    edges = jnp.searchsorted(d, jnp.arange(n_dst + 1, dtype=d.dtype))
    kept = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(keep, dtype=jnp.int32)])
    return jnp.diff(kept[edges])


def _bucketize_combine_cols(dst, key_cols, val_leaves, n_dst,
                            merge_leaves, monoid, order_col=None,
                            out_cap=None):
    """Sort by (dst, *key_cols) carrying values, merge rows equal in
    every key column, compact; dst and key_cols must already carry the
    sentinel / sentinel-bucket on invalid rows.  Returns
    (key_cols', vals', counts[n_dst], offsets[n_dst]).

    `out_cap` (optional): a PROVEN bound on the rows the combine keeps,
    below the input's capacity (the query planner's key bound over a
    resident table's ranges, as executor._pack_cap classes it).  The
    pack then sorts only the keep flags with an iota riding and gathers
    the first out_cap rows of that order a column at a time: the same
    rows the un-cut pack leaves first, where it gathers every row of the
    record to keep a few (Q1: 8,388,608 rows of 17 words for four).
    counts and offsets count EVERY kept row, whatever the cut, so a bound
    that was wrong shows as a count past the store's capacity, which
    the executor's first read of the counts refuses: loud, never a group
    dropped.  Without it the function and its HLO are as they were.

    `order_col` (optional, composite keys): a single synthetic
    ordering column (e.g. the 32-bit composite key hash) used INSTEAD
    of the n key columns for the sort — one comparison key regardless
    of key width (the key columns ride as payloads).  Correct because
    the map-side combine only needs equal keys ADJACENT within their
    destination run (boundaries are still detected by comparing every
    real key column, so a hash collision merely splits one group into
    two partial combiners — the reduce side merges them anyway).  Do
    NOT use it where callers require true key-sorted output (the
    spilled-run stream's export relies on lexicographic run order) or
    an EXACT combine: bucketize_combine_keys passes it only when
    n_dst > 1; on one device the store it writes is the reduce's
    answer (executor._finish_stage marks it pre_reduced), every key
    once and in key order as _segment_reduce_cols would leave it."""
    nk = len(key_cols)
    if order_col is not None:
        sorted_ops = _lex_sort(
            (dst, order_col) + tuple(key_cols) + tuple(val_leaves), 2)
        d = sorted_ops[0]
        ks = list(sorted_ops[2:2 + nk])
        vals = sorted_ops[2 + nk:]
    else:
        sorted_ops = _lex_sort(
            (dst,) + tuple(key_cols) + tuple(val_leaves), 1 + nk)
        d = sorted_ops[0]
        ks = list(sorted_ops[1:1 + nk])
        vals = sorted_ops[1 + nk:]
    keep, reduced = _segment_merge(
        [d] + ks, vals,
        lambda flags: flags & (d < n_dst), merge_leaves, monoid)
    dd_full = jnp.where(keep, d, n_dst)
    k_fulls = [jnp.where(keep, k, _sentinel(k.dtype)) for k in ks]
    if out_cap is not None:
        order = _lex_sort((~keep, lax.iota(jnp.int32, keep.shape[0])),
                          1)[1][:out_cap]
        counts = _kept_counts(d, keep, n_dst)
        return ([k[order] for k in k_fulls], [v[order] for v in reduced],
                counts, jnp.cumsum(counts) - counts)
    packed = _lex_sort((~keep, dd_full) + tuple(k_fulls)
                       + tuple(reduced), 1)
    counts = _bucket_counts(packed[1], n_dst + 1)[:n_dst]
    return (list(packed[2:2 + nk]), list(packed[2 + nk:]), counts,
            jnp.cumsum(counts) - counts)


def bucketize_combine_rid(rid, key_cols, val_leaves, valid, n_dst,
                          merge_leaves, monoid=None):
    """Map-side pre-combine for the spilled-run stream (r > mesh): sort
    one device's rows by (device, rid, k1, ..., kn) — device =
    rid % n_dst — merge rows equal in (rid, every key column), compact.
    Cuts exchange volume to O(#distinct keys per wave) before the wire.
    `key_cols` is a list (composite tuple keys ride as multiple
    columns).

    Returns (sorted_leaves=[rid', key cols'...] + vals', counts[n_dst],
    offsets[n_dst]) with rows device-sorted and combined."""
    key_cols = list(key_cols)
    dev = jnp.where(valid, (rid % n_dst).astype(jnp.int32), n_dst)
    rd = jnp.where(valid, rid, _sentinel(rid.dtype))
    ks = [jnp.where(valid, key_cols[0], _sentinel(key_cols[0].dtype))]
    ks += key_cols[1:]
    out_ks, vv, counts, offsets = _bucketize_combine_cols(
        dev, [rd] + ks, val_leaves, n_dst, merge_leaves, monoid)
    return out_ks + vv, counts, offsets


def _segment_reduce_cols(key_cols, val_leaves, valid_mask, merge_leaves,
                         monoid):
    """segment_reduce over a composite key (rows equal in ALL columns
    merge); key_cols[0] carries the sentinel on invalid rows.  Returns
    (packed_key_cols, reduced_vals, n_unique), uniques at the front
    sorted by the key columns."""
    m = key_cols[0].shape[0]
    nk = len(key_cols)
    sorted_ops = _lex_sort(tuple(key_cols) + tuple(val_leaves), nk)
    ks = list(sorted_ops[:nk])
    nvalid = jnp.sum(valid_mask).astype(jnp.int32)
    keep, reduced = _segment_merge(
        ks, sorted_ops[nk:],
        lambda flags: (flags & (jnp.arange(m) < nvalid)
                       & (ks[0] != _sentinel(ks[0].dtype))),
        merge_leaves, monoid)
    k_fulls = [jnp.where(keep, k, _sentinel(k.dtype)) for k in ks]
    packed = _lex_sort((~keep,) + tuple(k_fulls) + tuple(reduced), 1)
    return (list(packed[1:1 + nk]), list(packed[1 + nk:]),
            jnp.sum(keep).astype(jnp.int32))


def segment_reduce_keys(key_cols, val_leaves, valid_mask, merge_leaves,
                        monoid=None):
    """segment_reduce over a COMPOSITE key: merge values of rows equal
    in EVERY key column (key column 0 carries the sentinel on invalid
    rows, as set by flatten_received).  Returns (key_cols', reduced
    vals', n_unique) with uniques packed to the front, sorted
    lexicographically by the key columns."""
    return _segment_reduce_cols(list(key_cols), val_leaves, valid_mask,
                                merge_leaves, monoid)


# ----------------------------------------------------------------------
# segment spans + power-of-two degree buckets: the shared infrastructure
# behind the device segmented apply (fuse.SegMapOp) and the histogram
# program that sizes its bucket layout.  The bucket idea generalizes the
# degree-class slicing of backend/tpu/bagel_obj.py: group sizes collapse
# into ceil(log2) classes, so an arbitrary size distribution costs at
# most one trace per power of two instead of one per distinct size.
# ----------------------------------------------------------------------

def bucket_index(sizes):
    """Per-segment power-of-two bucket index: size s -> ceil(log2(s))
    (sizes 0/1 -> bucket 0, 2 -> 1, 3..4 -> 2, ...).  Bit-twiddled in
    int space — float log2 rounding must not shift a 2^k-sized group
    into the next bucket."""
    x = jnp.maximum(sizes.astype(jnp.int64), 1) - 1
    bits = jnp.zeros(x.shape, jnp.int32)          # bit_length(x)
    for shift in (32, 16, 8, 4, 2, 1):
        big = x >= (jnp.int64(1) << shift)
        bits = bits + jnp.where(big, shift, 0).astype(jnp.int32)
        x = jnp.where(big, x >> shift, x)
    return bits + (x > 0).astype(jnp.int32)


def _segment_table(key_cols, n):
    """Shared boundary scan of one device's KEY-SORTED valid-prefix
    rows (a segment starts where ANY key column changes).  Returns
    (starts, seg_of_row, sizes, n_seg) — the core both segment_spans
    and segment_sizes build on, so the boundary rule lives once."""
    k0 = key_cols[0]
    cap = k0.shape[0]
    idx = jnp.arange(cap)
    valid = idx < n
    ks0 = jnp.where(valid, k0, _sentinel(k0.dtype))
    changed = ks0 != jnp.roll(ks0, 1)
    for kc in key_cols[1:]:
        changed = changed | (kc != jnp.roll(kc, 1))
    starts = valid & ((idx == 0) | changed)
    seg = jnp.where(valid, jnp.cumsum(starts.astype(jnp.int32)) - 1,
                    cap - 1)
    from jax import ops as jops
    sizes = jops.segment_sum(valid.astype(jnp.int32), seg,
                             num_segments=cap)
    # the all-rows-valid case can leave real rows in segment cap-1; the
    # sizes entry is still correct because only valid rows contribute
    return starts, seg, sizes, jnp.sum(starts).astype(jnp.int32)


def segment_spans(key_cols, n):
    """Segment table of one device's KEY-SORTED valid-prefix rows.

    key_cols: list of (cap,) key columns, rows sorted lexicographically
    with the valid prefix first (the no-combine reduce's row order —
    the same precondition SegAggOp documents).

    Returns (start_rows, sizes, seg_of_row, n_seg):
      start_rows (cap,) int32 — row index of segment j's first row for
        j < n_seg (ascending; padding past n_seg is garbage);
      sizes (cap,) int32 — rows in segment j (0 past n_seg);
      seg_of_row (cap,) int32 — segment id per row (invalid rows get
        cap - 1, same convention as SegAggOp);
      n_seg () int32.
    """
    starts, seg, sizes, n_seg = _segment_table(key_cols, n)
    cap = starts.shape[0]
    # start rows by SCATTER, not by sort: segment j's first row writes
    # its own index at position j (XLA:CPU sorts run ~4x slower than
    # the equivalent O(n) scatter at a million rows — round-3 lesson,
    # re-learned while profiling the segmented apply)
    tgt = jnp.where(starts, seg, cap)
    start_rows = jnp.zeros((cap + 1,), jnp.int32) \
        .at[tgt].set(jnp.arange(cap, dtype=jnp.int32))[:cap]
    return start_rows, sizes, seg, n_seg


def segment_sizes(key_cols, n):
    """(sizes, n_seg) of the key-sorted valid prefix — the cheap subset
    of segment_spans (no start-row scatter) that the bucket histogram
    needs."""
    _, _, sizes, n_seg = _segment_table(key_cols, n)
    return sizes, n_seg


def bucket_histogram(key_cols, n, nbuckets=32):
    """(counts[nbuckets], max_size) of the segment-size power-of-two
    buckets of one device's key-sorted rows — the host reads this to
    build a SegMapOp bucket layout before compiling the apply
    program."""
    sizes, n_seg = segment_sizes(key_cols, n)
    cap = sizes.shape[0]
    live = jnp.arange(cap) < n_seg
    b = jnp.where(live, bucket_index(sizes), nbuckets)
    counts = jnp.bincount(b, length=nbuckets + 1)[:nbuckets] \
        .astype(jnp.int32)
    max_size = jnp.max(jnp.where(live, sizes, 0)).astype(jnp.int32)
    return counts, max_size


def bucket_members(sizes, n_seg, bucket, G):
    """(seg_sel (G,), gvalid (G,)) — the segment ids of bucket
    `bucket`, packed in segment order WITHOUT a sort: one cumsum ranks
    the members, one scatter packs them (XLA:CPU sorts cost ~4x the
    equivalent O(n) passes at a million rows)."""
    cap = sizes.shape[0]
    live = jnp.arange(cap) < n_seg
    mask = live & (bucket_index(sizes) == bucket)
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    cnt = jnp.sum(mask).astype(jnp.int32)
    pos = jnp.where(mask & (rank < G), rank, G)
    seg_sel = jnp.zeros((G + 1,), jnp.int32) \
        .at[pos].set(jnp.arange(cap, dtype=jnp.int32))[:G]
    return seg_sel, jnp.arange(G) < cnt


def gather_bucket_groups(start_rows, sizes, seg_sel, gvalid, B,
                         val_col, pad):
    """Padded (G, B) value matrix of the groups selected by `seg_sel`
    (their segment ids, from bucket_members; garbage lanes masked by
    `gvalid`).  `pad` fills columns past each group's
    true size: "zero" writes the dtype zero, "edge" repeats the group's
    last row (admission verified the user function is invariant under
    the chosen fill)."""
    cap = sizes.shape[0]
    st = start_rows[jnp.clip(seg_sel, 0, cap - 1)]
    sz = sizes[jnp.clip(seg_sel, 0, cap - 1)]
    o = jnp.arange(B)
    if pad == "edge":
        off = jnp.minimum(o[None, :], jnp.maximum(sz, 1)[:, None] - 1)
        rows = st[:, None] + off
        vals = val_col[jnp.clip(rows, 0, cap - 1)]
    else:
        rows = st[:, None] + o[None, :]
        in_range = o[None, :] < sz[:, None]
        vals = jnp.where(
            in_range, val_col[jnp.clip(rows, 0, cap - 1)],
            jnp.zeros((), val_col.dtype))
    # whole-garbage groups: zero the inputs so the user fn computes on
    # benign data (its outputs are scatter-masked away regardless)
    vals = jnp.where(gvalid[:, None], vals, jnp.zeros((), vals.dtype))
    return vals


def segment_reduce(key, val_leaves, valid_mask, merge_leaves,
                   monoid=None):
    """Combine values of equal keys with an associative merge.

    key: (m,) int with invalid rows already set to the dtype sentinel.
    val_leaves: list of (m, ...) value arrays.
    merge_leaves: callable (va_leaves, vb_leaves) -> merged leaves, built
    from the user's merge_combiners by fuse.py (vmapped, leaf-level).

    Returns (unique_keys, reduced_val_leaves, n_unique) with uniques packed
    to the front (sorted ascending by key).
    """
    ks, vv, n = _segment_reduce_cols([key], val_leaves, valid_mask,
                                     merge_leaves, monoid)
    return ks[0], vv, n
