"""The exchange sends slices (ISSUE 34): collectives.exchange_round cuts
each destination's contiguous run out of the destination-sorted leaves.

The reference, kept here in numpy, is the form it replaced: an index a
slot row, `offsets[:, None] + sent[:, None] + arange(slot)` clipped to
the leaf, every row gathered through it, rows past `sendable` zeroed,
the wire dtype put on and taken off around the collective (which on one
host is a transpose: device d receives from device s what s cut for d).

The contract under test: the receive buffers, the received counts, the
new `sent` and the overflow are BIT-IDENTICAL to the reference's, dtypes
kept, round after round, on 1, 2, 4 and 8 devices with rows of their own:
int64 / int32 / float32 / bool leaves and a rank-2 leaf, narrowed on the
wire and not, a second round (`sent > 0`), a destination that gets
nothing, a last block that runs past the leaf's end (where a
dynamic_slice would move its start back), a slot as large as the leaf.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dpark_tpu.backend.tpu import collectives
from dpark_tpu.backend.tpu.executor import AXIS, _shard_map


@pytest.fixture(autouse=True)
def _x64():
    """int64 leaves, as the executor runs them (it turns x64 on when it
    is built; this program is built without one)."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


# leaf dtypes and trailing shapes of a record; `narrow` beside them
RECORD = [("int64", ()), ("int32", ()), ("float32", ()), ("bool", ()),
          ("int64", (3,))]
NARROWED = ("int32", None, None, None, "int32")


def _full(rng, R, cap, small):
    """Counts that fill the leaf to its last row: under `small` rows
    for every destination but the last, which takes the rest."""
    counts = np.empty((R, R), np.int64)
    counts[:, :-1] = rng.integers(1, small, (R, R - 1))
    counts[:, -1] = cap - counts[:, :-1].sum(axis=1)
    return counts


# case -> (cap, slot, narrow, counts (device, destination)) of R devices
CASES = {
    # every run fits its block; nothing narrowed on the wire
    "one_round": lambda rng, R: (
        32 * R, 24, None, rng.integers(1, 24, (R, R))),
    "narrowed": lambda rng, R: (
        32 * R, 24, NARROWED, rng.integers(1, 24, (R, R))),
    # runs longer than a block: the second round starts at sent > 0
    "two_rounds": lambda rng, R: (
        32 * R, 16, NARROWED, rng.integers(17, 32, (R, R))),
    "empty_destination": lambda rng, R: (
        32 * R, 24, None,
        rng.integers(1, 24, (R, R)) * (np.arange(R) != R // 2)),
    # the leaf is full and the last destination's run is no multiple of
    # a block: in its last round, at sent > 0, offsets + sent + slot > cap
    "block_past_cap": lambda rng, R: (
        20 + 8 * R, 12 + 4 * R, None, _full(rng, R, 20 + 8 * R, 4)),
    "slot_ge_cap": lambda rng, R: (
        20 + 8 * R, 24 + 8 * R, NARROWED, _full(rng, R, 20 + 8 * R, 8)),
}


def _leaves(rng, cap):
    out = []
    for dtype, tail in RECORD:
        if dtype == "bool":
            a = rng.integers(0, 2, (cap,) + tail).astype(bool)
        elif dtype == "float32":
            a = rng.standard_normal((cap,) + tail).astype(np.float32)
            a[a == 0] = -0.0        # a zero the mask must not confuse
        else:       # an int64 that narrows: the guard's to promise
            a = rng.integers(-2**31, 2**31, (cap,) + tail).astype(dtype)
        out.append(a)
    return out


def _gather_round(leaves, offsets, counts, sent, slot, narrow):
    """The replaced form on ONE device: its (R, slot, ...) send buffers
    as the receiver widens them, and what it now counts as sent."""
    cap = leaves[0].shape[0]
    sendable = np.minimum(counts - sent, slot).astype(np.int32)
    j = np.arange(slot)
    idx = np.clip(offsets[:, None] + sent[:, None] + j[None, :], 0, cap - 1)
    mask = j[None, :] < sendable[:, None]
    send = []
    for li, leaf in enumerate(leaves):
        g = leaf[idx]
        g = np.where(mask.reshape(mask.shape + (1,) * (g.ndim - 2)), g,
                     np.zeros((), g.dtype))
        if narrow is not None and narrow[li] is not None:
            g = g.astype(narrow[li]).astype(leaf.dtype)
        send.append(g)
    return send, sendable


@pytest.mark.parametrize("ndev", [1, 2, 4,
                                  pytest.param(8, marks=pytest.mark.mesh)])
@pytest.mark.parametrize("case", sorted(CASES))
def test_receive_buffers_equal_the_gather_forms(case, ndev):
    rng = np.random.default_rng([sorted(CASES).index(case), ndev])
    cap, slot, narrow, counts = CASES[case](rng, ndev)
    counts = counts.astype(np.int32)
    assert (counts >= 0).all() and (counts.sum(axis=1) <= cap).all()
    offsets = (np.cumsum(counts, axis=1) - counts).astype(np.int32)
    leaves = [np.stack(per) for per in zip(
        *[_leaves(rng, cap) for _ in range(ndev)])]     # (ndev, cap, ...)
    rounds = max(1, -(-int(counts.max()) // slot))  # as _exchange_all's

    def per_device(off, cnt, sent, *lv):
        recv, recv_cnt, new_sent, overflow = collectives.exchange_round(
            AXIS, [l[0] for l in lv], off[0], cnt[0], sent[0], slot,
            narrow=narrow)
        out = (recv_cnt, new_sent, jnp.reshape(overflow, (1,))) + tuple(recv)
        return tuple(jnp.expand_dims(o, 0) for o in out)

    n = 3 + len(leaves)
    mesh = Mesh(np.array(jax.devices()[:ndev]), (AXIS,))
    fn = jax.jit(_shard_map(per_device, mesh, in_specs=(P(AXIS),) * n,
                            out_specs=(P(AXIS),) * n))

    sent = np.zeros((ndev, ndev), np.int32)
    want_sent = sent.copy()
    clamps = False      # would a dynamic_slice of the bare leaf clamp?
    for rnd in range(rounds):
        got = [np.asarray(o) for o in fn(offsets, counts, sent, *leaves)]
        cut = [_gather_round([l[d] for l in leaves], offsets[d], counts[d],
                             want_sent[d], slot, narrow)
               for d in range(ndev)]
        sendable = np.stack([c[1] for c in cut])
        clamps |= bool(((offsets + want_sent + slot > cap)
                        & (want_sent > 0) & (sendable > 0))[:, -1].any())
        want_sent = want_sent + sendable
        # the collective: device d receives from s what s cut for d
        assert np.array_equal(got[0], sendable.T), rnd
        assert got[0].dtype == np.int32
        assert np.array_equal(got[1], want_sent), rnd
        assert (got[2] == (counts - want_sent).sum()).all(), rnd
        for li, leaf in enumerate(leaves):
            want = np.stack([np.stack([cut[s][0][li][d]
                                       for s in range(ndev)])
                             for d in range(ndev)])
            assert got[3 + li].dtype == leaf.dtype, (rnd, li)
            assert got[3 + li].shape == (ndev, ndev, slot) + leaf.shape[2:]
            # bit for bit: -0.0 is not 0.0, and no NaN is drawn
            assert np.array_equal(got[3 + li].view(np.uint8),
                                  want.view(np.uint8)), (rnd, li)
        sent = got[1]
    assert (want_sent == counts).all()      # the rounds drained every run
    assert rounds == {"two_rounds": 2, "block_past_cap": 2}.get(case, 1)
    assert clamps == (case == "block_past_cap")


def test_the_send_side_lowers_to_slices_and_no_gather():
    """R dynamic_slices a leaf, of the leaf padded by a block (so that
    no start clamps), and not one gather: the program's lowered text."""
    R, cap, slot = 4, 64, 24

    def per_device(off, cnt, sent, *lv):
        recv = collectives.exchange_round(
            AXIS, [l[0] for l in lv], off[0], cnt[0], sent[0], slot,
            narrow=NARROWED)[0]
        return tuple(jnp.expand_dims(o, 0) for o in recv)

    mesh = Mesh(np.array(jax.devices()[:R]), (AXIS,))
    text = jax.jit(_shard_map(
        per_device, mesh, in_specs=(P(AXIS),) * (3 + len(RECORD)),
        out_specs=(P(AXIS),) * len(RECORD))).lower(
        *[jax.ShapeDtypeStruct((R, R), jnp.int32)] * 3,
        *[jax.ShapeDtypeStruct((R, cap) + tail, jnp.dtype(dt))
          for dt, tail in RECORD]).as_text()
    assert "stablehlo.gather" not in text
    assert "stablehlo.dynamic_gather" not in text
    assert text.count("stablehlo.dynamic_slice") == R * len(RECORD)
    assert "-> tensor<%dxi64>" % (cap + slot) in text       # the pad
