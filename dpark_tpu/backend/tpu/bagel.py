"""Device-native Bagel: the Pregel superstep as fused XLA programs.

Reference: dpark/bagel.py superstep loop (SURVEY.md 3.2).  The survey's
[H] TPU mapping is implemented literally: messages ride a hash(dst)
all_to_all, the message combine is a monoid segment reduction, the
global aggregator is a psum over the mesh axis, and the halting counters
come back to the host loop each superstep.

Vertex state is columnar — int64 ids, numeric value leaves, bool active
flags — sharded over the mesh by hash(id), so hash-routed messages land
on the device that owns their target.  Edges are stored with their
SOURCE vertex's device, making message generation a local gather; the
per-edge messages are pre-combined per destination (the Combiner
optimization) before the exchange.  The Python superstep loop stays on
the host, exactly like the reference; everything between two host
iterations is three jitted shard_map programs plus the count-exchange
rounds.

On ONE device the owner of every destination is the device itself, so
where a message lands never changes: the load puts the arcs in their
destination's order once, and a superstep is one gather of the senders'
rows, `send`, a segmented scan over the load's segments and a read at a
fixed slot per vertex (`_p_gen_static`, the step program's `delivered`
form): no sort, no search, no exchange.  Across devices what a device
receives is a merge of what the others sent, of sizes the frontier
decides, and the programs are the bucketizing ones.

Two objects: a DeviceGraph is what a graph's load leaves on the devices
(the vertex-id table, the arcs) and lives until it is dropped; a
DevicePregel is one run over it, with fresh vertex state and the user's
functions.  The programs are the executor's
(`JAXExecutor._compiled`), keyed by all they close over, so a later run
with the same functions over the same size classes builds none.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from dpark_tpu import conf, trace
from dpark_tpu.bagel import (
    PregelInputError, as_leaves, monoid_identity, rewrap)
from dpark_tpu.backend.tpu import collectives, layout
from dpark_tpu.backend.tpu.executor import _shard_map
from dpark_tpu.utils.log import get_logger
from dpark_tpu.utils.phash import phash_np

logger = get_logger("tpu.bagel")

AXIS = conf.MESH_AXIS
_SENT = np.iinfo(np.int64).max


def _local_reduce(kind, x):
    return {"add": jnp.sum, "min": jnp.min,
            "max": jnp.max, "mul": jnp.prod}[kind](x)


def _axis_reduce(kind, x):
    """Cross-device reduction of a per-device scalar (the psum of the
    survey mapping; min/max ride pmin/pmax, mul gathers — it's one
    scalar per device)."""
    if kind == "add":
        return lax.psum(x, AXIS)
    if kind == "min":
        return lax.pmin(x, AXIS)
    if kind == "max":
        return lax.pmax(x, AXIS)
    return jnp.prod(lax.all_gather(x, AXIS))


class _Held:
    """A captured value that a program key can only tell apart by
    identity; the key keeps it alive, so the identity is never reused
    while the program is cached."""
    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _Held) and other.obj is self.obj

    def __repr__(self):
        return "<held %s>" % type(self.obj).__name__


def _value_key(v, depth):
    if v is None or isinstance(v, (bool, int, float, complex, str,
                                   bytes)):
        return (type(v).__name__, v)       # 1, 1.0 and True differ
    if isinstance(v, np.generic):
        return (v.dtype.str, v.item())
    if isinstance(v, tuple):
        return tuple(_value_key(x, depth) for x in v)
    if callable(v) and depth < 4:
        return fn_identity(v, depth + 1)
    return _Held(v)


def fn_identity(fn, depth=0):
    """What a traced program takes from a user function, as a hashable
    key: the code object, the closure cells' contents, `__defaults__`
    and `__kwdefaults__` (two functions that differ only in a default
    are two programs: fuse.fn_key, ROADMAP M1, leaves them out).  Plain
    values compare by value, captured functions by this same rule, and
    anything else (an array, an object, a callable without a code
    object) by identity.  Module globals are not in it, as they are not
    in jax.jit's own cache key."""
    inner = getattr(fn, "__func__", None)
    if inner is not None:                       # a bound method
        return ("method", fn_identity(inner, depth + 1),
                _Held(fn.__self__))
    code = getattr(fn, "__code__", None)
    if code is None:
        return _Held(fn)
    cells = []
    for cell in fn.__closure__ or ():
        try:
            cells.append(_value_key(cell.cell_contents, depth))
        except ValueError:                      # an empty cell
            cells.append(("empty",))
    return (code, tuple(cells),
            tuple(_value_key(v, depth) for v in fn.__defaults__ or ()),
            tuple(sorted((k, _value_key(v, depth)) for k, v in
                         (fn.__kwdefaults__ or {}).items())))


def _specs(leaves):
    """(dtype, trailing shape) of each leaf: what a program sees of a
    column beside its capacity."""
    return tuple((np.dtype(l.dtype).str, tuple(l.shape[1:]))
                 for l in leaves)


def _rides_rows(col):
    kind = np.dtype(col.dtype).kind
    return col.ndim == 1 and kind in "biuf" and (
        kind != "f" or col.dtype.itemsize >= 4)


def _take_columns(cols, idx):
    """Rows `idx` of parallel columns, every rank-1 column of flags,
    integers or whole-word floats in ONE whole-row gather (a gather
    costs a row of up to 16 words what it costs a word, and
    collectives.take_rows gathers a column at a time as soon as one is
    narrower than a word, which a flag is); any other leaf by
    itself."""
    rides = [_rides_rows(c) for c in cols]
    moved = iter(collectives._take_whole_rows(
        [c for c, r in zip(cols, rides) if r], idx))
    return [next(moved) if r else c[idx] for c, r in zip(cols, rides)]


class DeviceGraph:
    """A graph on the executor's mesh: vertices partitioned by
    hash(id), each device's ids sorted (`vid`, padded with the sentinel;
    `vcnt`), every arc with its source's device (`e_dst`, `e_slot` the
    source's place in that device's table, `e_deg` its out-degree, the
    edge values; `ecnt`, the padding behind it), in the order given.
    On one device (`dst_ordered`) the arcs lie in the order of their
    destination id instead (stable), beside two columns that say where
    a destination's arcs end: `e_start`, bool[cap_e], set at slot 0,
    wherever an arc's destination differs from the one before it and at
    the first padding slot; `v_last`, int32[cap_v], for every vertex
    slot the slot of the last arc that points at it, or -1 (an arc to
    no vertex forms a segment that no `v_last` points at).  Built once
    from the host index (bagel._HostGraph) and read by every run; a run
    writes none of it."""

    def __init__(self, executor, host):
        self.ex = executor
        self.ndev = ndev = executor.ndev
        self.mesh = executor.mesh
        self.n = n = host.n
        ids = host.ids                              # sorted
        if n and int(ids[-1]) == _SENT:
            raise PregelInputError(
                "vertex id equals the padding sentinel")
        self.e_tuple = host.e_tuple
        # message leaf specs by (send, vertex leaf specs): a run with
        # functions this graph has seen traces nothing to learn them
        self._msg_specs = {}

        # vertices: ids are sorted, so a stable order by device gives
        # each device its ids in order (the `rounds` form of the step
        # program searches them, and v_last below is found the same way)
        vdev = (phash_np(ids) % np.uint32(ndev)).astype(np.int64)
        vorder = np.argsort(vdev, kind="stable")
        vbounds = np.searchsorted(vdev[vorder], np.arange(ndev + 1))
        vcnt = np.diff(vbounds).astype(np.int32)
        self.cap_v = cap_v = layout.round_capacity(
            int(vcnt.max()) if n else 1)
        local_slot = np.empty(n, np.int64)
        local_slot[vorder] = np.arange(n) - vbounds[vdev[vorder]]
        # where sorted vertex i sits in a flattened (ndev, cap_v)
        # column, and the same for the vertices in the caller's order
        slot = vdev * cap_v + local_slot
        self._slot_of_input = np.empty(n, np.int64)
        self._slot_of_input[host.order] = slot
        vid = np.full(ndev * cap_v, _SENT, np.int64)
        vid[slot] = ids

        # arcs: on every device those of its own vertices, as given;
        # on ONE device in the order of their destination, which a
        # superstep then neither sorts nor searches for
        src_idx = host.src_idx
        ne = src_idx.size
        edev = vdev[src_idx] if ne else np.zeros(0, np.int64)
        self.dst_ordered = ndev == 1
        eorder = np.argsort(host.dst if self.dst_ordered else edev,
                            kind="stable")
        ebounds = np.searchsorted(edev[eorder], np.arange(ndev + 1))
        ecnt = np.diff(ebounds).astype(np.int32)
        self.cap_e = cap_e = layout.round_capacity(
            int(ecnt.max()) if ne else 1)
        eslot = np.empty(ne, np.int64)
        eslot[eorder] = edev[eorder] * cap_e + (
            np.arange(ne) - ebounds[edev[eorder]])
        e_dst = np.full(ndev * cap_e, _SENT, np.int64)
        e_slot = np.zeros(ndev * cap_e, np.int32)
        e_deg = np.ones(ndev * cap_e, np.int64)
        e_dst[eslot] = host.dst
        e_slot[eslot] = local_slot[src_idx]
        e_deg[eslot] = host.deg[src_idx]
        h_evals = []
        for l in host.eleaves:
            hl = np.zeros((ndev * cap_e,) + l.shape[1:], l.dtype)
            hl[eslot] = l
            h_evals.append(hl.reshape((ndev, cap_e) + l.shape[1:]))
        self.e_specs = _specs(host.eleaves)

        statics = []
        if self.dst_ordered:
            d = e_dst[:ne]                          # non-decreasing
            e_start = np.zeros(cap_e, bool)
            e_start[0] = True
            e_start[1:ne] = d[1:] != d[:-1]
            e_start[ne:ne + 1] = True    # padding joins no real segment
            v_last = np.full(cap_v, -1, np.int32)
            if ne:
                last = np.searchsorted(d, vid, "right") - 1
                hit = (last >= 0) & (d[np.maximum(last, 0)] == vid)
                v_last[hit] = last[hit]
            statics = [e_start.reshape(1, cap_e), v_last.reshape(1, cap_v)]

        tables = [vid.reshape(ndev, cap_v), vcnt,
                  e_dst.reshape(ndev, cap_e), e_slot.reshape(ndev, cap_e),
                  e_deg.reshape(ndev, cap_e), ecnt] + statics + h_evals
        with executor._mesh_lock, \
                trace.span("ingest", "exec", rows=n + ne,
                           bytes=sum(int(t.nbytes) for t in tables),
                           site="pregel.graph"):
            (self.vid, self.vcnt, self.e_dst, self.e_slot, self.e_deg,
             self.ecnt, *rest) = [self.put(t) for t in tables]
        self.e_start, self.v_last = rest[:2] if statics else (None, None)
        self.e_vals = rest[len(statics):]
        executor.pregel_graph_loads += 1

    def put(self, arr):
        return layout.put_sharded(arr, NamedSharding(self.mesh, P(AXIS)))

    def place(self, leaf):
        """A column over the vertices, in the order of the ids the graph
        was given, as its (ndev, cap_v, ...) device column."""
        leaf = np.asarray(leaf)
        if leaf.shape[:1] != (self.n,):
            raise PregelInputError(
                "a vertex column has %s rows for %d vertices"
                % (leaf.shape[:1], self.n))
        h = np.zeros((self.ndev * self.cap_v,) + leaf.shape[1:],
                     leaf.dtype)
        h[self._slot_of_input] = leaf
        return self.put(h.reshape((self.ndev, self.cap_v)
                                  + leaf.shape[1:]))

    def message_specs(self, send, vleaves, v_tuple):
        """(dtypes, trailing shapes, was_tuple) of what `send` emits,
        discovered by tracing it once (the per-edge/per-vertex structs
        keep their trailing dims — a vector vertex state must probe as
        a vector, or the discovered message shape collapses to a
        scalar)."""
        v_specs = _specs(vleaves)
        key = (fn_identity(send), v_specs, v_tuple)
        if key not in self._msg_specs:
            struct = lambda specs: tuple(            # noqa: E731
                jax.ShapeDtypeStruct(shp, np.dtype(dt))
                for dt, shp in specs)
            has_e = bool(self.e_specs)
            out = jax.eval_shape(
                lambda sv, ev, dg: send(
                    rewrap(list(sv), v_tuple),
                    rewrap(list(ev), self.e_tuple) if has_e else None,
                    dg),
                struct(v_specs), struct(self.e_specs),
                jax.ShapeDtypeStruct((), np.int64))
            m_leaves, m_tuple = as_leaves(out)
            for m in m_leaves:
                if len(m.shape) > 1:
                    raise PregelInputError(
                        "message leaves must be scalars or 1-D vectors")
            # trailing per-message shape of each leaf: () scalars, or
            # (k,) sum-vector leaves riding as one rank-2 exchange
            # column
            self._msg_specs[key] = (
                [np.dtype(m.dtype) for m in m_leaves],
                [tuple(m.shape) for m in m_leaves], m_tuple)
        return self._msg_specs[key]


class DevicePregel:
    """One Pregel run over a DeviceGraph.  See bagel.run_pregel for the
    user-facing contract."""

    def __init__(self, graph, values, compute, send, combine="add",
                 active=None, initial_messages=None, aggregator=None,
                 max_superstep=80, static_superstep=False,
                 send_gate_leaf=None):
        self.g = graph
        self.ex = graph.ex
        self.ndev = graph.ndev
        # static_superstep: compile one step program PER superstep with
        # `s` as a Python int (user compute branches on it — e.g. the
        # columnarized object-Bagel adapter); default traces s as data
        # so one program serves every superstep
        self.static_superstep = bool(static_superstep)
        # send_gate_leaf: index of a bool vertex-state leaf that
        # REPLACES post-compute `active` as the send mask (the object
        # contract delivers messages from a vertex that emitted and
        # then halted, and nothing from an active vertex that emitted
        # none — neither is expressible with the active gate alone)
        self.send_gate = send_gate_leaf
        self.compute = compute
        self.send = send
        self.combine = combine
        self.aggregator = aggregator
        self.max_superstep = max_superstep

        vleaves, self.v_tuple = as_leaves(values)
        vleaves = [np.asarray(l) for l in vleaves]
        self.msg_dtypes, self.msg_shapes, self.m_tuple = \
            graph.message_specs(send, vleaves, self.v_tuple)
        self.values = [graph.place(l) for l in vleaves]
        self.active = graph.place(
            np.ones(graph.n, bool) if active is None
            else np.asarray(active, bool))
        # all that the gen and step programs close over beside the
        # user's functions
        self._sig = (_specs(vleaves), self.v_tuple, graph.e_specs,
                     graph.e_tuple,
                     tuple((dt.str, shp) for dt, shp in
                           zip(self.msg_dtypes, self.msg_shapes)),
                     self.m_tuple, graph.cap_v, graph.cap_e, combine)
        # the user's functions as program keys, once a run
        self._send_key = fn_identity(send)
        self._compute_key = (
            fn_identity(compute),
            None if aggregator is None
            else (fn_identity(aggregator[0]), aggregator[1]))
        self.init = self._place_messages(initial_messages)

    def _place_messages(self, init_msgs):
        """The initial messages, routed to their target's device."""
        if init_msgs is None:
            return None
        ndev = self.ndev
        idst = np.asarray(init_msgs[0], np.int64)
        ivls, _ = as_leaves(init_msgs[1])
        ivls = [np.asarray(l) for l in ivls]
        if not idst.size:
            return None
        if len(ivls) != len(self.msg_dtypes):
            raise PregelInputError(
                "initial message leaves mismatch: got %d, send "
                "produces %d" % (len(ivls), len(self.msg_dtypes)))
        mdev = (phash_np(idst) % np.uint32(ndev)).astype(np.int64)
        mc = np.bincount(mdev, minlength=ndev)
        cap_m = layout.round_capacity(int(mc.max() or 1))
        hm_d = np.full((ndev, cap_m), _SENT, np.int64)
        hm_v = [np.zeros((ndev, cap_m) + shp, dt)
                for dt, shp in zip(self.msg_dtypes, self.msg_shapes)]
        mcnt = np.zeros(ndev, np.int32)
        for d in range(ndev):
            m = mdev == d
            c = int(m.sum())
            mcnt[d] = c
            if c:
                hm_d[d, :c] = idst[m]
                for hl, l in zip(hm_v, ivls):
                    hl[d, :c] = l[m].astype(hl.dtype)
        put = self.g.put
        return (put(mcnt), put(hm_d), [put(l) for l in hm_v])

    # ------------------------------------------------------------------
    # the programs: init, gen (bucketizing, or static on one device), step
    # ------------------------------------------------------------------
    def _program(self, key, build, n_in, n_out):
        """The executor's program under `key`, built on a miss: `build`
        returns the per-device function, which closes over plain values
        and the user's functions and never over this run."""
        cache = self.ex._compiled
        if key in cache:
            return cache[key]
        if trace._PLANE is not None:
            trace.event("compile", "exec", program=key[0],
                        cap_v=self.g.cap_v, cap_e=self.g.cap_e)
        wrapped = _shard_map(build(), self.g.mesh,
                             in_specs=(P(AXIS),) * n_in,
                             out_specs=(P(AXIS),) * n_out)
        cache[key] = jax.jit(wrapped)
        return cache[key]

    def _p_init(self, cap_m):
        """Bucketize the user's initial messages by hash(dst)."""
        ndev = self.ndev
        combine = self.combine
        nm = len(self.msg_dtypes)

        def build():
            def per_device(mcnt, mdst, *mvals):
                m, d = mcnt[0], mdst[0]
                vs = [v[0] for v in mvals]
                kk, vv, counts, offsets = collectives.bucketize_combine(
                    d, vs, m, ndev, None, monoid=combine)
                out = (counts, offsets, kk) + tuple(vv)
                return tuple(jnp.expand_dims(o, 0) for o in out)
            return per_device

        return self._program(("pregel.init", self._sig[4], combine, cap_m),
                             build, 2 + nm, 3 + nm)

    def _edge_messages(self):
        """`send` over every arc slot: (the senders' state leaves, the
        edge value leaves, the degrees) -> the message leaves, each
        (cap_e, ...)."""
        cap_e = self.g.cap_e
        has_e = bool(self.g.e_vals)
        send = self.send
        v_tuple, e_tuple = self.v_tuple, self.g.e_tuple
        msg_shapes = self.msg_shapes

        def messages(sv, evs, deg):
            msg = send(rewrap(sv, v_tuple),
                       rewrap(evs, e_tuple) if has_e else None, deg)
            m_leaves, _ = as_leaves(msg)
            return [jnp.broadcast_to(jnp.asarray(l), (cap_e,) + shp)
                    for l, shp in zip(m_leaves, msg_shapes)]
        return messages

    def _p_gen_static(self):
        """The gen program over arcs in their destination's order (one
        device): the senders' state by ONE whole-row gather, `send`, a
        segmented scan over the load's segments (`e_start`) that leaves
        every destination's combined message and whether any of its
        senders sent at the segment's last slot, and a read of those at
        `v_last`: per-vertex message columns and `has`, as the step
        program's `delivered` form takes them.  No sort, no search."""
        cap_v, cap_e = self.g.cap_v, self.g.cap_e
        op = collectives._MONOID_OPS[self.combine]
        idents = [monoid_identity(self.combine, dt)
                  for dt in self.msg_dtypes]
        nv = len(self.values)
        nm = len(self.msg_dtypes)
        send_gate = self.send_gate
        messages = self._edge_messages()

        def merge(first, later):
            return [op(x, y) for x, y in zip(first[:-1], later[:-1])] \
                + [first[-1] | later[-1]]

        def build():
            def per_device(vcnt, act, vlast, estart, eslot, edeg, ecnt,
                           *rest):
                vals = [v[0] for v in rest[:nv]]
                evs = [v[0] for v in rest[nv:]]
                ev = jnp.arange(cap_e) < ecnt[0]
                if send_gate is not None:
                    sv = _take_columns(vals, eslot[0])
                    sa = sv[send_gate].astype(bool) & ev
                else:
                    gate, *sv = _take_columns([act[0]] + vals, eslot[0])
                    sa = gate & ev
                m_leaves = [
                    jnp.where(collectives._bcast(sa, l), l, ident)
                    for l, ident in zip(messages(sv, evs, edeg[0]),
                                        idents)]
                # a leaf at a time: cap_v reads of cap_e-slot columns,
                # which stacked into rows first would be written out
                # whole (and the TPU compiler then carries the scan in
                # the rows' padded [cap_e, 1] layout: 128 times the
                # bytes, more than the chip has)
                last = jnp.maximum(vlast[0], 0)
                *combined, sent = [
                    leaf[last] for leaf in collectives.segmented_combine(
                        estart[0], m_leaves + [sa], merge)]
                has = (vlast[0] >= 0) & sent \
                    & (jnp.arange(cap_v) < vcnt[0])
                msg = [jnp.where(collectives._bcast(has, l), l, ident)
                       for l, ident in zip(combined, idents)]
                cnt = jnp.sum(sa).astype(jnp.int32)
                out = tuple(msg) + (has, jnp.reshape(cnt, (1,)))
                return tuple(jnp.expand_dims(o, 0) for o in out)
            return per_device

        return self._program(
            ("pregel.gen", self._sig, self._send_key, send_gate,
             "static"),
            build, 7 + nv + len(self.g.e_vals), nm + 2)

    def _p_gen(self):
        """Generate per-edge messages from the current vertex state,
        pre-combine per destination, bucketize by hash(dst)."""
        ndev = self.ndev
        cap_e = self.g.cap_e
        combine = self.combine
        nv = len(self.values)
        ne = len(self.g.e_vals)
        send_gate = self.send_gate
        messages = self._edge_messages()

        def build():
            def per_device(vcnt, act, edst, eslot, edeg, ecnt, *rest):
                a = act[0]
                slot = eslot[0]
                vals = [v[0] for v in rest[:nv]]
                evs = [v[0] for v in rest[nv:]]
                ev = jnp.arange(cap_e) < ecnt[0]
                sv = [v[slot] for v in vals]
                if send_gate is not None:
                    sa = vals[send_gate][slot].astype(bool) & ev
                else:
                    sa = a[slot] & ev
                m_leaves = messages(sv, evs, edeg[0])
                dstk = jnp.where(sa, edst[0],
                                 collectives._sentinel(jnp.int64))
                packed, cnt = collectives.compact([dstk] + m_leaves, sa)
                kk, vv, counts, offsets = collectives.bucketize_combine(
                    packed[0], packed[1:], cnt, ndev, None,
                    monoid=combine)
                out = (counts, offsets, kk) + tuple(vv) + (
                    jnp.reshape(cnt, (1,)),)
                return tuple(jnp.expand_dims(o, 0) for o in out)
            return per_device

        nm = len(self.msg_dtypes)
        return self._program(
            ("pregel.gen", self._sig, self._send_key, send_gate),
            build, 6 + nv + ne, 4 + nm)

    def _p_step(self, rounds, slot, s_static=None, delivered=False):
        """Deliver combined messages, run the vertex compute, count the
        still-active vertices.  aggregated (if any) is computed from the
        PRE-compute state and psum'd across the mesh.  Three forms: no
        message pending (`rounds` 0), `rounds` exchange rounds of
        bucketized messages to reduce and look the ids up in, and
        `delivered`: the static gen program's per-vertex message columns
        and `has`, taken as they are."""
        cap_v = self.g.cap_v
        combine = self.combine
        nv = len(self.values)
        nm = len(self.msg_dtypes)
        nleaves = 1 + nm                        # dst key + msg leaves
        static = self.static_superstep
        compute, aggregator = self.compute, self.aggregator
        v_tuple, m_tuple = self.v_tuple, self.m_tuple
        msg_dtypes, msg_shapes = self.msg_dtypes, self.msg_shapes

        def build():
            def per_device(*all_args):
                if static:
                    vcnt, vid, act = all_args[:3]
                    rest = all_args[3:]
                    s = s_static
                else:
                    sstep, vcnt, vid, act = all_args[:4]
                    rest = all_args[4:]
                    s = sstep[0]
                cnt = vcnt[0]
                ids = vid[0]
                a = act[0]
                vals = [v[0] for v in rest[:nv]]
                valid_v = jnp.arange(cap_v) < cnt

                ag = None
                if aggregator is not None:
                    create, amon = aggregator
                    a_leaves, a_tuple = as_leaves(
                        create(rewrap(vals, v_tuple)))
                    glob = []
                    for leaf in a_leaves:
                        ident = monoid_identity(amon, leaf.dtype)
                        masked = jnp.where(
                            collectives._bcast(valid_v, leaf), leaf,
                            ident)
                        glob.append(_axis_reduce(
                            amon, _local_reduce(amon, masked)))
                    ag = rewrap(glob, a_tuple)

                if delivered:
                    msg = [m[0] for m in rest[nv:nv + nm]]
                    has = rest[nv + nm][0]
                elif rounds:
                    cnts = [c[0] for c in rest[nv:nv + rounds]]
                    bufs = rest[nv + rounds:]
                    recvs = []
                    for r in range(rounds):
                        recvs.append([bufs[r * nleaves + li][0]
                                      for li in range(nleaves)])
                    flat, mask = collectives.flatten_received(recvs,
                                                              cnts)
                    uk, uv, _ = collectives.segment_reduce(
                        flat[0], flat[1:], mask, None, monoid=combine)
                    pos = jnp.clip(jnp.searchsorted(uk, ids), 0,
                                   uk.shape[0] - 1)
                    has = (uk[pos] == ids) & valid_v \
                        & (ids != collectives._sentinel(jnp.int64))
                    msg = [jnp.where(collectives._bcast(has, u[pos]),
                                     u[pos],
                                     monoid_identity(combine, dt))
                           for u, dt in zip(uv, msg_dtypes)]
                else:
                    has = jnp.zeros(cap_v, bool)
                    msg = [jnp.full((cap_v,) + shp,
                                    monoid_identity(combine, dt), dt)
                           for dt, shp in zip(msg_dtypes, msg_shapes)]

                nv_, na_ = compute(
                    rewrap(vals, v_tuple), rewrap(msg, m_tuple), has,
                    a & valid_v, ag, s)
                new_leaves, _ = as_leaves(nv_)
                new_act = jnp.broadcast_to(
                    jnp.asarray(na_, bool), (cap_v,)) & valid_v
                new_leaves = [
                    jnp.where(collectives._bcast(valid_v, l), l,
                              jnp.zeros((), l.dtype))
                    for l in [jnp.broadcast_to(l,
                                               (cap_v,) + l.shape[1:])
                              for l in new_leaves]]
                n_active = jnp.sum(new_act).astype(jnp.int32)
                out = tuple(new_leaves) + (new_act,
                                           jnp.reshape(n_active, (1,)))
                return tuple(jnp.expand_dims(o, 0) for o in out)
            return per_device

        n_in = (3 if static else 4) + nv + (
            nm + 1 if delivered else rounds + rounds * nleaves)
        return self._program(
            ("pregel.step", self._sig, self._compute_key,
             "delivered" if delivered else rounds, slot,
             static, s_static if static else None),
            build, n_in, nv + 2)

    # ------------------------------------------------------------------
    def run(self):
        """The superstep loop.  Every program goes through the
        executor's _launch and every blocking read through
        layout.host_read, so a job's launches and reads are counted and
        under spans: a superstep is its exchange (none on one device),
        the step program, the read of the active count, the gen program
        and the read of the message count.  Over a graph whose arcs are
        in their destination's order (one device) the gen program hands
        per-vertex messages straight to the next step program: nothing
        is exchanged, and `pregel_static_supersteps` counts it."""
        g, ex = self.g, self.ex
        nv = len(self.values)
        nm = len(self.msg_dtypes)
        tracing = trace._PLANE is not None
        pending = None            # (counts, offsets, kk, vv) bucketized
        delivered = None          # [message columns..., has] a vertex
        delivery = "static" if g.dst_ordered else "exchange"
        total_msgs = 0
        if self.init is not None:
            mcnt, mdst, mvals = self.init
            outs = ex._launch("pregel.init", self._p_init(mdst.shape[1]),
                              mcnt, mdst, *mvals)
            pending = (outs[0], outs[1], outs[2], list(outs[3:]))
            total_msgs = int(layout.host_read(
                outs[0], site="pregel.init").sum())

        s = 0
        while s < self.max_superstep:
            sp = trace.span("pregel.superstep", "exec", s=s)
            with sp:
                if self.static_superstep:
                    head = [g.vcnt, g.vid, self.active]
                else:
                    head = [g.put(np.full((self.ndev,), s, np.int32)),
                            g.vcnt, g.vid, self.active]
                rounds = 0
                if delivered is not None:
                    step = self._p_step(0, 0, s_static=s, delivered=True)
                    args = head + self.values + delivered
                elif pending is not None and total_msgs > 0:
                    counts, offsets, kk, vv = pending
                    recv_rounds, cnt_rounds, slot = ex._exchange_all(
                        [kk] + vv, counts, offsets)
                    rounds = len(recv_rounds)
                    step = self._p_step(rounds, slot, s_static=s)
                    args = head + self.values + list(cnt_rounds)
                    for r in range(rounds):
                        args.extend(recv_rounds[r])
                else:
                    step = self._p_step(0, 0, s_static=s)
                    args = head + self.values
                outs = ex._launch("pregel.step", step, *args)
                self.values = list(outs[:nv])
                self.active = outs[nv]
                n_active = int(layout.host_read(
                    outs[nv + 1], site="pregel.active").sum())

                if g.dst_ordered:
                    gouts = ex._launch(
                        "pregel.gen", self._p_gen_static(), g.vcnt,
                        self.active, g.v_last, g.e_start, g.e_slot,
                        g.e_deg, g.ecnt, *(self.values + g.e_vals))
                    delivered = list(gouts[:nm + 1])
                    ex.pregel_static_supersteps += 1
                else:
                    gouts = ex._launch(
                        "pregel.gen", self._p_gen(), g.vcnt, self.active,
                        g.e_dst, g.e_slot, g.e_deg, g.ecnt,
                        *(self.values + g.e_vals))
                    pending = (gouts[0], gouts[1], gouts[2],
                               list(gouts[3:3 + nm]))
                total_msgs = int(layout.host_read(
                    gouts[-1], site="pregel.msgs").sum())
                if tracing:
                    sp.args.update(active=n_active, msgs=total_msgs,
                                   rounds=rounds, delivery=delivery)
            s += 1
            ex.pregel_supersteps += 1
            ex.pregel_messages += total_msgs
            logger.debug("superstep %d: active=%d msgs=%d",
                         s, n_active, total_msgs)
            if n_active == 0 and total_msgs == 0:
                break
        return self._collect()

    def _collect(self):
        """The final state to the host, unpadded, in the order of the
        sorted ids: one `egest` span around its reads."""
        g = self.g
        sp = trace.span("egest", "exec", site="pregel.collect")
        with sp:
            vid = layout.host_read(g.vid, site="pregel.collect")
            vcnt = layout.host_read(g.vcnt, site="pregel.collect")
            vals = [layout.host_read(l, site="pregel.collect")
                    for l in self.values]
            act = layout.host_read(self.active, site="pregel.collect")
            if trace._PLANE is not None:
                sp.args.update(rows=int(vcnt.sum()), bytes=sum(
                    int(a.nbytes) for a in [vid, vcnt, act] + vals))
            keep = (np.arange(g.cap_v) < vcnt[:, None]).reshape(-1)
            order = np.argsort(vid.reshape(-1)[keep])
            flat = lambda a: a.reshape(     # noqa: E731
                (-1,) + a.shape[2:])[keep][order]
            return (flat(vid), rewrap([flat(l) for l in vals],
                                      self.v_tuple), flat(act))
