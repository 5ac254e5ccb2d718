"""Erasure-coded shuffle exchange (ISSUE 6 tentpole).

PR 5's chaos plane proved lineage recovery is CORRECT but expensive: a
lost ``hbm://`` bucket invalidates all of a device parent's outputs,
so one injected fetch fault costs a full stage resubmit round.  Coded
MapReduce / CAMR (PAPERS.md) show the alternative — pay a little
parity at MAP time so a failed or straggling fetch is *decoded* from
surviving shards instead of recomputed through lineage.

This module is the codec: systematic XOR (m=1) and Reed–Solomon over
GF(2^8) (Cauchy parity matrix, so every k-subset of the n=k+m shard
rows is invertible), numpy-vectorized with a pure-Python fallback.
Shuffle bucket payloads and spill runs split into k equal-padded data
chunks plus m parity chunks; any k of the n shards reconstruct the
payload exactly.

Mode grammar (the ``DPARK_SHUFFLE_CODE`` env var / conf knob)::

    off          no coding (the default; zero hot-path cost)
    xor          k=4 data shards + 1 XOR parity (survives any 1 loss)
    xor(k)       same with k data shards
    rs(k,m)      k data + m Reed–Solomon parity shards (any m losses)

One on-disk shape, two wire shapes, share the codec:

* **shard containers** — shuffle buckets and spill runs/chunks stay
  ONE file, but the body is n back-to-back framed shards with
  per-shard crc32c, so a corrupted region drops exactly the shards it
  touched and the reader decodes around them (a local ``file://``
  fetch reads the container once — no per-shard syscall cost);
* **shard frames** — REMOTE fetches (``tcp://`` peers, the ``hbm://``
  export bridge) stay per-shard units: the fetch side issues all n
  frame reads concurrently and decodes as soon as any k arrive
  (fastest-k also wins against stragglers, which speculation only
  partially covers).

Decode outcomes feed process-global counters (``repair`` — parity
replaced a FAILED shard; ``straggler_win`` — parity merely arrived
before a slow shard; ``decode_failures`` — fewer than k survived, so
the fetch fell back to lineage), attributed per shuffle id.  The
scheduler snapshots them into job records / ``recovery_summary()``
and the web UI shows them per stage.  Counters are per-process: the
multiprocess master's workers decode in their own processes, so their
counts don't surface on the driver (same contract as ``faults``).
"""

import re
import struct
import threading

__all__ = [
    "ALGO_XOR", "ALGO_RS", "Code", "ShardCorrupt", "ShardShortfall",
    "parse_code", "configure", "active", "active_code", "describe",
    "pack_shard", "unpack_shard", "encode_bucket_frames",
    "encode_container", "decode_container", "is_container",
    "parse_container", "extract_container_frame",
    "note", "counters_snapshot", "reset_counters", "stats",
    "set_shuffle_code", "shuffle_code", "clear_shuffle_codes",
    "note_parity_bytes", "parity_bytes", "choose_code",
    "record_choice", "code_history", "adaptive_enabled",
]

ALGO_XOR = 0
ALGO_RS = 1

# a recorded peer counts as a straggler when its persisted fetch-tail
# sketch shows p99/p50 at or above this ratio (and at least
# ADAPT_MIN_SAMPLES observations); below it with a bounded p99 the
# exchange is priced tight and runs uncoded
ADAPT_TAIL_RATIO = 3.0
ADAPT_MIN_SAMPLES = 8

SHARD_MAGIC = b"DSH1"
CONTAINER_MAGIC = b"DCC1"

# magic, algo, k, m, shard index, original payload length, shard
# length, crc32c of the shard payload.  8-byte lengths: one bucket of
# giant combiners must not overflow a 4 GiB prefix (same contract as
# the PR 5 spill chunk framing).
_SHARD_HDR = struct.Struct("<4sBBBBQQI")


def _crc(blob):
    """crc32c when the native library is loaded, else C-speed
    zlib.crc32 (the shuffle spill framing's exact policy — shards are
    written and read by the same installation, so the polynomial only
    needs in-process consistency)."""
    from dpark_tpu import native
    if native.get_lib() is not None:
        return native.crc32c(blob)
    import zlib
    return zlib.crc32(blob) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# GF(2^8) arithmetic (polynomial 0x11D)
# ---------------------------------------------------------------------------

_EXP = None            # 510-entry exp table (doubled: no mod in mul)
_LOG = None
_NP_MUL = None         # lazily built 256x256 uint8 product table
_MUL_ROWS = {}         # coefficient -> 256-byte row (pure-Python path)
_FORCE_PURE = False    # tests flip this to exercise the fallback


def _numpy():
    if _FORCE_PURE:
        return None
    try:
        import numpy
        return numpy
    except ImportError:
        return None


def _tables():
    global _EXP, _LOG
    if _EXP is None:
        exp = [0] * 510
        log = [0] * 256
        x = 1
        for i in range(255):
            exp[i] = x
            log[x] = i
            x <<= 1
            if x & 0x100:
                x ^= 0x11D
        for i in range(255, 510):
            exp[i] = exp[i - 255]
        _EXP, _LOG = exp, log
    return _EXP, _LOG


def gf_mul(a, b):
    if not a or not b:
        return 0
    exp, log = _tables()
    return exp[log[a] + log[b]]


def gf_inv(a):
    exp, log = _tables()
    return exp[255 - log[a]]


def _np_mul_table():
    global _NP_MUL
    if _NP_MUL is None:
        np = _numpy()
        exp, log = _tables()
        le = np.array(exp, dtype=np.int32)
        ll = np.array(log, dtype=np.int32)
        t = np.zeros((256, 256), dtype=np.uint8)
        for c in range(1, 256):
            t[c, 1:] = le[ll[c] + ll[1:]].astype(np.uint8)
        _NP_MUL = t
    return _NP_MUL


def _xor_bytes(a, b):
    np = _numpy()
    if np is not None:
        return (np.frombuffer(a, np.uint8)
                ^ np.frombuffer(b, np.uint8)).tobytes()
    return bytes(x ^ y for x, y in zip(a, b))


def _mul_bytes(c, buf):
    """GF product of scalar coefficient `c` with every byte of `buf`."""
    if c == 0:
        return b"\0" * len(buf)
    if c == 1:
        return bytes(buf)
    np = _numpy()
    if np is not None:
        return _np_mul_table()[c][np.frombuffer(buf, np.uint8)].tobytes()
    row = _MUL_ROWS.get(c)
    if row is None:
        row = bytes(gf_mul(c, b) for b in range(256))
        _MUL_ROWS[c] = row
    return bytes(row[b] for b in buf)


def _gf_invert_matrix(rows):
    """Gauss-Jordan inverse of a k x k matrix over GF(2^8).  The Cauchy
    construction guarantees invertibility for every survivor subset;
    the pivot assert is a corruption tripwire, not a reachable path."""
    k = len(rows)
    a = [list(r) + [1 if j == i else 0 for j in range(k)]
         for i, r in enumerate(rows)]
    for col in range(k):
        piv = next((r for r in range(col, k) if a[r][col]), None)
        assert piv is not None, "singular survivor matrix"
        a[col], a[piv] = a[piv], a[col]
        pv = gf_inv(a[col][col])
        a[col] = [gf_mul(pv, v) for v in a[col]]
        for r in range(k):
            if r != col and a[r][col]:
                f = a[r][col]
                a[r] = [v ^ gf_mul(f, a[col][j])
                        for j, v in enumerate(a[r])]
    return [r[k:] for r in a]


# ---------------------------------------------------------------------------
# the code itself
# ---------------------------------------------------------------------------

class ShardCorrupt(IOError):
    """A shard frame failed its crc32c / structural check.  The shard
    is dropped; decode proceeds from the survivors."""


class ShardShortfall(Exception):
    """Fewer than k shards survived: the payload is information-
    theoretically gone and only lineage recovery can help.  Carries
    the counts the FetchFailed translation reports."""

    def __init__(self, found, needed, total):
        super().__init__(
            "%d of %d shards survived; %d needed to decode"
            % (found, total, needed))
        self.found = found
        self.needed = needed
        self.total = total


class Code:
    """A systematic (k, m) erasure code: shards 0..k-1 are the data
    chunks verbatim, shards k..k+m-1 are parity."""

    def __init__(self, algo, k, m):
        if algo not in (ALGO_XOR, ALGO_RS):
            raise ValueError("unknown code algo %r" % (algo,))
        if k < 1 or m < 1:
            raise ValueError("code needs k >= 1 and m >= 1, got "
                             "k=%d m=%d" % (k, m))
        if algo == ALGO_XOR and m != 1:
            raise ValueError("xor parity is single-loss only (m=1)")
        if k + m > 255:
            raise ValueError("GF(2^8) supports at most 255 shards, "
                             "got k+m=%d" % (k + m))
        self.algo = algo
        self.k = k
        self.m = m
        self.n = k + m
        self._cauchy = None

    def describe(self):
        if self.algo == ALGO_XOR:
            return "xor(%d)" % self.k
        return "rs(%d,%d)" % (self.k, self.m)

    __repr__ = describe

    def _parity_rows(self):
        """m x k Cauchy matrix C[i][j] = 1/(x_i + y_j) with x_i = k+i,
        y_j = j (disjoint label sets, so every entry is defined).  The
        systematic generator [I; C] then has every k x k row-subset
        invertible — the MDS property the decoder relies on."""
        if self._cauchy is None:
            self._cauchy = [
                [gf_inv((self.k + i) ^ j) for j in range(self.k)]
                for i in range(self.m)]
        return self._cauchy

    def encode(self, data):
        """bytes -> n shard payloads (k data chunks zero-padded to a
        common length, then m parity chunks)."""
        k = self.k
        shard_len = max(1, -(-len(data) // k))
        padded = data.ljust(k * shard_len, b"\0")
        chunks = [bytes(padded[i * shard_len:(i + 1) * shard_len])
                  for i in range(k)]
        if self.algo == ALGO_XOR:
            parity = chunks[0]
            for c in chunks[1:]:
                parity = _xor_bytes(parity, c)
            return chunks + [parity]
        out = list(chunks)
        for row in self._parity_rows():
            acc = _mul_bytes(row[0], chunks[0])
            for j in range(1, k):
                acc = _xor_bytes(acc, _mul_bytes(row[j], chunks[j]))
            out.append(acc)
        return out

    def decode(self, shards, orig_len):
        """{shard index -> payload} (any >= k of them) -> the original
        bytes.  Raises ShardShortfall with fewer than k survivors."""
        k = self.k
        have = dict(shards)
        if len(have) < k:
            raise ShardShortfall(len(have), k, self.n)
        missing = [j for j in range(k) if j not in have]
        if not missing:
            return b"".join(have[j] for j in range(k))[:orig_len]
        if self.algo == ALGO_XOR:
            # one absent data chunk: it is the XOR of everything else
            acc = None
            for i in sorted(have):
                acc = have[i] if acc is None else _xor_bytes(acc,
                                                             have[i])
            have[missing[0]] = acc
            return b"".join(have[j] for j in range(k))[:orig_len]
        # RS: invert the survivor rows of the generator, then rebuild
        # only the MISSING data chunks (present ones ride verbatim)
        chosen = [i for i in sorted(have) if i < k]
        for i in sorted(have):
            if len(chosen) == k:
                break
            if i >= k:
                chosen.append(i)
        cau = self._parity_rows()
        rows = [[1 if t == s else 0 for t in range(k)] if s < k
                else list(cau[s - k]) for s in chosen]
        inv = _gf_invert_matrix(rows)
        shard_len = len(have[chosen[0]])
        for j in missing:
            acc = b"\0" * shard_len
            for t, s in enumerate(chosen):
                c = inv[j][t]
                if c:
                    acc = _xor_bytes(acc, _mul_bytes(c, have[s]))
            have[j] = acc
        return b"".join(have[j] for j in range(k))[:orig_len]


def parse_code(text):
    """``off|xor|xor(k)|rs(k,m)`` -> Code or None.  Malformed specs
    raise ValueError — a run with a typo'd mode silently writing
    uncoded buckets would "prove" a recovery path it never took."""
    t = (text or "").strip().lower()
    if t in ("", "off", "0", "none"):
        return None
    m = re.fullmatch(r"xor(?:\((\d+)\))?", t)
    if m:
        return Code(ALGO_XOR, int(m.group(1) or 4), 1)
    m = re.fullmatch(r"rs\((\d+)\s*,\s*(\d+)\)", t)
    if m:
        return Code(ALGO_RS, int(m.group(1)), int(m.group(2)))
    raise ValueError(
        "unknown shuffle code %r (one of: off, xor, xor(k), rs(k,m))"
        % (text,))


# ---------------------------------------------------------------------------
# shard frames + containers
# ---------------------------------------------------------------------------

class _Frame:
    __slots__ = ("algo", "k", "m", "idx", "orig_len", "crc",
                 "payload", "end")

    def __init__(self, algo, k, m, idx, orig_len, crc, payload, end):
        self.algo = algo
        self.k = k
        self.m = m
        self.idx = idx
        self.orig_len = orig_len
        self.crc = crc
        self.payload = payload
        self.end = end


def pack_shard(code, idx, orig_len, payload):
    """One self-describing shard frame: geometry + index + original
    length ride the header so reads never depend on reader config."""
    return _SHARD_HDR.pack(SHARD_MAGIC, code.algo, code.k, code.m,
                           idx, orig_len, len(payload),
                           _crc(payload)) + payload


def unpack_shard(buf, off=0, verify=True):
    """Parse one shard frame at `off`.  With verify the payload crc is
    checked here; container readers verify AFTER routing the payload
    through the spill_read chaos site instead."""
    if len(buf) < off + _SHARD_HDR.size:
        raise ShardCorrupt("short shard frame (%d bytes at %d)"
                           % (len(buf) - off, off))
    magic, algo, k, m, idx, orig_len, slen, crc = \
        _SHARD_HDR.unpack_from(buf, off)
    if magic != SHARD_MAGIC:
        raise ShardCorrupt("bad shard magic %r" % (magic,))
    end = off + _SHARD_HDR.size + slen
    if end > len(buf):
        raise ShardCorrupt("truncated shard payload")
    payload = bytes(buf[off + _SHARD_HDR.size:end])
    if verify and _crc(payload) != crc:
        raise ShardCorrupt("shard %d: crc32c mismatch" % idx)
    return _Frame(algo, k, m, idx, orig_len, crc, payload, end)


def encode_bucket_frames(blob, code):
    """Bucket payload -> n framed shard blobs, one per shard FILE /
    shard request (each an independent fetch unit)."""
    return [pack_shard(code, i, len(blob), p)
            for i, p in enumerate(code.encode(blob))]


def encode_container(blob, code, fault_site=None):
    """Single-file shard container for spill runs/chunks: the crc is
    computed over the TRUE shard bytes, then each payload routes
    through the write chaos site — injected corruption lands in
    exactly one shard and is caught (and decoded around) at read."""
    from dpark_tpu import faults
    parts = [CONTAINER_MAGIC, struct.pack("<B", code.n)]
    for idx, p in enumerate(code.encode(blob)):
        crc = _crc(p)
        if fault_site is not None:
            p = faults.hit(fault_site, p)
        parts.append(_SHARD_HDR.pack(SHARD_MAGIC, code.algo, code.k,
                                     code.m, idx, len(blob), len(p),
                                     crc))
        parts.append(p)
    return b"".join(parts)


def is_container(raw):
    return raw[:4] == CONTAINER_MAGIC


def parse_container(raw):
    """Container bytes -> list of _Frame, crc NOT yet verified (the
    caller owns chaos-site routing + verification per shard).  A lost
    frame boundary truncates the list — later shards are unreachable,
    which the decode treats as erasures."""
    if not is_container(raw):
        raise ShardCorrupt("not a shard container")
    (n,) = struct.unpack_from("<B", raw, 4)
    off = 5
    frames = []
    for _ in range(n):
        try:
            fr = unpack_shard(raw, off, verify=False)
        except ShardCorrupt:
            break
        off = fr.end
        frames.append(fr)
    return frames


def extract_container_frame(raw, idx):
    """The framed bytes of shard `idx` inside a container — what a
    bucket server returns for one shard request (the remote fetch
    unit).  Raises KeyError when the container holds no such shard."""
    for fr in parse_container(raw):
        if fr.idx == idx:
            start = fr.end - len(fr.payload) - _SHARD_HDR.size
            return bytes(raw[start:fr.end])
    raise KeyError(idx)


def decode_container(raw, fault_site=None, shuffle_id=None):
    """Read a shard container back, dropping shards whose crc fails
    (or whose read chaos-site hit raises) and decoding from the rest.
    Raises ShardShortfall when fewer than k survive — the caller
    translates that into SpillCorruption / FetchFailed."""
    from dpark_tpu import faults
    if not is_container(raw):
        raise ShardCorrupt("not a shard container")
    (n,) = struct.unpack_from("<B", raw, 4)
    good = {}
    geom = None
    orig_len = 0
    for fr in parse_container(raw):
        geom = (fr.algo, fr.k, fr.m)
        orig_len = fr.orig_len
        payload = fr.payload
        try:
            if fault_site is not None:
                payload = faults.hit(fault_site, payload)
            if _crc(payload) != fr.crc:
                raise ShardCorrupt("shard %d: crc32c mismatch"
                                   % fr.idx)
        except Exception:
            continue            # this shard is gone; decode around it
        good[fr.idx] = payload
    if geom is None:
        note("decode_failures", shuffle_id)
        raise ShardShortfall(0, 1, n)
    code = Code(*geom)
    if len(good) < code.k:
        note("decode_failures", shuffle_id)
        raise ShardShortfall(len(good), code.k, code.n)
    data = code.decode(good, orig_len)
    if any(j not in good for j in range(code.k)):
        # parity actually reconstructed data: a repair, free of lineage
        note("repair", shuffle_id)
    return data


# ---------------------------------------------------------------------------
# active-mode plumbing + decode counters
# ---------------------------------------------------------------------------

_CODE = None

_LOCK = threading.Lock()
_KINDS = ("repair", "straggler_win", "decode_failures",
          # peer-death masked by parity (ISSUE 20): a lease-expired
          # peer's shards were failed fast and the decode still closed
          # from live peers — the recovery path the liveness layer buys
          "peer_masked")
_TOTALS = {k: 0 for k in _KINDS}
_PER_SHUFFLE = {}
_PER_PEER = {}
_PARITY_BYTES = [0]

# per-shuffle code overrides (ISSUE 19): the straggler-adaptive policy
# prices (k,m) PER EXCHANGE, so one process can be writing rs(4,2)
# containers for a straggly exchange while a tight one stays plain.
# The registry maps shuffle_id -> Code (None = explicitly uncoded);
# unregistered shuffles use the global _CODE.  Both the map side
# (ShuffleMapTask.run) and the reduce side (ShuffledRDD /
# CoGroupedRDD.compute) register from the serialized dep before
# touching buckets, so worker processes see the driver's choice.
_SHUFFLE_CODES = {}
_SHUFFLE_CODES_CAP = 1024
_UNSET = object()


def configure(spec=None):
    """Install the shuffle code from a spec string (None/"" / "off"
    clears it).  Returns the installed Code or None."""
    global _CODE
    _CODE = parse_code(spec) if spec else None
    return _CODE


def active():
    return _CODE is not None


def active_code():
    return _CODE


def describe():
    return _CODE.describe() if _CODE is not None else "off"


def note(kind, shuffle_id=None, peer=None):
    """Count a decode outcome, attributed to `shuffle_id` when the
    caller knows it (bucket fetches do; spill-run decodes don't) and
    to the serving `peer` (ISSUE 19 satellite: /metrics and the health
    plane name WHICH peer's straggling triggered an escalation)."""
    with _LOCK:
        _TOTALS[kind] += 1
        if shuffle_id is not None:
            per = _PER_SHUFFLE.setdefault(
                shuffle_id, {k: 0 for k in _KINDS})
            per[kind] += 1
        if peer is not None:
            pp = _PER_PEER.setdefault(
                str(peer), {k: 0 for k in _KINDS})
            pp[kind] += 1
    from dpark_tpu import trace
    if trace._PLANE is not None:
        # timeline twin of the counter (ISSUE 8): each decode outcome
        # is an instant event on the fetching task's span context
        trace.event("decode." + kind, "coding", shuffle=shuffle_id,
                    peer=peer)


def note_parity_bytes(nbytes):
    """Count parity OVERHEAD bytes written (encoded container/frame
    bytes minus the original payload) — the adaptive-code bench grades
    itself on total parity bytes vs the static code."""
    if nbytes > 0:
        with _LOCK:
            _PARITY_BYTES[0] += int(nbytes)


def parity_bytes():
    with _LOCK:
        return _PARITY_BYTES[0]


def counters_snapshot():
    """Deep copy of the counters — the scheduler diffs two snapshots
    to attribute decode activity to one job record."""
    with _LOCK:
        return {"totals": dict(_TOTALS),
                "per_shuffle": {sid: dict(c)
                                for sid, c in _PER_SHUFFLE.items()},
                "per_peer": {p: dict(c)
                             for p, c in _PER_PEER.items()},
                "parity_bytes": _PARITY_BYTES[0]}


def reset_counters():
    with _LOCK:
        for k in _KINDS:
            _TOTALS[k] = 0
        _PER_SHUFFLE.clear()
        _PER_PEER.clear()
        _PARITY_BYTES[0] = 0


def stats():
    """{mode, repair, straggler_win, decode_failures, parity_bytes,
    per_peer} — the bench JSON's `decodes` section and
    recovery_summary()'s decode view (decode_failures stays distinct
    from plain fetch failures)."""
    with _LOCK:
        out = dict(_TOTALS)
        out["parity_bytes"] = _PARITY_BYTES[0]
        out["per_peer"] = {p: dict(c) for p, c in _PER_PEER.items()}
    out["mode"] = describe()
    return out


# ---------------------------------------------------------------------------
# straggler-adaptive per-exchange code selection (ISSUE 19 tentpole 1)
# ---------------------------------------------------------------------------

def set_shuffle_code(shuffle_id, spec):
    """Install a per-shuffle code override from a spec string.  "off"
    pins the exchange uncoded (overriding a global code); None clears
    the override (global code applies).  Malformed specs raise
    ValueError, same contract as configure()."""
    code = parse_code(spec) if spec is not None else _UNSET
    with _LOCK:
        if code is _UNSET:
            _SHUFFLE_CODES.pop(shuffle_id, None)
            return None
        if len(_SHUFFLE_CODES) >= _SHUFFLE_CODES_CAP \
                and shuffle_id not in _SHUFFLE_CODES:
            # bounded: a long-lived service mints shuffle ids forever
            _SHUFFLE_CODES.pop(next(iter(_SHUFFLE_CODES)))
        _SHUFFLE_CODES[shuffle_id] = code
    return code


def shuffle_code(shuffle_id):
    """The code governing one exchange: its registered override when
    the adaptive policy priced it, else the global active code.  Both
    the bucket writer and the fetch path resolve through here, so a
    mixed-code run stays self-consistent end to end."""
    with _LOCK:
        if shuffle_id in _SHUFFLE_CODES:
            return _SHUFFLE_CODES[shuffle_id]
    return _CODE


def clear_shuffle_codes():
    with _LOCK:
        _SHUFFLE_CODES.clear()


_CHOICES = []
_CHOICES_CAP = 256


def record_choice(site, spec, reason, applied, predicted_ms=None):
    """Append one (k,m) policy choice to the bounded in-process
    history — rides /api/health's executor evidence so an operator can
    see the chosen code tracking the observed tails."""
    with _LOCK:
        if len(_CHOICES) >= _CHOICES_CAP:
            del _CHOICES[0]
        _CHOICES.append({"site": site, "code": spec,
                         "reason": reason, "applied": bool(applied),
                         "predicted_ms": predicted_ms})


def code_history():
    with _LOCK:
        return [dict(c) for c in _CHOICES]


def adaptive_enabled():
    """True when the per-exchange policy is allowed to STEER: the
    conf gate is on and the adapt plane is in steering mode."""
    from dpark_tpu import adapt, conf
    return bool(getattr(conf, "CODE_ADAPT", False)) and adapt.steering()


def choose_code(peers, tails, fault_rates=None, static_spec=None):
    """Price (k,m) for one exchange from its recorded peers' fetch-tail
    sketches and observed decode/fault rates.  Pure policy — no store
    access, no side effects — so tests drive it with synthesized tails.

    `peers`: peer labels recorded for this exchange.
    `tails`: {peer: sketch digest (health.Sketch.to_dict shape)}.
    `fault_rates`: {peer or "*": {"repair"/"decode_failures": n}} —
    any observed repair or decode failure escalates (the exchange
    demonstrably consumed parity or lost shards).

    Returns (spec, reason, predicted_ms):
      spec None      -> no history worth acting on; keep the static
                        code (CODE_ADAPT's do-nothing outcome)
      spec "off"     -> all recorded peers tight: drop the parity tax
      spec escalated -> conf.CODE_ADAPT_ESCALATE for this exchange
    predicted_ms is the policy's own fetch-wall forecast (worst-peer
    p50 when escalating — fastest-k dodges the tail — else worst-peer
    p99), recorded against the observed wall by decision point 6."""
    from dpark_tpu import conf
    from dpark_tpu.health import Sketch
    worst = None                      # (ratio, p50_ms, p99_ms, peer)
    for peer in sorted(set(peers or ())):
        sk = Sketch.from_dict((tails or {}).get(peer) or {})
        if sk.n < ADAPT_MIN_SAMPLES or sk.sum <= 0:
            continue
        p50 = sk.quantile(0.50) or 0.0
        p99 = sk.quantile(0.99) or 0.0
        ratio = (p99 / p50) if p50 > 0 else 0.0
        if worst is None or ratio > worst[0]:
            worst = (ratio, p50 * 1e3, p99 * 1e3, peer)
    decoded = sum(int(c.get(k, 0))
                  for c in (fault_rates or {}).values()
                  for k in ("repair", "decode_failures"))
    if worst is None:
        return (None, "no recorded tails for peers %s"
                % (sorted(set(peers or ())),), None)
    ratio, p50_ms, p99_ms, peer = worst
    if decoded or ratio >= ADAPT_TAIL_RATIO:
        spec = getattr(conf, "CODE_ADAPT_ESCALATE", "rs(4,2)")
        why = ("%d decode(s) consumed parity here" % decoded
               if decoded else
               "peer %s tail p99/p50 %.1f >= %.1f" % (peer, ratio,
                                                      ADAPT_TAIL_RATIO))
        return spec, "escalate: " + why, round(p50_ms, 3)
    return ("off", "tight tails: worst peer %s p99/p50 %.1f < %.1f"
            % (peer, ratio, ADAPT_TAIL_RATIO), round(p99_ms, 3))


def _init_from_conf():
    from dpark_tpu import conf
    spec = getattr(conf, "DPARK_SHUFFLE_CODE", "")
    if spec and spec != "off":
        configure(spec)


_init_from_conf()
