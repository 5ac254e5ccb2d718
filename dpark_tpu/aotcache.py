"""Persistent AOT executable cache (ISSUE 17 tentpole; ROADMAP item 2).

PR 9 made compile amortization a property of one resident process:
`_ProgramCache` means a re-submitted DAG compiles nothing — until the
process dies.  A JobServer restart (deploy, crash, autoscale) is a
cold-start storm: every replica re-pays every compile, exactly the
restart-latency tail the reference dpark's resident-worker design was
meant to hide.  This module is the second tier: compiled executables
serialize through jax's AOT export path (``jax.experimental.
serialize_executable``) into an on-disk cache a FRESH process loads
instead of compiling.

Entry format (one file per program, ``<disk_key>.aot``)::

    <crc32 hex> <canonical json header>\\n      # utils.frame_jsonl
    <crc32 hex> <payload length hex>\\n
    <pickled (payload, in_tree, out_tree)>      # serialize() triple

The header carries the full identity — disk key, adapt signature,
jax/jaxlib versions, backend platform/device topology, x64 flag — and
is RE-VERIFIED at load: a mismatch on any field skips the entry
silently (a cache dir surviving a jax upgrade must never feed a stale
executable to the wrong runtime).  Files are written tmp+rename
(``utils.atomic_file``) and an ``index.jsonl`` of crc-framed lines is
appended with single O_APPEND writes — the adapt-store idioms — so
ONE cache directory is safely shared across service replicas and
concurrent writers: readers see whole entries or no entry, torn index
lines skip at load, and corruption always means "fall back to
compile", never an error.

Modes (``DPARK_AOT_CACHE`` / conf.AOT_CACHE):

  off   no plane installed.  The program-cache seam costs exactly one
        module-global load + ``is None`` check — the same off-mode
        contract as the faults/trace/health/ledger/lockcheck planes,
        machine-checked by the ``plane-contract`` dlint rule.
  read  memory misses consult the disk tier but never write — a
        replica trusting a cache directory it does not own.
  on    read + newly compiled programs store back, and eviction under
        DPARK_PROGRAM_CACHE_MAX writes back before dropping.

Boot warming: a starting JobServer ranks the index by the adapt
store's observed cost profiles (compile ms x hit count — the same
observed-cost-steers-work framing the coded-shuffle plane uses) and
deserializes the hottest entries into a preload map under a
``DPARK_AOT_WARM_BUDGET_MS`` deadline, so the first submission after
a restart starts from loaded executables: zero backend compiles.
"""

import os
import pickle
import threading
import time

from dpark_tpu import conf, locks
from dpark_tpu.utils import atomic_file, frame_jsonl, unframe_jsonl
from dpark_tpu.utils.log import get_logger

logger = get_logger("aotcache")

__all__ = ["MODES", "AotCachePlane", "AotProgram", "configure",
           "active", "plane", "stats", "set_current_sig",
           "version_key"]

MODES = ("off", "read", "on")

# entry-format generation: bump on any layout change so old dirs skip
FORMAT = "dpark-aot-1"

INDEX_FILE = "index.jsonl"

COUNTERS = ("loads", "load_misses", "load_errors", "version_skips",
            "stores", "store_errors", "evict_writebacks", "warmed",
            "warm_hits", "fallbacks")

_PLANE = None
_tls = threading.local()


def _crc(data):
    from dpark_tpu.shuffle import spill_crc
    return spill_crc(data)


def version_key():
    """The compatibility half of an entry's identity: a serialized
    executable is machine code, only as portable as the stack that
    produced it.  jax/jaxlib versions, backend platform, device count
    and kinds, and the x64 flag — any drift invalidates (by missing
    the keyed filename AND by the header re-check at load)."""
    import jax
    try:
        import jaxlib
        jl = str(getattr(jaxlib, "__version__", "?"))
    except Exception:
        jl = "?"
    devs = jax.devices()
    return {
        "fmt": FORMAT,
        "jax": str(jax.__version__),
        "jaxlib": jl,
        "platform": str(devs[0].platform) if devs else "?",
        "ndev": len(devs),
        "kinds": ",".join(sorted({str(getattr(d, "device_kind", "?"))
                                  for d in devs})),
        "x64": bool(jax.config.jax_enable_x64),
    }


class AotCachePlane:
    """One process's handle on a shared on-disk executable cache."""

    def __init__(self, mode, cache_dir):
        self.mode = mode
        self.dir = cache_dir
        self._mu = locks.named_lock("aot.store")
        self._counters = {k: 0 for k in COUNTERS}
        self._warm = {}          # disk_key -> preloaded Compiled
        self._ver = None         # version_key(), computed lazily (the
        #                          first use may be the first jax
        #                          backend touch of the process)

    # -- identity --------------------------------------------------------
    def _version(self):
        ver = self._ver
        if ver is None:
            ver = self._ver = version_key()
        return ver

    def disk_key(self, mem_key):
        """Entry filename stem: the cross-process-stable hash of the
        executor's full program-cache key tuple combined with the
        version/topology key (adapt.stable_key strips transient
        ``at 0x...`` addresses, hashes code objects by bytecode)."""
        from dpark_tpu import adapt
        ver = self._version()
        return adapt.stable_key((mem_key, tuple(sorted(ver.items()))))

    def _entry_path(self, dk):
        return os.path.join(self.dir, dk + ".aot")

    def _bump(self, name, n=1):
        with self._mu:
            self._counters[name] += n

    # -- store -----------------------------------------------------------
    def store(self, dk, compiled, sig=None, compile_ms=0.0,
              reason="store"):
        """Serialize one compiled executable to ``<dk>.aot`` (tmp +
        rename) and append its index line.  Mode-gated; never raises
        (a program jax cannot serialize simply stays memory-only)."""
        if self.mode != "on" or dk is None:
            return False
        from dpark_tpu import trace
        try:
            with trace.span("aot.store", "aot", key=dk, sig=sig,
                            reason=reason):
                from jax.experimental import serialize_executable
                payload, in_tree, out_tree = \
                    serialize_executable.serialize(compiled)
                blob = pickle.dumps((payload, in_tree, out_tree),
                                    protocol=pickle.HIGHEST_PROTOCOL)
                header = dict(self._version())
                # the devices the executable was compiled FOR: a
                # sliced mesh (tpu:1 on a four-chip host) must load
                # onto the same devices, not onto every device of the
                # backend (deserialize_and_load's default)
                header.update(key=dk, sig=sig,
                              compile_ms=round(float(compile_ms), 3),
                              nbytes=len(blob),
                              created=round(time.time(), 3),
                              devices=[int(d.id) for d in compiled
                                       .runtime_executable()
                                       .local_devices()])
                with atomic_file(self._entry_path(dk)) as f:
                    f.write(frame_jsonl(header))
                    f.write(b"%08x %08x\n" % (_crc(blob), len(blob)))
                    f.write(blob)
                self._append_index({"k": dk, "sig": sig,
                                    "compile_ms": round(
                                        float(compile_ms), 3),
                                    "nbytes": len(blob)})
            self._bump("stores")
            if reason == "evict":
                self._bump("evict_writebacks")
            return True
        except Exception as e:
            logger.debug("aot store failed for %s: %s", dk, e)
            self._bump("store_errors")
            return False

    def _append_index(self, rec):
        """One crc-framed line, one O_APPEND write: concurrent
        replicas interleave whole lines (the adapt-store idiom)."""
        line = frame_jsonl(rec)
        fd = os.open(os.path.join(self.dir, INDEX_FILE),
                     os.O_APPEND | os.O_CREAT | os.O_WRONLY, 0o644)
        try:
            os.write(fd, line)
        finally:
            os.close(fd)

    def index(self):
        """{disk_key: latest index record}.  Torn/corrupt lines skip;
        duplicate keys (same program re-stored by another replica)
        fold latest-wins."""
        try:
            with open(os.path.join(self.dir, INDEX_FILE), "rb") as f:
                raw = f.read()
        except OSError:
            return {}
        recs, _ = unframe_jsonl(raw)
        out = {}
        for r in recs:
            dk = r.get("k")
            if dk:
                out[str(dk)] = r
        return out

    # -- load ------------------------------------------------------------
    def load(self, dk, sig=None):
        """The disk tier: a boot-warm preload if one is pending for
        this key, else read + verify + deserialize the entry file.
        None on any miss or defect — the caller compiles."""
        with self._mu:
            exe = self._warm.pop(dk, None)
            if exe is not None:
                self._counters["warm_hits"] += 1
        if exe is not None:
            return exe
        from dpark_tpu import trace
        with trace.span("aot.load", "aot", key=dk, sig=sig):
            exe = self._load_entry(dk)
        self._bump("loads" if exe is not None else "load_misses")
        return exe

    def _load_entry(self, dk):
        """Read one entry file; None on ANY defect — missing file,
        torn header, version/topology drift, payload crc or length
        mismatch, unpicklable blob, deserialize failure.  Corruption
        means recompute, never an error (the adapt-store contract)."""
        try:
            with open(self._entry_path(dk), "rb") as f:
                raw = f.read()
        except OSError:
            return None
        try:
            head, _, rest = raw.partition(b"\n")
            recs, skipped = unframe_jsonl(head + b"\n")
            if skipped or not recs:
                raise ValueError("corrupt header")
            header = recs[0]
            for k, v in self._version().items():
                if header.get(k) != v:
                    self._bump("version_skips")
                    return None
            crcline, _, blob = rest.partition(b"\n")
            crc_hex, _, len_hex = crcline.partition(b" ")
            if len(blob) != int(len_hex, 16):
                raise ValueError("truncated payload")
            if int(crc_hex, 16) != _crc(blob):
                raise ValueError("payload crc mismatch")
            payload, in_tree, out_tree = pickle.loads(blob)
            import jax
            from jax.experimental import serialize_executable
            by_id = {d.id: d for d in jax.devices()}
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in header["devices"]])
        except Exception as e:
            logger.debug("aot entry %s unusable: %s", dk, e)
            self._bump("load_errors")
            return None

    # -- boot warming ----------------------------------------------------
    def ranked_entries(self, idx=None, costs=None):
        """Index records, hottest first: score = the adapt store's
        observed compile ms x hit count for the entry's signature
        (ties and unprofiled entries fall back to the compile_ms the
        storing process measured)."""
        if idx is None:
            idx = self.index()
        if costs is None:
            from dpark_tpu import adapt
            costs = adapt.program_costs()

        def _score(rec):
            prof = costs.get(str(rec.get("sig"))) or {}
            ms = float(prof.get("compile_ms", 0.0) or 0.0)
            hits = float(prof.get("hits", 0.0) or 0.0)
            return (ms * max(hits, 1.0),
                    float(rec.get("compile_ms", 0.0) or 0.0))

        return sorted(idx.values(), key=_score, reverse=True)

    def warm(self, budget_ms=None, costs=None):
        """Deserialize the hottest entries into the preload map under
        a wall-clock deadline; the first proxy resolution for each key
        then starts from a loaded executable.  Returns a summary for
        the boot log / service stats."""
        t0 = time.time()
        if budget_ms is None:
            budget_ms = float(getattr(conf, "AOT_WARM_BUDGET_MS", 0.0)
                              or 0.0)
        ranked = self.ranked_entries(costs=costs)
        deadline = t0 + budget_ms / 1e3
        from dpark_tpu import trace
        warmed = 0
        for rec in ranked:
            if time.time() >= deadline:
                break
            dk = str(rec.get("k"))
            with self._mu:
                pending = dk in self._warm
            if pending:
                continue
            with trace.span("aot.warm", "aot", key=dk,
                            sig=rec.get("sig")):
                exe = self._load_entry(dk)
            if exe is None:
                continue
            with self._mu:
                self._warm[dk] = exe
                self._counters["warmed"] += 1
            warmed += 1
        return {"warmed": warmed, "entries": len(ranked),
                "ms": round((time.time() - t0) * 1e3, 1),
                "budget_ms": budget_ms}

    # -- the seam --------------------------------------------------------
    def wrap(self, key, jitted):
        """Wrap one freshly inserted program in the lazy two-tier
        proxy (idempotent: re-inserting an already-wrapped value keeps
        its resolved executable)."""
        if isinstance(jitted, AotProgram):
            return jitted
        return AotProgram(self, key, jitted,
                          getattr(_tls, "sig", None))

    def stats(self):
        with self._mu:
            out = dict(self._counters)
            out["mode"] = self.mode
            out["warm_pending"] = len(self._warm)
        return out


class AotProgram:
    """Lazy two-tier program handle the executor's ``_ProgramCache``
    stores instead of the raw ``jax.jit`` callable.

    The first call resolves the executable: boot-warm preload ->
    disk load -> (mode ``on``) AOT compile via ``jitted.lower(*args)
    .compile()`` with store-back.  The raw jitted callable rides
    along as the permanent fallback — any executable-level failure
    (arg shape/dtype drift vs. the serialized program, a backend that
    refuses the payload) drops the executable and falls back to the
    live jit path, bit-identical by construction.
    """

    __slots__ = ("_plane", "_key", "_jitted", "_sig", "_exe",
                 "_resolved", "_stored", "_dk", "_mu")

    def __init__(self, plane, key, jitted, sig=None):
        self._plane = plane
        self._key = key
        self._jitted = jitted
        self._sig = sig
        self._exe = None
        self._resolved = False
        self._stored = False
        self._dk = None
        self._mu = threading.Lock()

    def lower(self, *args, **kw):
        # the ledger's cost capture prices programs via .lower() — a
        # host-side re-trace of the LIVE jit, never the executable
        return self._jitted.lower(*args, **kw)

    def __call__(self, *args):
        exe = self._exe
        if exe is None and not self._resolved:
            exe = self._resolve(args)
        if exe is not None:
            try:
                return exe(*args)
            except Exception:
                # executable-level drift: fall back for good (the jit
                # path recompiles under its own cache and stays
                # correct for every later shape)
                self._exe = None
                self._plane._bump("fallbacks")
        return self._jitted(*args)

    def _resolve(self, args):
        with self._mu:
            if self._resolved:
                return self._exe
            plane = self._plane
            exe = None
            try:
                dk = self._dk = plane.disk_key(self._key)
                exe = plane.load(dk, self._sig)
                if exe is not None:
                    self._stored = True        # it came FROM disk
                    self._note(0.0)
                elif plane.mode == "on":
                    t0 = time.time()
                    exe = self._jitted.lower(*args).compile()
                    ms = (time.time() - t0) * 1e3
                    self._stored = plane.store(dk, exe, self._sig, ms)
                    self._note(ms)
                else:
                    self._note(0.0)
            except Exception as e:
                logger.debug("aot resolve failed for %r: %s",
                             self._sig or self._key, e)
                exe = None
            self._exe = exe
            self._resolved = True
            return exe

    def _note(self, compile_ms):
        """Fold this resolution into the adapt store's program profile
        (hits accumulate, compile_ms smooths) — the observed-cost
        signal boot warming ranks by."""
        if not self._sig:
            return
        from dpark_tpu import adapt
        prof = {"hits": 1}
        if compile_ms:
            prof["compile_ms"] = round(compile_ms, 3)
        adapt.record_program_cost(self._sig, prof)

    def writeback(self):
        """Eviction hook: persist a resolved-but-unstored executable
        before the memory tier drops it (a later re-insert then loads
        instead of compiling).  store() carries the mode gate."""
        exe = self._exe
        if exe is None or self._stored:
            return False
        ok = self._plane.store(self._dk, exe, self._sig, 0.0,
                               reason="evict")
        self._stored = bool(ok)
        return ok


# ---------------------------------------------------------------------------
# module seams (plane-contract shapes, registered in
# analysis/concurrency.py PLANE_SEAMS)
# ---------------------------------------------------------------------------

def set_current_sig(sig):
    """Stamp the adapt signature tuple (progid, shapeclass) program
    insertions on THIS thread belong to (None clears) — the executor
    calls this where it stamps trace.set_compile_sig.  One global
    load + ``is None`` check when the plane is off."""
    if _PLANE is None:
        return None
    _tls.sig = "%s|%s" % (sig[0], sig[1]) if sig else None


def stats():
    """Hot counters + mode for /metrics and the web UI; None when the
    plane is off."""
    plane = _PLANE
    if plane is None:
        return None
    return plane.stats()


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def configure(mode=None, cache_dir=None):
    """Install (read/on) or clear (off) the process plane.  None
    reads conf.AOT_CACHE.  Returns the installed plane or None."""
    global _PLANE
    if mode is None:
        mode = str(getattr(conf, "AOT_CACHE", "off") or "off")
    mode = str(mode).strip().lower()
    if mode in ("", "0", "none", "disable", "disabled"):
        mode = "off"
    if mode not in MODES:
        raise ValueError("DPARK_AOT_CACHE=%r (expected off|read|on)"
                         % mode)
    if mode == "off":
        _PLANE = None
        return None
    _PLANE = AotCachePlane(mode, cache_dir or conf.AOT_CACHE_DIR)
    return _PLANE


def active():
    return _PLANE is not None


def plane():
    return _PLANE


def _init_from_conf():
    m = str(getattr(conf, "AOT_CACHE", "off") or "off")
    if m not in ("off", ""):
        configure(m)


_init_from_conf()
