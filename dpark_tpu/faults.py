"""Chaos plane: deterministic, conf-driven fault injection at named
sites (ISSUE 5 tentpole).

dpark's promise is lineage-based recovery — FetchFailed resubmits the
parent stage, failed tasks retry with escalation, stragglers speculate
— but recovery code that is never exercised is recovery code that is
assumed, not proven.  This module provides NAMED INJECTION SITES wired
through the shuffle, scheduler, executor, dcn, and checkpoint layers;
a seeded spec activates them deterministically so the same chaos run
replays bit-identically, and the parity suite (tests/test_faults.py)
asserts that jobs complete with results identical to their clean runs.

Spec grammar (the ``DPARK_FAULTS`` env var / ``conf.DPARK_FAULTS``)::

    site:param=value,param=value;site2:...

    DPARK_FAULTS="shuffle.fetch:p=0.2,seed=7;executor.dispatch:nth=3,kind=oom"

Sites (each a choke point the runtime already flows through):

    shuffle.fetch        reduce-side bucket fetch (per replica attempt)
    shuffle.spill_write  spill-run / spill-chunk write (host + device paths)
    shuffle.spill_read   spill-run / spill-chunk read-back
    executor.dispatch    device program dispatch (per program / per wave)
    executor.compile     device program compile (per cache miss)
    dcn.connect          TCP connect to a peer bucket server
    dcn.transfer         bulk-channel chunk transfer (per frame, BOTH
                         sides: a server-side `raise` kills the stream
                         mid-transfer — deterministic peer-death — and
                         a client-side `corrupt` flips payload bytes
                         the frame crc must catch)
    checkpoint.write     checkpoint / snapshot part-file write

Per-site parameters:

    nth=N     fire on exactly the Nth hit of the site (1-based)
    p=X       fire per hit with probability X from a seeded RNG
    seed=S    RNG seed for p= draws (default 0; the draw SEQUENCE is
              deterministic, so a chaos run replays exactly)
    times=T   cap total firings (default: 1 for nth/bare specs,
              unlimited for p=)
    kind=K    what a firing does:
                raise    raise FaultInjected (an OSError) [default]
                enospc   raise OSError(ENOSPC) — disk full
                oom      raise JaxRuntimeError("RESOURCE_EXHAUSTED...")
                corrupt  flip a byte of the site's payload bytes
                         (crc framing downstream must catch it)
                delay    sleep ms= milliseconds, then proceed
                kill     os._exit(137) — the process dies as if
                         `kill -9`-ed at the site: no atexit, no
                         finally blocks, no journal flush.  The crash
                         leg of the chaos certification (ISSUE 20).
    ms=M      delay duration for kind=delay (default 50)

A bare ``site`` (no params) fires once, on the first hit.

Hot-path cost when no plane is configured: one global ``is None``
check per hit.  Thread-safe: sites are hit from fetcher/spill-writer
threads concurrently.
"""

import errno
import os
import threading
import time

__all__ = ["SITES", "FaultInjected", "configure", "active", "hit",
           "stats"]

SITES = ("shuffle.fetch", "shuffle.spill_write", "shuffle.spill_read",
         "executor.dispatch", "executor.compile", "dcn.connect",
         "dcn.transfer", "checkpoint.write")

KINDS = ("raise", "enospc", "oom", "corrupt", "delay", "kill")


class FaultInjected(OSError):
    """An injected fault.  Subclasses OSError so every site treats it
    as the I/O error it simulates: the shuffle fetch wraps it into
    FetchFailed, the dcn connect retry backs off on it, the spill
    writer surfaces it as a task failure."""

    def __init__(self, site, detail=""):
        msg = "injected fault at %s%s" % (site,
                                          " (%s)" % detail if detail
                                          else "")
        super().__init__(errno.EIO, msg)
        self.site = site


def _oom_error():
    """A device-OOM-shaped error of the real class XLA raises, so the
    degradation ladder's production except-clauses are exercised
    verbatim."""
    import jax
    return jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: injected device OOM (chaos plane); "
        "allocating 0B exceeds 0B HBM")


def corrupt_bytes(data):
    """Deterministically flip one byte in the middle of `data`
    (length-preserving — simulates on-disk/in-flight corruption that
    only an integrity check can catch)."""
    buf = bytearray(data)
    if not buf:
        return bytes(buf)
    buf[len(buf) // 2] ^= 0xFF
    return bytes(buf)


class _SiteSpec:
    def __init__(self, site, params):
        import random
        self.site = site
        self.kind = params.get("kind", "raise")
        if self.kind not in KINDS:
            raise ValueError("unknown fault kind %r (one of %s)"
                             % (self.kind, ", ".join(KINDS)))
        self.p = float(params["p"]) if "p" in params else None
        self.nth = int(params["nth"]) if "nth" in params else None
        self.seed = int(params.get("seed", 0))
        self.ms = float(params.get("ms", 50.0))
        if "times" in params:
            self.times = int(params["times"])
        else:
            # nth naturally fires once; a bare spec fires once too so a
            # recovery test terminates; p= runs until told otherwise
            self.times = None if self.p is not None else 1
        self.rng = random.Random(self.seed)
        self.hits = 0
        self.fired = 0

    def should_fire(self):
        """Count a hit; decide (deterministically) whether to fire."""
        self.hits += 1
        if self.times is not None and self.fired >= self.times:
            return False
        if self.nth is not None:
            fire = self.hits == self.nth
        elif self.p is not None:
            # the draw happens on EVERY hit so the firing pattern is a
            # pure function of (seed, hit index), independent of caps
            fire = self.rng.random() < self.p
        else:
            fire = True
        if fire:
            self.fired += 1
        return fire

    def describe(self):
        out = {"kind": self.kind, "hits": self.hits,
               "fired": self.fired}
        if self.p is not None:
            out["p"] = self.p
            out["seed"] = self.seed
        if self.nth is not None:
            out["nth"] = self.nth
        return out


class FaultPlane:
    def __init__(self, specs):
        self.specs = specs              # site -> _SiteSpec
        self._lock = threading.Lock()

    def hit(self, site, payload=None):
        spec = self.specs.get(site)
        if spec is None:
            return payload
        with self._lock:
            fire = spec.should_fire()
        if not fire:
            return payload
        if spec.kind == "delay":
            time.sleep(spec.ms / 1000.0)
            return payload
        if spec.kind == "kill":
            # hard process death, bypassing atexit/finally — the only
            # honest way to certify crash recovery is to never give the
            # dying process a chance to tidy up
            os._exit(137)
        if spec.kind == "corrupt":
            if payload is None:
                # the site carries no byte payload: corruption
                # degenerates to a failure, not a silent no-op
                raise FaultInjected(site, "corrupt at a payload-less "
                                          "site")
            return corrupt_bytes(payload)
        if spec.kind == "oom":
            raise _oom_error()
        if spec.kind == "enospc":
            raise OSError(errno.ENOSPC,
                          "injected fault at %s (disk full)" % site)
        raise FaultInjected(site, "kind=raise")

    def stats(self):
        with self._lock:
            return {site: spec.describe()
                    for site, spec in self.specs.items()}


def parse_spec(text):
    """``site:k=v,k=v;site2:...`` -> {site: _SiteSpec}.  Unknown sites
    and malformed params raise ValueError — a chaos run with a typo'd
    site silently injecting nothing would "prove" recovery it never
    exercised."""
    specs = {}
    for part in (text or "").split(";"):
        part = part.strip()
        if not part:
            continue
        site, _, rest = part.partition(":")
        site = site.strip()
        if site not in SITES:
            raise ValueError("unknown fault site %r (one of %s)"
                             % (site, ", ".join(SITES)))
        params = {}
        for kv in rest.split(","):
            kv = kv.strip()
            if not kv:
                continue
            k, sep, v = kv.partition("=")
            if not sep:
                raise ValueError("malformed fault param %r in %r"
                                 % (kv, part))
            params[k.strip()] = v.strip()
        specs[site] = _SiteSpec(site, params)
    return specs


_PLANE = None


def configure(spec=None):
    """Install the chaos plane from a spec string (None/"" clears it).
    Counters start fresh — configuring the same spec twice replays the
    same firing sequence.  Returns the installed FaultPlane or None."""
    global _PLANE
    if not spec:
        _PLANE = None
        return None
    _PLANE = FaultPlane(parse_spec(spec))
    return _PLANE


def active():
    """True when a chaos plane with at least one site is installed."""
    return _PLANE is not None and bool(_PLANE.specs)


def site_active(site):
    """True when the installed plane names `site`.  Fetch paths use
    this to decide whether per-shard chaos routing is worth racing
    through a thread pool (an injected delay must be able to LOSE the
    fastest-k race) or can run inline on the hot path."""
    plane = _PLANE
    return plane is not None and site in plane.specs


def hit(site, payload=None):
    """Record a hit at `site`.  May raise (raise/enospc/oom kinds),
    sleep (delay), or return a corrupted copy of `payload` (corrupt);
    otherwise returns `payload` unchanged.  No-op without a plane."""
    plane = _PLANE
    if plane is None:
        return payload
    return plane.hit(site, payload)


def stats():
    """{site: {hits, fired, kind, ...}} for the installed plane (empty
    when inactive) — the bench JSON's `faults` section."""
    plane = _PLANE
    if plane is None:
        return {}
    return plane.stats()


def _init_from_conf():
    from dpark_tpu import conf
    spec = getattr(conf, "DPARK_FAULTS", "")
    if spec:
        configure(spec)


_init_from_conf()
