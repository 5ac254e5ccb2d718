"""Shared helpers: compression codec selection, atomic file writes, call-site
extraction for job naming.

Reference parity: dpark/utils/__init__.py (codec selection lz4-else-zlib),
dpark/utils/atomic_file.py (tmp+rename), dpark/utils/frame.py (call-site
scope names).  SURVEY.md section 2.1.
"""

import os
import sys
import zlib
import tempfile
import contextlib

try:
    import lz4.frame as _lz4

    def compress(data):
        return _lz4.compress(data)

    def decompress(data):
        return _lz4.decompress(data)

    CODEC = "lz4"
except ImportError:
    def compress(data):
        return zlib.compress(data, 1)

    def decompress(data):
        return zlib.decompress(data)

    CODEC = "zlib"


@contextlib.contextmanager
def atomic_file(path, mode="wb", fsync=True):
    """Write to a temp file in the same dir, fsync, rename over `path`.

    Reference parity: dpark/utils/atomic_file.py.

    `fsync=False` keeps the no-partial-file guarantee (tmp+rename)
    but skips the durability barrier — for outputs that are
    recomputable through lineage anyway (shuffle bucket files), where
    the per-file fsync dominates the bucket write on slow filesystems.
    """
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp-" + os.path.basename(path))
    f = os.fdopen(fd, mode)
    try:
        yield f
        f.flush()
        if fsync:
            os.fsync(f.fileno())
        f.close()
        os.rename(tmp, path)
    except BaseException:
        f.close()
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def apply_platform_override():
    """The CPU-test route: DPARK_TPU_PLATFORM=cpu asks jax for that
    platform before its first backend init (with
    XLA_FLAGS=--xla_force_host_platform_device_count=N, a virtual
    N-device mesh)."""
    plat = os.environ.get("DPARK_TPU_PLATFORM")
    if plat:
        try:
            import jax
        except ImportError:     # the pure-host masters run without jax
            return
        jax.config.update("jax_platforms", plat)


def user_call_site(depth_limit=12):
    """Return 'file:lineno' of the first stack frame outside dpark_tpu.

    Used for job/stage naming so progress lines read like user code.
    Reference parity: dpark/utils/frame.py.
    """
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    frame = sys._getframe(1)
    for _ in range(depth_limit):
        if frame is None:
            break
        fn = frame.f_code.co_filename
        if not os.path.abspath(fn).startswith(pkg_dir):
            return "%s:%d" % (os.path.basename(fn), frame.f_lineno)
        frame = frame.f_back
    return "<unknown>"


def izip(*its):
    return zip(*its)


def builtin_globals_ok(f, code=None):
    """Every global `f`'s bytecode references still resolves to the
    builtin of that name — the proof obligation shared by all the
    bytecode-template classifiers (fuse.classify_merge/classify_segagg,
    dstream's state-update idiom): a local `sum` shadowing the builtin
    defeats template equality."""
    import builtins
    code = code if code is not None else f.__code__
    fglobals = f.__globals__
    fbuiltins = fglobals.get("__builtins__", builtins)
    for g in code.co_names:
        expected = getattr(builtins, g, None)
        if expected is None:
            return False
        if g in fglobals:
            if fglobals[g] is not expected:
                return False
        elif isinstance(fbuiltins, dict):
            if fbuiltins.get(g) is not expected:
                return False
        elif getattr(fbuiltins, g, None) is not expected:
            return False
    return True


# ---------------------------------------------------------------------------
# crc-framed JSON lines — the shared on-disk format of the adapt store
# and the trace spool: each line is b"<crc32 hex> <canonical json>\n",
# appended with a single O_APPEND write so concurrent processes
# interleave whole lines, and a torn/corrupt line skips at load.
# ---------------------------------------------------------------------------

def frame_jsonl(rec):
    """One record -> one framed line (newline included)."""
    import json
    from dpark_tpu.shuffle import spill_crc
    payload = json.dumps(rec, sort_keys=True,
                         separators=(",", ":")).encode("utf-8")
    return b"%08x %s\n" % (spill_crc(payload), payload)


def unframe_jsonl(raw):
    """Framed bytes -> ([dict records], skipped line count).  Lines
    failing the crc, the JSON parse, or the dict shape skip — never an
    error (a torn concurrent append must not poison the load)."""
    import json
    from dpark_tpu.shuffle import spill_crc
    recs, skipped = [], 0
    for line in raw.split(b"\n"):
        if not line.strip():
            continue
        head, _, payload = line.partition(b" ")
        try:
            if int(head, 16) != spill_crc(payload):
                raise ValueError("crc mismatch")
            rec = json.loads(payload.decode("utf-8"))
            if not isinstance(rec, dict):
                raise ValueError("non-dict record")
        except Exception:
            skipped += 1
            continue
        recs.append(rec)
    return recs, skipped
