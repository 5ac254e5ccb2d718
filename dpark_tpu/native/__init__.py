"""ctypes bindings for the C++ host kernels (dpark_tpu/native/native.cpp).

Reference parity: replaces dpark's Cython portable_hash + C crc32c + native
codec dependencies (SURVEY.md section 2.6).  The shared library is built
lazily with g++ on first import and cached next to the source; every
binding degrades to a pure-Python fallback when no compiler is available.
"""

import ctypes
import os
import subprocess
import threading

import numpy as np

from dpark_tpu.utils.log import get_logger

logger = get_logger("native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cpp")
_SO = os.path.join(_HERE, "libdpark_native.so")
_lock = threading.Lock()
_lib = None
_tried = False


def _build():
    import tempfile
    fd, tmp = tempfile.mkstemp(prefix=".build-", suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        cmd = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
               "-o", tmp, _SRC]
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, _SO)        # atomic rename: concurrent builds safe
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def get_lib():
    """The loaded shared library, or None when unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                _build()
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.CalledProcessError) as e:
            stderr = getattr(e, "stderr", None) or b""
            logger.warning(
                "native library unavailable (%s); pure-Python "
                "fallbacks in use%s", e,
                "\n" + stderr.decode("utf-8", "replace")
                if stderr else "")
            return None
        lib.phash_i64.restype = ctypes.c_uint32
        lib.phash_i64.argtypes = [ctypes.c_int64]
        lib.phash_i64_array.restype = None
        lib.phash_i64_array.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
        lib.phash_i64_cols.restype = None
        lib.phash_i64_cols.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p]
        lib.phash_bytes.restype = ctypes.c_uint32
        lib.phash_bytes.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.crc32c.restype = ctypes.c_uint32
        lib.crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                               ctypes.c_uint32]
        lib.split_lines.restype = ctypes.c_int64
        lib.split_lines.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                    ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_int64]
        lib.tokendict_new.restype = ctypes.c_void_p
        lib.tokendict_free.argtypes = [ctypes.c_void_p]
        lib.tokendict_size.restype = ctypes.c_int64
        lib.tokendict_size.argtypes = [ctypes.c_void_p]
        lib.tokendict_encode.restype = ctypes.c_int64
        lib.tokendict_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64]
        lib.tokendict_encode_sep.restype = ctypes.c_int64
        lib.tokendict_encode_sep.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_uint8, ctypes.c_void_p, ctypes.c_int64]
        lib.tokendict_get.restype = ctypes.c_int64
        lib.tokendict_get.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64]
        lib.tokendict_put.restype = ctypes.c_int64
        lib.tokendict_put.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        lib.tokendict_merge.restype = ctypes.c_int64
        lib.tokendict_merge.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.csv_scan.restype = ctypes.c_int64
        lib.csv_scan.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint8,
            ctypes.c_uint8, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_void_p,
            ctypes.c_int64]
        _lib = lib
        return _lib


def phash_i64_bulk(keys):
    """uint32 portable hash of an int64 numpy array (C++ when available)."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    lib = get_lib()
    out = np.empty(keys.shape, dtype=np.uint32)
    if lib is not None:
        lib.phash_i64_array(keys.ctypes.data, out.ctypes.data, keys.size)
        return out
    from dpark_tpu.utils.phash import portable_hash
    for i, k in enumerate(keys.ravel()):
        out.ravel()[i] = portable_hash(int(k))
    return out


def phash_i64_cols_bulk(cols):
    """Composite (tuple-key) uint32 portable hash over N int64 column
    arrays — C++ when available, phash_np_cols otherwise.  Row i hashes
    as portable_hash((cols[0][i], ..., cols[-1][i]))."""
    cols = [np.ascontiguousarray(c, dtype=np.int64) for c in cols]
    lib = get_lib()
    if lib is not None and len(cols) >= 1:
        n = cols[0].size
        flat = np.concatenate([c.ravel() for c in cols]) \
            if len(cols) > 1 else cols[0].ravel()
        flat = np.ascontiguousarray(flat, dtype=np.int64)
        out = np.empty(n, dtype=np.uint32)
        lib.phash_i64_cols(flat.ctypes.data, len(cols), n,
                           out.ctypes.data)
        return out.reshape(cols[0].shape)
    from dpark_tpu.utils.phash import phash_np_cols
    return phash_np_cols(cols)


def crc32c(data, crc=0):
    lib = get_lib()
    if lib is not None:
        return lib.crc32c(bytes(data), len(data), crc)
    # pure-Python table fallback
    global _py_table
    if "_py_table" not in globals():
        t = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if c & 1 else (c >> 1)
            t.append(c)
        globals()["_py_table"] = t
    c = crc ^ 0xFFFFFFFF
    for b in bytes(data):
        c = _py_table[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def split_lines(buf):
    """(starts, lens) int64 arrays for the lines of `buf` (bytes)."""
    lib = get_lib()
    n = len(buf)
    if lib is not None:
        max_lines = buf.count(b"\n") + 1
        starts = np.empty(max_lines, dtype=np.int64)
        lens = np.empty(max_lines, dtype=np.int64)
        cnt = lib.split_lines(buf, n, starts.ctypes.data,
                              lens.ctypes.data, max_lines)
        return starts[:cnt], lens[:cnt]
    starts, lens = [], []
    off = 0
    for line in buf.split(b"\n"):
        body = line[:-1] if line.endswith(b"\r") else line
        if off < n or body:
            starts.append(off)
            lens.append(len(body))
        off += len(line) + 1
    if buf.endswith(b"\n") and starts and lens[-1] == 0 \
            and starts[-1] >= n:
        starts.pop()
        lens.pop()
    return (np.array(starts, dtype=np.int64),
            np.array(lens, dtype=np.int64))


class TokenDict:
    """Exact string->dense-id dictionary encoder (C++ hashmap inside)."""

    def __init__(self):
        self._lib = get_lib()
        if self._lib is not None:
            self._h = self._lib.tokendict_new()
        else:
            self._h = None
            self._map = {}
            self._rev = []

    def __del__(self):
        if getattr(self, "_lib", None) is not None \
                and getattr(self, "_h", None):
            self._lib.tokendict_free(self._h)
            self._h = None

    def __len__(self):
        if self._h:
            return self._lib.tokendict_size(self._h)
        return len(self._rev)

    def encode(self, buf, sep=None):
        """Tokenize bytes -> int64 id array.

        sep=None: whitespace runs (str.split() over ASCII bytes).
        sep=<1-byte str/bytes>: per \\n-line (trailing \\r stripped,
        TextFileRDD's rule), split on EVERY separator occurrence —
        exact str.split(sep) semantics incl. empty fields."""
        if isinstance(buf, str):
            buf = buf.encode("utf-8")
        if sep is not None and isinstance(sep, str):
            sep = sep.encode("utf-8")
        if self._h:
            if sep is None:
                max_tokens = max(1, len(buf) // 2 + 1)
                out = np.empty(max_tokens, dtype=np.int64)
                cnt = self._lib.tokendict_encode(
                    self._h, buf, len(buf), out.ctypes.data,
                    max_tokens)
                return out[:cnt]
            # fields per line = seps + 1; lines <= \n count + 1
            max_tokens = buf.count(b"\n") + buf.count(sep) + 2
            out = np.empty(max_tokens, dtype=np.int64)
            cnt = self._lib.tokendict_encode_sep(
                self._h, buf, len(buf), sep[0], out.ctypes.data,
                max_tokens)
            return out[:cnt]
        ids = []
        if sep is None:
            toks = buf.split()
        else:
            toks = []
            lines = buf.split(b"\n")
            if lines and lines[-1] == b"":
                lines.pop()
            for ln in lines:
                toks.extend(ln.rstrip(b"\r").split(sep))
        for tok in toks:
            tid = self._map.get(tok)
            if tid is None:
                tid = len(self._rev)
                self._map[tok] = tid
                self._rev.append(tok)
            ids.append(tid)
        return np.array(ids, dtype=np.int64)

    def put(self, s):
        """Encode ONE exact string (it may contain whitespace) -> id."""
        if isinstance(s, str):
            s = s.encode("utf-8")
        if self._h:
            return self._lib.tokendict_put(self._h, s, len(s))
        tid = self._map.get(s)
        if tid is None:
            tid = len(self._rev)
            self._map[s] = tid
            self._rev.append(s)
        return tid

    def decode(self, tid):
        return self.raw(tid).decode("utf-8", "replace")

    def raw(self, tid):
        """The EXACT bytes of token `tid` (decode() lossily re-encodes
        invalid utf-8)."""
        if self._h:
            buf = ctypes.create_string_buffer(1 << 16)
            n = self._lib.tokendict_get(self._h, int(tid), buf, len(buf))
            if n < 0:
                raise KeyError(tid)
            return buf.raw[:n]
        return self._rev[tid]

    def merge_from(self, other):
        """Merge `other`'s vocabulary into this dict in other-id order;
        returns remap (np.int64, len(other)) with remap[i] = this
        dict's id for other's token i.  C++ loop when both dicts are
        native — the parallel-ingest merge must not walk tokens in
        Python."""
        m = len(other)
        remap = np.empty(m, dtype=np.int64)
        if self._h and other._h:
            self._lib.tokendict_merge(self._h, other._h,
                                      remap.ctypes.data)
            return remap
        for i in range(m):
            remap[i] = self.put(other.raw(i))
        return remap


class CsvScanner:
    """Incremental CSV record-boundary scanner (exact RFC4180-style
    state machine, C++ with a pure-Python fallback): feed byte chunks,
    collect record-start offsets >= a moving target stepped by `step`.
    A bare quote inside an unquoted field never flips state — the case
    where a quote-parity heuristic would corrupt records."""

    def __init__(self, step, quote=b'"', delim=b","):
        self.step = step
        self.quote = quote[0]
        self.delim = delim[0]
        self.state = 2                   # field_start at file start
        self.target = step
        self.pos = 0
        self.bounds = []
        self._lib = get_lib()

    def feed(self, chunk):
        if not chunk:
            return                       # state must survive empty reads
        if self._lib is not None:
            # exact upper bound: one boundary per newline, never capped
            max_out = chunk.count(b"\n") + 2
            out = np.empty(max_out, dtype=np.int64)
            st = ctypes.c_int64()
            tg = ctypes.c_int64()
            cnt = self._lib.csv_scan(
                chunk, len(chunk), self.quote, self.delim, self.state,
                ctypes.byref(st), self.pos, self.target, self.step,
                ctypes.byref(tg), out.ctypes.data, max_out)
            self.state = st.value
            self.target = tg.value
            self.bounds.extend(out[:cnt].tolist())
        else:
            in_q = bool(self.state & 1)
            fstart = bool(self.state & 2)
            pending = bool(self.state & 4)
            q, d = self.quote, self.delim
            if not in_q and not pending \
                    and bytes([q]) not in chunk:
                # vectorized fast path: no quotes in this chunk means
                # every newline ends a record
                npos = np.flatnonzero(
                    np.frombuffer(chunk, np.uint8) == 0x0A)
                for off in (npos + self.pos + 1).tolist():
                    if off >= self.target:
                        self.bounds.append(off)
                        self.target = off + self.step
                last = chunk[-1:]
                self.state = 2 if last in (b"\n", bytes([d])) else 0
                self.pos += len(chunk)
                return
            for i, c in enumerate(chunk):
                if pending:
                    pending = False
                    if c == q:
                        continue
                    in_q = False
                if in_q:
                    if c == q:
                        pending = True
                    continue
                if c == 0x0A:
                    off = self.pos + i + 1
                    if off >= self.target:
                        self.bounds.append(off)
                        self.target = off + self.step
                    fstart = True
                elif c == d:
                    fstart = True
                elif c == q and fstart:
                    in_q = True
                    fstart = False
                else:
                    fstart = False
            self.state = ((1 if in_q else 0) | (2 if fstart else 0)
                          | (4 if pending else 0))
        self.pos += len(chunk)
