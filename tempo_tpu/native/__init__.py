"""Native runtime loader: compiles + binds the C++ hot paths via ctypes.

`available()` is False (and every helper falls back to numpy/python) when
g++ or the compiled library is missing — the framework never hard-requires
the native layer, it just gets faster with it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading

import numpy as np

_LIB = None
_LIB_HOLD = None   # the short calls that keep the interpreter lock
_TRIED = False
_LOCK = threading.Lock()

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "native.cpp")


def _so_path() -> str:
    """Source-hash-keyed build target in a user cache dir (the build
    artifact is never committed; a stale hash simply rebuilds)."""
    with open(_SRC, "rb") as f:
        tag = hashlib.sha1(f.read()).hexdigest()[:12]
    base = os.environ.get("TEMPO_TPU_CACHE") or os.path.join(
        os.environ.get("XDG_CACHE_HOME")
        or os.path.expanduser("~/.cache"), "tempo_tpu")
    try:
        os.makedirs(base, exist_ok=True)
    except OSError:
        # last resort: a per-uid private dir under tmp — never load a .so
        # another user could have planted at a predictable shared path
        base = os.path.join(tempfile.gettempdir(),
                            f"tempo_tpu-{os.getuid()}")
        os.makedirs(base, mode=0o700, exist_ok=True)
        st = os.stat(base)
        if st.st_uid != os.getuid() or (st.st_mode & 0o077):
            raise OSError(f"refusing unsafe cache dir {base}")
    return os.path.join(base, f"_tempo_native_{tag}.so")

# numpy mirror of SpanRec (padding-free C layout, see native.cpp)
SPAN_REC_DTYPE = np.dtype([
    ("trace_id", np.uint8, 16),
    ("span_id", np.uint8, 8),
    ("parent_span_id", np.uint8, 8),
    ("start_ns", np.uint64),
    ("end_ns", np.uint64),
    ("name_off", np.int64),
    ("status_msg_off", np.int64),
    ("res_off", np.int64),
    ("span_off", np.int64),
    ("name_len", np.int32),
    ("status_msg_len", np.int32),
    ("res_len", np.int32),
    ("span_len", np.int32),
    ("kind", np.int32),
    ("status_code", np.int32),
    ("tid_len", np.int32),
    ("sid_len", np.int32),
    ("pid_len", np.int32),
    ("_pad", np.int32),
])
assert SPAN_REC_DTYPE.itemsize == 120

ATTR_REC_DTYPE = np.dtype([
    ("key_off", np.int64),
    ("sval_off", np.int64),
    ("ival", np.int64),
    ("fval", np.float64),
    ("key_len", np.int32),
    ("sval_len", np.int32),
    ("typ", np.int32),
    ("span_idx", np.int32),
])
assert ATTR_REC_DTYPE.itemsize == 48

# numpy mirrors of the otlp_stage output records (see native.cpp)
STAGE_REC_DTYPE = np.dtype([
    ("trace_id", np.uint8, 16),
    ("span_id", np.uint8, 8),
    ("parent_span_id", np.uint8, 8),
    ("start_ns", np.uint64),
    ("end_ns", np.uint64),
    ("name_id", np.int32),
    ("status_msg_id", np.int32),
    ("service_id", np.int32),
    ("res_idx", np.int32),
    ("kind", np.int32),
    ("status_code", np.int32),
    ("span_len", np.int32),
    ("tid_len", np.int32),
    ("sid_len", np.int32),
    ("pid_len", np.int32),
])
assert STAGE_REC_DTYPE.itemsize == 88

STAGE_ATTR_DTYPE = np.dtype([
    ("sval_off", np.int64),
    ("ival", np.int64),
    ("fval", np.float64),
    ("sval_len", np.int32),
    ("key_id", np.int32),
    ("sval_id", np.int32),
    ("typ", np.int32),
    ("owner", np.int32),
    ("_pad", np.int32),
])
assert STAGE_ATTR_DTYPE.itemsize == 48

STAGE_RES_DTYPE = np.dtype([
    ("service_id", np.int32),
    ("attr_start", np.int32),
    ("attr_count", np.int32),
    ("_pad", np.int32),
])
assert STAGE_RES_DTYPE.itemsize == 16

EV_REC_DTYPE = np.dtype([
    ("name_off", np.int64),
    ("time_ns", np.uint64),
    ("name_len", np.int32),
    ("span_idx", np.int32),
])
assert EV_REC_DTYPE.itemsize == 24

LINK_REC_DTYPE = np.dtype([
    ("trace_id", np.uint8, 16),
    ("span_id", np.uint8, 8),
    ("span_idx", np.int32),
    ("tid_len", np.int32),
    ("sid_len", np.int32),
    ("_pad", np.int32),
])
assert LINK_REC_DTYPE.itemsize == 40


def _build() -> str | None:
    try:
        so = _so_path()
    except OSError:
        return None
    if os.path.exists(so):
        return so
    tmp = f"{so}.{os.getpid()}.tmp"  # pid-unique: concurrent builds race
    try:
        subprocess.run(
            ["g++", "-O3", "-march=native", "-pthread", "-shared", "-fPIC",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return so
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def _load():
    global _LIB, _LIB_HOLD, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("TEMPO_TPU_NO_NATIVE"):
            return None
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            # corrupt cached build: remove so the next process rebuilds
            try:
                os.unlink(so)
            except OSError:
                pass
            return None
        try:
            c = ctypes
            u8p, i32p, i64p = (c.POINTER(c.c_uint8), c.POINTER(c.c_int32),
                               c.POINTER(c.c_int64))
            lib.fnv1_tokens.argtypes = [
                c.c_char_p, c.c_int64, u8p, c.c_int64, c.c_int64,
                c.POINTER(c.c_uint32)]
            lib.fnv1_tokens.restype = None
            lib.crc32c.argtypes = [c.c_char_p, c.c_int64]
            lib.crc32c.restype = c.c_uint32
            lib.group_keys.argtypes = [u8p, c.c_int64, c.c_int32, i32p, i32p]
            lib.group_keys.restype = c.c_int64
            lib.otlp_scan.argtypes = [u8p, c.c_int64, c.c_void_p, c.c_int64]
            lib.otlp_scan.restype = c.c_int64
            lib.otlp_scan_mt.argtypes = [
                u8p, c.c_int64, c.c_void_p, c.c_int64, c.c_int32]
            lib.otlp_scan_mt.restype = c.c_int64
            lib.otlp_scan2.argtypes = [
                u8p, c.c_int64, c.c_void_p, c.c_int64,
                c.c_void_p, c.c_int64, i64p]
            lib.otlp_scan2.restype = c.c_int64
            # interner
            lib.interner_new.restype = c.c_void_p
            lib.interner_free.argtypes = [c.c_void_p]
            lib.interner_intern.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
            lib.interner_intern.restype = c.c_int32
            lib.interner_find.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
            lib.interner_find.restype = c.c_int32
            lib.interner_count.argtypes = [c.c_void_p]
            lib.interner_count.restype = c.c_int64
            lib.interner_dump.argtypes = [
                c.c_void_p, c.c_int32, c.c_int32, u8p, c.c_int64, i32p]
            lib.interner_dump.restype = c.c_int64
            # row table
            lib.rowtable_new.argtypes = [c.c_int32]
            lib.rowtable_new.restype = c.c_void_p
            lib.rowtable_free.argtypes = [c.c_void_p]
            lib.rowtable_lookup.argtypes = [
                c.c_void_p, i32p, c.c_int64, u8p, i32p, i64p, c.c_int64]
            lib.rowtable_lookup.restype = c.c_int64
            lib.rowtable_insert.argtypes = [c.c_void_p, i32p, c.c_int32]
            lib.rowtable_insert.restype = None
            lib.rowtable_remove.argtypes = [c.c_void_p, i32p]
            lib.rowtable_remove.restype = None
            lib.rowtable_size.argtypes = [c.c_void_p]
            lib.rowtable_size.restype = c.c_int64
            lib.otlp_events.argtypes = [
                u8p, c.c_int64, c.c_void_p, c.c_int64,
                c.c_void_p, c.c_int64, i64p]
            lib.otlp_events.restype = c.c_int32
            # full staging
            lib.otlp_stage.argtypes = [
                c.c_void_p, u8p, c.c_int64,
                c.c_void_p, c.c_int64, c.c_void_p, c.c_int64,
                c.c_void_p, c.c_int64, c.c_void_p, c.c_int64,
                c.c_int32, i64p]
            lib.otlp_stage.restype = c.c_int32
            lib.otlp_stage_mt.argtypes = [
                c.c_void_p, u8p, c.c_int64,
                c.c_void_p, c.c_int64, c.c_void_p, c.c_int64,
                c.c_void_p, c.c_int64,
                c.c_int32, i64p, c.c_int32]
            lib.otlp_stage_mt.restype = c.c_int32
            lib.spanmetrics_resolve.argtypes = [
                c.c_void_p, c.c_void_p, c.c_int64,      # table, spans, n
                i32p, c.c_int32, i32p, i32p,            # dims, kind/status
                c.c_int64, c.c_int64, c.c_double,       # slack lo/hi, now
                c.POINTER(c.c_double),                  # last_seen
                i32p, c.c_void_p, c.c_void_p,           # slots, dur, size
                i32p, u8p, i64p, c.c_int64, i64p]       # rows, valid, miss
            lib.spanmetrics_resolve.restype = c.c_int64
            lib.spanmetrics_from_recs.argtypes = [
                c.c_void_p, c.c_void_p, u8p, c.c_int64,  # table, it, buf
                c.c_void_p, c.c_int64,                   # recs, n
                i32p, c.c_int32, i32p, i32p,             # dims, kind/status
                c.c_int64, c.c_int64, c.c_double,        # slack, now
                c.POINTER(c.c_double),                   # last_seen
                i32p, c.c_void_p, c.c_void_p,            # slots, dur, size
                i32p, u8p, i64p, c.c_int64, i64p]        # rows, valid, miss
            lib.spanmetrics_from_recs.restype = c.c_int64
            lib.group_keys_recs.argtypes = [
                c.c_void_p, c.c_int64, u8p, i32p, i32p]
            lib.group_keys_recs.restype = c.c_int64
            lib.group_keys_strided.argtypes = [
                c.c_void_p, c.c_int64, c.c_int64, c.c_int64, c.c_int64,
                u8p, i32p, i32p]
            lib.group_keys_strided.restype = c.c_int64
            _bind_held(lib)
            # the same entry points through a handle that keeps the
            # interpreter lock across the call: a call of tens of
            # microseconds that gives the lock up can wait a whole switch
            # interval (5 ms) for it again behind a busy Python thread
            _LIB_HOLD = _bind_held(ctypes.PyDLL(so))
            _LIB = lib
        except Exception:
            _LIB = None
        return _LIB


def _bind_held(lib):
    """The entry points also called with the interpreter lock held: the
    exact key index's (live traces, service-graph halves) and the staged
    batch's."""
    c = ctypes
    lib.kindex_new.argtypes = [c.c_int64]
    lib.kindex_new.restype = c.c_void_p
    lib.tindex_free.argtypes = [c.c_void_p]
    for name in ("tindex_lookup", "tindex_upsert", "tindex_discard"):
        getattr(lib, name).argtypes = [
            c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p]
        getattr(lib, name).restype = None
    lib.tindex_size.argtypes = [c.c_void_p]
    lib.tindex_size.restype = c.c_int64
    lib.sg_pair.argtypes = [
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
        c.c_int64, c.c_void_p, c.c_int64, c.c_void_p, c.c_void_p,
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p]
    lib.sg_pair.restype = c.c_int64
    lib.first_svals.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p, c.c_int64,
        c.c_void_p, c.c_int64, c.c_void_p]
    lib.first_svals.restype = None
    lib.sg_expire.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p, c.c_int64,
        c.c_double, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p]
    lib.sg_expire.restype = c.c_int64
    lib.stage_widths.argtypes = [
        c.c_void_p, c.c_int64, c.c_void_p, c.c_int64, c.c_void_p,
        c.c_int64, c.c_int64, c.c_int32, c.c_int32]
    lib.stage_widths.restype = c.c_int64
    lib.stage_derive.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64, c.c_void_p, c.c_int64,
        c.c_int64, c.c_void_p, c.c_int64, c.c_int64, c.c_void_p,
        c.c_int64, c.c_int32, c.c_void_p, c.c_void_p]
    lib.stage_derive.restype = c.c_int64
    return lib


def available() -> bool:
    return _load() is not None


def crc32c(data: bytes) -> "int | None":
    """Native Castagnoli CRC (kafka record batches); None when the
    library is unavailable (callers fall back to the python table)."""
    lib = _load()
    if lib is None:
        return None
    return int(lib.crc32c(data, len(data)))


# -- fnv tokens --------------------------------------------------------------

def token_for(tenant: str, trace_ids: np.ndarray) -> np.ndarray:
    """Native `TokenFor` batch; falls back to the numpy implementation."""
    lib = _load()
    tids = np.ascontiguousarray(trace_ids, np.uint8)
    if tids.ndim == 1:
        tids = tids[None, :]
    if lib is None:
        from tempo_tpu.ops import hashing
        return hashing.token_for(tenant, tids)
    out = np.empty(tids.shape[0], np.uint32)
    tb = tenant.encode()
    lib.fnv1_tokens(
        tb, len(tb),
        tids.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        tids.shape[0], tids.shape[1],
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return out


# -- OTLP scan ---------------------------------------------------------------

def group_keys(keys: np.ndarray) -> "tuple[np.ndarray, np.ndarray] | None":
    """Group [n, k] uint8 fixed-width keys in first-occurrence order.

    Returns (first_idx[int32, n_uniq], inverse[int32, n]) — the O(n) hash
    replacement for `np.unique` over void views (which argsorts). Falls
    back to numpy when the native layer is unavailable.
    """
    keys = np.ascontiguousarray(keys, np.uint8)
    n, k = keys.shape
    lib = _load()
    if lib is None:
        void = keys.view([("v", f"V{k}")]).ravel()
        _, first, inverse = np.unique(void, return_index=True,
                                      return_inverse=True)
        # relabel np.unique's sorted order to first-occurrence order so
        # fallback hosts group identically to the native path
        order = np.argsort(first, kind="stable")
        remap = np.empty(len(order), np.int64)
        remap[order] = np.arange(len(order))
        return (first[order].astype(np.int32),
                remap[inverse].astype(np.int32))
    inverse = np.empty(n, np.int32)
    first = np.empty(max(n, 1), np.int32)
    got = lib.group_keys(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n, k,
        inverse.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        first.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return first[:got], inverse


_SCAN_THREADS = min(8, os.cpu_count() or 1)
_SCAN_MT_BYTES = 256 << 10        # payloads below this stay single-thread
# adaptive capacity hints: start where the last payload ended so steady
# traffic never pays the scan-twice-regrow pass
_CAP_HINTS: dict = {}


def otlp_scan(data: bytes, cap_hint: "int | None" = None) -> np.ndarray | None:
    """Single-pass OTLP proto scan → SpanRec structured array.

    Large payloads fan ResourceSpans ranges across threads (the GIL is
    released inside the ctypes call); output order matches the sequential
    scan exactly. Returns None when the native library is unavailable
    (callers fall back to the python decoder). Raises ValueError on
    malformed input.
    """
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)
    bp = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    # an EXPLICIT cap_hint is honored exactly (tests exercise the regrow
    # branch with it); only the default consults the adaptive hint
    cap = cap_hint if cap_hint is not None else max(
        _CAP_HINTS.get("scan", 4096), 16)
    cap = max(cap, 16)
    mt = len(data) >= _SCAN_MT_BYTES and _SCAN_THREADS > 1
    while True:
        recs = np.empty(cap, SPAN_REC_DTYPE)   # scan fills every used rec
        if mt:
            n = lib.otlp_scan_mt(bp, len(data), recs.ctypes.data, cap,
                                 _SCAN_THREADS)
        else:
            n = lib.otlp_scan(bp, len(data), recs.ctypes.data, cap)
        if n < 0:
            raise ValueError("malformed OTLP protobuf payload")
        if n <= cap:
            # 25% headroom + a floor: size jitter must not re-trigger
            # the scan-twice regrow this hint exists to kill
            _CAP_HINTS["scan"] = max(4096, int(n) * 5 // 4)
            if n * 4 < cap:
                # don't let a small result pin a hint-inflated buffer
                return recs[:n].copy()
            return recs[:n]
        cap = int(n)


def otlp_scan2(data: bytes, cap_hint: int = 4096
               ) -> tuple[np.ndarray, np.ndarray] | None:
    """Single-pass scan → (SpanRec array, AttrRec array). None when the
    native library is unavailable; ValueError on malformed input."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)
    bp = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    cap, attr_cap = max(cap_hint, 16), max(cap_hint * 4, 64)
    while True:
        recs = np.zeros(cap, SPAN_REC_DTYPE)
        attrs = np.zeros(attr_cap, ATTR_REC_DTYPE)
        n_attrs = ctypes.c_int64(0)
        n = lib.otlp_scan2(bp, len(data), recs.ctypes.data, cap,
                           attrs.ctypes.data, attr_cap,
                           ctypes.byref(n_attrs))
        if n < 0:
            raise ValueError("malformed OTLP protobuf payload")
        if n <= cap and n_attrs.value <= attr_cap:
            return recs[:n], attrs[: n_attrs.value]
        cap = max(cap, int(n))
        attr_cap = max(attr_cap, int(n_attrs.value))


# -- persistent interner / row table ----------------------------------------

class NativeInterner:
    """Handle on the C++ string intern table (bytes → dense int32 id).

    The Python StringInterner fronts this with a str-keyed cache and a
    lazily synced id → str mirror; see tempo_tpu.model.interner."""

    __slots__ = ("_h", "_lib")

    def __init__(self) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.interner_new())

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h and getattr(self, "_lib", None) is not None:
            try:
                self._lib.interner_free(h)
            except Exception:
                pass

    def intern_bytes(self, b: bytes) -> int:
        return int(self._lib.interner_intern(self._h, b, len(b)))

    def find_bytes(self, b: bytes) -> int:
        return int(self._lib.interner_find(self._h, b, len(b)))

    def count(self) -> int:
        return int(self._lib.interner_count(self._h))

    def dump(self, first: int, n: int) -> list[bytes]:
        """Strings [first, first+n) as raw bytes (mirror sync)."""
        if n <= 0:
            return []
        cap = max(n * 16, 1024)
        lens = np.empty(n, np.int32)
        while True:
            out = np.empty(cap, np.uint8)
            got = self._lib.interner_dump(
                self._h, first, n,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), cap,
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            if got == -1:
                raise IndexError(f"interner_dump [{first}, {first + n})")
            if got < 0:
                cap = -got
                continue
            buf = out.tobytes()
            res, o = [], 0
            for ln in lens.tolist():
                res.append(buf[o:o + ln])
                o += ln
            return res

class NativeRowTable:
    """Handle on the C++ label-row → slot table (series resolution)."""

    __slots__ = ("_h", "_lib", "n_labels")

    def __init__(self, n_labels: int) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.n_labels = n_labels
        self._h = ctypes.c_void_p(lib.rowtable_new(n_labels))

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h and getattr(self, "_lib", None) is not None:
            try:
                self._lib.rowtable_free(h)
            except Exception:
                pass

    def lookup(self, rows: np.ndarray, valid: np.ndarray | None
               ) -> tuple[np.ndarray, np.ndarray]:
        """(slots [n] int32 with -1 unresolved, miss first-occurrence idx).

        Every reported miss MUST be resolved via insert() or remove()
        before the next lookup (pending entries are not re-reported)."""
        rows = np.ascontiguousarray(rows, np.int32)
        n = rows.shape[0]
        out = np.empty(n, np.int32)
        miss = np.empty(n, np.int64)
        vp = None
        if valid is not None:
            vbuf = np.ascontiguousarray(valid, np.uint8)
            vp = vbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        n_miss = self._lib.rowtable_lookup(
            self._h, rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), n,
            vp, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            miss.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n)
        return out, miss[:n_miss]

    def insert(self, row: np.ndarray, slot: int) -> None:
        row = np.ascontiguousarray(row, np.int32)
        self._lib.rowtable_insert(
            self._h, row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            slot)

    def remove(self, row: np.ndarray) -> None:
        row = np.ascontiguousarray(row, np.int32)
        self._lib.rowtable_remove(
            self._h, row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))

    def size(self) -> int:
        return int(self._lib.rowtable_size(self._h))


class KeyIndex:
    """Exact fixed-width key -> int64 value: a key is a [width] uint8 row.
    A call takes a whole push's or cut's keys. `discard` forgets a key
    only where it still names the given value, so a sweep may discard
    outside the store's lock while pushes upsert. Without the native
    library (or with `use_native=False`) a dict under a lock does the
    same, call for call."""

    width = 0
    # keep the interpreter lock through a call: for an index that a lock
    # of its owner already serialises, whose calls are short
    hold_gil = False

    def __init__(self, use_native: bool = True, width: int = 0) -> None:
        self.width = width or self.width
        if self.width < 8:
            raise ValueError(f"a key is 8 bytes or more, not {self.width}")
        self._lib = _load() if use_native else None
        if self._lib is not None and self.hold_gil:
            self._lib = _LIB_HOLD
        if self._lib is None:
            self._d: dict[bytes, int] = {}
            self._lock = threading.Lock()
        else:
            self._h = ctypes.c_void_p(self._lib.kindex_new(self.width))

    @property
    def native(self) -> bool:
        return self._lib is not None

    def __del__(self) -> None:
        h = getattr(self, "_h", None)
        if h and self._lib is not None:
            try:
                self._lib.tindex_free(h)
            except Exception:
                pass

    def __len__(self) -> int:
        if self._lib is None:
            return len(self._d)
        return int(self._lib.tindex_size(self._h))

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """The value of each key, -1 where it has none."""
        keys = self._keys(keys)
        out = np.empty(len(keys), np.int64)
        if self._lib is None:
            with self._lock:
                out[:] = list(map(self._d.get, _key_bytes(keys),
                                  [-1] * len(keys)))
        elif len(keys):
            self._lib.tindex_lookup(self._h, keys.ctypes.data, len(keys),
                                    out.ctypes.data)
        return out

    def upsert(self, keys: np.ndarray, slots: np.ndarray) -> None:
        keys = self._keys(keys)
        slots = np.ascontiguousarray(slots, np.int64)
        if slots.shape != (len(keys),):
            raise ValueError("one slot a key")
        if self._lib is None:
            with self._lock:
                self._d.update(zip(_key_bytes(keys), slots.tolist()))
        elif len(keys):
            self._lib.tindex_upsert(self._h, keys.ctypes.data, len(keys),
                                    slots.ctypes.data)

    def discard(self, keys: np.ndarray, slots: np.ndarray) -> None:
        keys = self._keys(keys)
        slots = np.ascontiguousarray(slots, np.int64)
        if slots.shape != (len(keys),):
            raise ValueError("one slot a key")
        if self._lib is None:
            with self._lock:
                for k, s in zip(_key_bytes(keys), slots.tolist()):
                    if self._d.get(k) == s:
                        del self._d[k]
        elif len(keys):
            self._lib.tindex_discard(self._h, keys.ctypes.data, len(keys),
                                     slots.ctypes.data)

    def _keys(self, keys: np.ndarray) -> np.ndarray:
        """[n, width] uint8, from those rows or from one void key a row."""
        if keys.dtype == np.dtype(f"V{self.width}") and keys.ndim == 1:
            keys = np.ascontiguousarray(keys).view(np.uint8).reshape(
                -1, self.width)
        keys = np.ascontiguousarray(keys, np.uint8)
        if keys.ndim != 2 or keys.shape[1] != self.width:
            raise ValueError(f"keys are [n, {self.width}] uint8, "
                             f"not {keys.shape}")
        return keys


class TraceIndex(KeyIndex):
    """Exact trace key -> int64 slot of a live store: a key is a [17]
    uint8 row, the id zero-padded to 16 bytes, then its length (the key a
    push is grouped by). A call takes a whole push's or cut's keys (one
    key where a dict route pushes beside staged traces)."""

    width = 17


class HalfIndex(KeyIndex):
    """The service-graph half-edge store's keys: a [24] uint8 row, trace id
    + span id of a CLIENT/PRODUCER half, trace id + parent span id of a
    SERVER/CONSUMER half; the value of a waiting half is `2 * slot +
    is_client`. `pair` walks a whole push's halves in one call, `expire`
    a whole due prefix of the TTL ring."""

    width = 24
    hold_gil = True

    def pair(self, trace_ids: np.ndarray, span_ids: np.ndarray,
             parent_ids: np.ndarray, rows: np.ndarray, is_client: np.ndarray,
             max_items: int, fresh: np.ndarray) -> tuple:
        """Walk batch rows `rows`, in order, against the waiting halves. A
        row's key is its trace id ([cap, 16]) and its own span id ([cap,
        8]) where it is a client, its parent's where it is a server. A row
        that meets a half of the other side under its key completes it:
        that half leaves, `out` = its slot, `matched` True. Else, while
        fewer than `max_items` halves wait, the row waits in the next slot
        of `fresh` (`out`), replacing a same-side half under its key
        (`prev` = that half's slot, else -1); with `max_items` waiting it
        is dropped (`out` -1) and a same-side half stays. Returns (keys,
        one void key a row; out; matched; prev; root, the rows whose
        parent id is all zero; how many of `fresh` were taken, in order);
        `fresh` holds a slot a row, none taken twice."""
        trace_ids = np.ascontiguousarray(trace_ids, np.uint8)
        span_ids = np.ascontiguousarray(span_ids, np.uint8)
        parent_ids = np.ascontiguousarray(parent_ids, np.uint8)
        rows = np.ascontiguousarray(rows, np.int64)
        n, cap = len(rows), len(trace_ids)
        side = np.ascontiguousarray(is_client, np.uint8)
        fresh = np.ascontiguousarray(fresh, np.int64)
        if (trace_ids.shape != (cap, 16) or span_ids.shape != (cap, 8)
                or parent_ids.shape != (cap, 8)):
            raise ValueError("ids are [cap, 16] and [cap, 8] uint8")
        if side.shape != (n,) or len(fresh) < n or (
                n and (rows.min() < 0 or rows.max() >= cap)):
            raise ValueError("a row, a side and a fresh slot a half")
        keys = np.empty((n, self.width), np.uint8)
        out = np.empty(n, np.int64)
        matched = np.zeros(n, np.uint8)
        prev = np.empty(n, np.int64)
        root = np.empty(n, np.uint8)
        if self._lib is None:
            keys[:, :16] = trace_ids[rows]
            keys[:, 16:] = np.where(side[:, None] == 1, span_ids[rows],
                                    parent_ids[rows])
            root[:] = ~parent_ids[rows].any(axis=1)
            with self._lock:
                taken = _pair_dict(self._d, _key_bytes(keys), side.tolist(),
                                   max_items, fresh.tolist(), out, matched,
                                   prev)
        elif n:
            taken = int(self._lib.sg_pair(
                self._h, trace_ids.ctypes.data, span_ids.ctypes.data,
                parent_ids.ctypes.data, rows.ctypes.data, n,
                side.ctypes.data, int(max_items), fresh.ctypes.data,
                out.ctypes.data, matched.ctypes.data, prev.ctypes.data,
                keys.ctypes.data, root.ctypes.data))
        else:
            taken = 0
        return (keys.view(f"V{self.width}").ravel(), out,
                matched.view(np.bool_), prev, root.view(np.bool_), taken)

    def expire(self, keys: np.ndarray, expire_at: np.ndarray, now: float
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Walk the ring entries `keys` in order: the half waiting under a
        key is due where `expire_at[slot] <= now`; it leaves, and its slot
        joins `gone` (in order; another entry of the same key then finds
        nothing). A half due later gives its entry's row to `later` and
        its time to `later_at`. Returns (gone, later, later_at)."""
        keys = self._keys(keys)
        n = len(keys)
        expire_at = np.ascontiguousarray(expire_at, np.float64)
        gone = np.empty(n, np.int64)
        later = np.empty(n, np.int64)
        later_at = np.empty(n, np.float64)
        if self._lib is None:
            with self._lock:
                ng, nl = _expire_dict(self._d, _key_bytes(keys), expire_at,
                                      now, gone, later, later_at)
        elif n:
            n_later = np.zeros(1, np.int64)
            ng = int(self._lib.sg_expire(
                self._h, keys.ctypes.data, n, expire_at.ctypes.data,
                len(expire_at), float(now), gone.ctypes.data,
                later.ctypes.data, later_at.ctypes.data, n_later.ctypes.data))
            if ng < 0:
                raise ValueError("a waiting half's slot is past expire_at")
            nl = int(n_later[0])
        else:
            ng = nl = 0
        return gone[:ng], later[:nl], later_at[:nl]


def first_svals(attr_keys: np.ndarray, attr_svals: np.ndarray,
                rows: np.ndarray, kids: list, use_native: bool = True
                ) -> np.ndarray:
    """For each batch row in `rows`: the interned string value of the
    first attribute key of `kids` (in order) that the row carries with
    one, else -1. A row's value for a key is that of the key's first
    column of `attr_keys` ([cap, width] int32), as
    `SpanBatch.attr_sval_column` reads it."""
    rows = np.ascontiguousarray(rows, np.int64)
    out = np.full(len(rows), -1, np.int32)
    width = attr_keys.shape[1]
    if not len(rows) or not kids or width == 0:
        return out
    lib = _load() if use_native else None
    if lib is None:
        keys, svals = attr_keys[rows], attr_svals[rows]
        at = np.arange(len(rows))
        for kid in kids:
            hit = keys == kid
            val = np.where(hit.any(axis=1), svals[at, hit.argmax(axis=1)],
                           -1)
            out = np.where(out != -1, out, val)
        return out
    attr_keys = np.ascontiguousarray(attr_keys, np.int32)
    attr_svals = np.ascontiguousarray(attr_svals, np.int32)
    if attr_svals.shape != attr_keys.shape or rows.min() < 0 \
            or rows.max() >= len(attr_keys):
        raise ValueError("rows of one [cap, width] key and value table")
    kids = np.asarray(kids, np.int32)
    _LIB_HOLD.first_svals(attr_keys.ctypes.data, attr_svals.ctypes.data,
                          width, rows.ctypes.data, len(rows),
                          kids.ctypes.data, len(kids), out.ctypes.data)
    return out


def _pair_dict(d: dict, keys: list, sides: list, max_items: int,
               fresh: list, out: np.ndarray, matched: np.ndarray,
               prev: np.ndarray) -> int:
    """`HalfIndex.pair` over a dict: the native walk, step for step."""
    taken = 0
    for r, (k, side) in enumerate(zip(keys, sides)):
        v = d.get(k)
        prev[r] = -1
        if v is not None and (v & 1) != side:
            del d[k]
            out[r] = v >> 1
            matched[r] = 1
            continue
        if len(d) >= max_items:
            out[r] = -1
            continue
        s = fresh[taken]
        taken += 1
        out[r] = s
        if v is not None:
            prev[r] = v >> 1
        d[k] = 2 * s + side
    return taken


def _expire_dict(d: dict, keys: list, expire_at: np.ndarray, now: float,
                 gone: np.ndarray, later: np.ndarray,
                 later_at: np.ndarray) -> tuple[int, int]:
    """`HalfIndex.expire` over a dict: the native walk, step for step."""
    ng = nl = 0
    for r, k in enumerate(keys):
        v = d.get(k)
        if v is None:
            continue
        at = expire_at[v >> 1]
        if at <= now:
            del d[k]
            gone[ng] = v >> 1
            ng += 1
        else:
            later[nl], later_at[nl] = r, at
            nl += 1
    return ng, nl


def _key_bytes(keys: np.ndarray) -> list[bytes]:
    """Each [w] key row as one bytes object (a void view keeps trailing
    zero bytes)."""
    return keys.view(f"V{keys.shape[1]}").ravel().tolist()


def otlp_stage(interner: "NativeInterner", data: bytes,
               cap_hint: "int | None" = None, skip_span_attrs: bool = False,
               trust_attrs: bool = False):
    """One-pass OTLP bytes → interned columns.

    Returns (spans StageRec[], span_attrs StageAttr[], res_attrs
    StageAttr[], resources StageRes[]) or None when the native library is
    unavailable. Raises ValueError on malformed input. With
    `skip_span_attrs` the scan validates span attributes but neither
    interns nor emits them (intrinsic-dims-only callers); `trust_attrs`
    additionally skips that validation — ONLY for bytes already validated
    in this process (the distributor's in-process tee)."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)
    bp = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    flags = (1 if skip_span_attrs else 0) | \
        (2 if trust_attrs and skip_span_attrs else 0)
    hint_key = "stage_skip" if skip_span_attrs else "stage_full"
    cap = cap_hint if cap_hint is not None else max(
        _CAP_HINTS.get(hint_key, 4096), 16)
    cap = max(cap, 16)
    acap = 16 if skip_span_attrs else max(
        cap * 4, _CAP_HINTS.get("stage_attrs", 64))
    rcap, rescap = 256, 64
    mt = (skip_span_attrs and len(data) >= _SCAN_MT_BYTES
          and _SCAN_THREADS > 1)
    while True:
        # stage fills every record it emits: empty alloc, no MB memsets
        spans = np.empty(cap, STAGE_REC_DTYPE)
        sattrs = np.empty(acap, STAGE_ATTR_DTYPE)
        rattrs = np.empty(rcap, STAGE_ATTR_DTYPE)
        res = np.empty(rescap, STAGE_RES_DTYPE)
        n_out = np.zeros(4, np.int64)
        if mt:
            # parallel staging (skip-attrs shapes): ResourceSpans ranges
            # fan across threads with thread-local intern memos
            rc = lib.otlp_stage_mt(
                interner._h, bp, len(data),
                spans.ctypes.data, cap,
                rattrs.ctypes.data, rcap, res.ctypes.data, rescap,
                flags, n_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                _SCAN_THREADS)
        else:
            rc = lib.otlp_stage(
                interner._h, bp, len(data),
                spans.ctypes.data, cap, sattrs.ctypes.data, acap,
                rattrs.ctypes.data, rcap, res.ctypes.data, rescap,
                flags, n_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if rc != 0:
            raise ValueError("malformed OTLP protobuf payload")
        ns, na, nr, nres = (int(x) for x in n_out)
        if ns <= cap and na <= acap and nr <= rcap and nres <= rescap:
            _CAP_HINTS[hint_key] = max(4096, ns * 5 // 4)
            if not skip_span_attrs:
                _CAP_HINTS["stage_attrs"] = max(256, na * 5 // 4)
            out = (spans[:ns], sattrs[:na], rattrs[:nr], res[:nres])
            if ns * 4 < cap:
                out = tuple(a.copy() for a in out)
            return out
        cap, acap = max(cap, ns), max(acap, na)
        rcap, rescap = max(rcap, nr), max(rescap, nres)


def stage_widths(sattrs: np.ndarray, rattrs: np.ndarray, res: np.ndarray,
                 n: int, svc_key: int, with_res: bool
                 ) -> "tuple[int, int] | None":
    """(most attributes one span holds, one resource) in a staging's
    records (`otlp_stage`; `n` spans, resource attributes read only
    `with_res`), or None where the library is absent or the numpy route
    must build the batch: a non-scalar value, a `service.name` (id
    `svc_key`) that is no string, attributes out of their owners' order."""
    if _load() is None:
        return None
    sattrs, rattrs, res = (np.ascontiguousarray(a)
                           for a in (sattrs, rattrs, res))
    _check_staged(None, sattrs, rattrs, res)
    got = _LIB_HOLD.stage_widths(sattrs.ctypes.data, len(sattrs),
                                 rattrs.ctypes.data, len(rattrs),
                                 res.ctypes.data, len(res), n, svc_key,
                                 int(with_res))
    if got < 0:
        return None
    return got >> 32, got & 0xFFFFFFFF


def _check_staged(spans, sattrs, rattrs, res) -> None:
    """`otlp_stage`'s record arrays, or a ValueError before any pointer
    reaches native code."""
    for a, dt in ((spans, STAGE_REC_DTYPE), (sattrs, STAGE_ATTR_DTYPE),
                  (rattrs, STAGE_ATTR_DTYPE), (res, STAGE_RES_DTYPE)):
        if a is not None and (a.dtype != dt or a.ndim != 1):
            raise ValueError(f"staged records are 1-D {dt}, not {a.dtype}")


# the fields of `stage_derive`'s one output buffer, in the order of its
# offsets (see native.cpp); a shape names `cap`, `sw` or `rw`
_DERIVED = (
    ("name_id", np.int32, ("cap",)), ("status_message_id", np.int32, ("cap",)),
    ("service_id", np.int32, ("cap",)), ("kind", np.int32, ("cap",)),
    ("status_code", np.int32, ("cap",)),
    ("start_unix_nano", np.int64, ("cap",)),
    ("end_unix_nano", np.int64, ("cap",)),
    ("trace_id", np.uint8, ("cap", 16)), ("span_id", np.uint8, ("cap", 8)),
    ("parent_span_id", np.uint8, ("cap", 8)),
    ("span_attr_key", np.int32, ("cap", "sw")),
    ("span_attr_sval", np.int32, ("cap", "sw")),
    ("span_attr_fval", np.float32, ("cap", "sw")),
    ("span_attr_typ", np.int8, ("cap", "sw")),
    ("res_attr_key", np.int32, ("cap", "rw")),
    ("res_attr_sval", np.int32, ("cap", "rw")),
    ("res_attr_fval", np.float32, ("cap", "rw")),
    ("res_attr_typ", np.int8, ("cap", "rw")),
    ("valid", np.bool_, ("cap",)), ("sizes", np.float32, ("cap",)),
    ("first", np.int32, ("cap",)), ("inverse", np.int32, ("cap",)),
    ("order", np.int64, ("cap",)), ("trace_spans", np.int64, ("cap",)),
    ("trace_sizes", np.int64, ("cap",)), ("keys", np.uint8, ("cap", 17)),
    ("info", np.int64, (2,)),
)
_DERIVED_LAYOUTS: dict = {}


def _derived_layout(cap: int, sw: int, rw: int):
    """(the buffer's dtype, its field names, their byte offsets as an
    int64 array) for one shape, made once."""
    key = (cap, sw, rw)
    got = _DERIVED_LAYOUTS.get(key)
    if got is None:
        dims = {"cap": cap, "sw": sw, "rw": rw}
        dt = np.dtype([(name, t, tuple(dims.get(d, d) for d in shape))
                       for name, t, shape in _DERIVED], align=True)
        names = tuple(name for name, _, _ in _DERIVED)
        got = (dt, names,
               np.array([dt.fields[f][1] for f in names], np.int64))
        if len(_DERIVED_LAYOUTS) < 1024:
            _DERIVED_LAYOUTS[key] = got
    return got


def stage_derive(spans: np.ndarray, sattrs: np.ndarray, rattrs: np.ndarray,
                 res: np.ndarray, cap: int, sw: int, rw: int, empty_id: int
                 ) -> "dict | None":
    """A staging's padded SpanBatch columns and its trace order, one pass.

    Returns {field: array}, None without the library or where the records
    need the numpy route. The SpanBatch's own column names ([cap] rows;
    `sw`, `rw`: the attribute matrices' padded widths, 0 leaves that
    scope out), `sizes` ([cap] float32 wire bytes), and over the n rows
    `first`, `inverse`, `order`, `trace_spans`, `trace_sizes`, `keys`,
    `same_length` (`model.otlp_batch.TraceOrder` says what each is)."""
    if _load() is None:
        return None
    spans, sattrs, rattrs, res = (np.ascontiguousarray(a)
                                  for a in (spans, sattrs, rattrs, res))
    _check_staged(spans, sattrs, rattrs, res)
    n = len(spans)
    dt, names, offs = _derived_layout(cap, sw, rw)
    buf = np.empty((), dt)
    if _LIB_HOLD.stage_derive(spans.ctypes.data, n, cap, sattrs.ctypes.data,
                              len(sattrs), sw, rattrs.ctypes.data,
                              len(rattrs), rw, res.ctypes.data, len(res),
                              empty_id, buf.ctypes.data,
                              offs.ctypes.data) < 0:
        return None
    out = {f: buf[f] for f in names}
    ng, same = (int(x) for x in out.pop("info"))
    for f in ("first", "trace_spans", "trace_sizes", "keys"):
        out[f] = out[f][:ng]
    out["inverse"] = out["inverse"][:n]
    out["order"] = out["order"][:n]
    out["same_length"] = bool(same)
    return out


def otlp_events(data: bytes, ev_hint: int = 256, link_hint: int = 64
                ) -> tuple[np.ndarray, np.ndarray] | None:
    """Span events + links keyed by span index (EvRec/LinkRec arrays);
    None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)
    bp = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    ecap, lcap = max(ev_hint, 16), max(link_hint, 16)
    while True:
        evs = np.zeros(ecap, EV_REC_DTYPE)
        links = np.zeros(lcap, LINK_REC_DTYPE)
        n_out = np.zeros(2, np.int64)
        rc = lib.otlp_events(
            bp, len(data), evs.ctypes.data, ecap, links.ctypes.data, lcap,
            n_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        if rc != 0:
            raise ValueError("malformed OTLP protobuf payload")
        ne, nl = int(n_out[0]), int(n_out[1])
        if ne <= ecap and nl <= lcap:
            return evs[:ne], links[:nl]
        ecap, lcap = max(ecap, ne), max(lcap, nl)


def spans_from_otlp_proto_native(data: bytes, return_recs: bool = False):
    """Native scan → flat span dicts (the wire-entry contract of
    `model.otlp.spans_from_otlp_proto`). The C pass extracts every fixed
    field and attribute range; python only slices strings and builds dicts.
    With `return_recs` returns (dicts, SpanRec array) so the caller can
    reuse the wire offsets (the distributor tee slices raw payloads with
    them) without a second scan."""
    scanned = otlp_scan2(data)
    if scanned is None:
        return (None, None) if return_recs else None
    recs, attrs = scanned
    from tempo_tpu.model.otlp import _pb_anyvalue

    # columnar extraction (bulk .tolist() beats per-row structured access)
    tid = recs["trace_id"].tobytes()
    sid = recs["span_id"].tobytes()
    pid = recs["parent_span_id"].tobytes()
    name_off = recs["name_off"].tolist(); name_len = recs["name_len"].tolist()
    sm_off = recs["status_msg_off"].tolist(); sm_len = recs["status_msg_len"].tolist()
    res_off = recs["res_off"].tolist(); res_len = recs["res_len"].tolist()
    start = recs["start_ns"].tolist(); end = recs["end_ns"].tolist()
    kind = recs["kind"].tolist(); code = recs["status_code"].tolist()

    res_cache: dict[tuple[int, int], dict] = {}

    def resource_attrs(ro: int, rl: int) -> dict:
        if ro < 0:
            return {}
        key = (ro, rl)
        cached = res_cache.get(key)
        if cached is None:
            from tempo_tpu.model import proto_wire as pw
            from tempo_tpu.model.otlp import _pb_attrs
            cached = res_cache[key] = _pb_attrs(
                [v for f, _, v in pw.iter_fields(data[ro:ro + rl]) if f == 1])
        return cached

    n = len(recs)
    tid_len = recs["tid_len"].tolist()
    sid_len = recs["sid_len"].tolist()
    pid_len = recs["pid_len"].tolist()
    # wire lengths preserved: an absent id slices to b"" and an oversized
    # one to its (uncopied, zeroed) declared size — both match the python
    # decoder's contract so the distributor's invalid-id validation fires
    # identically on either path
    out = [{
        "trace_id": tid[i * 16: i * 16 + min(tid_len[i], 16)]
        if tid_len[i] <= 16 else b"\x00" * tid_len[i],
        "span_id": sid[i * 8: i * 8 + min(sid_len[i], 8)]
        if sid_len[i] <= 8 else b"\x00" * sid_len[i],
        "parent_span_id": pid[i * 8: i * 8 + min(pid_len[i], 8)]
        if pid_len[i] <= 8 else b"\x00" * pid_len[i],
        "name": data[name_off[i]: name_off[i] + name_len[i]].decode("utf-8", "replace"),
        "service": "",
        "kind": kind[i],
        "status_code": code[i],
        "status_message": data[sm_off[i]: sm_off[i] + sm_len[i]].decode("utf-8", "replace"),
        "start_unix_nano": start[i],
        "end_unix_nano": end[i],
        "attrs": {},
        "res_attrs": None,
    } for i in range(n)]
    for i in range(n):
        ra = resource_attrs(res_off[i], res_len[i])
        out[i]["res_attrs"] = ra
        out[i]["service"] = str(ra.get("service.name", ""))

    # span attrs from the flat attr table
    a_key_off = attrs["key_off"].tolist(); a_key_len = attrs["key_len"].tolist()
    a_sval_off = attrs["sval_off"].tolist(); a_sval_len = attrs["sval_len"].tolist()
    a_fval = attrs["fval"].tolist(); a_ival = attrs["ival"].tolist()
    a_typ = attrs["typ"].tolist(); a_span = attrs["span_idx"].tolist()
    for j in range(len(attrs)):
        ko = a_key_off[j]
        k = data[ko: ko + a_key_len[j]].decode("utf-8", "replace") \
            if ko >= 0 else ""
        t = a_typ[j]
        if t == 1:
            v = data[a_sval_off[j]: a_sval_off[j] + a_sval_len[j]].decode("utf-8", "replace")
        elif t == 2:
            v = bool(a_fval[j])
        elif t == 3:
            v = a_ival[j]  # exact int64 (no double round-trip)
        elif t == 4:
            v = a_fval[j]
        else:
            v = _pb_anyvalue(data[a_sval_off[j]: a_sval_off[j] + a_sval_len[j]]) \
                if a_sval_off[j] >= 0 else None
        out[a_span[j]]["attrs"][k] = v

    # events/links (separate native pass; same span traversal order —
    # keeps the output contract aligned with the python decoder)
    got_ev = otlp_events(data)
    if got_ev is not None:
        evs, links = got_ev
        e_off = evs["name_off"].tolist(); e_len = evs["name_len"].tolist()
        e_t = evs["time_ns"].tolist(); e_s = evs["span_idx"].tolist()
        for j in range(len(evs)):
            o = e_off[j]
            out[e_s[j]].setdefault("events", []).append({
                "time_unix_nano": e_t[j],
                "name": data[o:o + e_len[j]].decode("utf-8", "replace")
                if o >= 0 else ""})
        l_tid = links["trace_id"].tobytes(); l_sid = links["span_id"].tobytes()
        l_tl = links["tid_len"].tolist(); l_sl = links["sid_len"].tolist()
        l_s = links["span_idx"].tolist()
        for j in range(len(links)):
            out[l_s[j]].setdefault("links", []).append({
                "trace_id": l_tid[j * 16: j * 16 + min(l_tl[j], 16)],
                "span_id": l_sid[j * 8: j * 8 + min(l_sl[j], 8)]})
    return (out, recs) if return_recs else out


class ResolveBuffers:
    """One pre-allocated staging-buffer set for the fused spanmetrics
    resolve: the arrays the C++ pass fills and the (async) device
    dispatch later reads. The ingest pipeline recycles these once the
    dispatch that reads them has landed — steady state allocates zero
    new staging memory per push."""

    __slots__ = ("cap", "n_labels", "slots", "packed", "rows", "valid",
                 "miss", "counts")

    def __init__(self, cap: int, n_labels: int) -> None:
        self.cap = cap
        self.n_labels = n_labels
        self.slots = np.full(cap, -1, np.int32)
        self.packed = np.zeros((3, cap), np.float32)
        self.rows = np.empty((max(cap, 1), n_labels), np.int32)
        self.valid = np.zeros(cap, np.uint8)
        self.miss = np.empty(max(cap, 1), np.int64)
        self.counts = np.zeros(2, np.int64)

    def reset(self) -> None:
        """Restore the fill values a fresh allocation would carry (the
        previous push's rows beyond the new n must read as padding)."""
        self.slots.fill(-1)
        self.packed.fill(0.0)
        self.valid.fill(0)


def _resolve_arrays(cap: int, n_labels: int, n: int,
                    out: "ResolveBuffers | None"):
    """(slots, packed, rows, valid, miss, counts) — from the reusable
    buffer set when one of the right shape is offered, else fresh."""
    if out is not None and out.cap == cap and out.n_labels == n_labels:
        out.reset()
        return (out.slots, out.packed, out.rows[:max(n, 1)], out.valid,
                out.miss, out.counts)
    return (np.full(cap, -1, np.int32), np.zeros((3, cap), np.float32),
            np.empty((max(n, 1), n_labels), np.int32),
            np.zeros(cap, np.uint8), np.empty(max(n, 1), np.int64),
            np.zeros(2, np.int64))


def spanmetrics_resolve(table: "NativeRowTable", spans: np.ndarray,
                        dims: np.ndarray, kind_lut: np.ndarray,
                        status_lut: np.ndarray, slack_lo: int, slack_hi: int,
                        now: float, last_seen: "np.ndarray | None",
                        cap: int, out: "ResolveBuffers | None" = None):
    """Fused staged-records → device-ready arrays (see native.cpp
    `spanmetrics_resolve`). Returns (slots, packed, rows, valid, miss_idx,
    n_valid, n_filtered): `packed` is the [3, cap] f32 single-H2D buffer
    whose rows 1/2 hold dur_s/sizes (row 0 is reserved for the caller's
    f32 slot copy); slots/valid are cap-padded (slot tail -1 → masked out
    of the scatter); rows is [n, L] for the miss-resolution pass. None
    when the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(spans)
    if cap < n:
        raise ValueError("cap must be >= len(spans)")
    spans = np.ascontiguousarray(spans)
    dims = np.ascontiguousarray(dims, np.int32)
    kind_lut = np.ascontiguousarray(kind_lut, np.int32)
    status_lut = np.ascontiguousarray(status_lut, np.int32)
    # dur/sizes are rows 1/2 of ONE packed [3, cap] f32 buffer: the fast
    # paths upload slots+dur+sizes as a single H2D transfer (row 0 takes
    # the f32 slot copy after miss resolution)
    slots, packed, rows, valid, miss, counts = _resolve_arrays(
        cap, int(dims.shape[0]), n, out)
    dur = packed[1]
    sizes = packed[2]
    i32 = ctypes.POINTER(ctypes.c_int32)
    lsp = None
    if last_seen is not None:
        assert last_seen.dtype == np.float64 and last_seen.flags.c_contiguous
        lsp = last_seen.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    nm = lib.spanmetrics_resolve(
        table._h, spans.ctypes.data, n,
        dims.ctypes.data_as(i32), int(dims.shape[0]),
        kind_lut.ctypes.data_as(i32), status_lut.ctypes.data_as(i32),
        slack_lo, slack_hi, now, lsp,
        slots.ctypes.data_as(i32), dur.ctypes.data, sizes.ctypes.data,
        rows.ctypes.data_as(i32),
        valid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        miss.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(miss),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return (slots, packed, rows, valid, miss[:nm],
            int(counts[0]), int(counts[1]))


def spanmetrics_from_recs(table: "NativeRowTable", interner_h, data: bytes,
                          recs: np.ndarray, dims: np.ndarray,
                          kind_lut: np.ndarray, status_lut: np.ndarray,
                          slack_lo: int, slack_hi: int, now: float,
                          last_seen: "np.ndarray | None", cap: int,
                          out: "ResolveBuffers | None" = None):
    """Distributor scan records → device-ready spanmetrics arrays (see
    native.cpp `spanmetrics_from_recs`): the tee path skips the second
    protobuf walk entirely. Same return shape as `spanmetrics_resolve`;
    None when the library is unavailable OR the payload needs the Python
    service.name fixup (caller falls back to the full staging path)."""
    lib = _load()
    if lib is None:
        return None
    n = len(recs)
    if cap < n:
        raise ValueError("cap must be >= len(recs)")
    recs = np.ascontiguousarray(recs)
    buf = np.frombuffer(data, np.uint8)
    dims = np.ascontiguousarray(dims, np.int32)
    kind_lut = np.ascontiguousarray(kind_lut, np.int32)
    status_lut = np.ascontiguousarray(status_lut, np.int32)
    # dur/sizes are rows 1/2 of ONE packed [3, cap] f32 buffer: the fast
    # paths upload slots+dur+sizes as a single H2D transfer (row 0 takes
    # the f32 slot copy after miss resolution)
    slots, packed, rows, valid, miss, counts = _resolve_arrays(
        cap, int(dims.shape[0]), n, out)
    dur = packed[1]
    sizes = packed[2]
    i32 = ctypes.POINTER(ctypes.c_int32)
    lsp = None
    if last_seen is not None:
        assert last_seen.dtype == np.float64 and last_seen.flags.c_contiguous
        lsp = last_seen.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    nm = lib.spanmetrics_from_recs(
        table._h, interner_h, buf.ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint8)), len(data),
        recs.ctypes.data, n,
        dims.ctypes.data_as(i32), int(dims.shape[0]),
        kind_lut.ctypes.data_as(i32), status_lut.ctypes.data_as(i32),
        slack_lo, slack_hi, now, lsp,
        slots.ctypes.data_as(i32), dur.ctypes.data, sizes.ctypes.data,
        rows.ctypes.data_as(i32),
        valid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        miss.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(miss),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    if nm < 0:
        return None      # -1 malformed / -2 fixup: full path re-validates
    return (slots, packed, rows, valid, miss[:nm],
            int(counts[0]), int(counts[1]))


def group_keys_recs(recs: np.ndarray, valid: "np.ndarray | None"
                    ) -> "tuple[np.ndarray, np.ndarray] | None":
    """`group_keys` over (trace_id ‖ tid_len) read straight from SpanRec
    rows — no key-matrix materialization. inverse/first index over the
    sequence of VALID rows (the caller's vrows order). None without the
    native library (caller builds keys and uses group_keys)."""
    lib = _load()
    if lib is None:
        return None
    recs = np.ascontiguousarray(recs)
    n = len(recs)
    nv = n if valid is None else int(valid.sum())
    inverse = np.empty(max(nv, 1), np.int32)
    first = np.empty(max(nv, 1), np.int32)
    vp = None
    if valid is not None:
        vbuf = np.ascontiguousarray(valid, np.uint8)
        vp = vbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    i32 = ctypes.POINTER(ctypes.c_int32)
    ng = lib.group_keys_recs(recs.ctypes.data, n, vp,
                             inverse.ctypes.data_as(i32),
                             first.ctypes.data_as(i32))
    return first[:ng], inverse[:nv]


def group_keys_strided(recs: np.ndarray, valid: "np.ndarray | None"
                       ) -> "tuple[np.ndarray, np.ndarray] | None":
    """`group_keys_recs` over ANY structured dtype carrying `trace_id`
    ([16] u8) and `tid_len` (i32) fields — the staged tee groups StageRec
    rows with this, no key-matrix materialization. None without the
    native library (caller builds keys and uses group_keys)."""
    lib = _load()
    if lib is None:
        return None
    recs = np.ascontiguousarray(recs)
    fields = recs.dtype.fields
    tid_off = int(fields["trace_id"][1])
    tidlen_off = int(fields["tid_len"][1])
    n = len(recs)
    nv = n if valid is None else int(valid.sum())
    inverse = np.empty(max(nv, 1), np.int32)
    first = np.empty(max(nv, 1), np.int32)
    vp = None
    if valid is not None:
        vbuf = np.ascontiguousarray(valid, np.uint8)
        vp = vbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    i32 = ctypes.POINTER(ctypes.c_int32)
    ng = lib.group_keys_strided(recs.ctypes.data, n,
                                recs.dtype.itemsize, tid_off, tidlen_off,
                                vp, inverse.ctypes.data_as(i32),
                                first.ctypes.data_as(i32))
    return first[:ng], inverse[:nv]
