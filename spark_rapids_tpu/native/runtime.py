"""ctypes bindings to the native host runtime (native/build/libsrtpu.so).

Loads lazily and degrades gracefully: every entry point has a numpy fallback at
its call site, so the framework is fully functional without the .so — the
native paths are the performance tier (the reference has the same shape: Scala
logic above, libcudf/RMM/nvcomp below, except its native tier is mandatory).

Build: `make -C native` at the repo root (g++, no external deps)."""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

import numpy as np

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False
_LOCK = threading.Lock()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# explicit env override beats the discovered in-repo build
_SO_PATHS = (
    os.environ.get("SRTPU_NATIVE_LIB", ""),
    os.path.join(_REPO_ROOT, "native", "build", "libsrtpu.so"),
)


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        for p in _SO_PATHS:
            if p and os.path.exists(p):
                try:
                    lib = ctypes.CDLL(p)
                except OSError:
                    continue
                _bind(lib)
                _LIB = lib
                break
        return _LIB


def _bind(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.srtpu_lz4_compress_bound.restype = ctypes.c_int64
    lib.srtpu_lz4_compress_bound.argtypes = [ctypes.c_int64]
    lib.srtpu_lz4_compress.restype = ctypes.c_int64
    lib.srtpu_lz4_compress.argtypes = [u8p, ctypes.c_int64, u8p,
                                       ctypes.c_int64]
    lib.srtpu_lz4_decompress.restype = ctypes.c_int64
    lib.srtpu_lz4_decompress.argtypes = [u8p, ctypes.c_int64, u8p,
                                         ctypes.c_int64]
    lib.srtpu_offsets_to_matrix.restype = ctypes.c_int32
    lib.srtpu_offsets_to_matrix.argtypes = [u8p, i64p, ctypes.c_int64,
                                            ctypes.c_int64, u8p, i32p]
    lib.srtpu_matrix_to_offsets.restype = ctypes.c_int64
    lib.srtpu_matrix_to_offsets.argtypes = [u8p, i32p, ctypes.c_int64,
                                            ctypes.c_int64, u8p, i64p]
    lib.srtpu_sum_lengths.restype = ctypes.c_int64
    lib.srtpu_sum_lengths.argtypes = [i32p, ctypes.c_int64]
    lib.srtpu_byte_array_scan.restype = ctypes.c_int64
    lib.srtpu_byte_array_scan.argtypes = [u8p, ctypes.c_int64,
                                          ctypes.c_int64, i64p, i32p]
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.srtpu_rle_scan.restype = ctypes.c_int64
    lib.srtpu_rle_scan.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64,
                                   ctypes.c_int32, u8p, i64p, u32p, i64p,
                                   u8p, i64p]
    lib.srtpu_orc_deframe.restype = ctypes.c_int64
    lib.srtpu_orc_deframe.argtypes = [u8p, ctypes.c_int64, ctypes.c_int32,
                                      u8p, ctypes.c_int64]
    lib.srtpu_varint_scan.restype = ctypes.c_int32
    lib.srtpu_varint_scan.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64,
                                      i64p, i64p]
    lib.srtpu_orc_run_table.restype = ctypes.c_int32
    lib.srtpu_orc_run_table.argtypes = [u8p, i64p, i64p, i64p, i64p, u8p,
                                        ctypes.c_int64, ctypes.c_int64,
                                        ctypes.POINTER(ctypes.c_int32),
                                        ctypes.POINTER(ctypes.c_uint32)]
    lib.srtpu_orc_rlev2_scan.restype = ctypes.c_int64
    lib.srtpu_orc_rlev2_scan.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64,
                                         ctypes.c_int32, u8p, i64p, i64p,
                                         i64p, i64p, u8p, u8p, i64p]
    lib.srtpu_chunk_walk.restype = ctypes.POINTER(_SrtpuChunk)
    lib.srtpu_chunk_walk.argtypes = [u8p, ctypes.c_int64, ctypes.c_int32,
                                     ctypes.c_int32, ctypes.c_int32,
                                     ctypes.POINTER(ctypes.c_int32)]
    lib.srtpu_chunk_free.restype = None
    lib.srtpu_chunk_free.argtypes = [ctypes.POINTER(_SrtpuChunk)]
    lib.srtpu_arena_init.restype = ctypes.c_int32
    lib.srtpu_arena_init.argtypes = [ctypes.c_int64]
    lib.srtpu_arena_alloc.restype = ctypes.c_void_p
    lib.srtpu_arena_alloc.argtypes = [ctypes.c_int64]
    lib.srtpu_arena_free.restype = None
    lib.srtpu_arena_free.argtypes = [ctypes.c_void_p]
    lib.srtpu_arena_in_use.restype = ctypes.c_int64
    lib.srtpu_arena_peak.restype = ctypes.c_int64
    lib.srtpu_arena_capacity.restype = ctypes.c_int64
    lib.srtpu_arena_destroy.restype = None


class _SrtpuChunk(ctypes.Structure):
    _fields_ = [
        ("num_pages", ctypes.c_int64),
        ("page_kind", ctypes.POINTER(ctypes.c_uint8)),
        ("page_bw", ctypes.POINTER(ctypes.c_int32)),
        ("page_num_values", ctypes.POINTER(ctypes.c_int64)),
        ("page_ndef", ctypes.POINTER(ctypes.c_int64)),
        ("page_plain_off", ctypes.POINTER(ctypes.c_int64)),
        ("page_idx_run_off", ctypes.POINTER(ctypes.c_int64)),
        ("page_idx_packed_off", ctypes.POINTER(ctypes.c_int64)),
        ("def_nruns", ctypes.c_int64),
        ("def_kinds", ctypes.POINTER(ctypes.c_uint8)),
        ("def_counts", ctypes.POINTER(ctypes.c_int64)),
        ("def_values", ctypes.POINTER(ctypes.c_uint32)),
        ("def_bitoffs", ctypes.POINTER(ctypes.c_int64)),
        ("def_packed", ctypes.POINTER(ctypes.c_uint8)),
        ("def_packed_len", ctypes.c_int64),
        ("idx_nruns", ctypes.c_int64),
        ("idx_kinds", ctypes.POINTER(ctypes.c_uint8)),
        ("idx_counts", ctypes.POINTER(ctypes.c_int64)),
        ("idx_values", ctypes.POINTER(ctypes.c_uint32)),
        ("idx_bitoffs", ctypes.POINTER(ctypes.c_int64)),
        ("idx_packed", ctypes.POINTER(ctypes.c_uint8)),
        ("idx_packed_len", ctypes.c_int64),
        ("plain", ctypes.POINTER(ctypes.c_uint8)),
        ("plain_len", ctypes.c_int64),
        ("dict_raw", ctypes.POINTER(ctypes.c_uint8)),
        ("dict_len", ctypes.c_int64),
        ("dict_count", ctypes.c_int64),
        ("total_values", ctypes.c_int64),
    ]


def available() -> bool:
    return _load() is not None


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


# -- LZ4 block codec ---------------------------------------------------------

def lz4_compress(data: bytes) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime not built (make -C native)")
    src = np.frombuffer(data, np.uint8)
    bound = lib.srtpu_lz4_compress_bound(len(data))
    dst = np.empty(bound, np.uint8)
    n = lib.srtpu_lz4_compress(_u8(src), len(data), _u8(dst), bound)
    if n < 0:
        raise RuntimeError("lz4 compression failed")
    return dst[:n].tobytes()


def lz4_decompress(data: bytes, uncompressed_len: int) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime not built (make -C native)")
    src = np.frombuffer(data, np.uint8)
    dst = np.empty(uncompressed_len, np.uint8)
    n = lib.srtpu_lz4_decompress(_u8(src), len(data), _u8(dst),
                                 uncompressed_len)
    if n != uncompressed_len:
        raise RuntimeError(f"lz4 decompression failed ({n})")
    return dst.tobytes()


# -- string repack -----------------------------------------------------------

def offsets_to_matrix(chars: np.ndarray, offsets: np.ndarray, width: int,
                      out: Optional[np.ndarray] = None) -> Optional[tuple]:
    """Arrow offsets+chars -> (matrix uint8[n,width], lengths int32[n]);
    None when the native lib is absent (caller uses the numpy path).
    `out` (zeroed, C-contiguous, >= n rows of `width`) lets the caller supply
    the destination (e.g. a capacity-padded device staging buffer) so the
    repack writes in place with no extra allocation."""
    lib = _load()
    if lib is None:
        return None
    n = len(offsets) - 1
    offsets = np.ascontiguousarray(offsets, np.int64)
    chars = np.ascontiguousarray(chars, np.uint8)
    if out is None:
        matrix = np.zeros((n, width), np.uint8)
    else:
        assert out.flags["C_CONTIGUOUS"] and out.shape[0] >= n \
            and out.shape[1] == width
        matrix = out[:n]
    lengths = np.zeros(n, np.int32)
    rc = lib.srtpu_offsets_to_matrix(
        _u8(chars), offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        n, width, _u8(matrix),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc != 0:
        raise ValueError("string exceeds matrix width")
    return matrix, lengths


def byte_array_scan(blob: np.ndarray, n: int) -> tuple:
    """Parquet PLAIN BYTE_ARRAY stream -> (starts int64[n], lens int32[n],
    max_len). The serial (u32 len, bytes)* prefix walk — native when built,
    numpy/python loop otherwise. Raises ValueError on a truncated stream."""
    starts = np.empty(n, np.int64)
    lens = np.empty(n, np.int32)
    blob = np.ascontiguousarray(blob, np.uint8)
    lib = _load()
    if lib is not None:
        mx = lib.srtpu_byte_array_scan(
            _u8(blob), blob.shape[0], n,
            starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        if mx < 0:
            raise ValueError("truncated BYTE_ARRAY stream")
        return starts, lens, int(mx)
    view = blob.view()
    pos, total, mx = 0, blob.shape[0], 0
    for i in range(n):
        if pos + 4 > total:
            raise ValueError("truncated BYTE_ARRAY stream")
        ln = int(view[pos]) | (int(view[pos + 1]) << 8) | \
            (int(view[pos + 2]) << 16) | (int(view[pos + 3]) << 24)
        pos += 4
        if pos + ln > total:
            raise ValueError("truncated BYTE_ARRAY stream")
        starts[i] = pos
        lens[i] = ln
        mx = max(mx, ln)
        pos += ln
    return starts, lens, mx


_RLE_SCRATCH = threading.local()


def rle_scan(payload: np.ndarray, num_values: int, bit_width: int):
    """Parquet RLE/bit-packed hybrid stream -> run table
    (kinds u8[R], counts i64[R], values u32[R], bitoffs i64[R],
    packed u8[...]); None when the native lib is absent (caller runs the
    python loop in io/parquet_device._rle_runs). Raises ValueError on a
    truncated stream — same contract as the fallback.

    The worst-case output arrays (one run per 2 stream bytes) are
    THREAD-LOCAL scratch reused across calls — allocating them fresh per
    page measured as the dominant scan cost; only the run-count-sized
    results are copied out."""
    lib = _load()
    if lib is None:
        return None
    payload = np.ascontiguousarray(payload, np.uint8)
    n = payload.shape[0]
    cap = n // 2 + 2  # a run consumes >= 2 stream bytes
    s = _RLE_SCRATCH
    if getattr(s, "cap", 0) < cap:
        s.cap = max(cap, 1 << 16)
        s.kinds = np.empty(s.cap, np.uint8)
        s.counts = np.empty(s.cap, np.int64)
        s.values = np.empty(s.cap, np.uint32)
        s.bitoffs = np.empty(s.cap, np.int64)
        s.packed = np.empty(max(s.cap * 2, 1), np.uint8)
    if s.packed.shape[0] < n:
        s.packed = np.empty(n, np.uint8)
    plen = ctypes.c_int64(0)
    i64 = ctypes.POINTER(ctypes.c_int64)
    nruns = lib.srtpu_rle_scan(
        _u8(payload), n, num_values, bit_width, _u8(s.kinds),
        s.counts.ctypes.data_as(i64),
        s.values.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        s.bitoffs.ctypes.data_as(i64), _u8(s.packed), ctypes.byref(plen))
    if nruns < 0:
        raise ValueError("truncated RLE stream")
    pl = max(plen.value, 1)
    return (s.kinds[:nruns].copy(), s.counts[:nruns].copy(),
            s.values[:nruns].copy(), s.bitoffs[:nruns].copy(),
            s.packed[:pl].copy())


def orc_deframe(buf: bytes, kind: int, bucket):
    """An ORC stream's compression blocks (kind 0 none, 2 snappy) decoded
    into ONE zero-tailed uint8 array of `bucket(n)` bytes, n the
    uncompressed length -> (array, n): no growing buffer, no copy to pad.
    None when the native lib is absent or the codec is another; raises
    ValueError on a truncated or malformed stream."""
    lib = _load()
    if lib is None or kind not in (0, 2):
        return None
    src = np.frombuffer(buf, np.uint8)
    n = lib.srtpu_orc_deframe(_u8(src), src.shape[0], kind, None, 0)
    if n < 0:
        raise ValueError("malformed compressed stream")
    out = np.zeros(bucket(n), np.uint8)
    if lib.srtpu_orc_deframe(_u8(src), src.shape[0], kind, _u8(out),
                             out.shape[0]) != n:
        raise ValueError("malformed compressed stream")
    return out, n


def varint_scan(stream: np.ndarray, values: int):
    """(values that end in the zigzag-varint `stream`, the longest of the
    first `values` in bytes, whether the stream ends inside a value) in one
    native pass with no temporary; None when the native lib is absent."""
    lib = _load()
    if lib is None:
        return None
    ends, longest = ctypes.c_int64(0), ctypes.c_int64(0)
    cut = lib.srtpu_varint_scan(_u8(stream), stream.shape[0], values,
                                ctypes.byref(ends), ctypes.byref(longest))
    return ends.value, longest.value, bool(cut)


def orc_run_table(kinds, counts, base, step, offs, width, rb: int):
    """`orc_rlev2_scan`'s run arrays -> (ends int32[rb], table
    uint32[7, rb], a width passes 32 bits) in one native pass with no
    temporary (`srtpu_orc_run_table`); None when the native lib is absent
    or an array is not of the scan's dtype."""
    lib = _load()
    arrays = (kinds, counts, base, step, offs, width)
    dtypes = (np.uint8, np.int64, np.int64, np.int64, np.int64, np.uint8)
    if lib is None or any(
            not isinstance(a, np.ndarray) or a.dtype != d
            or not a.flags.c_contiguous for a, d in zip(arrays, dtypes)):
        return None
    ends = np.empty(rb, np.int32)
    table = np.zeros((7, rb), np.uint32)
    i64 = ctypes.POINTER(ctypes.c_int64)
    wide = lib.srtpu_orc_run_table(
        _u8(kinds), counts.ctypes.data_as(i64), base.ctypes.data_as(i64),
        step.ctypes.data_as(i64), offs.ctypes.data_as(i64), _u8(width),
        kinds.shape[0], rb, ends.ctypes.data_as(
            ctypes.POINTER(ctypes.c_int32)),
        table.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return ends, table, bool(wide)


_ORC_SCRATCH = threading.local()


def orc_rlev2_scan(buf: bytes, num_values: int, signed: bool):
    """ORC RLEv2 stream -> run table (kinds u8[R], counts, base, step, offs
    i64[R], width u8[R], packed u8[...]) by `srtpu_orc_rlev2_scan`: kinds 0
    repeat, 1 arithmetic, 2 packed (offs: bit offset into `packed`), 4
    literal-delta DELTA and 5 PATCHED_BASE (offs: the run header's byte
    position in `buf`, for the caller to decode). None when the native lib
    is absent (io/orc_device._rlev2_runs then walks in Python); raises
    ValueError on a truncated or short stream, as that walk does."""
    lib = _load()
    if lib is None:
        return None
    payload = np.frombuffer(buf, np.uint8)
    n = payload.shape[0]
    cap = n // 2 + 2  # a run takes >= 2 stream bytes
    # worst-case outputs are thread-local scratch, as `rle_scan`'s are and
    # for its reason; only the run-count-sized results are copied out
    s = _ORC_SCRATCH
    if getattr(s, "cap", 0) < cap:
        s.cap = max(cap, 1 << 16)
        s.u8 = [np.empty(s.cap, np.uint8) for _ in range(2)]
        s.i64 = [np.empty(s.cap, np.int64) for _ in range(4)]
    if getattr(s, "packed", np.empty(0, np.uint8)).shape[0] < n:
        s.packed = np.empty(max(n, 1), np.uint8)
    (kinds, width), (counts, base, step, offs) = s.u8, s.i64
    plen = ctypes.c_int64(0)
    i64 = ctypes.POINTER(ctypes.c_int64)
    nruns = lib.srtpu_orc_rlev2_scan(
        _u8(payload), n, num_values, int(signed), _u8(kinds),
        counts.ctypes.data_as(i64), base.ctypes.data_as(i64),
        step.ctypes.data_as(i64), offs.ctypes.data_as(i64), _u8(width),
        _u8(s.packed), ctypes.byref(plen))
    if nruns < 0:
        raise ValueError("short RLEv2 stream" if nruns == -2
                         else "truncated RLEv2 stream")
    return (kinds[:nruns].copy(), counts[:nruns].copy(), base[:nruns].copy(),
            step[:nruns].copy(), offs[:nruns].copy(), width[:nruns].copy(),
            s.packed[:plen.value].copy())


class _ChunkHold:
    """Owns the native SrtpuChunk allocation: every array in the walk
    result is a zero-copy VIEW into it, so the holder must stay
    referenced as long as any view does (the result dict carries it, and
    the decode keeps the dict alive on the _Chunk)."""

    def __init__(self, lib, cp):
        self._lib = lib
        self._cp = cp

    def __del__(self):
        try:
            self._lib.srtpu_chunk_free(self._cp)
        except Exception:
            pass


_CTYPE_NP = {ctypes.c_uint8: np.uint8, ctypes.c_int32: np.int32,
             ctypes.c_int64: np.int64, ctypes.c_uint32: np.uint32}


def _view(ptr, n):
    """Zero-copy numpy view over a C pointer (dtype from the pointer)."""
    np_dt = _CTYPE_NP[ptr._type_]
    if n <= 0 or not ptr:
        return np.zeros(max(n, 0), np_dt)
    return np.ctypeslib.as_array(ptr, shape=(n,))


def chunk_walk(buf, codec: int, optional: bool, is_bool: bool):
    """Full parquet column-chunk page walk in C++ (headers, snappy, RLE
    scans, PLAIN concat — native/src/chunk_walk.cpp). Returns a dict of
    numpy VIEWS into one native allocation (plus the '_hold' owner —
    callers must keep the dict alive while using the arrays), or None
    when the lib is absent / the chunk is outside the fast shape (caller
    runs the python walk). codec: 0 uncompressed, 1 snappy."""
    lib = _load()
    if lib is None:
        return None
    src = np.frombuffer(buf, np.uint8)
    err = ctypes.c_int32(0)
    cp = lib.srtpu_chunk_walk(_u8(src), src.shape[0], codec,
                              int(optional), int(is_bool),
                              ctypes.byref(err))
    if not cp:
        return None  # err codes 2/3/4: python walk decides/diagnoses
    hold = _ChunkHold(lib, cp)
    c = cp.contents
    npages = c.num_pages
    return {
        "_hold": hold,
        "page_kind": _view(c.page_kind, npages),
        "page_bw": _view(c.page_bw, npages),
        "page_num_values": _view(c.page_num_values, npages),
        "page_ndef": _view(c.page_ndef, npages),
        "page_plain_off": _view(c.page_plain_off, npages),
        "page_idx_run_off": _view(c.page_idx_run_off, npages),
        "page_idx_packed_off": _view(c.page_idx_packed_off, npages),
        "def_runs": (_view(c.def_kinds, c.def_nruns),
                     _view(c.def_counts, c.def_nruns),
                     _view(c.def_values, c.def_nruns),
                     _view(c.def_bitoffs, c.def_nruns),
                     _view(c.def_packed, max(c.def_packed_len, 1))),
        "idx_runs": (_view(c.idx_kinds, c.idx_nruns),
                     _view(c.idx_counts, c.idx_nruns),
                     _view(c.idx_values, c.idx_nruns),
                     _view(c.idx_bitoffs, c.idx_nruns),
                     _view(c.idx_packed, max(c.idx_packed_len, 1))),
        "idx_packed_len": int(c.idx_packed_len),
        "plain": _view(c.plain, c.plain_len),
        "dict_raw": (_view(c.dict_raw, c.dict_len)
                     if c.dict_len or c.dict_count else None),
        "dict_count": int(c.dict_count),
        "total_values": int(c.total_values),
    }


def matrix_to_offsets(matrix: np.ndarray,
                      lengths: np.ndarray) -> Optional[tuple]:
    """(matrix, lengths) -> (offsets int64[n+1], chars uint8[total]);
    None when the native lib is absent."""
    lib = _load()
    if lib is None:
        return None
    n, width = matrix.shape
    matrix = np.ascontiguousarray(matrix, np.uint8)
    lengths = np.ascontiguousarray(lengths, np.int32)
    lp = lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    total = lib.srtpu_sum_lengths(lp, n)
    chars = np.empty(total, np.uint8)
    offsets = np.empty(n + 1, np.int64)
    lib.srtpu_matrix_to_offsets(
        _u8(matrix), lp, n, width, _u8(chars),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return offsets, chars


# -- host staging arena ------------------------------------------------------

class HostArena:
    """Python view over the native staging arena (pinned-pool analog)."""

    def __init__(self, size: int):
        lib = _load()
        if lib is None:
            raise RuntimeError("native runtime not built (make -C native)")
        rc = lib.srtpu_arena_init(size)
        if rc == -1:
            raise RuntimeError(
                "host arena already initialized (one process-wide arena; "
                "destroy() the existing one first)")
        if rc == -2:
            raise MemoryError(f"cannot map {size} byte host arena")
        self._lib = lib

    def alloc(self, n: int) -> int:
        p = self._lib.srtpu_arena_alloc(n)
        if not p:
            raise MemoryError(f"host arena exhausted allocating {n} bytes")
        return p

    def free(self, p: int) -> None:
        self._lib.srtpu_arena_free(p)

    @property
    def in_use(self) -> int:
        return self._lib.srtpu_arena_in_use()

    @property
    def peak(self) -> int:
        return self._lib.srtpu_arena_peak()

    @property
    def capacity(self) -> int:
        return self._lib.srtpu_arena_capacity()

    def destroy(self) -> None:
        self._lib.srtpu_arena_destroy()
