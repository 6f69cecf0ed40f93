"""Device-side Parquet decode (reference `GpuParquetScan.scala:1600,1796,2461`:
the reference's scan performance comes from copying RAW column chunks to a
buffer and decoding whole pages on the accelerator).

TPU shape of the same idea — PLAIN + DICT (RLE_DICTIONARY/PLAIN_DICTIONARY)
values, RLE/bit-packed definition levels, and BYTE_ARRAY strings, i.e. the
encodings default pyarrow/Spark output actually uses:

  host (cheap, control-plane):
    * footer via pyarrow metadata: row groups, chunk offsets, codecs;
    * page headers via a minimal Thrift compact-protocol parser;
    * page decompression (snappy/gzip/zstd via pyarrow) — byte plumbing only;
    * RLE run STRUCTURE scan: def-level and dictionary-index streams split
      into small per-run tables (kind, count, value, bit offset) without
      expanding any values, then ONE table per column chunk and stream
      (`_run_words`: per run its end slot, bit base, value and width, so
      pages of every bit width share it), the packed bits as 32-bit words;
      a chunk whose def levels define every row ships none;
    * BYTE_ARRAY offset scan: the serial (u32 len, bytes)* prefix walk
      (native C++, srtpu_byte_array_scan) — each length's position depends
      on all previous lengths, the one genuinely sequential step.
  device (the actual data work):
    * def-level + index expansion (`_unpack_runs`): output slot -> run by
      one mark per run scattered into the slots and a prefix sum over them
      (`rowops.slot_runs`: the slots are all queried, in order, so no table
      is searched), the run's words by one stacked gather and the two packed
      words that hold the slot's bits by one more (1-bit def levels,
      up-to-32-bit dictionary indices) — values never exist row-wise on
      the host;
    * PLAIN values: the raw little-endian byte buffer is shipped once and
      viewed as int32/int64/float32/float64 lanes;
    * DICT values: dictionary gather by expanded indices;
    * BYTE_ARRAY: every value span gathered out of the shipped page/dict
      blobs into the byte-matrix string layout (uint8[cap, width]);
    * null scatter: non-null values land at their row slots via the
      rank = cumsum(defined) gather (same shape as the join expansion);
      where a chunk has no def-level table the rank is the slot, and the
      values are masked, not gathered.

Logical-type coverage beyond the primitives (reference decodes the full
matrix in one `Table.readParquet`, `GpuParquetScan.scala:2461`):
  * DECIMAL backed by INT32/INT64 (Spark's small-precision layout) rides
    the primitive path and lands as the engine's scaled-int64 unscaled
    representation;
  * DECIMAL backed by FIXED_LEN_BYTE_ARRAY (pyarrow's layout, any
    precision <= 38): the big-endian two's-complement bytes convert to
    int64 (precision <= 18) or the expr/decimal128 (hi, lo) limb pair on
    device with vector shifts — no per-value host work;
  * TIMESTAMP(MICROS|MILLIS) on INT64 (nanos is rejected, as Spark does);
  * INT96 timestamps (julian day + nanos-of-day) convert to Spark micros
    on device.

Unsupported COLUMNS no longer evict the file: `columns_supported` returns
the per-column fallback set, `decode_row_group` decodes the supported
columns on device and merges host-decoded (pyarrow) siblings at batch
assembly — per-column granularity, like the reference's per-column decode.
Page-level surprises (v2 pages, unsupported codecs, truncated streams)
still raise DeviceDecodeUnsupported and fall just that row group back to
the host path."""

from __future__ import annotations

import functools
import os
import struct
from typing import Iterator, List, Optional, Tuple

import numpy as np

from .. import types as T
from ..columnar.padding import LANE, row_bucket
from ..ops.rowops import ahead, gather_rows, slot_runs
from ..utils import spans
from .walkers import walk_all

__all__ = ["DeviceDecodeUnsupported", "columns_supported",
           "decode_row_group", "decode_row_groups_fused",
           "device_decode_file", "file_supported"]


def _note_dispatches(n: int = 1) -> None:
    """Count device dispatch events the scan initiates: one per host->device
    buffer shipped plus one per program invocation — an (approximate, lower
    bound) proxy for host-device round-trips. Feeds TaskMetrics.scan_dispatches;
    bench.py reports dispatches-per-scan-batch from it."""
    from ..utils.metrics import TaskMetrics
    TaskMetrics.get().scan_dispatches += n


def _ship(buffers):
    """The scan's one batched host -> device transfer of `buffers` (an array
    or a list of them): the `scan.h2d` span, and `TaskMetrics.h2d_ns` and
    `h2d_bytes`."""
    import jax
    nbytes = sum(a.nbytes for a in buffers) if isinstance(buffers, list) \
        else buffers.nbytes
    with spans.span("scan.h2d", kind=spans.KIND_IO,
                    add={"h2d_bytes": nbytes}, bytes=nbytes):
        return jax.device_put(buffers)


class DeviceDecodeUnsupported(Exception):
    pass


# ----------------------------------------------------------------------------
# Thrift compact protocol (just enough for parquet PageHeader)
# ----------------------------------------------------------------------------

def _varint(buf: memoryview, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _zigzag(v: int) -> int:
    return (v >> 1) ^ -(v & 1)


def _skip_field(buf, pos, ftype):
    if ftype in (1, 2):  # bool true/false encoded in the field header
        return pos
    if ftype == 3:
        return pos + 1
    if ftype in (4, 5, 6):
        _, pos = _varint(buf, pos)
        return pos
    if ftype == 7:
        return pos + 8
    if ftype == 8:
        n, pos = _varint(buf, pos)
        return pos + n
    if ftype == 9:  # list
        head = buf[pos]
        pos += 1
        n = head >> 4
        etype = head & 0x0F
        if n == 15:
            n, pos = _varint(buf, pos)
        for _ in range(n):
            pos = _skip_field(buf, pos, etype)
        return pos
    if ftype == 12:  # struct
        return _skip_struct(buf, pos)
    raise DeviceDecodeUnsupported(f"thrift type {ftype}")


def _skip_struct(buf, pos):
    fid = 0
    while True:
        head = buf[pos]
        pos += 1
        if head == 0:
            return pos
        delta = head >> 4
        ftype = head & 0x0F
        fid = fid + delta if delta else _zigzag(_varint(buf, pos)[0])
        if not delta:
            _, pos = _varint(buf, pos)
        pos = _skip_field(buf, pos, ftype)


def _read_struct_fields(buf, pos):
    """Yields (field_id, field_type, value_or_None, new_pos); i32/i64 decoded."""
    fid = 0
    while True:
        head = buf[pos]
        pos += 1
        if head == 0:
            yield None, None, None, pos
            return
        delta = head >> 4
        ftype = head & 0x0F
        if delta:
            fid += delta
        else:
            raw, pos = _varint(buf, pos)
            fid = _zigzag(raw)
        if ftype in (4, 5, 6):
            raw, pos = _varint(buf, pos)
            yield fid, ftype, _zigzag(raw), pos
        elif ftype in (1, 2):
            yield fid, ftype, ftype == 1, pos
        else:
            start = pos
            pos = _skip_field(buf, pos, ftype)
            yield fid, ftype, (start, pos), pos


class _PageHeader:
    __slots__ = ("type", "uncompressed", "compressed", "num_values",
                 "encoding", "def_encoding", "header_len")


def _parse_page_header(buf: memoryview, pos: int) -> _PageHeader:
    h = _PageHeader()
    start = pos
    h.type = h.uncompressed = h.compressed = None
    h.num_values = h.encoding = h.def_encoding = None
    for fid, ftype, val, pos in _read_struct_fields(buf, pos):
        if fid is None:
            break
        if fid == 1:
            h.type = val
        elif fid == 2:
            h.uncompressed = val
        elif fid == 3:
            h.compressed = val
        elif fid in (5, 7) and ftype == 12:
            span = val  # (start, end) of the nested header struct
            sub_pos = span[0]
            for sfid, sftype, sval, sub_pos in _read_struct_fields(buf,
                                                                   sub_pos):
                if sfid is None:
                    break
                if sfid == 1:
                    h.num_values = sval
                elif sfid == 2:
                    h.encoding = sval
                elif sfid == 3:
                    h.def_encoding = sval
    h.header_len = pos - start
    return h


# ----------------------------------------------------------------------------
# RLE/bit-packed hybrid: host structure scan (no value expansion)
# ----------------------------------------------------------------------------

def _rle_runs(payload: memoryview, num_values: int, bit_width: int = 1):
    """Split an RLE/bit-packed hybrid stream into a run table.
    Returns (kinds u8 [R] 0=rle 1=packed, counts i64, values u32, bitoffs i64)
    where bitoffs indexes into the packed byte blob for packed runs.
    bit_width=1 is the def-level stream; dictionary index streams carry
    their width in the page payload's first byte (up to 32 bits).

    The native scanner (srtpu_rle_scan, native/src/chunk_walk.cpp) runs
    when built — the
    python loop below is the fallback and the semantic spec."""
    from ..native import runtime as _native
    if _native.available():
        try:
            native = _native.rle_scan(
                np.frombuffer(payload, np.uint8), num_values, bit_width)
        except ValueError as e:
            raise DeviceDecodeUnsupported("truncated RLE stream") from e
        if native is not None:
            return native
    vbytes = (bit_width + 7) // 8
    kinds: List[int] = []
    counts: List[int] = []
    values: List[int] = []
    bitoffs: List[int] = []
    packed = bytearray()
    pos, out = 0, 0
    vmask = (1 << bit_width) - 1
    while out < num_values and pos < len(payload):
        header, pos = _varint(payload, pos)
        if header & 1:  # bit-packed group: (header>>1)*8 values
            n = (header >> 1) * 8
            nbytes = (header >> 1) * bit_width
            kept = min(n, num_values - out)
            # short slices must NOT silently read as zeros (silent
            # corruption); the stream is malformed -> host fallback
            if pos + (kept * bit_width + 7) // 8 > len(payload):
                raise DeviceDecodeUnsupported("truncated RLE stream")
            kinds.append(1)
            counts.append(kept)
            values.append(0)
            bitoffs.append(len(packed) * 8)
            packed.extend(payload[pos:pos + nbytes])
            pos += nbytes
            out += kept
        else:  # RLE run of header>>1 copies of a vbytes-wide LE value
            n = header >> 1
            if pos + vbytes > len(payload):
                raise DeviceDecodeUnsupported("truncated RLE stream")
            v = int.from_bytes(bytes(payload[pos:pos + vbytes]), "little")
            pos += vbytes
            kinds.append(0)
            counts.append(min(n, num_values - out))
            values.append(v & vmask)
            bitoffs.append(0)
            out += counts[-1]
    if out < num_values:
        raise DeviceDecodeUnsupported("truncated RLE stream")
    if not packed:
        packed = bytearray(1)
    return (np.array(kinds, np.uint8), np.array(counts, np.int64),
            np.array(values, np.uint32), np.array(bitoffs, np.int64),
            np.frombuffer(bytes(packed), np.uint8))


# ----------------------------------------------------------------------------
# Device kernels
# ----------------------------------------------------------------------------

class _Tally:
    """What a decode program's per-slot gathers are, counted while it is
    traced: `stacked`, the stacked gathers lowered into it (two a run
    table, and the chunk merge's word matrices); `elided`, the gathers by
    an index the host knows to be the slot itself that it does not make
    (a null scatter's rank gather in a chunk with no def-level table, a
    string column's two, and each array of a chunk merge over full
    leading chunks). The program keeps the two in its box (`[stacked,
    elided]`), which the compile service restores on every cache hit."""
    __slots__ = ("stacked", "elided")

    def __init__(self):
        self.stacked = self.elided = 0


def _unpack_runs(ends, table, words, cap: int):
    """Run table (`_run_words`) -> uint32[cap], traced. Per slot: its run
    by `slot_runs`, the run's three words (bit base, repeated value,
    width) by one stacked gather, then the two 32-bit words of the
    LSB-first packed stream that hold its bits by one more: a packed
    run's slot reads `width` bits at `base + j * width`, a repeated run's
    has width 0 and reads `value`. uint32 throughout; the host keeps every
    bit position under 2^31. Slots past the table's total read a padding
    run or the last run: the callers mask them."""
    import jax.numpy as jnp
    u32 = jnp.uint32
    run = slot_runs(ends, cap)
    base, value, width = gather_rows(table, run)
    bitpos = base + jnp.arange(cap, dtype=u32) * width
    r = bitpos & u32(31)
    lo, hi = gather_rows([words, ahead(words, 1)],
                         (bitpos >> u32(5)).astype(jnp.int32))
    # r + width <= 63: the bits lie inside the two words
    bits = (lo >> r) | jnp.where(r > 0, hi << ((u32(32) - r) & u32(31)),
                                 u32(0))
    mask = jnp.where(width >= 32, ~u32(0),
                     (u32(1) << (width & u32(31))) - u32(1))
    return (bits & mask) | value


@functools.partial(__import__("jax").jit, static_argnums=(3,))
def _expand_def_levels(ends, table, words, cap: int):
    """Def-level run table -> bool[cap] defined mask, entirely on device."""
    import jax.numpy as jnp
    lvl = _unpack_runs(ends, table, words, cap)
    return (lvl == 1) & (jnp.arange(cap, dtype=jnp.int32) < ends[-1])


@functools.partial(__import__("jax").jit, static_argnums=(3,))
def _expand_rle_u32(ends, table, words, cap: int):
    """Dictionary-index run table -> u32[cap] values, on device; one table
    holds the runs of pages of any bit widths up to 32."""
    import jax.numpy as jnp
    out = _unpack_runs(ends, table, words, cap)
    return jnp.where(jnp.arange(cap, dtype=jnp.int32) < ends[-1], out, 0)


@__import__("jax").jit
def _scatter_values(vals, defined):
    """Dense non-null values (padded to cap) + defined mask -> row slots."""
    import jax.numpy as jnp
    rank = jnp.cumsum(defined.astype(jnp.int32)) - 1
    safe = jnp.clip(rank, 0, vals.shape[0] - 1)
    data = vals[safe]
    return jnp.where(defined, data, jnp.zeros((), vals.dtype)), defined


def _to_slots(vals, defined, has_def: bool, tally: _Tally):
    """`_scatter_values`, traced, where the chunk has a def-level table;
    without one every slot below the row count is defined, so a value's
    rank is its slot: masked, not gathered."""
    import jax.numpy as jnp
    if has_def:
        return _scatter_values(vals, defined)
    tally.elided += 1
    return jnp.where(defined, vals, jnp.zeros((), vals.dtype)), defined


def _dict_gather(dicts, idx):
    """[d[idx] for d in dicts]: dictionaries of 4- and 8-byte integers as
    the 32-bit rows of one stacked matrix (`gather_rows`; at most eight
    rows), gathered once; any other dtype alone. A stacked matrix costs
    per index, not per row (PERF.md price list), and the v5e compiler
    took 320 s over one nullable int64 column of a 231,037-entry
    dictionary gathered as 64-bit values, 116 s as stacked words (sandbox
    v5e compiler, PR 38)."""
    import jax
    import jax.numpy as jnp
    u32 = jnp.uint32
    rows, plans = [], []
    for d in dicts:
        size = d.dtype.itemsize
        if d.dtype.kind not in "iu" or size not in (4, 8) or \
                len(rows) + size // 4 > 8:
            plans.append(d[idx])
            continue
        u = jax.lax.bitcast_convert_type(d, np.dtype(f"uint{8 * size}"))
        plans.append((len(rows), d.dtype))
        rows.append(u.astype(u32))
        if size == 8:
            rows.append((u >> jnp.uint64(32)).astype(u32))
    g = gather_rows(rows, idx.astype(jnp.int32)) if rows else None
    out = []
    for p in plans:
        if not isinstance(p, tuple):
            out.append(p)
            continue
        at, dt = p
        if dt.itemsize == 4:
            out.append(jax.lax.bitcast_convert_type(g[at], dt))
        else:
            u = g[at].astype(jnp.uint64) | \
                (g[at + 1].astype(jnp.uint64) << jnp.uint64(32))
            out.append(jax.lax.bitcast_convert_type(u, dt))
    return out


@functools.partial(__import__("jax").jit, static_argnums=(4, 5))
def _gather_strings(blob, starts, lens, defined, width: int,
                    dense: bool = False):
    """Device bytes->matrix: row r reads value rank[r]'s span out of the
    page/dict blob into the fixed-width byte-matrix string layout
    (data uint8[cap, width] + lengths int32[cap]). The variable-length
    stream never exists row-wise on the host — only the serial offset
    scan (native byte_array_scan) ran there. `dense`: every defined row is
    a slot below the row count, so rank[r] is r and nothing is gathered
    by it."""
    import jax.numpy as jnp
    if not dense:
        cap = defined.shape[0]
        rank = jnp.cumsum(defined.astype(jnp.int32)) - 1
        safe = jnp.clip(rank, 0, cap - 1)
        starts, lens = starts[safe], lens[safe]
    return _string_matrix_tail(blob, starts, lens, defined, width)


def _string_matrix_tail(blob, starts, lens, valid, width: int):
    """Row-aligned span read shared by `_gather_strings` (after its rank
    gather) and the pushdown survivor gather: uint8[cap, width] byte
    matrix + int32 lengths out of `blob`, invalid rows zeroed."""
    import jax.numpy as jnp
    ln = jnp.where(valid, lens, 0).astype(jnp.int32)
    j = jnp.arange(width)
    idx = starts[:, None] + j[None, :]
    mat = blob[jnp.clip(idx, 0, blob.shape[0] - 1)]
    keep = (j[None, :] < ln[:, None]) & valid[:, None]
    return jnp.where(keep, mat, 0).astype(jnp.uint8), ln


@functools.partial(__import__("jax").jit, static_argnums=(1,))
def _flba_to_limbs(mat, flen: int):
    """Big-endian two's-complement bytes [n, flen] -> (hi, lo) int64 limb
    pair (the expr/decimal128 layout), sign-extended past flen, entirely
    with vector shifts on device."""
    import jax.numpy as jnp
    neg = mat[:, 0] >= 128
    fill = jnp.where(neg, jnp.uint64(0xFF), jnp.uint64(0))
    lo = jnp.zeros(mat.shape[0], jnp.uint64)
    hi = jnp.zeros(mat.shape[0], jnp.uint64)
    for j in range(16):  # byte j counts from the LEAST significant end
        src = flen - 1 - j
        b = mat[:, src].astype(jnp.uint64) if src >= 0 else fill
        if j < 8:
            lo = lo | (b << jnp.uint64(8 * j))
        else:
            hi = hi | (b << jnp.uint64(8 * (j - 8)))
    return hi.astype(jnp.int64), lo.astype(jnp.int64)


@__import__("jax").jit
def _int96_to_micros(mat):
    """INT96 timestamps [n, 12]: little-endian nanos-of-day int64 + LE
    julian day uint32 -> Spark micros since epoch (truncating division,
    `ParquetRowConverter`'s julian-day arithmetic)."""
    import jax.numpy as jnp
    nanos = jnp.zeros(mat.shape[0], jnp.uint64)
    for j in range(8):
        nanos = nanos | (mat[:, j].astype(jnp.uint64) << jnp.uint64(8 * j))
    day = jnp.zeros(mat.shape[0], jnp.int64)
    for j in range(4):
        day = day | (mat[:, 8 + j].astype(jnp.int64) << (8 * j))
    # 2440588 = julian day of 1970-01-01; nanos-of-day is non-negative so
    # // truncates like Java integer division here
    return (day - 2440588) * 86_400_000_000 + \
        (nanos.astype(jnp.int64) // 1000)


def _flba_values(mat, post: str, flen: int):
    """Fixed-width byte values [n, flen] -> the engine's value arrays:
    [micros] (INT96), [unscaled int64] (decimal of at most 18 digits) or
    [hi, lo] limbs (wider decimals)."""
    if post == "int96":
        return [_int96_to_micros(mat)]
    hi, lo = _flba_to_limbs(mat, flen)
    return [lo] if post == "dec64" else [hi, lo]


# ----------------------------------------------------------------------------
# Host orchestration
# ----------------------------------------------------------------------------

_PHYS_TO_NP = {
    "BOOLEAN": "bool",
    "INT32": "int32",
    "INT64": "int64",
    "FLOAT": "float32",
    "DOUBLE": "float64",
}

# parquet "LZ4" is the legacy Hadoop-framed variant, which pyarrow's
# lz4-frame codec cannot decode — deliberately NOT mapped (falls back)
_CODEC = {"SNAPPY": "snappy", "GZIP": "gzip", "ZSTD": "zstd"}


def _decompress(data: bytes, codec: str, size: int) -> bytes:
    import pyarrow as pa
    if codec == "UNCOMPRESSED":
        return data
    name = _CODEC.get(codec)
    if name is None:
        raise DeviceDecodeUnsupported(f"codec {codec}")
    try:
        return pa.decompress(data, decompressed_size=size, codec=name)
    except (pa.ArrowInvalid, ValueError, OSError) as e:
        # corrupt compressed page: a documented fallback mode, not a crash
        raise DeviceDecodeUnsupported(f"decompress failed: {e}") from e


def _defined_count(part) -> int:
    """Non-null count of one page's def-level run table (host, tiny)."""
    kinds, counts, values, bitoffs, packed = part
    bits = np.unpackbits(packed, bitorder="little")
    total = 0
    for k, c, v, bo in zip(kinds, counts, values, bitoffs):
        if k == 0:
            total += int(c) if v == 1 else 0
        else:
            total += int(bits[bo:bo + c].sum())
    return total


class _Page:
    """One data page's decoded control plane: def-level run table (None for
    required columns), non-null count, and either a PLAIN value byte blob
    or a dictionary-index run table."""
    __slots__ = ("num_values", "ndef", "runs", "kind", "payload", "bw")


class _Chunk:
    # def_runs_merged: whole-chunk def-level run table with GLOBAL bit
    # offsets, produced by the native walk (pages then carry runs=None);
    # python-walk chunks leave it None and _host_phase merges per page.
    # plain_all: the native walk's ALREADY-concatenated plain payload
    # (page payloads are consecutive views into it, so the fast-path prep
    # can pass a slice through instead of re-concatenating).
    # hold: owner of the native allocation every view points into — must
    # outlive the chunk (see native/runtime._ChunkHold).
    __slots__ = ("pages", "dict_raw", "dict_count", "total",
                 "def_runs_merged", "plain_all", "hold")


_NATIVE_CODEC = {"UNCOMPRESSED": 0, "SNAPPY": 1}


def _decode_chunk(buf: bytes, col_meta, optional: bool) -> _Chunk:
    """One column chunk -> _Chunk page descriptors. The native page walk
    (native/src/chunk_walk.cpp: headers + snappy + RLE scans in one
    GIL-free call) handles the common shape; the python walk below is the
    fallback and the semantic spec. Malformed page streams surface as
    DeviceDecodeUnsupported (not raw IndexError/struct.error) so callers
    can keep a NARROW fallback net — a genuine code bug elsewhere must
    not be silently swallowed into the host path."""
    codec = _NATIVE_CODEC.get(col_meta.compression)
    if codec is not None:
        from ..native import runtime as _native
        if _native.available():
            is_bool = col_meta.physical_type == "BOOLEAN"
            res = _native.chunk_walk(buf, codec, optional, is_bool)
            if res is not None:
                return _chunk_from_native(res, is_bool)
    try:
        return _decode_chunk_inner(buf, col_meta, optional)
    except (IndexError, struct.error) as e:
        raise DeviceDecodeUnsupported(f"malformed page stream: {e}") from e


def _chunk_from_native(res: dict, is_bool: bool) -> _Chunk:
    """Native walk result -> the python walk's exact _Chunk shape. Dict
    pages get LOCAL run-table slices (bit offsets rebased per page) so
    every downstream consumer — _dict_segments, _merge_runs,
    _expand_indices — behaves identically; the merged def-level table
    keeps its global offsets and rides _Chunk.def_runs_merged."""
    chunk = _Chunk()
    # Own every array that outlives this call: the walk returns zero-copy
    # views into ONE native allocation freed by _ChunkHold.__del__, while
    # the decode programs consume these arrays ASYNCHRONOUSLY — jax keeps
    # refcounted numpy inputs alive until a dispatched program has read
    # them, but a refcount on a view cannot keep a ctypes allocation
    # alive, so a view reaching jax after the hold dies reads freed
    # memory (wrong values / all-null validity once the allocator reuses
    # it). One memcpy per chunk here is far cheaper than fencing the
    # async pipeline per row group.
    dict_raw = res["dict_raw"]
    chunk.dict_raw = None if dict_raw is None else dict_raw.copy()
    chunk.dict_count = res["dict_count"]
    chunk.total = res["total_values"]
    chunk.def_runs_merged = tuple(a.copy() for a in res["def_runs"]) \
        if res["def_runs"][0].shape[0] else None
    plain = res["plain"].copy()
    chunk.plain_all = plain if not is_bool else None
    # the copies above make the native block unreferenced by anything that
    # escapes this call; the hold rides along only as the "native walk
    # engaged" marker and dies with the chunk
    chunk.hold = res["_hold"]
    chunk.pages = []
    npages = res["page_kind"].shape[0]
    ik, ic, iv, ib, ip = (a.copy() for a in res["idx_runs"])
    for i in range(npages):
        p = _Page()
        p.num_values = int(res["page_num_values"][i])
        p.ndef = int(res["page_ndef"][i])
        p.runs = None  # merged def table carries the levels
        if res["page_kind"][i] == 0:
            p.kind = "plain"
            p.bw = 0
            lo = int(res["page_plain_off"][i])
            hi = int(res["page_plain_off"][i + 1]) if i + 1 < npages \
                else plain.shape[0]
            pay = plain[lo:hi]
            p.payload = np.unpackbits(
                pay, bitorder="little")[:p.ndef] if is_bool else pay
        else:
            p.kind = "dict"
            p.bw = int(res["page_bw"][i])
            rlo = int(res["page_idx_run_off"][i])
            rhi = int(res["page_idx_run_off"][i + 1]) if i + 1 < npages \
                else ik.shape[0]
            plo = int(res["page_idx_packed_off"][i])
            phi = int(res["page_idx_packed_off"][i + 1]) \
                if i + 1 < npages else res["idx_packed_len"]
            if p.bw and p.ndef:
                packed = ip[plo:phi]
                if packed.shape[0] == 0:
                    packed = np.zeros(1, np.uint8)
                p.payload = (ik[rlo:rhi], ic[rlo:rhi], iv[rlo:rhi],
                             ib[rlo:rhi] - plo * 8, packed)
            else:
                p.payload = None
        chunk.pages.append(p)
    return chunk


def _decode_chunk_inner(buf: bytes, col_meta, optional: bool) -> _Chunk:
    phys = col_meta.physical_type
    if phys not in _PHYS_TO_NP and phys not in (
            "BYTE_ARRAY", "FIXED_LEN_BYTE_ARRAY", "INT96"):
        raise DeviceDecodeUnsupported(f"physical type {phys}")
    is_bool = phys == "BOOLEAN"
    mv = memoryview(buf)
    pos = 0
    chunk = _Chunk()
    chunk.pages = []
    chunk.dict_raw = None
    chunk.dict_count = 0
    chunk.total = 0
    chunk.def_runs_merged = None
    chunk.plain_all = None
    chunk.hold = None
    while pos < len(mv):
        h = _parse_page_header(mv, pos)
        if h.type is None or h.compressed is None or h.uncompressed is None:
            raise DeviceDecodeUnsupported("unparseable page header")
        pos += h.header_len
        if h.type == 2:  # dictionary page: PLAIN-encoded distinct values
            if chunk.pages or chunk.dict_raw is not None:
                raise DeviceDecodeUnsupported("out-of-order dictionary page")
            if h.encoding not in (0, 2):  # PLAIN / PLAIN_DICTIONARY
                raise DeviceDecodeUnsupported(
                    f"dict page encoding {h.encoding}")
            chunk.dict_raw = _decompress(bytes(mv[pos:pos + h.compressed]),
                                         col_meta.compression,
                                         h.uncompressed)
            chunk.dict_count = h.num_values or 0
            pos += h.compressed
            continue
        if h.type != 0:  # only v1 data pages; a v2 body is NOT fully
            # compressed, so it must be rejected BEFORE decompression
            raise DeviceDecodeUnsupported(f"page type {h.type}")
        payload = _decompress(bytes(mv[pos:pos + h.compressed]),
                              col_meta.compression, h.uncompressed)
        pos += h.compressed
        body = memoryview(payload)
        p = _Page()
        p.num_values = h.num_values
        if optional:
            if h.def_encoding != 3:  # RLE
                raise DeviceDecodeUnsupported(
                    f"def-level encoding {h.def_encoding}")
            (dlen,) = struct.unpack_from("<i", body, 0)
            p.runs = _rle_runs(body[4:4 + dlen], h.num_values)
            page_vals = body[4 + dlen:]
            p.ndef = _defined_count(p.runs)
        else:
            p.runs = None
            page_vals = body
            p.ndef = h.num_values
        if h.encoding == 0:  # PLAIN
            p.kind = "plain"
            p.bw = 0
            if is_bool:
                # page bit-packing restarts at a byte boundary per page; a
                # byte concat would misalign — keep unpacked 0/1 bytes
                if len(page_vals) * 8 < p.ndef:
                    raise DeviceDecodeUnsupported("truncated bool page")
                p.payload = np.unpackbits(
                    np.frombuffer(page_vals, np.uint8),
                    bitorder="little")[:p.ndef]
            else:
                p.payload = bytes(page_vals)
        elif h.encoding in (2, 8):  # PLAIN_DICTIONARY / RLE_DICTIONARY
            if chunk.dict_raw is None:
                raise DeviceDecodeUnsupported("dict page missing")
            p.kind = "dict"
            p.bw = page_vals[0] if len(page_vals) else 0
            if p.bw > 32:
                raise DeviceDecodeUnsupported(f"index bit width {p.bw}")
            p.payload = _rle_runs(page_vals[1:], p.ndef, p.bw) \
                if p.bw and p.ndef else None
        else:
            raise DeviceDecodeUnsupported(f"value encoding {h.encoding}")
        chunk.pages.append(p)
        chunk.total += h.num_values
    return chunk


def _run_words(parts):
    """The run table `_unpack_runs` reads, for one column chunk's stream in
    slot order: `parts` are (runs, bit width, values) of its pages, `runs`
    `_rle_runs`' five arrays (bit offsets local to the part's packed bytes)
    or None for `values` zeros (a one-entry dictionary's width-0 page).
    Pages of any widths share the table. Returns (ends int32[Rb], table
    uint32[3, Rb], words uint32[Pb]): per run its exclusive end slot and
    three words, the bit position of slot 0 were the run to start there
    (bit offset - first slot * width, modulo 2^32, so that a slot's is
    `base + j * width`), the repeated value (0 in a packed run) and the
    width (0 in a repeated run); the packed bytes of every part one after
    the other as little-endian words. Rb and Pb are power-of-two buckets
    (padding runs hold nothing and end where the last real one does), so
    repeated row groups share one program's shapes."""
    kinds, counts, values, bitoffs, widths, packed = [], [], [], [], [], []
    nbytes = 0
    for runs, width, n in parts:
        if runs is None:
            if not n:
                continue
            runs = (np.zeros(1, np.uint8), np.full(1, n, np.int64),
                    np.zeros(1, np.uint32), np.zeros(1, np.int64),
                    np.zeros(0, np.uint8))
        k, c, v, b, pk = runs
        kinds.append(k)
        counts.append(c)
        values.append(v)
        bitoffs.append(b + 8 * nbytes)
        widths.append(np.where(k == 1, width, 0))
        packed.append(pk)
        nbytes += pk.shape[0]
    r = sum(len(c) for c in counts)
    rb = _pow2(max(r, 1))
    ends_b = np.zeros(rb, np.int32)
    table = np.zeros((3, rb), np.uint32)
    if r:
        count = np.concatenate(counts).astype(np.int64)
        ends = np.cumsum(count)
        if ends[-1] >= 2 ** 31 or nbytes * 8 >= 2 ** 31:
            raise DeviceDecodeUnsupported("run table past 2^31 slots or bits")
        w = np.concatenate(widths).astype(np.int64)
        packed_run = w > 0
        table[0, :r] = np.where(
            packed_run, np.concatenate(bitoffs) - (ends - count) * w, 0) \
            & 0xFFFFFFFF
        table[1, :r] = np.where(packed_run, 0, np.concatenate(values))
        table[2, :r] = w
        ends_b[:] = ends[-1]
        ends_b[:r] = ends
    words = np.zeros(_pow2(max(-(-nbytes // 4), 1)) * 4, np.uint8)
    if nbytes:
        words[:nbytes] = np.concatenate(packed)
    return ends_b, table, words.view("<u4")


_OK_ENCODINGS = {"PLAIN", "RLE", "BIT_PACKED", "PLAIN_DICTIONARY",
                 "RLE_DICTIONARY"}

_EXPECTED_PHYS = {
    T.BooleanType: ("BOOLEAN",),
    T.IntegerType: ("INT32",),
    T.LongType: ("INT64",),
    T.FloatType: ("FLOAT",),
    T.DoubleType: ("DOUBLE",),
    T.DateType: ("INT32",),
    T.StringType: ("BYTE_ARRAY",),
}


class _ColSpec:
    """Footer-derived decode plan for one column.
    kind: 'prim' (bitcast/dict primitive), 'string' (BYTE_ARRAY),
          'flba' (fixed-width byte values: FLBA decimals, INT96).
    post: value conversion applied on device after decode —
          None | 'ts_ms' (millis->micros) | 'dec64' | 'dec128' | 'int96'.
    flen: fixed byte width for kind='flba'."""
    __slots__ = ("kind", "post", "flen")

    def __init__(self, kind, post=None, flen=0):
        self.kind = kind
        self.post = post
        self.flen = flen


def _column_spec(pqcol, dt) -> _ColSpec:
    """Footer column descriptor + engine dtype -> decode spec, or raise
    DeviceDecodeUnsupported with the per-column reason."""
    phys = pqcol.physical_type
    if isinstance(dt, T.DecimalType):
        lt = pqcol.logical_type
        if lt is None or lt.type != "DECIMAL":
            raise DeviceDecodeUnsupported(f"{phys} without DECIMAL "
                                          "annotation")
        if pqcol.scale != dt.scale or pqcol.precision > dt.precision:
            raise DeviceDecodeUnsupported(
                f"decimal({pqcol.precision},{pqcol.scale}) in file vs "
                f"{dt.simple_string()} in schema")
        if phys in ("INT32", "INT64"):
            # Spark's small-precision layout: the unscaled value itself
            if dt.precision > T.DecimalType.MAX_LONG_DIGITS:
                raise DeviceDecodeUnsupported(
                    f"{phys} for {dt.simple_string()}")
            return _ColSpec("prim")
        if phys == "FIXED_LEN_BYTE_ARRAY":
            flen = pqcol.length
            if not 0 < flen <= 16:
                raise DeviceDecodeUnsupported(f"FLBA length {flen}")
            post = "dec128" if dt.precision > T.DecimalType.MAX_LONG_DIGITS \
                else "dec64"
            return _ColSpec("flba", post, flen)
        raise DeviceDecodeUnsupported(f"{phys} for {dt.simple_string()}")
    if isinstance(dt, T.TimestampType):
        if phys == "INT96":
            return _ColSpec("flba", "int96", 12)
        if phys != "INT64":
            raise DeviceDecodeUnsupported(f"{phys} for timestamp")
        lt = pqcol.logical_type
        unit = None
        if lt is not None and lt.type == "TIMESTAMP":
            import json
            unit = json.loads(lt.to_json()).get("timeUnit")
        elif str(pqcol.converted_type) in ("TIMESTAMP_MICROS",
                                           "TIMESTAMP_MILLIS"):
            unit = {"TIMESTAMP_MICROS": "microseconds",
                    "TIMESTAMP_MILLIS": "milliseconds"}[
                        str(pqcol.converted_type)]
        if unit == "microseconds":
            return _ColSpec("prim")
        if unit == "milliseconds":
            return _ColSpec("prim", "ts_ms")
        # nanos would need lossy narrowing (Spark rejects NANOS outright)
        raise DeviceDecodeUnsupported(f"timestamp unit {unit}")
    ok_phys = _EXPECTED_PHYS.get(type(dt))
    if ok_phys is None:
        raise DeviceDecodeUnsupported(f"logical type {dt}")
    if phys not in ok_phys:
        raise DeviceDecodeUnsupported(f"{phys} for {dt}")
    return _ColSpec("string" if phys == "BYTE_ARRAY" else "prim")


def columns_supported(path, schema):
    """Footer-only PER-COLUMN supportability check — no page bytes read.
    Returns (ParquetFile, {column name: reason}) where the dict holds the
    columns that must host-decode (pyarrow) while their siblings take the
    device path. File-level failures (unparseable footer) raise."""
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(path)
    meta = pf.metadata
    pq_schema = meta.schema
    col_index = {pq_schema.column(i).path: i
                 for i in range(len(pq_schema))}
    bad = {}
    for name, dt in zip(schema.names, schema.types):
        try:
            if name not in col_index:
                raise DeviceDecodeUnsupported(f"column {name} not flat")
            ci = col_index[name]
            pqcol = pq_schema.column(ci)
            if pqcol.max_repetition_level > 0:
                raise DeviceDecodeUnsupported("repeated column")
            phys0 = pqcol.physical_type
            _column_spec(pqcol, dt)
            for rg in range(meta.num_row_groups):
                cm = meta.row_group(rg).column(ci)
                if cm.physical_type != phys0:
                    raise DeviceDecodeUnsupported(
                        f"{cm.physical_type} for {dt}")
                if cm.compression != "UNCOMPRESSED" and \
                        cm.compression not in _CODEC:
                    raise DeviceDecodeUnsupported(
                        f"codec {cm.compression}")
                if not set(cm.encodings) <= _OK_ENCODINGS:
                    raise DeviceDecodeUnsupported(
                        f"encodings {cm.encodings}")
        except DeviceDecodeUnsupported as e:
            bad[name] = str(e)
    return pf, bad


def file_supported(path, schema):
    """All-or-nothing wrapper over columns_supported: raises
    DeviceDecodeUnsupported if ANY column needs the host path. Returns the
    parsed ParquetFile so the decode pass doesn't re-parse the footer."""
    pf, bad = columns_supported(path, schema)
    if bad:
        name, reason = next(iter(bad.items()))
        raise DeviceDecodeUnsupported(f"{name}: {reason}")
    return pf


class _ColWork:
    """One column's host-phase product: the parsed chunk + merged
    def-level run table (numpy), plus the fast-path ship list/meta when
    the page layout allows the batched-transfer path (ship None -> the
    device phase uses the general eager assemble)."""
    __slots__ = ("name", "dt", "spec", "phys", "optional", "chunk",
                 "defruns", "ship", "meta")


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _fileno(f) -> Optional[int]:
    """`f`'s file descriptor, or None for BytesIO and friends (the cache
    exec), which seek instead."""
    try:
        return f.fileno()
    except (OSError, ValueError, AttributeError):
        return None


def _chunk_walks(pf, f, rg: int, schema, host_cols=None):
    """One row group's host phase, cut into its columns -> (row count,
    [(name, walk)]): `walk()` reads one column chunk, parses its pages,
    decompresses them and scans their RLE runs into the column's
    `_ColWork` — numpy/bytes only, no device work. The footer is read here,
    on the caller; a walk touches only its chunk's bytes, at offsets of its
    own, so the walks may run side by side."""
    meta = pf.metadata
    pq_schema = meta.schema
    col_index = {pq_schema.column(i).path: i
                 for i in range(len(pq_schema))}
    rgm = meta.row_group(rg)
    nrows = rgm.num_rows
    host_cols = set(host_cols or ())
    dev_names = [n for n in schema.names if n not in host_cols]
    cis = {}
    for name in dev_names:
        ci = col_index.get(name)
        if ci is None:
            # file changed on disk since the footer support check
            raise DeviceDecodeUnsupported(f"column {name} missing from file")
        cis[name] = ci
    fd = _fileno(f)

    def read_chunk(cm):
        start = cm.dictionary_page_offset or cm.data_page_offset
        want = cm.total_compressed_size
        if fd is not None:
            # positional reads leave the handle's offset alone; loop
            # because one pread may return short (2GiB syscall cap, NFS)
            parts = []
            got = 0
            while got < want:
                part = os.pread(fd, want - got, start + got)
                if not part:
                    break  # EOF: the decode raises on the short buffer
                parts.append(part)
                got += len(part)
            return parts[0] if len(parts) == 1 else b"".join(parts)
        f.seek(start)
        return f.read(want)

    def prep(name, dt, cm, pqcol) -> _ColWork:
        buf = read_chunk(cm)
        w = _ColWork()
        w.name, w.dt = name, dt
        w.spec = _column_spec(pqcol, dt)
        w.phys = cm.physical_type
        w.optional = pqcol.max_definition_level > 0
        if pqcol.max_repetition_level > 0:
            raise DeviceDecodeUnsupported("repeated column")
        w.chunk = _decode_chunk(buf, cm, w.optional)
        if w.chunk.total != nrows:
            raise DeviceDecodeUnsupported("page/row-group mismatch")
        if sum(p.ndef for p in w.chunk.pages) == nrows:
            # every row defined (a required column, or one that could
            # hold nulls and holds none): no def-level table, and each
            # value's slot is its rank
            w.defruns = None
        elif w.chunk.def_runs_merged is not None:
            w.defruns = _run_words([(w.chunk.def_runs_merged, 1, nrows)])
        else:
            w.defruns = _run_words([(p.runs, 1, p.num_values)
                                    for p in w.chunk.pages])
        w.ship = w.meta = None
        if w.spec.kind == "prim":
            prepped = _prep_fixed(w.chunk, w.phys)
            if prepped is not None:
                w.ship, w.meta = prepped
        elif w.spec.kind == "flba":
            prepped = _prep_flba(w.chunk, w.spec.flen)
            if prepped is not None:
                w.ship, w.meta = prepped
        return w

    by_name = dict(zip(schema.names, schema.types))
    return nrows, [(nm, functools.partial(prep, nm, by_name[nm],
                                          rgm.column(cis[nm]),
                                          pq_schema.column(cis[nm])))
                   for nm in dev_names]


def _read_chunks(pf, f, rgs, schema, host_cols=None, walkers=None):
    """HOST phase for a dispatch group: parse every row group's chunks
    once -> ([(rg, works, nrows)], total rows). On `walkers` (where
    execution is pipelined) every column chunk of the group is walked at
    once, so the chip waits for the longest chunk and not for all of them;
    without, or on a handle with no file descriptor, one after the other
    on this thread. Either way the products are the same."""
    with spans.span("scan.walk", kind=spans.KIND_IO):
        plans = [(rg, *_chunk_walks(pf, f, rg, schema, host_cols))
                 for rg in rgs]
        walks = [walk for _, _, ws in plans for _, walk in ws]
        side_by_side = walkers is not None and _fileno(f) is not None
        if not side_by_side:
            works = [walk() for walk in walks]
    if side_by_side:
        # each walk charges a `scan.walk` span of its own, outside this one
        works = walk_all(walkers, walks)
    done = iter(works)
    chunks = [(rg, {name: next(done) for name, _ in ws}, nrows)
              for rg, nrows, ws in plans]
    return chunks, sum(nrows for _, nrows, _ in plans)


def _host_phase(pf, f, rg: int, schema, host_cols=None, walkers=None):
    """HOST half of a row-group decode -> ({name: _ColWork}, row count):
    chunk reads, page parsing, decompression and RLE run scans, one row
    group's `_read_chunks`. Its columns were once walked one after the
    other because the image it was tuned on had one CPU core, where a
    thread pool was pure context switching; the chip's host has a dozen,
    and a walk's read (`os.pread`) and native page walk release the GIL, so
    on `walkers` the columns are walked side by side."""
    ((_, works, nrows),), _ = _read_chunks(pf, f, [rg], schema, host_cols,
                                           walkers)
    return works, nrows


def _device_phase(pf, rg: int, schema, works, nrows: int, host_cols=None,
                  note=None):
    """DEVICE half: ship every column's control-plane arrays in ONE
    batched transfer (a transfer costs per call more than per byte), then
    run the jitted expansion kernels. `note`, if given, is called with the
    box (`_Tally`'s two counts) of each decode program executed."""
    import jax
    import jax.numpy as jnp
    from ..columnar.batch import ColumnarBatch
    cap = row_bucket(nrows, op="scan.parquet")
    host_decoded = _host_decode_cols(pf, rg, schema, host_cols or (),
                                     cap, nrows)

    from ..columnar.column import Column
    # fast-path (prim/flba) columns fuse into ONE jitted program fed by
    # ONE batched H2D; strings and odd page layouts run their eager
    # assembles afterwards
    fused = [w for w in works.values() if w.ship is not None]
    fused_cols = {}
    if fused:
        with spans.span("scan.pack", kind=spans.KIND_IO):
            flat: List[np.ndarray] = []
            for w in fused:
                if w.defruns is not None:
                    flat.extend(w.defruns)
                flat.extend(w.ship)
            sig = tuple(_col_sig(w) for w in fused)
            program = _fused_decode_program(sig, cap)
        outs = program(np.int64(nrows), *_ship(flat))
        # one buffer per flat array + the nrows scalar + one program
        _note_dispatches(len(flat) + 2)
        if note is not None:
            note(program.msgs_box)
        for w, (data, validity) in zip(fused, outs):
            fused_cols[w.name] = Column(w.dt, data, validity)

    cols = []
    for name, dt in zip(schema.names, schema.types):
        if name in host_decoded:
            cols.append(host_decoded[name])
            continue
        if name in fused_cols:
            cols.append(fused_cols[name])
            continue
        w = works[name]
        # eager (non-fast-path) column: charge a coarse per-column floor —
        # the eager assembles below issue at least a handful of transfers
        # and program dispatches each (exact counts live on the fused path,
        # the one the bench compares)
        _note_dispatches(4)
        if w.defruns is not None:
            defined = _expand_def_levels(
                *[jnp.asarray(a) for a in w.defruns], cap)
        else:  # required column, or a 0-row row group (no pages)
            defined = jnp.arange(cap) < nrows
        if w.spec.kind == "string":
            cols.append(_assemble_strings(w.chunk, dt, defined, cap))
        elif w.spec.kind == "flba":
            cols.append(_assemble_flba(w.chunk, w.spec, dt, defined, cap))
        else:
            cols.append(_assemble_fixed(w.chunk, w.phys, dt, defined,
                                        cap, w.spec.post))
    # Buffer-lifetime note: everything shipped to the (asynchronous) decode
    # programs above is an owning, refcounted numpy array — _chunk_from_native
    # copies the native walk's views out of the _ChunkHold allocation — so the
    # programs can consume their inputs after this frame returns.
    return ColumnarBatch(schema, tuple(cols),
                         jnp.asarray(nrows, jnp.int32)), nrows


def decode_row_group(pf, f, rg: int, schema, host_cols=None):
    """Decode ONE row group on the TPU -> (device ColumnarBatch, row count).
    `pf` is a parsed ParquetFile whose supportability columns_supported()
    already vouched for; `f` is an open binary handle on the same file.
    `host_cols` names columns the support check routed to the host: they
    decode via ONE pyarrow read_row_group and merge into the batch at
    assembly — an unsupported column costs itself, not the file (reference
    decodes per column, `GpuParquetScan.scala:2461`). Page-level surprises
    the footer can't reveal (e.g. v2 pages) raise DeviceDecodeUnsupported
    so the caller can fall just THIS row group back to the host
    (pf.read_row_group) — per-row-group granularity keeps the stream lazy
    (one device batch live at a time, the reference's chunked-reader
    discipline) with no double decode."""
    works, nrows = _host_phase(pf, f, rg, schema, host_cols)
    out = _device_phase(pf, rg, schema, works, nrows, host_cols)
    from ..utils.metrics import TaskMetrics
    TaskMetrics.get().scan_chunks += 1
    return out


def _host_cols_to_device(t, schema, names, cap: int):
    """Host-decoded arrow columns -> {name: device Column} at the shared
    capacity bucket, cast to the SCAN schema's type first — the file's
    own type may differ (that mismatch is often exactly why the column
    host-decodes), and merging file-typed values into a batch whose
    schema declares the scan type would silently corrupt (e.g. a
    decimal read at the wrong scale). A cast pyarrow deems lossy raises,
    falling the whole unit back to the host path."""
    import pyarrow as pa
    from ..columnar.column import from_arrow
    by_name = dict(zip(schema.names, schema.types))
    out = {}
    for name in names:
        arr = t.column(name)
        want = T.to_arrow(by_name[name])
        if arr.type != want:
            try:
                arr = arr.cast(want)
            except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
                raise DeviceDecodeUnsupported(
                    f"host column cast {arr.type} -> {want}: {e}") from e
        col, _ = from_arrow(arr, capacity=cap)
        out[name] = col
    return out


def _host_decode_cols(pf, rg: int, schema, host_cols, cap: int, nrows: int):
    """Host (pyarrow) decode of the fallback columns of one row group ->
    {name: device Column} at the shared capacity bucket, cast to the scan
    schema's types (see _host_cols_to_device)."""
    names = [n for n in schema.names if n in set(host_cols)]
    if not names:
        return {}
    import pyarrow as pa
    try:
        t = pf.read_row_group(rg, columns=names)
    except (OSError, pa.ArrowInvalid, KeyError) as e:
        # KeyError: column vanished from the file since the footer sweep
        raise DeviceDecodeUnsupported(f"host column decode: {e}") from e
    if t.num_rows != nrows:
        raise DeviceDecodeUnsupported("host column row-count mismatch")
    return _host_cols_to_device(t, schema, names, cap)


def _expand_indices(page: _Page, dict_count: int):
    """One dict-encoded page's index stream -> u32 device values [ndef]."""
    import jax.numpy as jnp
    if page.bw == 0 or page.payload is None:
        return jnp.zeros(page.ndef, jnp.uint32)
    ends, table, words = _run_words([(page.payload, page.bw, page.ndef)])
    idx = _expand_rle_u32(jnp.asarray(ends), jnp.asarray(table),
                          jnp.asarray(words),
                          row_bucket(page.ndef))[:page.ndef]
    return jnp.clip(idx, 0, max(dict_count - 1, 0))


def _index_runs(pages):
    """A chunk's dictionary-coded pages -> (the values they hold, their one
    run table, `_run_words`, or None when they hold none)."""
    n = sum(p.ndef for p in pages)
    if not n:
        return 0, None
    return n, _run_words([(p.payload, p.bw, p.ndef) for p in pages])


def _prep_fast_path(chunk: _Chunk, meta: dict, build_dict_vals,
                    build_plain, passthrough):
    """Shared HOST half of the dict-prefix + plain-suffix fast path:
    returns (ship list of numpy arrays, meta) or None when the page
    layout needs the general eager path. The ship list joins the row
    group's single batched H2D; the fused decode program consumes the
    device arrays in the same order. Value materialization is supplied
    by the type-specific callbacks: build_dict_vals(chunk) -> array,
    build_plain(page) -> array, passthrough(total_plain_values) -> the
    native walk's pre-concatenated buffer or None."""
    kinds_seq = [p.kind for p in chunk.pages]
    ndict = 0
    while ndict < len(kinds_seq) and kinds_seq[ndict] == "dict":
        ndict += 1
    if not chunk.pages or \
            not all(k == "plain" for k in kinds_seq[ndict:]):
        return None
    ship: List[np.ndarray] = []
    meta.update({"ndict": 0, "dict_count": chunk.dict_count,
                 "has_dict_vals": False, "has_plain": False})
    if ndict:
        if chunk.dict_raw is None or not chunk.dict_count:
            raise DeviceDecodeUnsupported("dict page missing values")
        ship.append(build_dict_vals(chunk))
        meta["has_dict_vals"] = True
        meta["ndict"], runs = _index_runs(chunk.pages[:ndict])
        if runs is not None:
            ship.extend(runs)
    plain_pages = [p for p in chunk.pages[ndict:] if p.ndef]
    if plain_pages:
        total = sum(p.ndef for p in plain_pages)
        whole = passthrough(total)
        if whole is not None:
            # the native walk already concatenated the plain suffix
            # (dict pages contribute no plain bytes) — pass it through
            # instead of re-copying page by page
            ship.append(whole)
        else:
            plain = [build_plain(p) for p in plain_pages]
            ship.append(plain[0] if len(plain) == 1
                        else np.concatenate(plain))
        meta["has_plain"] = True
    return ship, meta


def _prep_fixed(chunk: _Chunk, phys: str):
    """Fixed-width fast-path prep (see _prep_fast_path)."""
    np_dt = np.dtype(_PHYS_TO_NP[phys])
    is_bool = phys == "BOOLEAN"

    def dict_vals(c):
        try:
            return np.frombuffer(c.dict_raw, np_dt, count=c.dict_count)
        except ValueError as e:
            raise DeviceDecodeUnsupported(
                f"truncated dict page: {e}") from e

    def plain_values(p):
        if is_bool:
            return p.payload.astype(np.bool_)
        try:
            return np.frombuffer(p.payload, np_dt, count=p.ndef)
        except ValueError as e:
            raise DeviceDecodeUnsupported(
                f"truncated value page: {e}") from e

    def passthrough(total):
        if chunk.plain_all is not None and not is_bool and \
                chunk.plain_all.nbytes == total * np_dt.itemsize:
            return chunk.plain_all.view(np_dt)
        return None

    return _prep_fast_path(chunk, {"np_dt": np_dt, "is_bool": is_bool},
                           dict_vals, plain_values, passthrough)


def _prep_flba(chunk: _Chunk, flen: int):
    """FLBA (byte-matrix values) fast-path prep (see _prep_fast_path)."""

    def dict_vals(c):
        need = c.dict_count * flen
        if len(c.dict_raw) < need:
            raise DeviceDecodeUnsupported("truncated dict page")
        return np.frombuffer(c.dict_raw, np.uint8,
                             count=need).reshape(-1, flen)

    def plain_mat(p):
        try:
            return np.frombuffer(p.payload, np.uint8,
                                 count=p.ndef * flen).reshape(-1, flen)
        except ValueError as e:
            raise DeviceDecodeUnsupported(
                f"truncated value page: {e}") from e

    def passthrough(total):
        if chunk.plain_all is not None and \
                chunk.plain_all.nbytes == total * flen:
            return chunk.plain_all.reshape(-1, flen)
        return None

    return _prep_fast_path(chunk, {"flen": flen}, dict_vals, plain_mat,
                           passthrough)


# -- fused multi-column decode ------------------------------------------------
# One jitted program decodes EVERY fast-path column of a row group in a
# single dispatch: def-level expansion, dictionary-index expansion,
# gathers, null scatter and dtype conversion all fuse under XLA instead of
# costing ~18 eager dispatches per column (the round-4 verdict's
# "merge per-column programs into one jitted multi-column decode"). The
# program is cached by structural signature; run tables pad to
# power-of-two shapes (_run_words) so uniform row groups share one trace.

def _col_sig(w):
    m = w.meta
    return (w.spec.kind, w.phys, w.spec.post, w.spec.flen,
            w.defruns is not None, m["has_dict_vals"], m["dict_count"],
            m["ndict"], m["has_plain"],
            str(w.dt.np_dtype) if w.spec.kind == "prim" else "",
            isinstance(w.dt, T.DateType))


def _read_idx_traced(it, ndict: int, tally: _Tally):
    """Dictionary-index expansion of a column chunk's one run table
    (traced); None when the chunk has no dictionary-coded values. Shared by
    the full decode, the deferred string-span decode and the pushdown
    predicate path."""
    if not ndict:
        return None
    ends, table, words = next(it), next(it), next(it)
    tally.stacked += 2
    return _expand_rle_u32(ends, table, words, row_bucket(ndict))[:ndict]


def _traced_defined(has_def: bool, cap: int, nrows, it, tally: _Tally):
    """A column chunk's defined mask (traced): its def-level table
    expanded, or, with none, every slot below the row count."""
    import jax.numpy as jnp
    if has_def:
        tally.stacked += 2
        return _expand_def_levels(next(it), next(it), next(it), cap)
    return jnp.arange(cap) < nrows


def _traced_decode_col(colsig, cap: int, nrows, it, tally: _Tally):
    """Decode ONE column (traced) from the ship-order array iterator `it`.
    Shared by the per-row-group fused program and the packed multi-chunk
    program. `colsig` is `_col_sig`'s tuple for prim/flba columns or
    `_string_sig`'s for the string fast path. Returns
    (data, validity, lengths_or_None)."""
    import jax.numpy as jnp
    if colsig[0] == "string":
        return _traced_decode_string(colsig, cap, nrows, it, tally)
    (kind, phys, post, flen, has_def, has_dict, dict_count,
     ndict, has_plain, np_dt_str, is_date) = colsig
    defined = _traced_defined(has_def, cap, nrows, it, tally)
    is_bool = phys == "BOOLEAN"
    dict_vals = next(it) if has_dict else None
    idx = _read_idx_traced(it, ndict, tally)
    if idx is not None:
        idx = jnp.clip(idx, 0, max(dict_count - 1, 0))
    if kind == "flba":
        # the bytes become values before the dictionary gather: each
        # dictionary entry is converted once, and values are gathered,
        # not rows of bytes
        parts = []
        if idx is not None:
            parts.append(_dict_gather(_flba_values(dict_vals, post, flen),
                                      idx))
        if has_plain:
            parts.append(_flba_values(next(it), post, flen))
        if not parts:
            parts.append(_flba_values(jnp.zeros((0, flen), jnp.uint8),
                                      post, flen))
        outs = []
        for comp in zip(*parts):
            v = comp[0] if len(comp) == 1 else jnp.concatenate(comp)
            if v.shape[0] < cap:
                v = jnp.pad(v, (0, cap - v.shape[0]))
            outs.append(_to_slots(v[:cap], defined, has_def, tally))
        if len(outs) == 1:
            return outs[0][0], outs[0][1], None
        return jnp.stack([d for d, _ in outs], axis=1), outs[0][1], None
    pieces = []
    if idx is not None:
        dv, = _dict_gather([dict_vals], idx)
        pieces.append(dv.astype(np.bool_) if is_bool else dv)
    if has_plain:
        pieces.append(next(it))
    np_dt = np.dtype(np_dt_str)
    if pieces:
        vals = pieces[0] if len(pieces) == 1 \
            else jnp.concatenate(pieces)
    else:
        vals = jnp.zeros(0, np.bool_ if is_bool
                         else np.dtype(_PHYS_TO_NP[phys]))
    if vals.shape[0] < cap:
        vals = jnp.pad(vals, (0, cap - vals.shape[0]))
    data, validity = _to_slots(vals[:cap], defined, has_def, tally)
    if is_date:
        data = data.astype(jnp.int32)
    elif data.dtype != np_dt:
        data = data.astype(np_dt)
    if post == "ts_ms":
        data = data * 1000
    return data, validity, None


def _traced_decode_string(colsig, cap: int, nrows, it, tally: _Tally):
    """String fast path (traced): dictionary-index expansion gathers
    per-value (start, len) spans out of the dictionary span tables, the
    plain suffix's spans arrive host-scanned; one `_gather_strings` builds
    the byte matrix from the shipped blob — the multi-chunk analog of
    `_assemble_strings`, restricted to the dict-prefix + plain-suffix page
    layout the fast path accepts."""
    import jax.numpy as jnp
    (_, has_def, has_dict, dict_count, ndict, has_plain,
     plain_ndef, width) = colsig
    defined = _traced_defined(has_def, cap, nrows, it, tally)
    st_parts, ln_parts = [], []
    if has_dict:
        dst = next(it)
        dln = next(it)
        idx = _read_idx_traced(it, ndict, tally)
        if idx is not None:
            idx = jnp.clip(idx, 0, max(dict_count - 1, 0))
            st, ln = _dict_gather([dst, dln], idx)
            st_parts.append(st)
            ln_parts.append(ln)
    if has_plain:
        st_parts.append(next(it))
        ln_parts.append(next(it))
    blob = next(it)
    if st_parts:
        starts = st_parts[0] if len(st_parts) == 1 \
            else jnp.concatenate(st_parts)
        lens = ln_parts[0] if len(ln_parts) == 1 \
            else jnp.concatenate(ln_parts)
    else:
        starts = jnp.zeros(0, jnp.int64)
        lens = jnp.zeros(0, jnp.int32)
    if starts.shape[0] < cap:
        starts = jnp.pad(starts, (0, cap - starts.shape[0]))
        lens = jnp.pad(lens, (0, cap - lens.shape[0]))
    if not has_def:
        tally.elided += 2       # the spans' gathers by rank
    matrix, lengths = _gather_strings(blob, starts[:cap], lens[:cap],
                                      defined, width, not has_def)
    return matrix, defined, lengths


@functools.lru_cache(maxsize=256)
def _fused_decode_program(sig_tuple, cap: int):
    """Build + jit the fused decoder for one structural signature.
    Takes the (traced) logical row count plus the flat array list in
    _device_phase's ship order and returns (data, validity) per column.
    nrows rides as a traced scalar so varied tail-row-group sizes share
    one compiled program per (signature, capacity bucket). Its box holds
    the program's `_Tally`."""
    box = [0, 0]

    def fn(nrows, *arrays):
        it = iter(arrays)
        tally = _Tally()
        outs = []
        for colsig in sig_tuple:
            data, validity, _ = _traced_decode_col(colsig, cap, nrows, it,
                                                   tally)
            outs.append((data, validity))
        box[:] = [tally.stacked, tally.elided]
        return tuple(outs)

    from ..compile import sjit
    return sjit(fn, op="io.parquet.fused_decode",
                key=repr((sig_tuple, cap)), msgs_box=box)


def _assemble_fixed(chunk: _Chunk, phys: str, dt, defined, cap: int,
                    post=None):
    """Fixed-width column: per-page non-null value streams (PLAIN bitcast
    or dictionary gather) concatenated in page order, then scattered to row
    slots by null rank. All-PLAIN chunks ship ONE host buffer. `post` is
    the spec's device conversion ('ts_ms': stored millis -> micros)."""
    import jax.numpy as jnp
    from ..columnar.column import Column
    npname = _PHYS_TO_NP[phys]
    np_dt = np.dtype(npname)
    is_bool = phys == "BOOLEAN"
    dict_vals = None
    if chunk.dict_raw is not None and chunk.dict_count:
        try:
            dict_vals = jnp.asarray(np.frombuffer(
                chunk.dict_raw, np_dt, count=chunk.dict_count))
        except ValueError as e:  # short dict blob: malformed, not a crash
            raise DeviceDecodeUnsupported(f"truncated dict page: {e}") from e
    def plain_values(p):
        if is_bool:
            return p.payload.astype(np.bool_)
        try:
            return np.frombuffer(p.payload, np_dt, count=p.ndef)
        except ValueError as e:  # short value payload
            raise DeviceDecodeUnsupported(
                f"truncated value page: {e}") from e

    def finish(vals):
        """Shared tail: pad to cap, scatter by null rank, logical dtype."""
        if vals.shape[0] == 0:
            vals = jnp.zeros(0, np.bool_ if is_bool else np_dt)
        if vals.shape[0] < cap:
            vals = jnp.pad(vals, (0, cap - vals.shape[0]))
        data, validity = _scatter_values(vals[:cap], defined)
        if isinstance(dt, T.DateType):
            data = data.astype(jnp.int32)
        elif data.dtype != dt.np_dtype:
            data = data.astype(dt.np_dtype)
        if post == "ts_ms":
            data = data * 1000
        return Column(dt, data, validity)

    # this eager assemble now serves only the page interleavings the
    # fast-path prep declines (not seen from real writers, but legal) —
    # uniform layouts ride the fused decode program instead
    parts = []
    host_run: List[np.ndarray] = []  # coalesce consecutive host parts

    def flush_host():
        if host_run:
            parts.append(jnp.asarray(np.concatenate(host_run)))
            host_run.clear()

    for p in chunk.pages:
        if p.kind == "plain":
            host_run.append(plain_values(p))
        else:
            if dict_vals is None:
                raise DeviceDecodeUnsupported("dict page missing values")
            flush_host()
            vals = dict_vals[_expand_indices(p, chunk.dict_count)]
            parts.append(vals.astype(np.bool_) if is_bool else vals)
    flush_host()
    if parts:
        vals = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    else:
        vals = jnp.zeros(0, np.bool_ if is_bool else np_dt)
    return finish(vals)


def _assemble_flba(chunk: _Chunk, spec: _ColSpec, dt, defined, cap: int):
    """Fixed-width byte values (FLBA decimals, INT96 timestamps): pages
    assemble into ONE value-dense uint8[n, flen] device matrix (dict pages
    gather rows out of the dictionary matrix; consecutive PLAIN pages ship
    as one host buffer), the type conversion runs as vector shifts on
    device, and results scatter to row slots by null rank like every other
    fixed-width column."""
    import jax.numpy as jnp
    from ..columnar.column import Column
    flen = spec.flen
    dict_mat = None
    if chunk.dict_raw is not None and chunk.dict_count:
        need = chunk.dict_count * flen
        if len(chunk.dict_raw) < need:
            raise DeviceDecodeUnsupported("truncated dict page")
        dict_mat = jnp.asarray(np.frombuffer(
            chunk.dict_raw, np.uint8, count=need).reshape(-1, flen))

    def plain_mat(p):
        try:
            return np.frombuffer(p.payload, np.uint8,
                                 count=p.ndef * flen).reshape(-1, flen)
        except ValueError as e:
            raise DeviceDecodeUnsupported(
                f"truncated value page: {e}") from e

    # serves only the page interleavings the fast-path prep declines —
    # uniform layouts ride the fused decode program instead
    pieces = []
    host_run: List[np.ndarray] = []
    for p in chunk.pages:
        if p.ndef == 0:
            continue
        if p.kind == "plain":
            host_run.append(plain_mat(p))
        else:
            if dict_mat is None:
                raise DeviceDecodeUnsupported("dict page missing values")
            if host_run:
                pieces.append(jnp.asarray(np.concatenate(host_run)))
                host_run.clear()
            pieces.append(
                dict_mat[_expand_indices(p, chunk.dict_count)])
    if host_run:
        pieces.append(jnp.asarray(np.concatenate(host_run)))
    if pieces:
        mat = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    else:
        mat = jnp.zeros((0, flen), jnp.uint8)
    if mat.shape[0] < cap:
        mat = jnp.pad(mat, ((0, cap - mat.shape[0]), (0, 0)))
    return _flba_column_from_matrix(mat[:cap], spec, dt, defined, flen)


def _flba_column_from_matrix(mat, spec: _ColSpec, dt, defined, flen: int):
    """Value-dense byte matrix [cap, flen] -> typed Column (shared tail
    of the eager and batched FLBA paths)."""
    import jax.numpy as jnp
    from ..columnar.column import Column
    if spec.post == "int96":
        vals, validity = _scatter_values(_int96_to_micros(mat), defined)
        return Column(dt, vals, validity)
    hi, lo = _flba_to_limbs(mat, flen)
    if spec.post == "dec64":
        # precision <= 18: the 128-bit value fits in int64, so the low
        # limb's bit pattern IS the unscaled value
        vals, validity = _scatter_values(lo, defined)
        return Column(dt, vals, validity)
    hi_s, validity = _scatter_values(hi, defined)
    lo_s, _ = _scatter_values(lo, defined)
    return Column(dt, jnp.stack([hi_s, lo_s], axis=1), validity)


def _assemble_strings(chunk: _Chunk, dt, defined, cap: int):
    """BYTE_ARRAY column -> byte-matrix string layout. Host does only the
    serial (len, bytes)* offset scans (native byte_array_scan); the device
    gathers every value span out of the shipped page/dict blobs into
    uint8[cap, width] (+ int32 lengths) — reference decodes strings on
    device too (`GpuParquetScan.scala:1796` via libcudf)."""
    import jax.numpy as jnp
    from ..columnar.column import Column
    from ..config import get_default_conf
    from ..native import runtime as _native

    # pass 1: lay out the device blob — plain page payloads in page order,
    # dictionary values (if any) at the end
    plain_bases = {}
    base = 0
    for i, p in enumerate(chunk.pages):
        if p.kind == "plain":
            plain_bases[i] = base
            base += len(p.payload)
    dict_base = base
    blob_np_parts = [np.frombuffer(p.payload, np.uint8)
                     for p in chunk.pages if p.kind == "plain"]
    max_len = 1
    dict_starts = dict_lens = None
    if any(p.kind == "dict" for p in chunk.pages):
        if chunk.dict_raw is None:
            raise DeviceDecodeUnsupported("dict page missing values")
        dict_blob = np.frombuffer(chunk.dict_raw, np.uint8)
        try:
            dst, dln, dmx = _native.byte_array_scan(dict_blob,
                                                    chunk.dict_count)
        except ValueError as e:
            raise DeviceDecodeUnsupported(str(e)) from e
        blob_np_parts.append(dict_blob)
        dict_starts = jnp.asarray(dst + dict_base)
        dict_lens = jnp.asarray(dln)
        max_len = max(max_len, dmx)

    # pass 2: per-value (start, len) streams in page order; consecutive
    # plain pages coalesce into ONE host concat + transfer (many tiny
    # pages must not become many tiny H2D copies)
    st_parts, ln_parts = [], []
    st_run: List[np.ndarray] = []
    ln_run: List[np.ndarray] = []

    def flush_host():
        if st_run:
            st_parts.append(jnp.asarray(np.concatenate(st_run)))
            ln_parts.append(jnp.asarray(np.concatenate(ln_run)))
            st_run.clear()
            ln_run.clear()

    for i, p in enumerate(chunk.pages):
        if p.ndef == 0:
            continue
        if p.kind == "plain":
            pl = np.frombuffer(p.payload, np.uint8)
            try:
                st, ln, mx = _native.byte_array_scan(pl, p.ndef)
            except ValueError as e:
                raise DeviceDecodeUnsupported(str(e)) from e
            max_len = max(max_len, mx)
            st_run.append(st + plain_bases[i])
            ln_run.append(ln)
        else:
            flush_host()
            idx = _expand_indices(p, chunk.dict_count)
            st_parts.append(dict_starts[idx])
            ln_parts.append(dict_lens[idx])
    flush_host()

    from ..columnar.padding import width_bucket
    width = width_bucket(max_len)
    if st_parts:
        starts = st_parts[0] if len(st_parts) == 1 else \
            jnp.concatenate(st_parts)
        lens = ln_parts[0] if len(ln_parts) == 1 else \
            jnp.concatenate(ln_parts)
    else:
        starts = jnp.zeros(0, jnp.int64)
        lens = jnp.zeros(0, jnp.int32)
    if starts.shape[0] < cap:
        starts = jnp.pad(starts, (0, cap - starts.shape[0]))
        lens = jnp.pad(lens, (0, cap - lens.shape[0]))
    blob = jnp.asarray(np.concatenate(blob_np_parts) if blob_np_parts
                       else np.zeros(1, np.uint8))
    if width > get_default_conf().string_max_width:
        # over-wide values build the CHUNKED long-string layout on device
        # (head matrix + shared tail blob) instead of host-falling-back —
        # the same representation from_arrow would build after a host
        # decode, so downstream behavior is identical, minus the fallback
        return _assemble_long_strings(jnp, dt, blob, starts, lens,
                                      defined, cap)
    matrix, lengths = _gather_strings(blob, starts[:cap], lens[:cap],
                                      defined, width)
    return Column(dt, matrix, defined, lengths)


def _assemble_long_strings(jnp, dt, blob, starts, lens, defined, cap: int):
    """Chunked layout from per-value blob spans: head bytes gather through
    the standard matrix kernel at the head width; tail bytes (beyond the
    head) flatten into the shared blob with a positional gather; offsets
    are one exclusive cumsum (columnar/strings.py layout).

    starts/lens are VALUE-dense (one entry per non-null value, like every
    parquet value stream) — rows map to values by null rank, the same
    mapping _gather_strings applies for the head."""
    from ..columnar.column import Column
    from ..columnar.strings import blob_bucket, head_width
    hw = head_width()
    head, lengths = _gather_strings(blob, starts[:cap], lens[:cap],
                                    defined, hw)
    rank = jnp.cumsum(defined.astype(jnp.int32)) - 1
    safe = jnp.clip(rank, 0, cap - 1)
    row_starts = starts[:cap][safe]
    row_lens = jnp.where(defined, lens[:cap][safe], 0)
    tail_lens = jnp.maximum(row_lens.astype(jnp.int64) - hw, 0)
    offs = jnp.cumsum(tail_lens)
    total = int(offs[cap - 1]) if cap else 0
    bb = blob_bucket(max(total, 1))
    if total == 0:
        tail_blob = jnp.zeros(bb, jnp.uint8)
    else:
        g = jnp.arange(total, dtype=jnp.int64)
        rid = jnp.searchsorted(offs, g, side="right").astype(jnp.int32)
        rid = jnp.minimum(rid, cap - 1)
        base = jnp.where(rid > 0, offs[jnp.maximum(rid - 1, 0)], 0)
        src = row_starts[rid] + hw + (g - base)
        tail_blob = jnp.pad(
            blob[jnp.clip(src, 0, blob.shape[0] - 1)], (0, bb - total))
    tail_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), offs[:-1].astype(jnp.int32)])
    return Column(dt, head, defined, lengths,
                  overflow=(tail_blob, tail_start))


# -- fused MULTI-CHUNK decode -------------------------------------------------
# The pipelined scan batches several row-group chunks per dispatch: every
# column's control-plane arrays (run tables, value payloads, string span
# tables, blobs) PACK into one contiguous host buffer, ship in ONE
# host->device transfer, and expand inside ONE compiled program that merges
# the chunks into one batch — O(1) dispatches per scan batch instead of
# O(columns x chunks) (the round-4 verdict's dispatch-amortization item).
# Offsets/shapes are static (part of the program signature); uniform row
# groups therefore share one compiled program, with at most one extra
# signature for the tail row group.

def _string_sig_from(meta: dict, w) -> tuple:
    return ("string", w.defruns is not None, meta["has_dict_vals"],
            meta["dict_count"], meta["ndict"], meta["has_plain"],
            meta["plain_ndef"], meta["width"])


def _prep_string(chunk: _Chunk):
    """HOST half of the string fast path (multi-chunk decode): dict-prefix
    + plain-suffix page layouts only (what real writers emit). The blob
    lays out plain page payloads in page order with the dictionary blob at
    the end (same layout as `_assemble_strings`); span tables come from the
    native byte_array_scan. Returns (ship, meta) or None when the page
    interleaving (or an over-wide value) needs the general eager path."""
    from ..columnar.padding import width_bucket
    from ..config import get_default_conf
    from ..native import runtime as _native
    kinds_seq = [p.kind for p in chunk.pages]
    ndict = 0
    while ndict < len(kinds_seq) and kinds_seq[ndict] == "dict":
        ndict += 1
    if not chunk.pages or not all(k == "plain" for k in kinds_seq[ndict:]):
        return None
    plain_pages = [p for p in chunk.pages[ndict:] if p.ndef]
    blob_parts = [np.frombuffer(p.payload, np.uint8) for p in plain_pages]
    plain_bases = []
    base = 0
    for p in plain_pages:
        plain_bases.append(base)
        base += len(p.payload)
    dict_base = base
    max_len = 1
    ship: List[np.ndarray] = []
    meta = {"ndict": 0, "dict_count": chunk.dict_count,
            "has_dict_vals": False, "has_plain": False, "plain_ndef": 0}
    if ndict:
        if chunk.dict_raw is None or not chunk.dict_count:
            raise DeviceDecodeUnsupported("dict page missing values")
        dict_blob = np.frombuffer(chunk.dict_raw, np.uint8)
        try:
            dst, dln, dmx = _native.byte_array_scan(dict_blob,
                                                    chunk.dict_count)
        except ValueError as e:
            raise DeviceDecodeUnsupported(str(e)) from e
        blob_parts.append(dict_blob)
        max_len = max(max_len, dmx)
        ship.append((dst + dict_base).astype(np.int64))
        ship.append(dln.astype(np.int32))
        meta["has_dict_vals"] = True
        meta["ndict"], runs = _index_runs(chunk.pages[:ndict])
        if runs is not None:
            ship.extend(runs)
    if plain_pages:
        st_parts, ln_parts = [], []
        for p, pb in zip(plain_pages, plain_bases):
            pl = np.frombuffer(p.payload, np.uint8)
            try:
                st, ln, mx = _native.byte_array_scan(pl, p.ndef)
            except ValueError as e:
                raise DeviceDecodeUnsupported(str(e)) from e
            max_len = max(max_len, mx)
            st_parts.append(st + pb)
            ln_parts.append(ln)
        ship.append(np.concatenate(st_parts).astype(np.int64))
        ship.append(np.concatenate(ln_parts).astype(np.int32))
        meta["has_plain"] = True
        meta["plain_ndef"] = sum(p.ndef for p in plain_pages)
    ship.append(np.concatenate(blob_parts) if blob_parts
                else np.zeros(1, np.uint8))
    width = width_bucket(max_len)
    if width > get_default_conf().string_max_width:
        return None  # over-wide: the eager path builds the chunked layout
    meta["width"] = width
    return ship, meta


# Byte alignment of each array in the packed buffer. Sliced out at odd
# offsets inside the decode program, they cost the v5e compiler minutes:
# `star.q3`'s fact program over two 524,288-row chunks took 471 s to
# compile, 15.5 s with every array at a multiple of 512 (sandbox v5e
# compiler, PR 38; the program now takes them from `_unpack_program`).
_PACK_ALIGN = 512


def _pack_arrays(arrays: List[np.ndarray]):
    """Flatten heterogeneous host arrays into ONE contiguous uint8 buffer
    (one H2D instead of one per array), each at a multiple of
    `_PACK_ALIGN` bytes. Returns (packed uint8[n], metas) where each meta
    is (dtype str, shape, byte offset) — static, so it rides the program
    signature and the device side reconstructs each array with slices +
    bitcasts."""
    metas = []
    parts = []
    off = 0
    for a in arrays:
        a = np.ascontiguousarray(a)
        raw = a.view(np.uint8).reshape(-1) if a.dtype != np.bool_ \
            else a.astype(np.uint8).reshape(-1)
        if off % _PACK_ALIGN:
            parts.append(np.zeros(_PACK_ALIGN - off % _PACK_ALIGN, np.uint8))
            off += _PACK_ALIGN - off % _PACK_ALIGN
        metas.append((str(a.dtype), a.shape, off))
        parts.append(raw)
        off += raw.size
    packed = np.concatenate(parts) if parts else np.zeros(1, np.uint8)
    return packed, tuple(metas)


def _unpack_traced(packed, meta):
    """Device side of `_pack_arrays`: slice + bitcast one array back out
    of the packed buffer (traced; offsets/shapes are static)."""
    import jax.numpy as jnp
    from jax import lax
    dt_str, shape, off = meta
    dt = np.dtype(dt_str)
    n = int(np.prod(shape)) if shape else 1
    seg = packed[off:off + n * dt.itemsize]
    if dt == np.bool_:
        return seg.astype(jnp.bool_).reshape(shape)
    if dt.itemsize == 1:
        return seg.reshape(shape)
    arr = lax.bitcast_convert_type(seg.reshape(-1, dt.itemsize),
                                   jnp.dtype(dt))
    return arr.reshape(shape)


def _merged_slot_source(nrows_arr, caps, cap_total: int):
    """(src int32[cap_total], live bool[cap_total]): where, in the
    concatenation of the chunks' outputs (chunk k padded to caps[k]), the
    row of merged slot j lies, and whether j is a row at all. Slot j
    belongs to the chunk after every chunk whose rows end at or before j
    (the last chunk takes the dead tail), and each of those chunks puts
    its padding, caps[k] - nrows[k], between j and its source. The chunk
    count is static and small, so that is a compare and a select per
    chunk, no table to search."""
    import jax.numpy as jnp
    assert sum(caps) < 2 ** 31 and cap_total < 2 ** 31, (caps, cap_total)
    nrows = nrows_arr.astype(jnp.int32)
    cum = jnp.cumsum(nrows)
    j = jnp.arange(cap_total, dtype=jnp.int32)
    src = j
    for k in range(len(caps) - 1):
        src = src + jnp.where(j >= cum[k], caps[k] - nrows[k], 0)
    return src, j < cum[-1]


@functools.lru_cache(maxsize=64)
def _unpack_program(metas):
    """The packed transfer buffer -> its arrays (`_unpack_traced`), as a
    program of its own: a copy of the buffer on the device, and the decode
    program then takes the arrays as arguments."""

    def fn(packed):
        return tuple(_unpack_traced(packed, m) for m in metas)

    from ..compile import sjit
    return sjit(fn, op="io.parquet.unpack", key=repr(metas))


@functools.lru_cache(maxsize=64)
def _fused_multi_program(groups_sig, caps, cap_total: int, full_lead: bool):
    """One compiled program decoding SEVERAL row-group chunks and merging
    them into one batch. `groups_sig` is, per chunk, (per-column sig
    tuple, packed-array metas); `caps` the per-chunk capacity buckets.
    Takes (nrows int64[nchunks], *arrays), the chunks' arrays in ship
    order as `_unpack_program` cuts them out of the one packed transfer:
    sliced out of it inside this program, the cells' fact program took
    the v5e compiler 770 s at two 1,048,576-row chunks, 20 s as arguments
    (sandbox v5e compiler, PR 38). `full_lead`: every chunk but the last fills its
    capacity (the host read it in the footer), so global row j is slot j
    of the chunks' outputs one after the other, and the merge cuts or pads
    that to `cap_total` and gathers nothing. Otherwise row j reads the slot
    `_merged_slot_source` gives it from the traced row counts, so tail
    chunks of any size share the program, and every column's arrays move
    by it together, in stacked word matrices (`rowops.gather_arrays`). The
    program holds no loop: every slot -> run and slot -> chunk map is a
    prefix sum or a compare, never a search (tests/test_parquet_device.py
    holds that line on the lowering). Its box holds the program's
    `_Tally`."""
    import jax.numpy as jnp
    from ..ops.rowops import GatherTally, gather_arrays
    nchunks = len(groups_sig)
    ncols = len(groups_sig[0][0])
    box = [0, 0]

    def fit(a):
        n = a.shape[0]
        if n >= cap_total:
            return a[:cap_total]
        return jnp.pad(a, ((0, cap_total - n),) + ((0, 0),) * (a.ndim - 1))

    def fn(nrows_arr, *arrays):
        tally = _Tally()
        per_col = [[] for _ in range(ncols)]
        it = iter(arrays)
        for c_i, (colsigs, metas) in enumerate(groups_sig):
            for ci, colsig in enumerate(colsigs):
                per_col[ci].append(_traced_decode_col(
                    colsig, caps[c_i], nrows_arr[c_i], it, tally))
        merged = []     # per column: data, valid and lens, if any
        for ci in range(ncols):
            datas = [d for d, _, _ in per_col[ci]]
            if datas[0].ndim == 2:
                w = max(d.shape[1] for d in datas)
                datas = [jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
                         if d.shape[1] < w else d for d in datas]
            parts = [datas, [v for _, v, _ in per_col[ci]]]
            if per_col[ci][0][2] is not None:
                parts.append([l for _, _, l in per_col[ci]])
            merged.append([jnp.concatenate(p) if nchunks > 1 else p[0]
                           for p in parts])
        flat = [a for col in merged for a in col]
        if full_lead:
            moved = [fit(a) for a in flat]
            tally.elided += len(flat)
        else:
            src, _ = _merged_slot_source(nrows_arr, caps, cap_total)
            gt = GatherTally()
            moved = gather_arrays(
                flat, jnp.clip(src, 0, flat[0].shape[0] - 1), gt)
            tally.stacked += gt.matrices
        live = jnp.arange(cap_total, dtype=jnp.int32) < \
            jnp.sum(nrows_arr).astype(jnp.int32)
        outs = []
        it = iter(moved)
        for col in merged:
            d, v = next(it), next(it) & live
            ln = jnp.where(live, next(it), 0) if len(col) == 3 else None
            outs.append((d, v, ln))
        box[:] = [tally.stacked, tally.elided]
        return tuple(outs)

    from ..compile import sjit
    return sjit(fn, op="io.parquet.fused_multi_decode",
                key=repr((groups_sig, caps, cap_total, full_lead)),
                msgs_box=box)


def _group_signatures(chunks, dev_names):
    """Fast-path prep for a whole dispatch group: per-chunk column sigs +
    the single packed transfer buffer. Returns (groups_sig, caps, packed,
    str_blob_offs) where str_blob_offs maps (chunk index, column name) to
    the string blob's byte offset inside the packed buffer (the pushdown
    gather program reads value spans straight out of it), or None when
    any column declines the fast path."""
    groups_sig = []
    caps = []
    all_arrays: List[np.ndarray] = []
    bounds = []
    blob_pos = {}
    for c_i, (_, works, nrows) in enumerate(chunks):
        # same op attribution as the serial path: the bucket tuner's scan
        # histogram must see the default-on chunk shapes too
        cap = row_bucket(nrows, op="scan.parquet")
        caps.append(cap)
        colsigs = []
        arrays: List[np.ndarray] = []
        for name in dev_names:
            w = works[name]
            ship, meta = w.ship, w.meta
            if ship is None and w.spec.kind == "string":
                # local only — `works` stays pristine so the per-rg
                # fallback's `_device_phase` eager-assembles strings
                # (its fused branch cannot consume a string ship)
                prepped = _prep_string(w.chunk)
                if prepped is not None:
                    ship, meta = prepped
            if ship is None:
                return None  # fast path declined: degrade
            if w.spec.kind == "string":
                colsigs.append(_string_sig_from(meta, w))
            else:
                colsigs.append(_col_sig(w))
            if w.defruns is not None:
                arrays.extend(w.defruns)
            arrays.extend(ship)
            if w.spec.kind == "string":
                blob_pos[(c_i, name)] = len(all_arrays) + len(arrays) - 1
        bounds.append(len(all_arrays))
        all_arrays.extend(arrays)
        groups_sig.append([tuple(colsigs), None])  # metas filled below
    packed, metas = _pack_arrays(all_arrays)
    bounds.append(len(all_arrays))
    for i, g in enumerate(groups_sig):
        g[1] = metas[bounds[i]:bounds[i + 1]]
    groups_sig = tuple((cs, m) for cs, m in groups_sig)
    str_blob_offs = {k: metas[v][2] for k, v in blob_pos.items()}
    return groups_sig, caps, packed, str_blob_offs


def decode_row_groups_fused(pf, f, rgs, schema, host_cols=None, note=None,
                            walkers=None):
    """Decode SEVERAL row groups as one dispatch group -> list of
    (device ColumnarBatch, rows). When every device column of every chunk
    takes a fast-path prep (prim/flba ship or the string span-table prep)
    the whole group decodes in ONE packed transfer + ONE program and the
    list holds one merged batch; a column that DECLINES the fast path
    (odd page interleaving, over-wide strings) degrades to per-row-group
    decode REUSING the already-computed host-phase products — host work
    (chunk reads, decompression, RLE scans) is never repeated. Only
    failures the per-row-group device path could not absorb either
    (malformed row groups, host-column read errors) raise
    DeviceDecodeUnsupported for the caller's pyarrow fallback.
    Host-fallback columns decode once via pyarrow's read_row_groups and
    merge at the total capacity. `note` as `_device_phase` takes it;
    `walkers` as `_read_chunks` takes them."""
    chunks, total = _read_chunks(pf, f, rgs, schema, host_cols, walkers)
    return _decode_chunks_fused(pf, rgs, schema, chunks, total, host_cols,
                                note)


def _per_rg_batches(pf, schema, chunks, host_cols, note=None):
    """Per-row-group decode from the SAME works — no second host
    phase. String works keep ship=None here, so `_device_phase`
    routes them through the eager assembles."""
    from ..utils.metrics import TaskMetrics
    out = []
    for rg, works, nrows in chunks:
        out.append(_device_phase(pf, rg, schema, works, nrows,
                                 host_cols, note))
        TaskMetrics.get().scan_chunks += 1
    return out


def _decode_chunks_fused(pf, rgs, schema, chunks, total, host_cols=None,
                         note=None):
    """DEVICE half of decode_row_groups_fused over pre-read chunks."""
    import jax
    import jax.numpy as jnp
    from ..columnar.batch import ColumnarBatch
    from ..columnar.column import Column
    from ..utils.metrics import TaskMetrics

    host_set = set(host_cols or ())
    dev_names = [n for n in schema.names if n not in host_set]
    if not dev_names or total == 0:
        return _per_rg_batches(pf, schema, chunks, host_cols, note)
    cap_total = row_bucket(total, op="scan.parquet")

    with spans.span("scan.pack", kind=spans.KIND_IO):
        sig = _group_signatures(chunks, dev_names)
    if sig is None:
        return _per_rg_batches(pf, schema, chunks, host_cols, note)
    groups_sig, caps, packed, _ = sig

    nrows = [n for _, _, n in chunks]
    full_lead = nrows[:-1] == caps[:-1]
    arrays = _unpack_program(tuple(m for _, ms in groups_sig for m in ms))(
        _ship(packed))
    program = _fused_multi_program(groups_sig, tuple(caps), cap_total,
                                   full_lead)
    outs = program(np.asarray(nrows, np.int64), *arrays)
    _note_dispatches(4)  # nrows + packed buffers, unpack + decode programs
    if note is not None:
        note(program.msgs_box)
    TaskMetrics.get().scan_chunks += len(rgs)

    host_decoded = {}
    if host_set:
        names = [n for n in schema.names if n in host_set]
        import pyarrow as pa
        try:
            t = pf.read_row_groups(list(rgs), columns=names)
        except (OSError, pa.ArrowInvalid, KeyError) as e:
            raise DeviceDecodeUnsupported(
                f"host column decode: {e}") from e
        if t.num_rows != total:
            raise DeviceDecodeUnsupported("host column row-count mismatch")
        host_decoded = _host_cols_to_device(t, schema, names, cap_total)

    by_name = dict(zip(schema.names, schema.types))
    dev_out = dict(zip(dev_names, outs))
    cols = []
    for name in schema.names:
        if name in host_decoded:
            cols.append(host_decoded[name])
            continue
        data, validity, lengths = dev_out[name]
        cols.append(Column(by_name[name], data, validity, lengths))
    return [(ColumnarBatch(schema, tuple(cols),
                           jnp.asarray(total, jnp.int32)), total)]


# -- pushdown: compute on compressed data --------------------------------------
# Predicate, projection and aggregate evaluation INSIDE the packed
# multi-chunk decode (plan/scan_pushdown.py carries the spec): pushed
# predicates are tested once per DICTIONARY VALUE and the verdict mapped
# over the RLE-expanded indices (and directly over PLAIN value streams),
# producing a per-row selection mask without materialising any column; a
# second program then gathers ONLY surviving rows of the projected columns
# at the survivor-count capacity bucket — for a selective predicate the
# big gathers (string byte matrices above all) run at a fraction of the
# row-group capacity. Pushed count/min/max/sum aggregates reduce over the
# mask inside the select program, so aggregate-only queries ship back a
# handful of scalars and materialise no row data at all. Both programs'
# compile keys include the pushed spec's param-faithful repr: two scans
# differing only in their pushed predicate never share an executable.


def _colsig_array_count(colsig) -> int:
    """How many packed arrays one column consumes in ship order."""
    if colsig[0] == "string":
        (_, has_def, has_dict, _dc, ndict, has_plain, _pn, _w) = colsig
        n = 3 if has_def else 0
        if has_dict:
            n += 2
        n += 3 if ndict else 0
        if has_plain:
            n += 2
        return n + 1  # + blob
    (_kind, _phys, _post, _flen, has_def, has_dict, _dc, ndict, has_plain,
     _np_dt, _is_date) = colsig
    n = 3 if has_def else 0
    if has_dict:
        n += 1
    n += 3 if ndict else 0
    if has_plain:
        n += 1
    return n


def _engine_values(colsig, arr):
    """Raw shipped values (dictionary array or plain stream) -> the
    engine-typed dense value stream, mirroring `_traced_decode_col`'s
    post-scatter conversions (dtype widen, date int32, millis->micros,
    FLBA limb/INT96 conversion) so predicate evaluation sees exactly what
    the full decode would have produced."""
    import jax.numpy as jnp
    (kind, _phys, post, flen, _hd, _hdict, _dc, _nd, _hp, np_dt_str,
     is_date) = colsig
    if kind == "flba":
        if post == "int96":
            return _int96_to_micros(arr)
        hi, lo = _flba_to_limbs(arr, flen)
        if post == "dec64":
            return lo
        return jnp.stack([hi, lo], axis=1)
    np_dt = np.dtype(np_dt_str)
    v = arr
    if is_date:
        v = v.astype(jnp.int32)
    elif v.dtype != np_dt:
        v = v.astype(np_dt)
    if post == "ts_ms":
        v = v * 1000
    return v


def _eval_pushed_leaf(expr, dt, data, lengths=None):
    """Evaluate one pushed predicate leaf over a DENSE (all-valid) value
    stream using the engine's own expression kernels — comparison,
    promotion, decimal, NaN and IN semantics are the very code the
    un-pushed TpuFilterExec runs, so the compressed-domain path cannot
    drift from it. Returns the is-true bool vector."""
    import jax.numpy as jnp
    from ..expr.base import EvalContext, Vec
    n = data.shape[0]
    ctx = EvalContext(jnp, row_mask=jnp.ones(n, dtype=bool), errors=[])
    vec = Vec(dt, data, jnp.ones(n, dtype=bool), lengths)
    res = expr.eval(ctx, [vec])
    return (res.data & res.validity).astype(jnp.bool_)


def _dense_to_rows(pieces, cap: int, defined, has_def: bool,
                   tally: _Tally):
    """Dense per-value bool verdicts -> per-row is-true (null rows false),
    the boolean analog of the value scatter."""
    import jax.numpy as jnp
    if pieces:
        dense = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
    else:
        dense = jnp.zeros(0, jnp.bool_)
    if dense.shape[0] < cap:
        dense = jnp.pad(dense, (0, cap - dense.shape[0]))
    row_bool, _ = _to_slots(dense[:cap], defined, has_def, tally)
    return row_bool & defined


def _traced_predicate_col(colsig, dt, cap: int, nrows, arrays, leaves,
                          tally: _Tally, lit_w: int = 1):
    """Evaluate this column's pushed predicate leaves on the COMPRESSED
    representation: the dictionary is tested ONCE (per leaf) and the
    verdict gathered over the expanded indices; plain value streams are
    tested densely; null checks read only the def-level mask. Returns
    (defined bool[cap], {leaf index: is-true bool[cap]})."""
    import jax.numpy as jnp
    it = iter(arrays)
    is_string = colsig[0] == "string"
    if is_string:
        (_, has_def, has_dict, dict_count, ndict, has_plain, plain_ndef,
         _w) = colsig
    else:
        (_kind, _phys, _post, _flen, has_def, has_dict, dict_count, ndict,
         has_plain, _np_dt, _is_date) = colsig
    defined = _traced_defined(has_def, cap, nrows, it, tally)
    out = {}
    if not leaves:
        return defined, out
    if is_string:
        dst = dln = None
        if has_dict:
            dst = next(it)
            dln = next(it)
        idx = _read_idx_traced(it, ndict, tally)
        pst = pln = None
        if has_plain:
            pst = next(it)
            pln = next(it)
        blob = next(it)
        # truncated-at-literal-width matrices are exact for literal
        # comparisons: equality checks lengths, and ordering vs a literal
        # of length <= lit_w is decided within the first lit_w bytes or by
        # the length tiebreak (string_compare semantics)
        dict_vals = plain_vals = None
        if idx is not None:
            dict_vals = _gather_strings(
                blob, dst, dln, jnp.ones(dict_count, bool), lit_w)
        if has_plain:
            plain_vals = _gather_strings(
                blob, pst, pln, jnp.ones(plain_ndef, bool), lit_w)
        for li, expr in leaves:
            pieces = []
            if dict_vals is not None:
                verdict = _eval_pushed_leaf(expr, dt, dict_vals[0],
                                            dict_vals[1])
                pieces.append(
                    verdict[jnp.clip(idx, 0, max(dict_count - 1, 0))])
            if plain_vals is not None:
                pieces.append(_eval_pushed_leaf(expr, dt, plain_vals[0],
                                                plain_vals[1]))
            out[li] = _dense_to_rows(pieces, cap, defined, has_def, tally)
        return defined, out
    dict_raw = next(it) if has_dict else None
    idx = _read_idx_traced(it, ndict, tally)
    plain_raw = next(it) if has_plain else None
    dict_vals = _engine_values(colsig, dict_raw) \
        if dict_raw is not None and idx is not None else None
    plain_vals = _engine_values(colsig, plain_raw) if has_plain else None
    for li, expr in leaves:
        pieces = []
        if dict_vals is not None:
            verdict = _eval_pushed_leaf(expr, dt, dict_vals)
            pieces.append(verdict[jnp.clip(idx, 0, max(dict_count - 1, 0))])
        if plain_vals is not None:
            pieces.append(_eval_pushed_leaf(expr, dt, plain_vals))
        out[li] = _dense_to_rows(pieces, cap, defined, has_def, tally)
    return defined, out


def _traced_string_spans(colsig, cap: int, nrows, it, tally: _Tally):
    """Deferred string decode: per-ROW (start, len) spans + defined mask,
    WITHOUT the byte-matrix gather — the pushdown gather program runs that
    single big gather only over surviving rows, straight out of the packed
    buffer. Start offsets are blob-relative; the caller adds the blob's
    static byte offset inside the packed buffer."""
    import jax.numpy as jnp
    (_, has_def, has_dict, dict_count, ndict, has_plain, _pn, _w) = colsig
    defined = _traced_defined(has_def, cap, nrows, it, tally)
    st_parts, ln_parts = [], []
    if has_dict:
        dst = next(it)
        dln = next(it)
        idx = _read_idx_traced(it, ndict, tally)
        if idx is not None:
            idxc = jnp.clip(idx, 0, max(dict_count - 1, 0))
            st_parts.append(dst[idxc])
            ln_parts.append(dln[idxc])
    if has_plain:
        st_parts.append(next(it))
        ln_parts.append(next(it))
    next(it)  # blob rides the packed buffer; spans index into it directly
    if st_parts:
        starts = st_parts[0] if len(st_parts) == 1 \
            else jnp.concatenate(st_parts)
        lens = ln_parts[0] if len(ln_parts) == 1 \
            else jnp.concatenate(ln_parts)
    else:
        starts = jnp.zeros(0, jnp.int64)
        lens = jnp.zeros(0, jnp.int32)
    if starts.shape[0] < cap:
        starts = jnp.pad(starts, (0, cap - starts.shape[0]))
        lens = jnp.pad(lens, (0, cap - lens.shape[0]))
    st_row, _ = _to_slots(starts[:cap], defined, has_def, tally)
    ln_row, _ = _to_slots(lens[:cap], defined, has_def, tally)
    return st_row, ln_row, defined


def _comb_tree(tree, leaf_bools, defined_by):
    if tree[0] == "and":
        return _comb_tree(tree[1], leaf_bools, defined_by) & \
            _comb_tree(tree[2], leaf_bools, defined_by)
    if tree[0] == "or":
        return _comb_tree(tree[1], leaf_bools, defined_by) | \
            _comb_tree(tree[2], leaf_bools, defined_by)
    if tree[0] == "leaf":
        return leaf_bools[tree[1]]
    if tree[0] == "isnull":  # root keep is &-ed with the live mask
        return ~defined_by[tree[1]]
    return defined_by[tree[1]]  # notnull


def _null_check_cols(tree, out):
    if tree is None:
        return out
    if tree[0] in ("and", "or"):
        _null_check_cols(tree[1], out)
        _null_check_cols(tree[2], out)
    elif tree[0] in ("isnull", "notnull"):
        out.add(tree[1])
    return out


def _string_lit_width(leaf_exprs) -> int:
    """Matrix width sufficient for exact literal comparisons on this
    column: the width bucket of the longest literal operand."""
    from ..columnar.padding import width_bucket
    from ..expr.base import Literal
    mx = 1
    for e in leaf_exprs:
        for lit in e.collect(lambda x: isinstance(x, Literal)):
            if isinstance(lit.value, str):
                mx = max(mx, len(lit.value.encode("utf-8")))
        for v in getattr(e, "items", ()) or ():
            if isinstance(v, str):
                mx = max(mx, len(v.encode("utf-8")))
    return width_bucket(mx)


def _pushdown_plan(dev, groups_sig, dev_names, dt_by_name):
    """Static per-column predicate layout shared by both programs."""
    leaves_by_col = {}
    str_w = {}
    for li, (cname, expr) in enumerate(dev.leaves):
        leaves_by_col.setdefault(cname, []).append((li, expr))
    for cname, lv in leaves_by_col.items():
        if dt_by_name[cname] == T.STRING:
            str_w[cname] = _string_lit_width([e for _, e in lv])
    pred_cols = set(leaves_by_col) | _null_check_cols(dev.tree, set())
    return leaves_by_col, pred_cols, str_w


def _col_array_slices(colsigs, metas, dev_names, packed):
    """Unpack the packed buffer and slice the arrays per column."""
    arrays = [_unpack_traced(packed, m) for m in metas]
    out = {}
    off = 0
    for name, cs in zip(dev_names, colsigs):
        cnt = _colsig_array_count(cs)
        out[name] = (cs, arrays[off:off + cnt])
        off += cnt
    # _colsig_array_count hand-mirrors the ship layout; drift must fail
    # loudly here, not as wrong predicate results over shifted slices
    assert off == len(arrays), (off, len(arrays))
    return out


def _pushdown_select_program(groups_sig, caps, cap_total: int, dev,
                             dt_by_name, dev_names):
    """Build + jit the SELECT program: evaluates the pushed predicate on
    the compressed representation of every chunk and returns either the
    merged selection mask + survivor count (row mode) or the pushed
    aggregates' partial values (aggregate mode — no row data at all)."""
    import functools as _ft
    import jax.numpy as jnp
    nchunks = len(groups_sig)
    leaves_by_col, pred_cols, str_w = _pushdown_plan(
        dev, groups_sig, dev_names, dt_by_name)
    aggs = dev.aggs
    agg_full_cols = sorted({a.column for a in aggs
                            if a.column is not None and a.op != "count"})
    agg_count_cols = sorted({a.column for a in aggs
                             if a.column is not None and a.op == "count"})
    cap1 = row_bucket(1)

    def fn(nrows_arr, packed):
        tally = _Tally()
        keeps = []
        chunk_vals = []   # per chunk: {col: (data, validity)}
        chunk_defs = []   # per chunk: {col: defined} (count-only columns)
        for c_i, (colsigs, metas) in enumerate(groups_sig):
            cols = _col_array_slices(colsigs, metas, dev_names, packed)
            defined_by = {}
            leaf_bools = {}
            for name in sorted(pred_cols):
                cs, arrs = cols[name]
                d, lb = _traced_predicate_col(
                    cs, dt_by_name[name], caps[c_i], nrows_arr[c_i], arrs,
                    tuple(leaves_by_col.get(name, ())), tally,
                    str_w.get(name, 1))
                defined_by[name] = d
                leaf_bools.update(lb)
            live = jnp.arange(caps[c_i]) < nrows_arr[c_i]
            if dev.tree is not None:
                keep = _comb_tree(dev.tree, leaf_bools, defined_by) & live
            else:
                keep = live
            keeps.append(keep)
            if aggs:
                vals = {}
                for name in agg_full_cols:
                    cs, arrs = cols[name]
                    data, validity, _ = _traced_decode_col(
                        cs, caps[c_i], nrows_arr[c_i], iter(arrs), tally)
                    vals[name] = (data, validity)
                defs = {}
                for name in agg_count_cols:
                    if name in defined_by:
                        defs[name] = defined_by[name]
                    else:
                        cs, arrs = cols[name]
                        d, _ = _traced_predicate_col(
                            cs, dt_by_name[name], caps[c_i],
                            nrows_arr[c_i], arrs, (), tally)
                        defs[name] = d
                chunk_vals.append(vals)
                chunk_defs.append(defs)
        kept_total = _ft.reduce(
            lambda a, b: a + b,
            [jnp.sum(k).astype(jnp.int64) for k in keeps])
        if aggs:
            outs = []
            for a in aggs:
                if a.op == "count":
                    if a.column is None:
                        val = kept_total
                    else:
                        val = _ft.reduce(lambda x, y: x + y, [
                            jnp.sum(k & d[a.column]).astype(jnp.int64)
                            for k, d in zip(keeps, chunk_defs)])
                    data = jnp.zeros(cap1, jnp.int64).at[0].set(val)
                    valid = jnp.zeros(cap1, bool).at[0].set(True)
                    outs.append((data, valid))
                    continue
                npdt = dt_by_name[a.column].np_dtype
                parts, anys = [], []
                for k, v in zip(keeps, chunk_vals):
                    data, validity = v[a.column]
                    m = k & validity
                    anys.append(jnp.any(m))
                    if a.op == "sum":
                        parts.append(jnp.sum(
                            jnp.where(m, data.astype(jnp.int64), 0)))
                    else:
                        from ..plan.scan_pushdown import _minmax_sentinel
                        sent = jnp.asarray(
                            _minmax_sentinel(npdt, a.op), npdt)
                        masked = jnp.where(m, data, sent)
                        parts.append(jnp.min(masked) if a.op == "min"
                                     else jnp.max(masked))
                if a.op == "sum":
                    val = _ft.reduce(lambda x, y: x + y, parts)
                    out_dt = np.dtype(np.int64)
                elif a.op == "min":
                    val = _ft.reduce(jnp.minimum, parts)
                    out_dt = npdt
                else:
                    val = _ft.reduce(jnp.maximum, parts)
                    out_dt = npdt
                anyv = _ft.reduce(lambda x, y: x | y, anys)
                data = jnp.zeros(cap1, out_dt).at[0].set(val.astype(out_dt))
                valid = jnp.zeros(cap1, bool).at[0].set(anyv)
                outs.append((data, valid))
            return kept_total, tuple(outs)
        src, live = _merged_slot_source(nrows_arr, caps, cap_total)
        keep_cat = keeps[0] if nchunks == 1 else jnp.concatenate(keeps)
        keep_g = keep_cat[jnp.clip(src, 0, keep_cat.shape[0] - 1)] & live
        return keep_g, kept_total

    from ..compile import sjit
    return sjit(fn, op="io.parquet.pushdown_select",
                key=repr((groups_sig, tuple(caps), cap_total, dev.key)))


def _pushdown_gather_program(groups_sig, caps, cap_total: int, out_cap: int,
                             dev, dt_by_name, dev_names, blob_offs):
    """Build + jit the GATHER program: late-materialise ONLY surviving
    rows of the projected columns at the survivor-count capacity bucket.
    Prim/FLBA columns decode per chunk and gather through the selection;
    string columns defer the byte-matrix gather until after selection and
    read value spans straight out of the packed buffer — the dominant
    byte cost scales with survivors, not scanned rows."""
    import jax.numpy as jnp
    nchunks = len(groups_sig)
    chunk_base = np.concatenate(([0], np.cumsum(caps)[:-1])).astype(np.int64)
    out_cols = dev.columns
    need = sorted({s for _, s in out_cols})
    str_cols = {n for n in need if dt_by_name[n] == T.STRING}
    str_width = {}
    for n in str_cols:
        ci = dev_names.index(n)
        str_width[n] = max(cs[ci][-1] for cs, _ in groups_sig)

    def fn(nrows_arr, packed, keep):
        count = jnp.sum(keep)
        sel = jnp.nonzero(keep, size=out_cap, fill_value=0)[0]
        live_out = jnp.arange(out_cap) < count
        cum = jnp.cumsum(nrows_arr)
        c_of = jnp.clip(jnp.searchsorted(cum, sel, side="right"),
                        0, nchunks - 1)
        base = jnp.where(c_of > 0, cum[jnp.maximum(c_of - 1, 0)], 0)
        src_row = jnp.asarray(chunk_base)[c_of] + (sel - base)
        per_src = {n: [] for n in need}
        tally = _Tally()
        for c_i, (colsigs, metas) in enumerate(groups_sig):
            cols = _col_array_slices(colsigs, metas, dev_names, packed)
            for name in need:
                cs, arrs = cols[name]
                if name in str_cols:
                    st, ln, d = _traced_string_spans(
                        cs, caps[c_i], nrows_arr[c_i], iter(arrs), tally)
                    per_src[name].append(
                        (st + blob_offs[(c_i, name)], ln, d))
                else:
                    data, validity, _ = _traced_decode_col(
                        cs, caps[c_i], nrows_arr[c_i], iter(arrs), tally)
                    per_src[name].append((data, validity))
        merged = {}
        for name in need:
            parts = per_src[name]
            if name in str_cols:
                st = jnp.concatenate([p[0] for p in parts]) \
                    if nchunks > 1 else parts[0][0]
                ln = jnp.concatenate([p[1] for p in parts]) \
                    if nchunks > 1 else parts[0][1]
                d = jnp.concatenate([p[2] for p in parts]) \
                    if nchunks > 1 else parts[0][2]
                gsrc = jnp.clip(src_row, 0, st.shape[0] - 1)
                v = d[gsrc] & live_out
                mat, lengths = _string_matrix_tail(
                    packed, st[gsrc], ln[gsrc], v, str_width[name])
                merged[name] = (mat, v, lengths)
            else:
                datas = [p[0] for p in parts]
                valids = [p[1] for p in parts]
                if datas[0].ndim == 2:
                    w = max(dd.shape[1] for dd in datas)
                    datas = [jnp.pad(dd, ((0, 0), (0, w - dd.shape[1])))
                             if dd.shape[1] < w else dd for dd in datas]
                data = jnp.concatenate(datas) if nchunks > 1 else datas[0]
                valid = jnp.concatenate(valids) if nchunks > 1 else valids[0]
                gsrc = jnp.clip(src_row, 0, data.shape[0] - 1)
                merged[name] = (data[gsrc], valid[gsrc] & live_out, None)
        return tuple(merged[s] for _, s in out_cols)

    from ..compile import sjit
    return sjit(fn, op="io.parquet.pushdown_gather",
                key=repr((groups_sig, tuple(caps), cap_total, out_cap,
                          dev.key)))


def decode_row_groups_pushdown(pf, f, rgs, schema, host_cols, dev,
                               walkers=None):
    """Pushdown-aware dispatch-group decode. `schema` is the scan's RAW
    column schema; `dev` a plan.scan_pushdown.DevicePushdown. Evaluates
    the pushed predicate on the compressed representation and emits only
    surviving rows of the projected columns (or aggregate partials) when
    the whole group is fast-path eligible; otherwise decodes the group
    fully (reusing the host phase) and applies the exact batch applier —
    never a silently different result. Returns a list of
    (batch, out_rows, in_rows, rows_kept, bytes_materialized); malformed
    groups raise DeviceDecodeUnsupported for the caller's per-row-group
    net. `walkers` as `_read_chunks` takes them."""
    import jax
    import jax.numpy as jnp
    from ..columnar.batch import ColumnarBatch
    from ..columnar.column import Column
    from ..utils.metrics import TaskMetrics
    chunks, total = _read_chunks(pf, f, rgs, schema, host_cols, walkers)
    host_set = set(host_cols or ())
    dev_names = [n for n in schema.names if n not in host_set]
    sig = None
    tried_sig = not host_set and dev.pred_device_ok and bool(dev_names) \
        and total > 0
    if tried_sig:
        with spans.span("scan.pack", kind=spans.KIND_IO):
            sig = _group_signatures(chunks, dev_names)
    if sig is None:
        return _pushdown_degrade(pf, rgs, schema, chunks, total,
                                 host_cols, dev, sig_declined=tried_sig)
    groups_sig, caps, packed, blob_offs = sig
    cap_total = row_bucket(total, op="scan.parquet")
    dt_by_name = dict(zip(schema.names, schema.types))
    nrows_arr = np.asarray([n for _, _, n in chunks], np.int64)
    packed_dev = _ship(packed)
    select = _pushdown_select_program(groups_sig, tuple(caps), cap_total,
                                      dev, dt_by_name, tuple(dev_names))
    TaskMetrics.get().scan_chunks += len(rgs)
    if dev.aggs:
        kept, agg_outs = select(nrows_arr, packed_dev)
        _note_dispatches(3)  # nrows + packed buffers + select program
        cols = [Column(dt, data, valid) for (data, valid), dt in
                zip(agg_outs, dev.out_schema.types)]
        batch = ColumnarBatch(dev.out_schema, tuple(cols),
                              jnp.asarray(1, jnp.int32))
        return [(batch, 1, total, int(kept), 0)]
    keep, kept = select(nrows_arr, packed_dev)
    kept_i = int(kept)
    out_cap = row_bucket(max(kept_i, 1), op="scan.parquet")
    gather = _pushdown_gather_program(groups_sig, tuple(caps), cap_total,
                                      out_cap, dev, dt_by_name,
                                      tuple(dev_names), blob_offs)
    outs = gather(nrows_arr, packed_dev, keep)
    _note_dispatches(4)  # 2 buffers + select + gather programs
    cols = []
    for (data, valid, lengths), dt in zip(outs, dev.out_schema.types):
        cols.append(Column(dt, data, valid, lengths))
    batch = ColumnarBatch(dev.out_schema, tuple(cols),
                          jnp.asarray(kept_i, jnp.int32))
    return [(batch, kept_i, total, kept_i,
             int(batch.device_memory_size()))]


def _pushdown_degrade(pf, rgs, schema, chunks, total, host_cols, dev,
                      sig_declined=False):
    """Full decode (fused or per-row-group, reusing the host phase) + the
    exact batch applier — the pushed contract holds on every path.
    `sig_declined` means the caller already computed _group_signatures and
    got a decline: go straight to per-row-group decode rather than having
    _decode_chunks_fused redo the signature pass to learn the same
    answer."""
    if sig_declined:
        inner = _per_rg_batches(pf, schema, chunks, host_cols)
    else:
        inner = _decode_chunks_fused(pf, rgs, schema, chunks, total,
                                     host_cols)
    outs = []
    for b, nrows in inner:
        in_bytes = int(b.device_memory_size())
        ob, kept = dev.applier.apply(b)
        out_rows = 1 if dev.aggs else kept
        outs.append((ob, out_rows, nrows, kept, in_bytes))
    return outs


def device_decode_file(pf, path: str, schema, host_cols=None,
                       chunks_per_dispatch: int = 1) -> Iterator:
    """Yield (device ColumnarBatch, row count), streaming — one dispatch
    group live at a time. `chunks_per_dispatch` > 1 batches that many row
    groups per fused dispatch (packed single-transfer decode); a group the
    fast path declines falls back to per-row-group decode, preserving the
    narrow fallback net. 1 reproduces the pre-pipeline per-row-group
    unit."""
    group = max(int(chunks_per_dispatch), 1)
    with open(path, "rb") as f:
        rgs = list(range(pf.metadata.num_row_groups))
        i = 0
        while i < len(rgs):
            chunk_rgs = rgs[i:i + group]
            i += len(chunk_rgs)
            if len(chunk_rgs) > 1:
                try:
                    yield from decode_row_groups_fused(pf, f, chunk_rgs,
                                                       schema, host_cols)
                    continue
                except DeviceDecodeUnsupported:
                    pass  # per-row-group decode below
            for rg in chunk_rgs:
                yield decode_row_group(pf, f, rg, schema, host_cols)
