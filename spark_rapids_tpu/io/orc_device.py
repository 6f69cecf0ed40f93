"""Device-side ORC decode (reference `GpuOrcScan.scala:826,1081,1750`: the
reference copies raw stripe streams to the accelerator and decodes whole
stripes there; ~2.7k LoC following the same strategy pattern as its
Parquet scan).

TPU shape of the same split as `parquet_device.py` — the serial,
byte-walking control plane stays on the host; every O(rows) expansion runs
on the device, one compile-service program a column (`io.orc.<kind>`:
`int`, `decimal`, `string_dict`, `string_direct`, `timestamp`, `float`,
`bool`, `byte`), so each has a name in a trace, counts as a dispatch and
reloads from the persistent cache:

  host (cheap, control-plane), a column at a time under `scan.walk`, the
  stripe's columns side by side on the process's few walkers
  (`walker_pool`) where execution is pipelined, so the chip is handed the
  first column as soon as it is walked and waits for no other:
    * postscript/footer/stripe-footer via a minimal protobuf wire parser;
    * compressed-stream deframing (3-byte block headers; zlib "deflate"
      blocks via zlib, snappy via pyarrow using the block's own varint
      length prefix; lz4/zstd raw blocks don't self-describe -> host);
    * RLEv2 run STRUCTURE scan: SHORT_REPEAT and fixed-delta DELTA ->
      arithmetic runs, DIRECT -> bit-packed runs (bytes shipped packed),
      PATCHED_BASE / variable-delta -> host-decoded (their varint/patch
      walks are inherently serial) and packed again as bit-packed runs, so
      the device knows two kinds of run — values are never expanded
      row-wise on the host;
    * present/boolean byte-RLE run scan (runs, not bits);
    * string LENGTH streams expanded host-side (tiny) -> offsets by cumsum;
    * every table, byte stream and blob padded to a power-of-two bucket
      (`scan.pack`) and shipped in one transfer a column (`scan.h2d`): the
      programs are keyed by buckets and a few static flags, never by a
      file's exact counts.
  device (the actual data work):
    * RLEv2 expansion: output slot -> run by one mark per run and a prefix
      sum (`parquet_device._slot_runs`), the run's words by one stacked
      gather, packed runs unpacked from two or three big-endian 32-bit
      words a slot by one more, zigzag undone with vector ops;
    * DECIMAL (precision <= 18): the zigzag base-128 varint mantissas fold
      from their value ends (`_varint_zigzag`): terminator bits per 32-byte
      block, a prefix sum, two stacked gathers, shifts;
    * present bits: byte runs expanded and bit-unpacked msb-first;
    * FLOAT/DOUBLE: raw little-endian stream shipped once, viewed as lanes;
    * strings: dictionary entries' bytes and lengths gathered by index out
      of the dictionary's own matrix (`_dictionary_rows`); direct values'
      spans gathered from the shipped blob (shared `_string_matrix_tail`);
    * null scatter by rank = prefix sum of the present bits, skipped where
      a column has no PRESENT stream.

Anything else (RLEv1 DIRECT encoding, decimals past 18 digits, nested
types, exotic codecs, over-wide strings) raises DeviceDecodeUnsupported and
the scan falls back to the pyarrow host path PER COLUMN or PER STRIPE — the
per-row-group fallback discipline of the parquet path applied to ORC's
stripe unit — and counts every such unit (`TaskMetrics.scan_host_decoded`)."""

from __future__ import annotations

import concurrent.futures as cf
import functools
import os
import struct
import sys
import threading
import zlib
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from .. import types as T
from ..columnar.padding import row_bucket
from ..ops.rowops import PACK_ROWS, ahead, gather_rows
from ..utils import spans
from ..utils.metrics import TaskMetrics
from .parquet_device import (DeviceDecodeUnsupported, _host_cols_to_device,
                             _note_dispatches, _pow2, _prefix_sum_i32, _ship,
                             _slot_runs, _string_matrix_tail)

__all__ = ["OrcFileInfo", "columns_supported", "decode_stripe",
           "device_decode_file", "file_supported"]


# ----------------------------------------------------------------------------
# Protobuf wire parser (just enough for the ORC metadata messages)
# ----------------------------------------------------------------------------

def _pb_varint(buf, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        if pos >= len(buf):
            raise DeviceDecodeUnsupported("truncated protobuf varint")
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _pb_fields(buf) -> Iterator[Tuple[int, int, object]]:
    """Yield (field_no, wire_type, value) over a protobuf message body."""
    pos = 0
    n = len(buf)
    while pos < n:
        key, pos = _pb_varint(buf, pos)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v, pos = _pb_varint(buf, pos)
        elif wt == 2:
            ln, pos = _pb_varint(buf, pos)
            v = bytes(buf[pos:pos + ln])
            pos += ln
        elif wt == 5:
            v = buf[pos:pos + 4]
            pos += 4
        elif wt == 1:
            v = buf[pos:pos + 8]
            pos += 8
        else:
            raise DeviceDecodeUnsupported(f"protobuf wire type {wt}")
        yield fno, wt, v


def _pb_packed_u32(v: bytes) -> List[int]:
    out = []
    pos = 0
    while pos < len(v):
        x, pos = _pb_varint(v, pos)
        out.append(x)
    return out


# ----------------------------------------------------------------------------
# File metadata
# ----------------------------------------------------------------------------

# orc_proto Type.Kind values
_K_BOOLEAN, _K_BYTE, _K_SHORT, _K_INT, _K_LONG = 0, 1, 2, 3, 4
_K_FLOAT, _K_DOUBLE, _K_STRING, _K_DATE = 5, 6, 7, 15
_K_VARCHAR, _K_CHAR = 16, 17
_K_TIMESTAMP, _K_DECIMAL, _K_TIMESTAMP_INSTANT = 9, 14, 18

_KIND_FOR_DT = {
    T.BooleanType: (_K_BOOLEAN,),
    T.ByteType: (_K_BYTE,),
    T.ShortType: (_K_SHORT,),
    T.IntegerType: (_K_INT,),
    T.LongType: (_K_LONG,),
    T.FloatType: (_K_FLOAT,),
    T.DoubleType: (_K_DOUBLE,),
    T.StringType: (_K_STRING, _K_VARCHAR, _K_CHAR),
    T.DateType: (_K_DATE,),
    T.TimestampType: (_K_TIMESTAMP, _K_TIMESTAMP_INSTANT),
    T.DecimalType: (_K_DECIMAL,),
}

# seconds from the unix epoch to the ORC timestamp epoch (2015-01-01 UTC)
_ORC_TS_BASE = 1420070400

# writer timezones the device timestamp decode accepts as "UTC wall clock"
_UTC_TZ = {"", "UTC", "GMT", "Etc/UTC", "Etc/GMT", "Universal", "Zulu"}

# CompressionKind
_COMP_NONE, _COMP_ZLIB, _COMP_SNAPPY = 0, 1, 2
_COMP_LZO, _COMP_LZ4, _COMP_ZSTD = 3, 4, 5

# Stream.Kind
_S_PRESENT, _S_DATA, _S_LENGTH, _S_DICT_DATA, _S_SECONDARY = 0, 1, 2, 3, 5

# ColumnEncoding.Kind
_E_DIRECT, _E_DICT, _E_DIRECT_V2, _E_DICT_V2 = 0, 1, 2, 3


@dataclass
class _Stripe:
    offset: int
    index_len: int
    data_len: int
    footer_len: int
    num_rows: int


@dataclass
class OrcFileInfo:
    path: str
    compression: int
    block_size: int
    stripes: List[_Stripe]
    col_ids: Dict[str, int]       # flat field name -> ORC column id
    col_kinds: Dict[int, int]     # ORC column id -> Type.Kind
    num_rows: int
    # ORC column id -> (precision, scale) for DECIMAL columns
    col_decimals: Dict[int, Tuple[int, int]] = field(default_factory=dict)


def _parse_footer(raw: bytes) -> OrcFileInfo:
    """Parse postscript + footer from a buffer holding the file TAIL
    (all offsets are end-relative)."""
    if len(raw) < 16:
        raise DeviceDecodeUnsupported("not an ORC file")
    ps_len = raw[-1]
    ps = raw[len(raw) - 1 - ps_len:len(raw) - 1]
    footer_len = comp = block = 0
    magic = b""
    for fno, _, v in _pb_fields(ps):
        if fno == 1:
            footer_len = v
        elif fno == 2:
            comp = v
        elif fno == 3:
            block = v
        elif fno == 8000:
            magic = v
    if magic != b"ORC":
        raise DeviceDecodeUnsupported("postscript magic missing")
    foot = raw[len(raw) - 1 - ps_len - footer_len:len(raw) - 1 - ps_len]
    foot = _deframe(foot, comp, block)
    stripes: List[_Stripe] = []
    types: List[Tuple[int, List[int], List[str]]] = []
    num_rows = 0
    for fno, _, v in _pb_fields(foot):
        if fno == 3:
            s = {1: 0, 2: 0, 3: 0, 4: 0, 5: 0}
            for f2, _, v2 in _pb_fields(v):
                s[f2] = v2
            stripes.append(_Stripe(s[1], s[2], s[3], s[4], s[5]))
        elif fno == 4:
            kind = 0
            prec = scale = 0
            subs: List[int] = []
            names: List[str] = []
            for f2, _, v2 in _pb_fields(v):
                if f2 == 1:
                    kind = v2
                elif f2 == 2:
                    subs = _pb_packed_u32(v2)
                elif f2 == 3:
                    names.append(v2.decode("utf-8"))
                elif f2 == 5:
                    prec = v2
                elif f2 == 6:
                    scale = v2
            types.append((kind, subs, names, prec, scale))
        elif fno == 6:
            num_rows = v
    if not types or types[0][0] != 12:  # root must be a STRUCT
        raise DeviceDecodeUnsupported("root type is not a struct")
    root_kind, subs, names = types[0][:3]
    col_ids = {nm: cid for nm, cid in zip(names, subs)}
    col_kinds = {cid: types[cid][0] for cid in subs if cid < len(types)}
    col_decimals = {cid: (types[cid][3], types[cid][4])
                    for cid in subs
                    if cid < len(types) and types[cid][0] == _K_DECIMAL}
    return OrcFileInfo("", comp, block, stripes, col_ids, col_kinds,
                       num_rows, col_decimals)


def columns_supported(path: str, schema):
    """Footer-only PER-COLUMN supportability check — no stripe bytes
    decoded. Returns (OrcFileInfo, {column name: reason}) where the dict
    holds columns that must host-decode (pyarrow read_stripe) while their
    siblings take the device path. File-level problems (bad footer,
    unsupported compression) raise."""
    try:
        with open(path, "rb") as f:
            f.seek(0, 2)
            size = f.tell()
            tail = min(size, 256 * 1024)
            f.seek(size - tail)
            raw_tail = f.read(tail)
            if not raw_tail:
                raise DeviceDecodeUnsupported("empty file")
            ps_len = raw_tail[-1]
            # postscript declares the footer length; re-read if the guess
            # didn't cover it
            need = ps_len + 1
            for fno, _, v in _pb_fields(
                    raw_tail[len(raw_tail) - 1 - ps_len:
                             len(raw_tail) - 1]):
                if fno == 1:
                    need += v
            if need > tail:
                if need > size:
                    raise DeviceDecodeUnsupported("footer exceeds file")
                f.seek(size - need)
                raw_tail = f.read(need)
            info = _parse_footer(raw_tail)
    except (OSError, struct.error, IndexError, KeyError) as e:
        raise DeviceDecodeUnsupported(f"footer read failed: {e}") from e
    info.path = path
    # NONE/ZLIB/SNAPPY decode here (snappy blocks carry their uncompressed
    # length as a varint prefix); lz4/zstd raw blocks don't self-describe a
    # size pyarrow will accept, so those files take the host path honestly
    if info.compression not in (_COMP_NONE, _COMP_ZLIB, _COMP_SNAPPY):
        raise DeviceDecodeUnsupported(f"compression {info.compression}")
    # the writer timezone lives in the stripe footers; read the FIRST
    # stripe's once so non-UTC TIMESTAMP columns route to the host at the
    # footer sweep (per column) instead of failing every stripe after its
    # streams were already read — decode_stripe still re-checks per stripe
    # as the correctness net for mixed-tz files
    tz_reason = None
    needs_tz = any(isinstance(dt, T.TimestampType) and
                   info.col_kinds.get(info.col_ids.get(nm)) == _K_TIMESTAMP
                   for nm, dt in zip(schema.names, schema.types))
    if needs_tz and info.stripes:
        try:
            with open(path, "rb") as f:
                tz = _stripe_writer_tz(info, f, info.stripes[0])
        except (OSError, struct.error, DeviceDecodeUnsupported):
            tz = None
        if tz not in _UTC_TZ:
            tz_reason = f"writer timezone {tz}"
    bad = {}
    for name, dt in zip(schema.names, schema.types):
        try:
            cid = info.col_ids.get(name)
            if cid is None:
                raise DeviceDecodeUnsupported(f"column {name} not flat")
            ok = _KIND_FOR_DT.get(type(dt))
            if ok is None:
                raise DeviceDecodeUnsupported(f"logical type {dt}")
            if info.col_kinds.get(cid) not in ok:
                raise DeviceDecodeUnsupported(
                    f"ORC kind {info.col_kinds.get(cid)} for {dt}")
            if tz_reason is not None and \
                    info.col_kinds.get(cid) == _K_TIMESTAMP:
                raise DeviceDecodeUnsupported(tz_reason)
            if isinstance(dt, T.DecimalType):
                prec, scale = info.col_decimals.get(cid, (0, 0))
                if scale != dt.scale or prec > dt.precision:
                    raise DeviceDecodeUnsupported(
                        f"decimal({prec},{scale}) in file vs "
                        f"{dt.simple_string()} in schema")
                if dt.precision > T.DecimalType.MAX_LONG_DIGITS:
                    # 128-bit mantissa varints would need carry-safe limb
                    # accumulation; host-decode just this column
                    raise DeviceDecodeUnsupported(
                        f"{dt.simple_string()} mantissa wider than 64-bit")
        except DeviceDecodeUnsupported as e:
            bad[name] = str(e)
    return info, bad


def file_supported(path: str, schema) -> OrcFileInfo:
    """All-or-nothing wrapper over columns_supported: raises
    DeviceDecodeUnsupported if ANY column needs the host path. Returns the
    parsed footer so the decode pass doesn't re-parse it."""
    info, bad = columns_supported(path, schema)
    if bad:
        name, reason = next(iter(bad.items()))
        raise DeviceDecodeUnsupported(f"{name}: {reason}")
    return info


# ----------------------------------------------------------------------------
# Compressed stream deframing (3-byte block headers)
# ----------------------------------------------------------------------------

def _deframe(buf: bytes, comp: int, block_size: int) -> bytes:
    if comp == _COMP_NONE:
        return buf
    out = bytearray()
    pos, n = 0, len(buf)
    while pos < n:
        if pos + 3 > n:
            raise DeviceDecodeUnsupported("truncated compression header")
        h = buf[pos] | (buf[pos + 1] << 8) | (buf[pos + 2] << 16)
        pos += 3
        ln = h >> 1
        chunk = buf[pos:pos + ln]
        if len(chunk) < ln:
            raise DeviceDecodeUnsupported("truncated compression block")
        pos += ln
        if h & 1:  # original (stored) block
            out += chunk
        elif comp == _COMP_ZLIB:
            try:
                out += zlib.decompress(chunk, -15)  # raw deflate
            except zlib.error as e:
                raise DeviceDecodeUnsupported(f"zlib: {e}") from e
        elif comp == _COMP_SNAPPY:
            # raw snappy blocks prefix their uncompressed length as a
            # varint; a block never decompresses past compressionBlockSize
            usize, _ = _pb_varint(chunk, 0)
            if block_size and usize > block_size:
                raise DeviceDecodeUnsupported(
                    f"snappy block claims {usize} > block size")
            import pyarrow as pa
            try:
                out += pa.decompress(chunk, decompressed_size=usize,
                                     codec="snappy")
            except Exception as e:
                raise DeviceDecodeUnsupported(f"snappy: {e}") from e
        else:
            raise DeviceDecodeUnsupported(f"compression {comp}")
    return bytes(out)


# ----------------------------------------------------------------------------
# Byte-RLE (present streams, boolean/byte data) -> run table
# ----------------------------------------------------------------------------

def _byte_rle_runs(buf: bytes, max_bytes: int):
    """Scan ORC byte-RLE into (kinds u8 0=repeat 1=literal, counts i64,
    values u8, offs i64, blob u8[...]) without expanding repeats."""
    kinds: List[int] = []
    counts: List[int] = []
    values: List[int] = []
    offs: List[int] = []
    blob = bytearray()
    pos, total = 0, 0
    n = len(buf)
    while total < max_bytes and pos < n:
        c = buf[pos]
        pos += 1
        if c < 128:  # run of c+3 copies of the next byte
            if pos >= n:
                raise DeviceDecodeUnsupported("truncated byte RLE")
            kinds.append(0)
            counts.append(c + 3)
            values.append(buf[pos])
            offs.append(0)
            pos += 1
            total += c + 3
        else:  # 256-c literal bytes
            ln = 256 - c
            if pos + ln > n:
                raise DeviceDecodeUnsupported("truncated byte RLE")
            kinds.append(1)
            counts.append(ln)
            values.append(0)
            offs.append(len(blob))
            blob += buf[pos:pos + ln]
            pos += ln
            total += ln
    if total < max_bytes:
        raise DeviceDecodeUnsupported("short byte-RLE stream")
    if not blob:
        blob = bytearray(1)
    return (np.array(kinds, np.uint8), np.array(counts, np.int64),
            np.array(values, np.uint8), np.array(offs, np.int64),
            np.frombuffer(bytes(blob), np.uint8))


_POPCOUNT = np.unpackbits(np.arange(256, dtype=np.uint8)[:, None],
                          axis=1).sum(axis=1).astype(np.int64)


def _present_ndef(runs, nrows: int) -> int:
    """Non-null count from the present RUN table in O(runs + literal
    bytes): popcount-per-byte-value for repeat runs, table-lookup popcount
    over literal slices — the bit stream is never expanded row-wise."""
    kinds, counts, values, offs, blob = runs
    nbytes = (nrows + 7) // 8
    rem = nrows - (nbytes - 1) * 8  # valid bits in the final byte (1..8)
    ndef = 0
    seen = 0
    last_byte = 0
    for k, c, v, o in zip(kinds, counts, values, offs):
        if seen >= nbytes:
            break
        take = min(int(c), nbytes - seen)
        if k == 0:
            ndef += int(_POPCOUNT[v]) * take
            lb = int(v)
        else:
            sl = blob[o:o + take]
            ndef += int(_POPCOUNT[sl].sum())
            lb = int(sl[-1]) if take else 0
        seen += take
        if seen == nbytes:
            last_byte = lb
    if rem < 8:  # drop the final byte's padding bits
        ndef -= int(_POPCOUNT[last_byte & ((1 << (8 - rem)) - 1)])
    return ndef


# ----------------------------------------------------------------------------
# RLEv2 -> run table
# ----------------------------------------------------------------------------

def _decode_width(code: int) -> int:
    if code <= 23:
        return code + 1
    return {24: 26, 25: 28, 26: 30, 27: 32,
            28: 40, 29: 48, 30: 56, 31: 64}[code]


def _closest_fixed_bits(n: int) -> int:
    """Round a bit width UP to the nearest width the readers use."""
    if n <= 24:
        return max(n, 1)
    for w in (26, 28, 30, 32, 40, 48, 56, 64):
        if n <= w:
            return w
    return 64


def _svarint(buf, pos: int) -> Tuple[int, int]:
    v, pos = _pb_varint(buf, pos)
    return (v >> 1) ^ -(v & 1), pos


def _unpack_be_host(buf: bytes, count: int, width: int) -> np.ndarray:
    """Host big-endian bit unpack (PATCHED_BASE / variable-delta literal
    runs only — both already require a serial host walk)."""
    if width == 0:
        return np.zeros(count, np.int64)
    arr = np.frombuffer(buf, np.uint8)
    if arr.size * 8 < count * width:
        raise DeviceDecodeUnsupported("truncated packed run")
    w = np.unpackbits(arr)[:count * width] \
        .reshape(count, width).astype(np.uint64)
    weights = (np.uint64(1) << np.arange(width - 1, -1, -1,
                                         dtype=np.uint64))
    return (w * weights).sum(axis=1, dtype=np.uint64).view(np.int64)


def _pack_be(u: np.ndarray, width: int) -> bytes:
    """Inverse of `_unpack_be_host`: uint64 values as a big-endian bit
    stream of `width` bits each (the last byte zero-padded)."""
    bits = np.unpackbits(u.astype(">u8").view(np.uint8).reshape(-1, 8),
                         axis=1)[:, 64 - width:]
    return np.packbits(bits.reshape(-1)).tobytes()


class _RunTable:
    """Accumulates RLEv2 runs: kind 0=repeat(base) 1=arith(base,step)
    2=packed(offs:bit,width) 3=literal(offs into aux). `arrays()` is the
    host mirror's view, `device_arrays()` the device's, in which a literal
    run is a packed one."""

    def __init__(self):
        self.kinds: List[int] = []
        self.counts: List[int] = []
        self.base: List[int] = []
        self.step: List[int] = []
        self.offs: List[int] = []
        self.width: List[int] = []
        self.packed = bytearray()
        self.aux: List[np.ndarray] = []
        self.aux_len = 0
        self.total = 0

    def add(self, kind, count, base=0, step=0, offs=0, width=0):
        self.kinds.append(kind)
        self.counts.append(count)
        self.base.append(base)
        self.step.append(step)
        self.offs.append(offs)
        self.width.append(width)
        self.total += count

    def add_literal(self, vals: np.ndarray):
        self.add(3, len(vals), offs=self.aux_len)
        self.aux.append(vals.astype(np.int64))
        self.aux_len += len(vals)

    def arrays(self):
        aux = (np.concatenate(self.aux) if self.aux
               else np.zeros(1, np.int64))
        packed = (np.frombuffer(bytes(self.packed), np.uint8)
                  if len(self.packed) else np.zeros(1, np.uint8))
        return (np.array(self.kinds, np.uint8),
                np.array(self.counts, np.int64),
                np.array(self.base, np.int64),
                np.array(self.step, np.int64),
                np.array(self.offs, np.int64),
                np.array(self.width, np.uint8),
                packed, aux)

    def device_arrays(self, signed: bool, cap: int = 0):
        """(ends int32[R], table uint32[7, R], words uint32[P], wide): what
        `_expand_rlev2` reads. Per run its exclusive end slot and seven
        words: first slot, base and step as 32-bit halves, bit offset into
        the packed stream, width | packed << 8. Literal runs (PATCHED_BASE,
        variable DELTA: the host decoded them) are packed again, big-endian
        at the width their largest value needs, zigzagged first in a signed
        stream, behind the DIRECT runs' bytes. R and P are power-of-two
        buckets (padding runs hold nothing and end where the last real run
        does; P is at least a bit for each of the `cap` slots, so two
        columns of few packed bits share a program); `wide` says a width
        passes 32 bits, the one fact of the widths a program is
        specialised on."""
        from ..native import runtime as native
        offs, width = self.offs, self.width
        literal = np.flatnonzero(np.asarray(self.kinds) == 3)
        packed = np.frombuffer(self.packed, np.uint8)
        total, tails = packed.size, []
        if literal.size:
            offs = np.array(offs, np.int64)
            width = np.array(width, np.uint8)
            for vals, i in zip(self.aux, literal):
                u = vals.view(np.uint64)
                if signed:
                    u = (u << np.uint64(1)) ^ (vals >> 63).view(np.uint64)
                w = max(int(u.max()).bit_length(), 1) if len(u) else 1
                offs[i], width[i] = total * 8, w
                tails.append(np.frombuffer(_pack_be(u, w), np.uint8))
                total += tails[-1].size
        if total >= 1 << 27:
            raise DeviceDecodeUnsupported("packed RLEv2 stream past 128 MiB")
        words = _be_words([packed] + tails, total, cap // 32)
        r, rb = len(self.kinds), _bucket(len(self.kinds))
        done = native.orc_run_table(self.kinds, self.counts, self.base,
                                    self.step, offs, width, rb)
        if done is not None:
            return done[0], done[1], words, done[2]
        kinds = np.array(self.kinds, np.int64)
        counts = np.array(self.counts, np.int64)
        ends = np.cumsum(counts)
        base = np.array(self.base, np.int64).view(np.uint64)
        step = np.array(self.step, np.int64).view(np.uint64)
        width = np.array(width, np.int64)
        # written row by row into the two arrays that ship: every
        # temporary of half a million runs is pages the host faults in
        table = np.zeros((7, rb), np.uint32)
        for row, vals in enumerate((
                ends - counts, base, base >> np.uint64(32), step,
                step >> np.uint64(32), np.array(offs, np.int64),
                width | ((kinds >= 2) << 8))):
            table[row, :r] = vals    # the cast keeps the low 32 bits
        ends_b = np.full(rb, ends[-1] if r else 0, np.int32)
        ends_b[:r] = ends
        return ends_b, table, words, bool(r and int(width.max()) > 32)


def _delta_literal_run(buf, pos: int, signed: bool):
    """A DELTA run with bit-packed deltas at `pos` -> (its values, the
    next run's position). A serial walk: each value is the one before it
    plus a delta."""
    n = len(buf)
    b0 = buf[pos]
    cnt = ((b0 & 1) << 8 | buf[pos + 1]) + 1
    p = pos + 2
    base, p = _svarint(buf, p) if signed else _pb_varint(buf, p)
    db, p = _svarint(buf, p)
    if cnt < 2:
        raise DeviceDecodeUnsupported(
            "DELTA run shorter than 2 with literal deltas")
    width = _decode_width((b0 >> 1) & 0x1F)
    nbytes = ((cnt - 2) * width + 7) // 8
    if p + nbytes > n:
        raise DeviceDecodeUnsupported("truncated DELTA run")
    deltas = _unpack_be_host(buf[p:p + nbytes], cnt - 2,
                             width).astype(np.int64)
    sign = 1 if db >= 0 else -1
    vals = np.empty(cnt, np.int64)
    vals[0] = base
    vals[1] = base + db
    np.cumsum(sign * deltas, out=deltas)
    vals[2:] = base + db + deltas
    return vals, p + nbytes


def _patched_base_run(buf, pos: int):
    """A PATCHED_BASE run at `pos` -> (its values, the next run's
    position): the base, the bit-packed low bits, and the patch list that
    restores the high bits of the few values that had any."""
    n = len(buf)
    if pos + 4 > n:
        raise DeviceDecodeUnsupported("truncated PATCHED header")
    b0 = buf[pos]
    width = _decode_width((b0 >> 1) & 0x1F)
    cnt = ((b0 & 1) << 8 | buf[pos + 1]) + 1
    b2, b3 = buf[pos + 2], buf[pos + 3]
    bw = ((b2 >> 5) & 7) + 1
    pw = _decode_width(b2 & 0x1F)
    pgw = ((b3 >> 5) & 7) + 1
    pl = b3 & 0x1F
    p = pos + 4
    if p + bw > n:
        raise DeviceDecodeUnsupported("truncated PATCHED base")
    base = int.from_bytes(buf[p:p + bw], "big")
    sign_mask = 1 << (bw * 8 - 1)
    if base & sign_mask:
        base = -(base & (sign_mask - 1))
    p += bw
    nbytes = (cnt * width + 7) // 8
    vals = _unpack_be_host(buf[p:p + nbytes], cnt, width).astype(np.int64)
    p += nbytes
    # patch entries: (gap:pgw bits | patch:pw bits) bit-packed at
    # the closest fixed width >= pgw+pw (the readers' contract)
    ew = _closest_fixed_bits(pgw + pw)
    nbytes = (pl * ew + 7) // 8
    if p + nbytes > n:
        raise DeviceDecodeUnsupported("truncated patch list")
    entries = _unpack_be_host(buf[p:p + nbytes], pl, ew).view(np.uint64)
    p += nbytes
    idx = 0
    pmask = (1 << pw) - 1
    for e in entries:
        gap = int(e) >> pw
        patch = int(e) & pmask
        idx += gap  # gaps accumulate; a (gap=255, patch=0)
        if patch == 0:  # entry is a pure continuation marker
            continue
        if idx < cnt:
            vals[idx] |= patch << width
    return base + vals, p


def _rlev2_runs(buf: bytes, num_values: int, signed: bool) -> _RunTable:
    """Scan an RLEv2 stream into a run table without expanding values.
    Big-endian bit-packed DIRECT payloads are carried packed (device
    unpacks); PATCHED_BASE and variable-delta runs host-decode into the
    aux literal array (their byte walks are serial by construction). The
    walk over the run headers is native where `native/` is built
    (`srtpu_orc_rlev2_scan`: a column of 387,064 runs took the Python loop
    below 0.45 s a query), and leaves those few runs to the two functions
    above either way."""
    from ..native import runtime as native
    try:
        walked = native.orc_rlev2_scan(buf, num_values, signed)
    except ValueError as e:
        raise DeviceDecodeUnsupported(str(e)) from e
    if walked is not None:
        rt = _RunTable()
        (rt.kinds, rt.counts, rt.base, rt.step, rt.offs, rt.width,
         packed) = walked
        rt.packed = packed
        rt.total = int(rt.counts.sum())
        for i in np.flatnonzero(rt.kinds >= 4):
            at = int(rt.offs[i])
            vals, _ = _delta_literal_run(buf, at, signed) \
                if rt.kinds[i] == 4 else _patched_base_run(buf, at)
            rt.kinds[i], rt.offs[i] = 3, rt.aux_len
            rt.aux.append(vals.astype(np.int64))
            rt.aux_len += len(vals)
        return rt
    rt = _RunTable()
    pos, n = 0, len(buf)
    while rt.total < num_values and pos < n:
        b0 = buf[pos]
        enc = b0 >> 6
        if enc == 0:  # SHORT_REPEAT
            nbytes = ((b0 >> 3) & 7) + 1
            cnt = (b0 & 7) + 3
            if pos + 1 + nbytes > n:
                raise DeviceDecodeUnsupported("truncated SHORT_REPEAT")
            v = int.from_bytes(buf[pos + 1:pos + 1 + nbytes], "big")
            if signed:
                v = (v >> 1) ^ -(v & 1)
            rt.add(0, cnt, base=v)
            pos += 1 + nbytes
        elif enc == 1:  # DIRECT
            if pos + 2 > n:
                raise DeviceDecodeUnsupported("truncated DIRECT header")
            width = _decode_width((b0 >> 1) & 0x1F)
            cnt = ((b0 & 1) << 8 | buf[pos + 1]) + 1
            nbytes = (cnt * width + 7) // 8
            if pos + 2 + nbytes > n:
                raise DeviceDecodeUnsupported("truncated DIRECT run")
            rt.add(2, cnt, offs=len(rt.packed) * 8, width=width)
            rt.packed += buf[pos + 2:pos + 2 + nbytes]
            pos += 2 + nbytes
        elif enc == 3:  # DELTA
            if pos + 2 > n:
                raise DeviceDecodeUnsupported("truncated DELTA header")
            if (b0 >> 1) & 0x1F:  # bit-packed deltas
                vals, pos = _delta_literal_run(buf, pos, signed)
                rt.add_literal(vals)
                continue
            cnt = ((b0 & 1) << 8 | buf[pos + 1]) + 1
            p = pos + 2
            base, p = _svarint(buf, p) if signed else _pb_varint(buf, p)
            db, pos = _svarint(buf, p)
            rt.add(1, cnt, base=base, step=db)  # v_i = base + i*db
        else:  # PATCHED_BASE
            vals, pos = _patched_base_run(buf, pos)
            rt.add_literal(vals)
    if rt.total < num_values:
        raise DeviceDecodeUnsupported("short RLEv2 stream")
    return rt


def _expand_runs_host(rt: _RunTable, num_values: int,
                      signed: bool) -> np.ndarray:
    """Host mirror of the device RLEv2 expansion — used ONLY for tiny
    metadata streams (string lengths, dictionary lengths) whose values
    feed host cumsum offsets, mirroring parquet's native offset scan."""
    kinds, counts, base, step, offs, width, packed, aux = rt.arrays()
    parts: List[np.ndarray] = []
    for i in range(len(kinds)):
        c = int(counts[i])
        k = int(kinds[i])
        if k == 0:
            parts.append(np.full(c, base[i], np.int64))
        elif k == 1:
            parts.append(base[i] + step[i] * np.arange(c, dtype=np.int64))
        elif k == 3:
            parts.append(aux[offs[i]:offs[i] + c])
        else:
            w = int(width[i])
            bitoff = int(offs[i])
            assert bitoff % 8 == 0  # packed runs start byte-aligned
            raw = packed[bitoff // 8: bitoff // 8 + (c * w + 7) // 8]
            vals = _unpack_be_host(raw.tobytes(), c, w)
            if signed:
                u = vals.view(np.uint64)
                vals = ((u >> np.uint64(1)) ^
                        (np.uint64(0) - (u & np.uint64(1)))).view(np.int64)
            parts.append(vals)
    out = (np.concatenate(parts) if parts else np.zeros(0, np.int64))
    return out[:num_values]


# ----------------------------------------------------------------------------
# Device kernels (traced: they run inside a column's `io.orc.*` program)
# ----------------------------------------------------------------------------

def _u64(lo, hi):
    import jax.numpy as jnp
    return lo.astype(jnp.uint64) | (hi.astype(jnp.uint64) << jnp.uint64(32))


def _unzigzag(u):
    import jax
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(
        (u >> jnp.uint64(1)) ^ (jnp.uint64(0) - (u & jnp.uint64(1))),
        jnp.int64)


def _expand_rlev2(ends, table, words, cap: int, signed: bool, wide: bool):
    """RLEv2 run table (`_RunTable.device_arrays`) -> int64[cap] values.
    Per slot: its run by `_slot_runs`, the run's seven words by one stacked
    gather, then either `base + within * step` (SHORT_REPEAT, fixed DELTA) or
    the run's `width` bits at `offs + within * width` of the big-endian
    packed stream (DIRECT; PATCHED_BASE and literal DELTA runs, which the
    host decoded, packed again the same way), read out of two 32-bit words
    a slot, three where a width passes 32 (`wide`), by one more stacked
    gather. Slots past the table's total read a padding run: the caller
    masks them."""
    import jax
    import jax.numpy as jnp
    u32, u64 = jnp.uint32, jnp.uint64
    run = _slot_runs(ends, cap)
    start, blo, bhi, slo, shi, offs, wk = gather_rows(table, run)
    within = jnp.arange(cap, dtype=jnp.int32) - start.astype(jnp.int32)
    arith = _u64(blo, bhi) + within.astype(u64) * _u64(slo, shi)
    width = wk & u32(0xFF)
    bitpos = offs + within.astype(u32) * width
    q, r = bitpos >> u32(5), (bitpos & u32(31)).astype(u64)
    w = width.astype(u64)
    rows = [words, ahead(words, 1)] + ([ahead(words, 2)] if wide else [])
    g = gather_rows(rows, q)
    hi = _u64(g[1], g[0])
    if wide:
        top = (hi << r) | (g[2].astype(u64) >> (u64(32) - r))
        pv = top >> jnp.minimum(u64(64) - w, u64(63))
    else:   # r <= 31 and w <= 32: the bits lie inside the two words
        w = jnp.minimum(w, u64(32))
        pv = (hi >> (u64(64) - r - w)) & ((u64(1) << w) - u64(1))
    if signed:
        pv = (pv >> u64(1)) ^ (u64(0) - (pv & u64(1)))
    return jax.lax.bitcast_convert_type(
        jnp.where((wk >> u32(8)) != 0, pv, arith), jnp.int64)


def _select_bit(mask, k):
    """Position of the k-th (0-based) set bit of each uint32 `mask`: five
    halvings by popcount."""
    import jax
    import jax.numpy as jnp
    u32 = jnp.uint32
    pos = jnp.zeros(mask.shape, u32)
    k = k.astype(u32)
    for half in (16, 8, 4, 2, 1):
        low = (mask >> pos) & u32((1 << half) - 1)
        c = jax.lax.population_count(low)
        up = k >= c
        k = jnp.where(up, k - c, k)
        pos = jnp.where(up, pos + u32(half), pos)
    return pos


def _varint_zigzag(words, cap: int):
    """Signed-varint (zigzag base-128) value stream -> int64[cap], the ORC
    DECIMAL mantissa encoding, from the value ENDS: a byte under 128 ends a
    value. `words` is the stream as little-endian uint32 words, zero-padded
    to a bucket of whole 32-byte blocks (a padding byte is a value 0 past
    the live ones). Per block the 32 terminator bits and their count, the
    counts' prefix sum, `_slot_runs` for the block that ends value v, one
    stacked gather for that block's bits and first value, `_select_bit` for
    the byte; a value starts after the one before it. Its up to nine bytes
    (18 digits zigzag into 61 bits) come out of three words by one more
    stacked gather, and the 7-bit groups fold with shifts: no scatter-add,
    no flat `cumsum`, no scan over the bytes (PERF.md, faults 9 and 15).
    Values past 64 bits never reach here (`columns_supported`, and the
    host's check of the value lengths)."""
    import jax
    import jax.numpy as jnp
    u32, u64 = jnp.uint32, jnp.uint64
    w8 = words.reshape(-1, 8)
    t = ~w8 & u32(0x80808080)
    nib = ((t >> u32(7)) & u32(1)) | ((t >> u32(14)) & u32(2)) | \
        ((t >> u32(21)) & u32(4)) | ((t >> u32(28)) & u32(8))
    mask = jnp.sum(nib << (u32(4) * jnp.arange(8, dtype=u32))[None, :],
                   axis=1, dtype=u32)
    counts = jax.lax.population_count(mask).astype(jnp.int32)
    ends = _prefix_sum_i32(counts)
    blk = _slot_runs(ends, cap)
    m, first = gather_rows([mask, (ends - counts).astype(u32)], blk)
    v = jnp.arange(cap, dtype=jnp.int32)
    end = blk * 32 + _select_bit(m, v - first.astype(jnp.int32)).astype(
        jnp.int32)
    start = jnp.concatenate([jnp.zeros(1, jnp.int32), end[:-1] + 1])
    n = (end - start + 1).astype(u64)
    r8 = (start & 3).astype(u64) * u64(8)
    w0, w1, w2 = gather_rows([words, ahead(words, 1), ahead(words, 2)],
                             start >> 2)
    # shift amounts stay under 64 in every lane, the unused ones too
    x = (_u64(w0, w1) >> r8) | jnp.where(
        r8 > 0, w2.astype(u64) << ((u64(64) - r8) & u64(63)), u64(0))
    ninth = (w2.astype(u64) >> r8) & u64(0x7F)
    x = jnp.where(n >= 8, x, x & (
        (u64(1) << (jnp.minimum(n, u64(7)) * u64(8))) - u64(1)))
    u = jnp.where(n >= 9, ninth << u64(56), u64(0))
    for k in range(8):
        u = u | (((x >> u64(8 * k)) & u64(0x7F)) << u64(7 * k))
    return _unzigzag(u)


def _expand_byte_rle(ends, table, blob, cap: int):
    """Byte-RLE run table (`_byte_rle_device`) -> uint8[cap] bytes."""
    import jax.numpy as jnp
    run = _slot_runs(ends, cap)
    start, vk, offs = gather_rows(table, run)
    within = jnp.arange(cap, dtype=jnp.int32) - start.astype(jnp.int32)
    lit = blob[jnp.clip(offs.astype(jnp.int32) + within, 0,
                        blob.shape[0] - 1)]
    return jnp.where((vk >> jnp.uint32(8)) != 0, lit,
                     (vk & jnp.uint32(0xFF)).astype(jnp.uint8))


def _bits_msb_first(byts):
    """uint8[n] -> bool[8 n]: bit 7 of a byte first, per the ORC spec."""
    import jax.numpy as jnp
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    return (((byts[:, None] >> shifts[None, :]) & 1) == 1).reshape(-1)


# nanos trailing-zero expansion table: encoded low 3 bits z -> 10^(z+1)
# multiplier (z=0 means no zeros were removed)
_NANO_MULT = np.array([1, 100, 1000, 10_000, 100_000, 1_000_000,
                       10_000_000, 100_000_000], np.int64)


def _orc_timestamp_micros(secs, nanos_enc):
    """ORC timestamp streams -> Spark micros since the unix epoch.
    secs counts from 2015-01-01; nanos carry their trailing-zero count in
    the low 3 bits (TimestampTreeReader.parseNanos). The sum is plain
    SIGNED addition: the C++ writer emits truncated seconds with a
    negative nanos remainder for pre-1970 values, the Java writer floored
    seconds with positive nanos — both reconstruct exactly this way
    (verified against pyarrow's reader on boundary values)."""
    import jax.numpy as jnp
    nanos = (nanos_enc >> 3) * jnp.asarray(_NANO_MULT)[nanos_enc & 7]
    return (secs + _ORC_TS_BASE) * 1_000_000 + nanos // 1000


# A dictionary of a handful of entries (TPC-H's flags: 3 and 2) is gathered
# byte by byte out of its blob in 1.2 ms for 2,097,152 rows of 8 bytes, the
# compiler's own way with a table that small; by rows it takes 9.5 (my chip
# run, PERF.md, PR 37). Between this and the thousands of entries at which
# the byte gather runs at 0.1 GB/s (fault 16) nothing is measured.
_TINY_DICTIONARY = 64


def _dictionary_rows(blob, dstarts, dlens, idx, valid, width: int):
    """Dictionary-encoded strings -> (uint8[cap, width], int32[cap]): the
    dictionary's own byte matrix is built once (`_string_matrix_tail` over
    its few rows), then every row takes its entry's bytes and length by ONE
    stacked gather of big-endian words (a row of at most 28 bytes and its
    length are the eight rows a gather moves at one price); a wider entry
    is gathered whole and its length beside it. A tiny dictionary's spans
    are gathered straight out of the blob (`_TINY_DICTIONARY`)."""
    import jax.numpy as jnp
    u32 = jnp.uint32
    dcap = dstarts.shape[0]
    if dcap <= _TINY_DICTIONARY:
        safe = jnp.clip(idx, 0, dcap - 1)
        return _string_matrix_tail(blob, dstarts[safe], dlens[safe], valid,
                                   width)
    dmat, dln = _string_matrix_tail(blob, dstarts, dlens,
                                    jnp.ones(dcap, bool), width)
    nw = width // 4
    if nw + 1 <= PACK_ROWS:
        b = dmat.reshape(dcap, nw, 4).astype(u32)
        ws = (b[:, :, 0] << u32(24)) | (b[:, :, 1] << u32(16)) | \
            (b[:, :, 2] << u32(8)) | b[:, :, 3]
        g = gather_rows([ws[:, i] for i in range(nw)] + [dln.astype(u32)],
                        idx)
        shifts = jnp.array([24, 16, 8, 0], dtype=u32)
        mat = ((jnp.stack(list(g[:nw]), axis=1)[:, :, None] >> shifts)
               & u32(0xFF)).astype(jnp.uint8).reshape(-1, width)
        ln = g[nw].astype(jnp.int32)
    else:
        safe = jnp.clip(idx, 0, dcap - 1)
        mat, ln = dmat[safe], dln[safe]
    return jnp.where(valid[:, None], mat, 0).astype(jnp.uint8), \
        jnp.where(valid, ln, 0)


def _traced_column(sig, cap: int, nrows, it):
    """Decode ONE column (traced) from its ship-order array iterator ->
    (data, validity, lengths or None). `sig` is the column's static
    signature `_ColPlan.sig`: (kind, has PRESENT, ...). Values decode dense
    (the non-null ones in order) and reach their rows by the null rank, a
    blocked prefix sum of the PRESENT bits; a column without a PRESENT
    stream, as every column of a NOT NULL table, skips that gather."""
    import jax.numpy as jnp
    kind, has_present = sig[0], sig[1]
    live = jnp.arange(cap, dtype=jnp.int32) < nrows
    if has_present:
        ends, table, blob = next(it), next(it), next(it)
        defined = _bits_msb_first(
            _expand_byte_rle(ends, table, blob, cap // 8)) & live
        rank = jnp.clip(_prefix_sum_i32(defined.astype(jnp.int32)) - 1,
                        0, cap - 1)
    else:
        defined, rank = live, None

    def to_rows(dense):
        return dense if rank is None else dense[rank]

    def fixed(dense, dtype):
        data = jnp.where(defined, to_rows(dense), jnp.zeros((), dense.dtype))
        return data.astype(dtype), defined, None

    def rlev2(signed: bool, wide: bool):
        return _expand_rlev2(next(it), next(it), next(it), cap, signed, wide)

    if kind == "int":
        wide, dtype = sig[2:]
        return fixed(rlev2(True, wide), np.dtype(dtype))
    if kind == "decimal":
        return fixed(_varint_zigzag(next(it), cap), np.int64)
    if kind == "timestamp":
        wide_secs, wide_nanos = sig[2:]
        secs = rlev2(True, wide_secs)
        return fixed(_orc_timestamp_micros(secs, rlev2(False, wide_nanos)),
                     np.int64)
    if kind == "float":
        return fixed(next(it), np.dtype(sig[2]))
    if kind == "bool":
        return fixed(_bits_msb_first(_expand_byte_rle(
            next(it), next(it), next(it), cap // 8)), np.bool_)
    if kind == "byte":
        return fixed(_expand_byte_rle(next(it), next(it), next(it), cap),
                     np.int8)
    if kind == "string_dict":
        wide, width, dcount = sig[2:]
        idx = jnp.clip(rlev2(False, wide), 0, max(dcount - 1, 0))
        dstarts, dlens, blob = next(it), next(it), next(it)
        mat, ln = _dictionary_rows(blob, dstarts, dlens,
                                   to_rows(idx).astype(jnp.int32), defined,
                                   width)
        return mat, defined, ln
    if kind == "string_direct":
        starts, lens, blob = next(it), next(it), next(it)
        mat, ln = _string_matrix_tail(blob, to_rows(starts), to_rows(lens),
                                      defined, sig[2])
        return mat, defined, ln
    raise AssertionError(sig)


@functools.lru_cache(maxsize=256)
def _column_program(sig, cap: int):
    """The service program of one column kind: `io.orc.<kind>`, keyed by
    the column's static signature and the batch capacity; the tables'
    bucketed shapes ride the service's own digest of the arguments. Takes
    the (traced) row count and the column's arrays in ship order."""

    def fn(nrows, *arrays):
        return _traced_column(sig, cap, nrows, iter(arrays))

    from ..compile import sjit
    return sjit(fn, op=f"io.orc.{sig[0]}", key=repr((sig, cap)))


# ----------------------------------------------------------------------------
# Stripe decode: the host's half (read, deframe, run walk, buckets)
# ----------------------------------------------------------------------------

@dataclass
class _ColStreams:
    encoding: int = _E_DIRECT
    dict_size: int = 0
    streams: Dict[int, bytes] = field(default_factory=dict)
    # a DECIMAL's DATA stream as the file frames it, (bytes, codec, block
    # size): `_decimal_stream` deframes it straight into its bucket
    framed: Optional[Tuple[bytes, int, int]] = None


@dataclass
class _ColPlan:
    """One column's host-phase product: its program's static signature and
    the numpy arrays to ship, in the order `_traced_column` reads them."""
    sig: tuple
    arrays: List[np.ndarray]


class _ScanStats:
    """What one stripe's host phase walked: RLEv2 and byte-RLE runs, and the
    bytes of varint streams (the scan's operator metrics)."""
    __slots__ = ("runs", "varint_bytes")

    def __init__(self):
        self.runs = self.varint_bytes = 0


def _stripe_footer(info: OrcFileInfo, f, st: _Stripe):
    """(streams [(kind, column, length)], encodings [(kind, dictionary
    size)], writer timezone) of one stripe, from its footer alone."""
    sf_raw = _deframe(_read_at(f, st.offset + st.index_len + st.data_len,
                               st.footer_len),
                      info.compression, info.block_size)
    streams: List[Tuple[int, int, int]] = []
    encodings: List[Tuple[int, int]] = []
    writer_tz = ""
    for fno, _, v in _pb_fields(sf_raw):
        if fno == 1:
            s = {1: 0, 2: 0, 3: 0}
            for f2, _, v2 in _pb_fields(v):
                s[f2] = v2
            streams.append((s[1], s[2], s[3]))
        elif fno == 2:
            e = {1: 0, 2: 0}
            for f2, _, v2 in _pb_fields(v):
                e[f2] = v2
            encodings.append((e[1], e[2]))
        elif fno == 3:
            writer_tz = v.decode("utf-8", "replace")
    return streams, encodings, writer_tz


def _stripe_writer_tz(info: OrcFileInfo, f, st: _Stripe) -> str:
    """Read ONLY a stripe's footer and return its writerTimezone."""
    return _stripe_footer(info, f, st)[2]


_READ_LOCK = threading.Lock()


def _read_at(f, pos: int, length: int) -> bytes:
    """`length` bytes of `f` from `pos`, whichever thread asks: `pread`
    moves no file position; a file object without a descriptor is read
    under a lock."""
    try:
        return os.pread(f.fileno(), length, pos)
    except (AttributeError, OSError):    # io.UnsupportedOperation is one
        with _READ_LOCK:
            f.seek(pos)
            return f.read(length)


def _column_streams(info: OrcFileInfo, f, st: _Stripe, directory,
                    cid: int) -> _ColStreams:
    """Read + deframe ONE column's data-area streams (a DECIMAL's DATA
    stream is only read: `_ColStreams.framed`)."""
    streams, encodings, _ = directory
    cs = _ColStreams()
    if cid < len(encodings):
        cs.encoding, cs.dict_size = encodings[cid]
    decimal = info.col_kinds.get(cid) == _K_DECIMAL
    pos = st.offset
    for kind, col, length in streams:
        if col == cid and kind in (_S_PRESENT, _S_DATA, _S_LENGTH,
                                   _S_DICT_DATA, _S_SECONDARY) \
                and pos >= st.offset + st.index_len:
            raw = _read_at(f, pos, length)
            if decimal and kind == _S_DATA:
                cs.framed = (raw, info.compression, info.block_size)
            else:
                cs.streams[kind] = _deframe(raw, info.compression,
                                            info.block_size)
        pos += length
    return cs


def _deframe_padded(buf: bytes, comp: int, block_size: int):
    """(a stream deframed into ONE zero-tailed uint8 array of a power-of-two
    bucket of at least 128 bytes, its live length): natively for the
    self-describing codecs, so that an 8 MB stream is written once where it
    ships from; `_deframe` and a padding copy otherwise."""
    from ..native import runtime as native
    kind = {_COMP_NONE: 0, _COMP_SNAPPY: 2}.get(comp, -1)
    try:
        done = native.orc_deframe(buf, kind, lambda n: _bucket(n, 128))
    except ValueError as e:
        raise DeviceDecodeUnsupported(str(e)) from e
    if done is not None:
        return done
    raw = np.frombuffer(_deframe(buf, comp, block_size), np.uint8)
    return _padded(raw, _bucket(raw.size, 128)), raw.size


def _bucket(n: int, least: int = 1) -> int:
    return _pow2(max(n, least))


def _padded(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    if a.shape[-1] >= n:
        return a
    pad = [(0, 0)] * (a.ndim - 1) + [(0, n - a.shape[-1])]
    return np.pad(a, pad, constant_values=fill)


def _be_words(parts, total: int, least: int = 0) -> np.ndarray:
    """A big-endian bit stream, given as uint8 arrays of `total` bytes
    together, as uint32 words zero-padded to a bucket of at least `least`
    words: written once into the buffer that ships and swapped there."""
    buf = np.zeros(_bucket(total, max(4 * least, 16)), np.uint8)
    at = 0
    for part in parts:
        buf[at:at + part.size] = part
        at += part.size
    words = buf.view(np.uint32)
    if sys.byteorder == "little":
        words.byteswap(inplace=True)
    return words


def _byte_rle_device(runs, total: int) -> List[np.ndarray]:
    """`_byte_rle_runs`' table in the shapes `_expand_byte_rle` reads:
    (ends int32[R], uint32[3, R] of start, value | literal << 8, offset;
    the literal bytes), R and the bytes in power-of-two buckets."""
    kinds, counts, values, offs, blob = runs
    ends = np.cumsum(counts)
    rb = _bucket(len(kinds))
    table = np.stack([ends - counts,
                      values.astype(np.int64) | (kinds.astype(np.int64) << 8),
                      offs]).astype(np.uint32)
    return [_padded(ends.astype(np.int32), rb, fill=total),
            _padded(table, rb), _padded(blob, _bucket(blob.size))]


def _present_arrays(cs: _ColStreams, nrows: int, stats: _ScanStats):
    """(arrays of the PRESENT stream's table or [], non-null count)."""
    present = cs.streams.get(_S_PRESENT)
    if present is None:
        return [], nrows
    nbytes = (nrows + 7) // 8
    runs = _byte_rle_runs(present, nbytes)
    stats.runs += len(runs[0])
    return _byte_rle_device(runs, nbytes), _present_ndef(runs, nrows)


def _require_data(cs: _ColStreams) -> bytes:
    raw = cs.streams.get(_S_DATA)
    if raw is None:
        raise DeviceDecodeUnsupported("missing DATA stream")
    return raw


def _rlev2_arrays(buf: bytes, count: int, signed: bool, cap: int,
                  stats: _ScanStats):
    """(arrays of an RLEv2 stream's run table, whether a width passes 32)."""
    rt = _rlev2_runs(buf, count, signed) if count else _RunTable()
    stats.runs += len(rt.kinds)
    ends, table, words, wide = rt.device_arrays(signed, cap)
    return [ends, table, words], wide


def _decimal_stream(cs: _ColStreams, dt, ndef: int,
                    stats: _ScanStats) -> np.ndarray:
    """A DECIMAL column's mantissa stream (precision <= 18) as the
    little-endian words `_varint_zigzag` reads, checked on the way: the
    SECONDARY per-value scale stream must equal the declared scale (writers
    emit constant runs, read from the run table without expanding it) or
    the stripe host-falls-back rather than rescale; the stream must hold
    `ndef` whole values of at most nine bytes."""
    if cs.framed is None and _S_DATA not in cs.streams:
        raise DeviceDecodeUnsupported("missing DATA stream")
    if cs.encoding != _E_DIRECT_V2:
        # DIRECT (Hive 0.11-era) pairs the mantissas with an RLEv1 scale
        # stream this parser would misread — like the integer path, only
        # the v2 encoding decodes here
        raise DeviceDecodeUnsupported(f"decimal encoding {cs.encoding}")
    scale_raw = cs.streams.get(_S_SECONDARY)
    if scale_raw is None:
        raise DeviceDecodeUnsupported("missing decimal scale stream")
    if ndef:
        rt = _rlev2_runs(scale_raw, ndef, True)
        stats.runs += len(rt.kinds)
        kinds = np.asarray(rt.kinds)
        if ((kinds <= 1) & (np.asarray(rt.step) == 0)).all():
            same = (np.asarray(rt.base) == dt.scale).all()
        else:
            same = (_expand_runs_host(rt, ndef, True) == dt.scale).all()
        if not same:
            raise DeviceDecodeUnsupported("per-value decimal rescale")
    if cs.framed is not None:
        padded, n = _deframe_padded(*cs.framed)
    else:
        raw = np.frombuffer(cs.streams[_S_DATA], np.uint8)
        padded, n = _padded(raw, _bucket(raw.size, 128)), raw.size
    stats.varint_bytes += n
    from ..native import runtime as native
    scan = native.varint_scan(padded[:n], ndef)
    if scan is None:
        last = np.flatnonzero(padded[:n] < 128)
        scan = (last.size,
                int(np.diff(last[:ndef], prepend=-1).max()) if ndef else 0,
                bool(n and padded[n - 1] >= 128))
    ends, longest, cut = scan
    if cut:
        raise DeviceDecodeUnsupported("decimal stream ends inside a value")
    if ends < ndef:
        raise DeviceDecodeUnsupported("short decimal mantissa stream")
    # a <=18-digit mantissa zigzags into <=63 bits -> <=9 varint bytes
    if longest > 9:
        raise DeviceDecodeUnsupported("mantissa varint wider than 64")
    return padded.view("<u4")


def _offsets(lens: np.ndarray) -> np.ndarray:
    starts = np.zeros(len(lens), np.int64)
    if len(lens):
        np.cumsum(lens[:-1], out=starts[1:])
    return starts


def _blob(raw: bytes) -> np.ndarray:
    buf = np.frombuffer(raw, np.uint8) if raw else np.zeros(1, np.uint8)
    return _padded(buf, _bucket(buf.size, 16))


def _string_plan(cs: _ColStreams, ndef: int, cap: int, stats: _ScanStats):
    """STRING column. DIRECT_V2: the LENGTH stream expands on the host
    (offsets are a serial sum), the device gathers spans from the DATA blob.
    DICTIONARY_V2: the index runs expand on the device, the dictionary's
    few offsets on the host, and every row takes its entry's bytes from the
    dictionary's own matrix (`_dictionary_rows`). Returns (the signature
    past its first two fields, the arrays)."""
    from ..columnar.padding import width_bucket
    from ..config import get_default_conf
    lens_raw = cs.streams.get(_S_LENGTH)
    if cs.encoding == _E_DIRECT_V2:
        if lens_raw is None:
            raise DeviceDecodeUnsupported("missing LENGTH stream")
        rt = _rlev2_runs(lens_raw, ndef, False)
        stats.runs += len(rt.kinds)
        lens = _expand_runs_host(rt, ndef, False)
        max_len = int(lens.max()) if ndef else 0
        width = width_bucket(max(max_len, 1))
        arrays = [_padded(_offsets(lens), cap),
                  _padded(lens.astype(np.int32), cap),
                  _blob(cs.streams.get(_S_DATA, b""))]
        sig = ("string_direct", width)
    elif cs.encoding == _E_DICT_V2:
        data = cs.streams.get(_S_DATA)
        if lens_raw is None or data is None:
            raise DeviceDecodeUnsupported("missing dictionary streams")
        dcount = cs.dict_size
        dlens = _expand_runs_host(_rlev2_runs(lens_raw, dcount, False),
                                  dcount, False)
        max_len = int(dlens.max()) if dcount else 0
        width = width_bucket(max(max_len, 1))
        idx_arrays, wide = _rlev2_arrays(data, ndef, False, cap, stats)
        db = _bucket(dcount, 8)
        arrays = idx_arrays + [_padded(_offsets(dlens), db),
                               _padded(dlens.astype(np.int32), db),
                               _blob(cs.streams.get(_S_DICT_DATA, b""))]
        # the live dictionary size rides the signature only as its bucket
        sig = ("string_dict", wide, width, db)
    else:
        raise DeviceDecodeUnsupported(f"string encoding {cs.encoding}")
    if width > get_default_conf().string_max_width:
        raise DeviceDecodeUnsupported(
            f"string width {max_len} exceeds device layout limit")
    return sig, arrays


def _column_plan(cs: _ColStreams, kind: int, dt, nrows: int, cap: int,
                 writer_tz: str, stats: _ScanStats) -> _ColPlan:
    """The host phase of one column: walk its streams into run tables,
    pad every table, byte stream and literal array to a power-of-two bucket
    (a file with 387,065 runs runs the program of one with 387,064), and
    name the program by the column's kind and static flags."""
    present, ndef = _present_arrays(cs, nrows, stats)
    has_present = bool(present)

    def plan(sig_tail: tuple, arrays) -> _ColPlan:
        return _ColPlan((sig_tail[0], has_present) + sig_tail[1:],
                        present + list(arrays))

    if kind in (_K_TIMESTAMP, _K_TIMESTAMP_INSTANT):
        if kind == _K_TIMESTAMP and writer_tz not in _UTC_TZ:
            # local-time semantics in a non-UTC zone need tz-rule
            # arithmetic; the host reader owns that
            raise DeviceDecodeUnsupported(f"writer timezone {writer_tz}")
        if cs.encoding != _E_DIRECT_V2:
            raise DeviceDecodeUnsupported(
                f"timestamp encoding {cs.encoding}")
        secondary = cs.streams.get(_S_SECONDARY)
        if secondary is None:
            raise DeviceDecodeUnsupported("missing SECONDARY stream")
        secs, wide_s = _rlev2_arrays(_require_data(cs), ndef, True, cap, stats)
        nanos, wide_n = _rlev2_arrays(secondary, ndef, False, cap, stats)
        return plan(("timestamp", wide_s, wide_n), secs + nanos)
    if kind == _K_DECIMAL:
        return plan(("decimal",), [_decimal_stream(cs, dt, ndef, stats)])
    if kind in (_K_SHORT, _K_INT, _K_LONG, _K_DATE):
        if cs.encoding != _E_DIRECT_V2:
            raise DeviceDecodeUnsupported(
                f"integer encoding {cs.encoding}")
        arrays, wide = _rlev2_arrays(_require_data(cs), ndef, True, cap, stats)
        return plan(("int", wide, str(np.dtype(dt.np_dtype))), arrays)
    if kind in (_K_FLOAT, _K_DOUBLE):
        npdt = np.float32 if kind == _K_FLOAT else np.float64
        try:
            host = np.frombuffer(_require_data(cs), npdt, count=ndef)
        except ValueError as e:
            raise DeviceDecodeUnsupported(f"short float stream: {e}") from e
        return plan(("float", str(np.dtype(dt.np_dtype))),
                    [_padded(host, cap)])
    if kind in (_K_BOOLEAN, _K_BYTE):
        nbytes = (ndef + 7) // 8 if kind == _K_BOOLEAN else ndef
        runs = _byte_rle_runs(_require_data(cs), nbytes) if ndef else (
            np.zeros(1, np.uint8), np.zeros(1, np.int64),
            np.zeros(1, np.uint8), np.zeros(1, np.int64),
            np.zeros(1, np.uint8))
        stats.runs += len(runs[0])
        return plan(("bool" if kind == _K_BOOLEAN else "byte",),
                    _byte_rle_device(runs, nbytes))
    if kind in (_K_STRING, _K_VARCHAR, _K_CHAR):
        return plan(*_string_plan(cs, ndef, cap, stats))
    raise DeviceDecodeUnsupported(f"ORC kind {kind}")


_WALKERS: Optional[cf.ThreadPoolExecutor] = None
_WALKERS_LOCK = threading.Lock()


def walker_pool() -> cf.ThreadPoolExecutor:
    """The process's few threads that walk a stripe's columns side by side
    (`decode_stripe`). They live as long as the process: a thread keeps its
    allocator arena, so a query's walk finds the buffers the last one's
    left, where a fresh thread for every query faults its pages in anew
    (PERF.md, fault 22)."""
    global _WALKERS
    with _WALKERS_LOCK:
        if _WALKERS is None:
            _WALKERS = cf.ThreadPoolExecutor(
                max_workers=max(1, min(4, len(os.sched_getaffinity(0)) - 1)),
                thread_name_prefix="srtpu-orc-walk")
        return _WALKERS


def decode_stripe(info: OrcFileInfo, f, si: int, schema, host_cols=None,
                  pushed=None, stats: Optional[_ScanStats] = None,
                  walkers: Optional[cf.ThreadPoolExecutor] = None):
    """Decode ONE stripe on the TPU -> (device ColumnarBatch, row count).
    Column by column: the host reads, deframes and walks the column's
    streams (`scan.walk`; on `walkers`, all columns at once, where the
    caller hands a pool in), pads its tables to their buckets
    (`scan.pack`), ships them in one transfer (`scan.h2d`) and queues the
    column's `io.orc.*` program in the schema's order, so the chip decodes
    one column while the host walks the others.
    `pushed` is the scan-pushdown seam (plan/scan_pushdown.py): applied
    to the decoded stripe batch with the engine's exact kernels (mask +
    compact in one program), returning (pushed batch, output rows) —
    mask-based late materialisation at the stripe unit, never a silently
    different result.
    `host_cols` names columns the support check routed to the host: they
    decode via ONE pyarrow read_stripe and merge into the batch at
    assembly — an unsupported column costs itself, not the stripe
    (reference decodes the full type matrix per column,
    `GpuOrcScan.scala:826`). Encoding surprises the footer can't reveal
    (RLEv1 integer runs, missing streams, non-UTC writer timezones) raise
    DeviceDecodeUnsupported so the caller falls just THIS stripe back to
    the host reader — per-stripe granularity, the parquet path's
    per-row-group discipline. `stats` gathers what the host walked."""
    import jax.numpy as jnp
    from ..columnar.batch import ColumnarBatch
    from ..columnar.column import Column

    st = info.stripes[si]
    nrows = st.num_rows
    cap = row_bucket(nrows, op="scan.orc")
    host_cols = set(host_cols or ())
    stats = stats if stats is not None else _ScanStats()
    host_decoded = _host_decode_stripe_cols(info, si, schema, host_cols,
                                            cap, nrows)
    with spans.span("scan.walk", kind=spans.KIND_IO):
        directory = _stripe_footer(info, f, st)
    tm = TaskMetrics.get()

    def walk(name, dt):
        cid, col_stats = info.col_ids[name], _ScanStats()
        # a walker thread outlives the query: its walk counts in the query's
        # TaskMetrics, and nothing after it does
        with TaskMetrics.adopted(tm), \
                spans.span("scan.walk", kind=spans.KIND_IO):
            cs = _column_streams(info, f, st, directory, cid)
            return _column_plan(cs, info.col_kinds[cid], dt, nrows, cap,
                                directory[2], col_stats), col_stats

    todo = [(name, dt) for name, dt in zip(schema.names, schema.types)
            if name not in host_decoded]
    # every column's host phase starts now, on the walkers: the chip is
    # handed the first column as soon as it is walked and decodes one while
    # the others are walked beside it, so past the first column the scan
    # goes at the chip's pace and not the host's
    walks = [walkers.submit(walk, *c) for c in todo] if walkers else []
    out_cols = dict(host_decoded)
    try:
        for i, (name, dt) in enumerate(todo):
            plan, col_stats = walks[i].result() if walks else walk(name, dt)
            stats.runs += col_stats.runs
            stats.varint_bytes += col_stats.varint_bytes
            with spans.span("scan.pack", kind=spans.KIND_IO):
                program = _column_program(plan.sig, cap)
            data, validity, lengths = program(np.int32(nrows),
                                              *_ship(plan.arrays))
            # one transfer, the row count and one program a column
            _note_dispatches(3)
            out_cols[name] = Column(dt, data, validity, lengths)
    finally:
        # a column that cannot decode here ends the stripe: no walk may
        # outlive the caller's open file
        for w in walks:
            w.cancel()
        cf.wait(walks)
    batch = ColumnarBatch(schema, tuple(out_cols[n] for n in schema.names),
                          jnp.asarray(nrows, jnp.int32))
    if pushed is not None:
        return pushed(batch, nrows)
    return batch, nrows


def _host_decode_stripe_cols(info: OrcFileInfo, si: int, schema,
                             host_cols, cap: int, nrows: int):
    """Host (pyarrow) decode of the fallback columns of one stripe ->
    {name: device Column} at the shared capacity bucket. Timestamps
    normalize to us/UTC exactly as the whole-file host path does."""
    names = [n for n in schema.names if n in host_cols]
    if not names:
        return {}
    import pyarrow as pa
    from pyarrow import orc as pa_orc
    # one pyarrow ORCFile per FILE (footer parse is not free), cached on
    # the info object the whole scan already threads through
    pf = getattr(info, "_pa_file", None)
    if pf is None:
        pf = pa_orc.ORCFile(info.path)
        info._pa_file = pf
    try:
        rb = pf.read_stripe(si, columns=names)
    except (OSError, pa.ArrowInvalid) as e:
        raise DeviceDecodeUnsupported(f"host column decode: {e}") from e
    t = pa.Table.from_batches([rb])
    if t.num_rows != nrows:
        raise DeviceDecodeUnsupported("host column row-count mismatch")
    return _host_cols_to_device(t, schema, names, cap)


def device_decode_file(info: OrcFileInfo, path: str, schema) -> Iterator:
    """Yield (device ColumnarBatch, row count) per stripe, streaming."""
    with open(path, "rb") as f:
        for si in range(len(info.stripes)):
            yield decode_stripe(info, f, si, schema)
