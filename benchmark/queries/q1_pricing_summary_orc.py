"""TPC-H query 1, the pricing summary report, as published (specification v3,
clause 2.4.1, DELTA = 90), over `lineitem` stored as ORC: the query of
`q1_pricing_summary.py` (loaded from beside this file for its text's
arithmetic, `compare` and `LIMITS`) with the scan an ORC one. The reference
reads the file with pyarrow's own ORC reader and computes in Python integers;
the bytes the scan has to move are counted from the file's stripe footers by
the small protobuf walk below, which shares nothing with the engine's."""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_queries_{name}", os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


Q1 = _beside("q1_pricing_summary")
TABLES, CUTOFF = Q1.TABLES, Q1.CUTOFF
# the parquet cell's numbers, and the guard on the configuration's fifth
# guarantee: stripes and columns that the host's reader decoded
LIMITS = {**Q1.LIMITS, "host_decoded": 0}
_READ = list(Q1._READ)
# decoded bytes a row: four 64-bit unscaled decimals, a 32-bit date, and for
# each flag the least a string column is (one byte, a 32-bit length)
_ROW_BYTES = 4 * 8 + 4 + 2 * (1 + 4)
_COUNTER = "scan_host_decoded"


def scans(session, paths: dict) -> dict:
    """The query's one scan, with the columns it reads. An engine whose ORC
    scan does not count what the host's reader decoded for it cannot be
    held to the configuration's fifth guarantee, and is refused here, at
    once and plainly, before any program of it compiles (ISSUE 37, step 8c:
    the commit before the counter compiles its ORC kernels for a quarter of
    an hour, inside a tenth of the harness's time limit)."""
    from spark_rapids_tpu.utils.metrics import TaskMetrics
    if not hasattr(TaskMetrics(), _COUNTER):
        raise RuntimeError(
            f"this engine has no TaskMetrics.{_COUNTER}: its ORC scan may "
            "hand a stripe to the host's reader unseen, so the cell's "
            "guarantee cannot be checked on it")
    return {"lineitem": session.read_orc(paths["lineitem"],
                                         columns=list(_READ))}


def build(session, paths: dict):
    """Query 1 as published, written as `q1_pricing_summary.build` writes
    it (Catalyst's ReadSchema, the date folded, Spark's decimal types), over
    the ORC scan."""
    import decimal

    from spark_rapids_tpu.expr import Average, Count, Sum, col, lit
    one = lit(decimal.Decimal(1))
    disc_price = col("l_extendedprice") * (one - col("l_discount"))
    return (scans(session, paths)["lineitem"]
            .filter(col("l_shipdate") <= lit(CUTOFF))
            .select(col("l_returnflag"), col("l_linestatus"),
                    col("l_quantity"), col("l_extendedprice"),
                    col("l_discount"),
                    disc_price.alias("disc_price"),
                    (disc_price * (one + col("l_tax"))).alias("charge"))
            .group_by("l_returnflag", "l_linestatus")
            .agg(sum_qty=Sum(col("l_quantity")),
                 sum_base_price=Sum(col("l_extendedprice")),
                 sum_disc_price=Sum(col("disc_price")),
                 sum_charge=Sum(col("charge")),
                 avg_qty=Average(col("l_quantity")),
                 avg_price=Average(col("l_extendedprice")),
                 avg_disc=Average(col("l_discount")),
                 count_order=Count())
            # Spark's null order when ascending: nulls first
            .sort((col("l_returnflag"), True, True),
                  (col("l_linestatus"), True, True)))


def _rows(paths: dict) -> dict:
    """The rows the filter keeps, as numpy: cents, and the group of each."""
    import pyarrow.compute as pc
    from pyarrow import orc
    t = orc.read_table(paths["lineitem"], columns=_READ)
    t = t.filter(pc.less_equal(t["l_shipdate"], CUTOFF))
    flags = np.char.add(t["l_returnflag"].to_numpy(zero_copy_only=False)
                        .astype("U1"),
                        t["l_linestatus"].to_numpy(zero_copy_only=False)
                        .astype("U1"))
    groups, gid = np.unique(flags, return_inverse=True)
    return {"groups": [(g[0], g[1]) for g in groups], "gid": gid,
            **{c: Q1._unscaled(t[c]) for c in _READ[:4]}}


def reference(paths: dict):
    """`q1_pricing_summary.reference`'s arithmetic (Python ints, averages
    rounded half up) on what pyarrow's ORC reader reads."""
    r = _rows(paths)
    if int(np.abs(r["l_extendedprice"]).max(initial=0)) * 110 * 110 >= 2 ** 63:
        r["l_extendedprice"] = r["l_extendedprice"].astype(object)
    return Q1._summary(r, lambda v: sum(v.tolist()),
                       lambda s, n: Q1._half_up(s * 10 ** 4, n))


def control(paths: dict, dtype: str):
    """`q1_pricing_summary.control` on the ORC file: products and sums
    carried in `dtype`, rounded back at the end."""
    r = _rows(paths)
    f = np.dtype(dtype).type
    for c in _READ[:4]:
        r[c] = r[c].astype(f)

    def back(x) -> int:
        return int(np.rint(np.float64(x)))
    return Q1._summary(r, lambda v: v.sum(dtype=f),
                       lambda s, n: back(s * f(10 ** 4) / f(n)), back)


def host_decoded() -> int:
    """Stripes and columns that pyarrow decoded for the engine in the device
    queries this process ran last (the ring of recent queries holds each
    query's `TaskMetrics`): the configuration guarantees none."""
    from spark_rapids_tpu.plugin import TpuSession
    return sum(tm.get(_COUNTER, 0)
               for _, _, tm in TpuSession.recent_queries())


def compare(got, want) -> dict:
    """`q1_pricing_summary.compare`, and `host_decoded`."""
    return {**Q1.compare(got, want), "host_decoded": host_decoded()}


# -- the file's streams, by a protobuf walk of the postscript, the footer and
# -- the stripe footers (ORC specification v1: "File Tail", "Stripe Footer")

def _varint(buf, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """(field number, value) of a message: varints as ints, length-delimited
    fields as bytes; ORC's metadata uses no other wire type but fixed64 in
    statistics, which are skipped."""
    pos = 0
    while pos < len(buf):
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = bytes(buf[pos:pos + n]), pos + n
        elif wire in (1, 5):
            value, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _blocks(buf, compressed: bool):
    """(stored bytes, whether they are stored as they are) of each
    compression block of a stream."""
    if not compressed:
        yield buf, True
        return
    pos = 0
    while pos < len(buf):
        head = int.from_bytes(buf[pos:pos + 3], "little")
        chunk = buf[pos + 3:pos + 3 + (head >> 1)]
        pos += 3 + len(chunk)
        yield chunk, bool(head & 1)


def _size(chunk, original: bool) -> int:
    """A block's uncompressed length: a snappy block starts with it, as a
    varint."""
    return len(chunk) if original else _varint(chunk, 0)[0]


def _inflate(buf, compression: int) -> bytes:
    import pyarrow as pa
    if compression not in (0, 2):
        raise ValueError(f"compression kind {compression}: the cell's files "
                         "are snappy")
    return b"".join(
        chunk if original else pa.decompress(
            chunk, decompressed_size=_size(chunk, False),
            codec="snappy").to_pybytes()
        for chunk, original in _blocks(buf, compression == 2))


def column_streams(path: str) -> dict:
    """{column name: {"stored": bytes in the file, "streams": uncompressed
    bytes}} over every stripe's DATA-area streams (PRESENT, DATA, LENGTH,
    DICTIONARY_DATA, SECONDARY; the row index and bloom filters, which a
    whole-stripe scan does not read, are left out)."""
    with open(path, "rb") as f:
        raw = f.read()
    ps_len = raw[-1]
    ps = dict(_fields(raw[-1 - ps_len:-1]))
    footer_len, compression = ps[1], ps.get(2, 0)
    footer = _inflate(raw[-1 - ps_len - footer_len:-1 - ps_len], compression)
    stripes, types = [], []
    for no, v in _fields(footer):
        if no == 3:
            stripes.append(dict(_fields(v)))
        elif no == 4:
            types.append(list(_fields(v)))
    names = [v.decode() for no, v in types[0] if no == 3]
    subs = [x for no, v in types[0] if no == 2
            for x in (_packed(v) if isinstance(v, bytes) else [v])]
    name_of = dict(zip(subs, names))
    out = {n: {"stored": 0, "streams": 0} for n in names}
    for s in stripes:
        offset, index_len, data_len = s.get(1, 0), s.get(2, 0), s.get(3, 0)
        start = offset + index_len + data_len
        sfoot = _inflate(raw[start:start + s[4]], compression)
        pos = offset
        for no, v in _fields(sfoot):
            if no != 1:
                continue
            st = dict(_fields(v))
            kind, col, length = st.get(1, 0), st.get(2, 0), st.get(3, 0)
            if pos >= offset + index_len and kind in (0, 1, 2, 3, 5) \
                    and col in name_of:
                slot = out[name_of[col]]
                slot["stored"] += length
                slot["streams"] += sum(
                    _size(*b) for b in _blocks(raw[pos:pos + length],
                                               compression != 0))
            pos += length
    return out


def _packed(v: bytes) -> list:
    out, pos = [], 0
    while pos < len(v):
        x, pos = _varint(v, pos)
        out.append(x)
    return out


def _rows_in(path: str) -> int:
    from pyarrow import orc
    return orc.ORCFile(path).nrows


def decode_bytes(path: str) -> int:
    """The least bytes the decode of the seven columns has to move whatever
    implements it: their uncompressed streams read once and the batch they
    become written once."""
    streams = column_streams(path)
    return sum(streams[c]["streams"] for c in _READ) \
        + _rows_in(path) * _ROW_BYTES


def least_bytes(tables: dict) -> int:
    """The least bytes the query has to move through HBM, whatever
    implements it: the stored bytes of the seven columns' streams, their
    decoded bytes once, and the four result rows (as
    `q1_pricing_summary.least_bytes`, the file's part from the ORC stripe
    footers)."""
    path = tables["lineitem"]["path"]
    streams = column_streams(path)
    return 4 * (2 + 7 * 16 + 8) + _rows_in(path) * _ROW_BYTES \
        + sum(streams[c]["stored"] for c in _READ)
