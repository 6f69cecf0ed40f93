"""TPC-H query 1, the pricing summary report, as published (specification v3,
clause 2.4.1, validation parameter DELTA = 90):

    select l_returnflag, l_linestatus,
           sum(l_quantity) sum_qty, sum(l_extendedprice) sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) sum_charge,
           avg(l_quantity) avg_qty, avg(l_extendedprice) avg_price,
           avg(l_discount) avg_disc, count(*) count_order
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus

written the way Spark hands it to the plugin after analysis: the scan reads
only the seven columns the query names (Catalyst's ReadSchema), the filter
sits on the scan with the date already folded to 1998-09-02, the literal 1 is
decimal(1,0), so `1 - l_discount` and `1 + l_tax` are decimal(13,2), the
first product decimal(26,4) and the second decimal(38,6) by Spark's rule
(precision p1 + p2 + 1, scale s1 + s2, bounded at 38); the sums add ten
digits (bounded at 38), an average of decimal(12,2) is decimal(16,6)."""

from __future__ import annotations

import datetime
import decimal
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _frames import scanned_bytes  # noqa: E402

TABLES = ("lineitem",)
CUTOFF = datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
# every number `compare` reports, with the most it may read; exact, so 0
LIMITS = {"rows_off": 0, "sums_off": 0}
_READ = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
         "l_returnflag", "l_linestatus", "l_shipdate"]
_KEYS = ["l_returnflag", "l_linestatus"]
# the answer's aggregate columns with the type the configuration guarantees
# (Spark's): (precision, scale) of a decimal, None for the long count
_AGGS = {"sum_qty": (22, 2), "sum_base_price": (22, 2),
         "sum_disc_price": (36, 4), "sum_charge": (38, 6),
         "avg_qty": (16, 6), "avg_price": (16, 6), "avg_disc": (16, 6),
         "count_order": None}
_OUT = _KEYS + list(_AGGS)
# bytes a row of the projection has to move whatever implements it: three
# decimal(12,2) read as 8-byte words, two 128-bit products written
_PROJECT_ROW_BYTES = 3 * 8 + 2 * 16


def scans(session, paths: dict) -> dict:
    """The query's one scan, with the columns it reads."""
    return {"lineitem": session.read_parquet(paths["lineitem"],
                                             columns=list(_READ))}


def build(session, paths: dict):
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
            .group_by(*_KEYS)
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


def _unscaled(column) -> np.ndarray:
    """decimal(12,2) arrow column -> its cents as int64 (no nulls here; a
    null would raise in `to_numpy`)."""
    import pyarrow as pa
    arr = column.combine_chunks()
    if arr.null_count:
        raise ValueError("lineitem holds no nulls")
    # the unscaled value is the low word of the 128-bit one: 12 digits
    words = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    words = words[2 * arr.offset: 2 * (arr.offset + len(arr))]
    if not pa.types.is_decimal128(arr.type) or arr.type.scale != 2:
        raise TypeError(f"{arr.type}, not decimal(p, 2)")
    return words[0::2].copy()


def _rows(paths: dict) -> dict:
    """The rows the filter keeps, as numpy: cents, flags, and the group of
    each row (index into the ordered distinct (returnflag, linestatus))."""
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    t = pq.read_table(paths["lineitem"], columns=_READ)
    t = t.filter(pc.less_equal(t["l_shipdate"], CUTOFF))
    flags = np.char.add(t["l_returnflag"].to_numpy(zero_copy_only=False)
                        .astype("U1"),
                        t["l_linestatus"].to_numpy(zero_copy_only=False)
                        .astype("U1"))
    groups, gid = np.unique(flags, return_inverse=True)
    return {"groups": [(g[0], g[1]) for g in groups], "gid": gid,
            **{c: _unscaled(t[c]) for c in _READ[:4]}}


def _half_up(numerator: int, denominator: int) -> int:
    """numerator / denominator rounded half away from zero, in integers."""
    q = (2 * abs(numerator) + denominator) // (2 * denominator)
    return -q if numerator < 0 else q


def _answer(groups, columns: dict):
    """Unscaled integers per group -> the arrow table of the Spark types."""
    import pyarrow as pa
    ctx = decimal.Context(prec=80)
    out = {"l_returnflag": pa.array([g[0] for g in groups], pa.string()),
           "l_linestatus": pa.array([g[1] for g in groups], pa.string())}
    for name, typ in _AGGS.items():
        if typ is None:
            out[name] = pa.array(columns[name], pa.int64())
        else:
            out[name] = pa.array(
                [None if v is None
                 else ctx.scaleb(decimal.Decimal(int(v)), -typ[1])
                 for v in columns[name]], pa.decimal128(*typ))
    return pa.table(out)


def _summary(r: dict, total, average, back=int):
    """The answer from the kept rows `r`, the arithmetic left to the caller:
    `total(values)` sums one group's column, `average(sum, count)` gives the
    average's unscaled integer at four more decimals, `back` turns a sum
    into an integer."""
    qty, ext, disc, tax = (r[c] for c in _READ[:4])
    disc_price = ext * (100 - disc)
    charge = disc_price * (100 + tax)
    cols = {name: [] for name in _AGGS}
    for g in range(len(r["groups"])):
        sel = r["gid"] == g
        n = int(sel.sum())
        s = {c: total(v[sel]) for c, v in (
            ("qty", qty), ("ext", ext), ("disc", disc),
            ("disc_price", disc_price), ("charge", charge))}
        for name, c in (("sum_qty", "qty"), ("sum_base_price", "ext"),
                        ("sum_disc_price", "disc_price"),
                        ("sum_charge", "charge")):
            cols[name].append(back(s[c]))
        for name, c in (("avg_qty", "qty"), ("avg_price", "ext"),
                        ("avg_disc", "disc")):
            cols[name].append(average(s[c], n))
        cols["count_order"].append(n)
    return _answer(r["groups"], cols)


def reference(paths: dict):
    """The answer in plain integers: pyarrow reads the file, a decimal is
    its unscaled integer, the products are `ext * (100 - d) * (100 + t)`,
    the sums are Python ints (no width assumed), an average is the sum
    times 10^4 over the count, rounded half up. Shares nothing with the
    engine."""
    r = _rows(paths)
    # per row the products stay under 2^63 by the types' bounds (12 digits
    # x 3 x 3); checked, not assumed
    if int(np.abs(r["l_extendedprice"]).max(initial=0)) * 110 * 110 >= 2 ** 63:
        r["l_extendedprice"] = r["l_extendedprice"].astype(object)
    return _summary(r, lambda v: sum(v.tolist()),
                    lambda s, n: _half_up(s * 10 ** 4, n))


def control(paths: dict, dtype: str):
    """The reference with the products and the sums carried in `dtype`
    ("float64", "float32") and rounded back to integers at the end: what a
    device path that gave up exact decimals would return. The unscaled
    integers are what is carried, so that a product is exact as far as the
    mantissa holds one: the control nearest to the exact answer."""
    r = _rows(paths)
    f = np.dtype(dtype).type
    for c in _READ[:4]:
        r[c] = r[c].astype(f)

    def back(x) -> int:
        return int(np.rint(np.float64(x)))
    return _summary(r, lambda v: v.sum(dtype=f),
                    lambda s, n: back(s * f(10 ** 4) / f(n)), back)


def compare(got, want) -> dict:
    """Place by place, in the order returned: `rows_off`, the places whose
    flags differ plus the rows one answer has more than the other;
    `sums_off`, the aggregate values (a place with the same flags, one of
    the eight columns) that differ in any digit or in their null flag; a
    column that came back under another type than the guaranteed one counts
    every place. All 0 for a right answer."""
    import pyarrow as pa
    if got.schema.names != _OUT:
        raise TypeError(f"columns {got.schema.names}, not {_OUT}")
    n = min(got.num_rows, want.num_rows)
    g = {c: got.column(c).to_pylist()[:n] for c in _OUT}
    w = {c: want.column(c).to_pylist()[:n] for c in _OUT}
    same = [all(g[k][i] == w[k][i] for k in _KEYS) for i in range(n)]
    sums_off = 0
    for name, typ in _AGGS.items():
        wanted = pa.int64() if typ is None else pa.decimal128(*typ)
        typed = got.schema.field(name).type == wanted
        sums_off += sum(1 for i in range(n) if same[i]
                        and not (typed and g[name][i] == w[name][i]))
    return {"rows_off": same.count(False)
            + abs(got.num_rows - want.num_rows),
            "sums_off": sums_off}


def least_bytes(tables: dict) -> int:
    """The least bytes the query has to move through HBM, whatever
    implements it: the parquet bytes of the seven columns it reads, their
    decoded bytes once, and the four result rows. From the table's
    metadata alone."""
    widths = {"l_quantity": 8, "l_extendedprice": 8, "l_discount": 8,
              "l_tax": 8, "l_returnflag": 1, "l_linestatus": 1,
              "l_shipdate": 4}
    # a result row: two one-byte flags, seven 128-bit decimals, one long
    return 4 * (2 + 7 * 16 + 8) + scanned_bytes(tables["lineitem"]["path"],
                                                widths)


def projection_least_bytes(path: str) -> int:
    """The least bytes the projection has to move, whatever implements it:
    per row that passes the filter (counted from the file by pyarrow), the
    three decimal(12,2) operands read once and the two 128-bit products
    written once."""
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    ship = pq.read_table(path, columns=["l_shipdate"])["l_shipdate"]
    return pc.sum(pc.less_equal(ship, CUTOFF)).as_py() * _PROJECT_ROW_BYTES
