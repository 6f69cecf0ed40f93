"""TPC-DS query 98 as published (specification v3, `query98.tpl` with the
qualification substitutions: categories Sports, Books, Home; 1999-02-22 and
the 30 days after it):

    select i_item_id, i_item_desc, i_category, i_class, i_current_price,
           sum(ss_ext_sales_price) as itemrevenue,
           sum(ss_ext_sales_price)*100/sum(sum(ss_ext_sales_price)) over
               (partition by i_class) as revenueratio
    from store_sales, item, date_dim
    where ss_item_sk = i_item_sk
      and i_category in ('Sports', 'Books', 'Home')
      and ss_sold_date_sk = d_date_sk
      and d_date between cast('1999-02-22' as date)
                     and (cast('1999-02-22' as date) + 30 days)
    group by i_item_id, i_item_desc, i_category, i_class, i_current_price
    order by i_category, i_class, i_item_id, i_item_desc, revenueratio

written the way Spark hands it to the plugin after analysis: each scan reads
only the columns the query names, each dimension's filter sits on its scan
with the dates folded, the filtered dimensions are the build sides of two
inner hash joins with keys in the FROM clause's order (`item` first: it keeps
30% of the fact rows and carries four strings, one of 200 bytes), the
aggregate's sum is decimal(17,2), the window sums it again over `i_class`
(decimal(27,2), the whole partition: no ORDER BY in the window), the literal
100 is decimal(3,0), so the product is decimal(21,2) and the quotient
decimal(38,17) by Spark's DecimalPrecision rule (scale max(6, s1 + p2 + 1),
precision p1 - s1 + s2 + scale, then bounded at 38 keeping the integral
digits), and there is no LIMIT: every row comes back."""

from __future__ import annotations

import datetime
import decimal
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _frames import scanned_bytes  # noqa: E402

TABLES = ("store_sales", "date_dim", "item")
CATEGORIES = ("Sports", "Books", "Home")
FIRST_DAY = datetime.date(1999, 2, 22)
LAST_DAY = FIRST_DAY + datetime.timedelta(days=30)
# every number `compare` reports, with the most it may read; exact, so 0
LIMITS = {"rows_off": 0, "sums_off": 0}
_READ = {"store_sales": ["ss_sold_date_sk", "ss_item_sk",
                         "ss_ext_sales_price"],
         "item": ["i_item_sk", "i_item_id", "i_item_desc", "i_current_price",
                  "i_class", "i_category"],
         "date_dim": ["d_date_sk", "d_date"]}
_KEYS = ["i_item_id", "i_item_desc", "i_category", "i_class",
         "i_current_price"]
# the two computed columns with the (precision, scale) the configuration
# guarantees (Spark's types)
_SUMS = {"itemrevenue": (17, 2), "revenueratio": (38, 17)}
_OUT = _KEYS + list(_SUMS)
_ORDER = ["i_category", "i_class", "i_item_id", "i_item_desc",
          "revenueratio"]
# bytes a row of the division has to move whatever implements it: two
# 128-bit operands read, one 128-bit quotient written
_DIVIDE_ROW_BYTES = 2 * 16 + 16
_DIVISION = decimal.Context(prec=38, rounding=decimal.ROUND_HALF_UP)
_PLACES = decimal.Decimal(1).scaleb(-_SUMS["revenueratio"][1])


def scans(session, paths: dict) -> dict:
    """The query's scans, one per table, with the columns it reads."""
    return {t: session.read_parquet(paths[t], columns=list(cols))
            for t, cols in _READ.items()}


def build(session, paths: dict):
    from spark_rapids_tpu.expr import Sum, col, lit
    from spark_rapids_tpu.expr.predicates import In
    s = scans(session, paths)
    item = (s["item"].filter(In(col("i_category"), list(CATEGORIES)))
            .select(col("i_item_sk").alias("ss_item_sk"),
                    *(col(c) for c in _KEYS)))
    dt = (s["date_dim"].filter((col("d_date") >= lit(FIRST_DAY))
                               & (col("d_date") <= lit(LAST_DAY)))
          .select(col("d_date_sk").alias("ss_sold_date_sk")))
    nulls_first = [(col(c), True, True) for c in _ORDER]
    return (s["store_sales"].join(item, on="ss_item_sk")
            .join(dt, on="ss_sold_date_sk")
            .group_by(*_KEYS)
            .agg(itemrevenue=Sum(col("ss_ext_sales_price")))
            .window(partition_by=[col("i_class")],
                    class_revenue=Sum(col("itemrevenue")))
            .select(*(col(c) for c in _KEYS), col("itemrevenue"),
                    (col("itemrevenue") * lit(100) / col("class_revenue"))
                    .alias("revenueratio"))
            # Spark's null order when ascending: nulls first
            .sort(*nulls_first))


def _cents(column) -> list:
    """decimal(7,2) arrow column -> its cents as Python ints, None for a
    null."""
    return [None if v is None else int(v.scaleb(2))
            for v in column.to_pylist()]


def _grouped(paths: dict):
    """The joined and filtered rows grouped by the five keys, in pandas:
    (keys frame in group order, per group the list of its rows' cents)."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    ss = pq.read_table(paths["store_sales"], columns=_READ["store_sales"])
    it = pq.read_table(paths["item"], columns=_READ["item"])
    dd = pq.read_table(paths["date_dim"], columns=_READ["date_dim"])
    it = it.filter(pc.is_in(it["i_category"],
                            value_set=pa.array(list(CATEGORIES))))
    dd = dd.filter(pc.and_(pc.greater_equal(dd["d_date"], FIRST_DAY),
                           pc.less_equal(dd["d_date"], LAST_DAY)))
    # pyarrow's join drops null keys, as an inner join on them does
    j = (ss.join(dd.select(["d_date_sk"]), keys="ss_sold_date_sk",
                 right_keys="d_date_sk", join_type="inner")
         .join(it, keys="ss_item_sk", right_keys="i_item_sk",
               join_type="inner"))
    f = pd.DataFrame({
        **{k: j[k].to_pylist() for k in _KEYS[:4]},
        "i_current_price": _cents(j["i_current_price"]),
        "cents": np.asarray(_cents(j["ss_ext_sales_price"]), dtype=object)})
    groups = f.groupby(_KEYS, sort=False, dropna=False)["cents"].agg(list)
    return groups.index.to_frame(index=False), groups.tolist()


def _ratio_exact(revenue, total):
    """Spark's decimal(21,2) / decimal(27,2): the quotient at 38 significant
    digits HALF_UP, then HALF_UP at 17 places; null on a null operand or a
    zero divisor."""
    if revenue is None or total is None or total == 0:
        return None
    q = _DIVISION.divide(decimal.Decimal(revenue * 100),
                         decimal.Decimal(total))
    return q.quantize(_PLACES, rounding=decimal.ROUND_HALF_UP,
                      context=decimal.Context(prec=80))


def _ratio_float64(revenue, total):
    """The same quotient carried in float64 (cents are exact in a double up
    to 2^53) and rounded to 17 places at the end."""
    if revenue is None or total is None or total == 0:
        return None
    q = np.float64(revenue) * np.float64(100) / np.float64(total)
    return decimal.Decimal(float(q)).quantize(
        _PLACES, rounding=decimal.ROUND_HALF_UP,
        context=decimal.Context(prec=80))


def _answer(paths: dict, ratio):
    """The published answer, the division left to `ratio(revenue cents,
    class total cents)`: sums are Python ints (null where a group has no
    price, a class total likewise), rows in the published order with nulls
    first."""
    import pyarrow as pa
    keys, cents = _grouped(paths)
    revenue = []
    for rows in cents:
        have = [c for c in rows if c is not None and c == c]
        revenue.append(sum(have) if have else None)
    by_class: dict = {}
    for cls, r in zip(keys["i_class"], revenue):
        if r is not None:
            by_class[cls] = by_class.get(cls, 0) + r
    ratios = [ratio(r, by_class.get(cls))
              for cls, r in zip(keys["i_class"], revenue)]
    exact = decimal.Context(prec=80)

    def place(i):
        # nulls first: (is not null, value) sorts a None before any value
        return tuple((v is not None, v) for v in (
            keys["i_category"][i], keys["i_class"][i], keys["i_item_id"][i],
            keys["i_item_desc"][i], ratios[i]))
    order = sorted(range(len(revenue)), key=place)
    price = keys["i_current_price"].tolist()
    cols = {k: pa.array([keys[k][i] for i in order], pa.string())
            for k in _KEYS[:4]}
    cols["i_current_price"] = pa.array(
        [None if price[i] is None or price[i] != price[i]
         else exact.scaleb(decimal.Decimal(int(price[i])), -2)
         for i in order], pa.decimal128(7, 2))
    cols["itemrevenue"] = pa.array(
        [None if revenue[i] is None
         else exact.scaleb(decimal.Decimal(revenue[i]), -2) for i in order],
        pa.decimal128(*_SUMS["itemrevenue"]))
    cols["revenueratio"] = pa.array([ratios[i] for i in order],
                                    pa.decimal128(*_SUMS["revenueratio"]))
    return pa.table(cols)


def reference(paths: dict):
    """The answer in plain integers and Python's `decimal`: pyarrow reads
    the files and joins them, a decimal is its unscaled integer, a sum is a
    sum of Python ints, the ratio is `decimal`'s division at 38 significant
    digits HALF_UP and then `quantize` to 17 places HALF_UP. Shares nothing
    with the engine."""
    return _answer(paths, _ratio_exact)


def control(paths: dict, dtype: str):
    """The reference with the ratio carried in `dtype` ("float64"): what a
    device path that divided in doubles, as `Divide` did for every type
    before this configuration, would return under the decimal type. A double
    holds 15-16 of the ratio's up to 19 digits."""
    if dtype != "float64":
        raise ValueError(f"no {dtype} control for query 98")
    return _answer(paths, _ratio_float64)


def compare(got, want) -> dict:
    """Place by place, in the order returned: `rows_off`, the places whose
    five keys differ plus the rows one answer has more than the other;
    `sums_off`, the values (a place with the same keys, one of
    `itemrevenue` and `revenueratio`) that differ in any digit or in their
    null flag; a column that came back under another type than the
    guaranteed one counts every place. All 0 for a right answer."""
    import pyarrow as pa
    if got.schema.names != _OUT:
        raise TypeError(f"columns {got.schema.names}, not {_OUT}")
    n = min(got.num_rows, want.num_rows)
    g = {c: got.column(c).to_pylist()[:n] for c in _OUT}
    w = {c: want.column(c).to_pylist()[:n] for c in _OUT}
    same = [all(g[k][i] == w[k][i] for k in _KEYS) for i in range(n)]
    sums_off = 0
    for name, typ in _SUMS.items():
        typed = got.schema.field(name).type == pa.decimal128(*typ)
        sums_off += sum(1 for i in range(n) if same[i]
                        and not (typed and g[name][i] == w[name][i]))
    return {"rows_off": same.count(False)
            + abs(got.num_rows - want.num_rows),
            "sums_off": sums_off}


def least_bytes(tables: dict) -> int:
    """The least bytes the query has to move through HBM, whatever
    implements it: the parquet bytes of the columns it reads and their
    decoded bytes once. From the tables' metadata alone (the answer's few
    thousand rows are not counted: their number is not in the metadata)."""
    widths = {"ss_sold_date_sk": 8, "ss_item_sk": 8, "ss_ext_sales_price": 8,
              "i_item_sk": 8, "i_item_id": 16, "i_item_desc": 200,
              "i_current_price": 8, "i_class": 50, "i_category": 50,
              "d_date_sk": 8, "d_date": 4}
    return sum(scanned_bytes(tables[t]["path"], {c: widths[c] for c in cols})
               for t, cols in _READ.items())


def division_least_bytes(paths: dict) -> int:
    """The least bytes the decimal division has to move, whatever implements
    it: per row of the answer (counted from the files by pyarrow and pandas,
    as the reference counts them), the two 128-bit operands read once and
    the 128-bit quotient written once."""
    return len(_grouped(paths)[1]) * _DIVIDE_ROW_BYTES
