"""Plan rewrite: CPU physical plan -> TPU operators with tagging/fallback.

Reference: `GpuOverrides.scala` — rule registries (expr rules `:866-3475`, exec rules
`:3641-4016`), wrapPlan/tag/convert (`:3633,:4036,:4363`), explain output
(`explainPotentialGpuPlan` `:4116`), per-op enable confs auto-registered per rule.
Mirrored here at reduced scale: each rule carries a TypeSig, an auto-registered
`spark.rapids.sql.{expression,exec}.*` conf key, optional extra tagging, and a
convert function. Conversion is per-subtree with host<->device transitions inserted
at boundaries (`GpuTransitionOverrides` analog lives in exec/transitions.py)."""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple, Type

from .. import config as C
from .. import types as T
from ..config import TpuConf
from ..expr import base as EB
from ..expr import (arithmetic as EA, bitwise as EW, cast as EC,
                    conditional as ECO, datetime_ as ED, hashing as EH,
                    math_ as EM, nullexprs as EN, predicates as EP,
                    strings as ES)
from ..expr.aggregates import (AggregateFunction, Average, Count, First, Last,
                               Max, Min, Sum)
from .meta import ExprMeta, PlanMeta
from .typesig import TypeSig
from . import nodes as N

# ----------------------------------------------------------------------------
# Expression rules
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class ExprRule:
    cls: Type
    sig: TypeSig
    conf_key: str
    incompat: bool = False
    disabled: bool = False
    tag_fn: Optional[Callable[[ExprMeta], None]] = None


_EXPR_RULES: Dict[Type, ExprRule] = {}


def expr_rule(cls: Type, sig: TypeSig, incompat: bool = False,
              disabled: bool = False, tag_fn=None, doc: str = "") -> None:
    key = f"spark.rapids.sql.expression.{cls.__name__}"
    C.register(key, "bool", not disabled,
               doc or f"Enable TPU execution of expression {cls.__name__}.")
    _EXPR_RULES[cls] = ExprRule(cls, sig, key, incompat, disabled, tag_fn)


def _tag_cast(meta: ExprMeta) -> None:
    e: EC.Cast = meta.expr
    try:
        src = e.children[0].data_type
    except Exception:
        return
    if not EC.device_supported(src, e.to):
        meta.will_not_work(
            f"cast {src.simple_string()} -> {e.to.simple_string()} is not "
            "supported on TPU")
    if meta.conf.is_ansi:
        # numeric<->numeric and decimal ANSI casts report overflow, and
        # string-parse casts report malformed input, via the kernel error
        # flags; string->float now parses bit-exactly on device
        # (expr/floatparse.py), closing the last cast fallback
        def plain_numeric(dt):
            return T.is_integral(dt) or T.is_floating(dt) or \
                isinstance(dt, T.BooleanType)
        ok = plain_numeric(src) and plain_numeric(e.to)
        ok = ok or isinstance(src, T.DecimalType) or \
            isinstance(e.to, T.DecimalType)
        ok = ok or (isinstance(src, T.StringType) and
                    (T.is_integral(e.to) or T.is_floating(e.to) or
                     isinstance(e.to, (T.BooleanType, T.DateType))))
        if not ok:
            meta.will_not_work(
                f"ANSI-mode cast {src.simple_string()} -> "
                f"{e.to.simple_string()} is not supported on TPU yet")


# ANSI arithmetic raises host-side from error flags the kernels return;
# every expression-evaluating context (project, filter, agg, sort, window,
# generate, join conditions) plumbs the traced flags back through
# kernel_errors/raise_kernel_errors (exec/base.py), so no context-based
# ANSI fallback remains.


_basic = TypeSig.all_basic()
_basic38 = TypeSig.all_basic(decimal_max=38)
_nested38 = TypeSig.all_with_nested(decimal_max=38)
_num = TypeSig.numeric()
_num38 = TypeSig.numeric(decimal_max=38)
_bool = TypeSig((T.BooleanType,))
_str = TypeSig((T.StringType,))
_int = TypeSig((T.IntegerType,))
_dbl = TypeSig((T.DoubleType,))

for cls in (EB.Literal, EB.AttributeReference, EB.BoundReference, EB.Alias):
    expr_rule(cls, _nested38)
for cls in (EA.Add, EA.Subtract):
    expr_rule(cls, _num38)  # decimal +/- via 128-bit limb kernels
expr_rule(EA.Multiply, _num38)  # decimal x decimal exact in 32-bit limbs
expr_rule(EA.Divide, _num38)  # decimal / decimal exact, one division loop
for cls in (EA.IntegralDivide, EA.Remainder, EA.Pmod):
    expr_rule(cls, _num)
for cls in (EA.UnaryMinus, EA.Abs):
    expr_rule(cls, _num38)
for cls in (EP.EqualTo, EP.EqualNullSafe, EP.LessThan, EP.LessThanOrEqual,
            EP.GreaterThan, EP.GreaterThanOrEqual):
    expr_rule(cls, _bool)
for cls in (EP.And, EP.Or, EP.Not, EP.In):
    expr_rule(cls, _bool)
for cls in (EN.IsNull, EN.IsNotNull, EN.IsNaN):
    expr_rule(cls, _bool)
for cls in (EN.Coalesce, ECO.If, ECO.CaseWhen):
    expr_rule(cls, _basic38)
for cls in (EN.NaNvl, ECO.Least, ECO.Greatest):
    expr_rule(cls, _basic)
for cls in (EM.Sqrt, EM.Exp, EM.Log, EM.Log10, EM.Log2, EM.Pow, EM.Signum,
            EM.Sin, EM.Cos, EM.Tan, EM.Asin, EM.Acos, EM.Atan, EM.Sinh,
            EM.Cosh, EM.Tanh, EM.Cbrt, EM.ToDegrees, EM.ToRadians):
    expr_rule(cls, _dbl, incompat=True,
              doc="Transcendental results may differ from the JVM in ULPs "
                  "(reference marks the same ops incompat).")
for cls in (EM.Floor, EM.Ceil, EM.Round):
    expr_rule(cls, _num)
for cls in (EW.BitwiseAnd, EW.BitwiseOr, EW.BitwiseXor, EW.BitwiseNot,
            EW.ShiftLeft, EW.ShiftRight, EW.ShiftRightUnsigned):
    expr_rule(cls, TypeSig.integral())
expr_rule(ES.Length, _int)
for cls in (ES.Upper, ES.Lower):
    expr_rule(cls, _str, incompat=True,
              doc="ASCII-only case mapping on device (non-ASCII passes through "
                  "unchanged); reference notes similar locale corner cases.")
for cls in (ES.Substring, ES.Concat, ES.StringTrim, ES.StringTrimLeft,
            ES.StringTrimRight):
    expr_rule(cls, _str)
for cls in (ES.StartsWith, ES.EndsWith, ES.Contains):
    expr_rule(cls, _bool)
for cls in (ED.Year, ED.Month, ED.DayOfMonth, ED.Quarter, ED.DayOfWeek,
            ED.WeekDay, ED.DayOfYear, ED.Hour, ED.Minute, ED.Second,
            ED.DateDiff):
    expr_rule(cls, _int)
expr_rule(ED.DateAdd, TypeSig((T.DateType,)))
expr_rule(ED.DateSub, TypeSig((T.DateType,)))
expr_rule(ED.UnixTimestampFromTs, TypeSig((T.LongType,)))
expr_rule(EH.Murmur3Hash, _int)
expr_rule(EC.Cast, _basic38, tag_fn=_tag_cast)

# collection / nested-type expressions (complexTypeExtractors.scala,
# complexTypeCreator.scala, collectionOperations.scala)
from ..expr import collections as ECL  # noqa: E402

_nested = TypeSig.all_with_nested()


def _tag_array_contains(meta: ExprMeta) -> None:
    et = meta.expr.children[0].data_type.element_type
    if isinstance(et, (T.StringType, T.ArrayType, T.StructType, T.MapType)):
        meta.will_not_work(
            f"array_contains over {et.simple_string()} elements is not "
            "supported on TPU")


def _tag_create_array(meta: ExprMeta) -> None:
    for c in meta.expr.children:
        try:
            if c.data_type.is_nested:
                meta.will_not_work("array() of nested elements is not "
                                   "supported on TPU")
        except Exception:
            pass


expr_rule(ECL.Size, _int)
expr_rule(ECL.NullLike, _nested38)
for cls in (ECL.GetArrayItem, ECL.ElementAt, ECL.GetStructField,
            ECL.CreateNamedStruct, ECL.Explode):
    expr_rule(cls, _nested)
expr_rule(ECL.CreateArray, _nested, tag_fn=_tag_create_array)
expr_rule(ECL.ArrayContains, _bool, tag_fn=_tag_array_contains)


def _tag_array_ordering(meta: ExprMeta) -> None:
    et = meta.expr.children[0].data_type.element_type
    if isinstance(et, (T.StringType, T.ArrayType, T.StructType, T.MapType,
                       T.DecimalType)):
        meta.will_not_work(
            f"{meta.expr.name} over {et.simple_string()} elements is not "
            "supported on TPU")


for cls in (ECL.ArrayMin, ECL.ArrayMax):
    expr_rule(cls, TypeSig.all_basic(), tag_fn=_tag_array_ordering)
expr_rule(ECL.SortArray, _nested, tag_fn=_tag_array_ordering)

# map expressions (GpuOverrides.scala:3416 CreateMap, :2423 GetMapValue,
# :2442-2482 MapKeys/MapValues/MapEntries/StringToMap, collectionOperations
# MapConcat/MapFromArrays)
from ..expr import maps as EMP  # noqa: E402


def _tag_string_to_map(meta: ExprMeta) -> None:
    e = meta.expr
    for d, what in ((e.pair_delim, "pair delimiter"),
                    (e.kv_delim, "key/value delimiter")):
        if not isinstance(d, str) or len(d) != 1 or ord(d) > 127:
            meta.will_not_work(
                f"str_to_map requires a literal single-byte ASCII {what} "
                "on TPU (the reference likewise rejects regex delimiters)")


def _tag_create_map(meta: ExprMeta) -> None:
    kv = meta.expr.children
    kts = {c.data_type for c in kv[0::2]}
    vts = {c.data_type for c in kv[1::2]}
    if len(kts) > 1 or len(vts) > 1:
        meta.will_not_work("map() requires uniform key and value types on "
                           "TPU (no implicit coercion)")
    if any(t.is_nested for t in kts | vts):
        meta.will_not_work("map() of nested key/value exprs is not "
                           "supported on TPU")


for cls in (EMP.MapKeys, EMP.MapValues, EMP.MapEntries, EMP.GetMapValue,
            EMP.MapFromArrays, EMP.MapConcat):
    expr_rule(cls, _nested)
expr_rule(EMP.CreateMap, _nested, tag_fn=_tag_create_map)
expr_rule(EMP.StringToMap, _nested, tag_fn=_tag_string_to_map)

# digest/checksum family (GpuOverrides.scala:2322 Md5, hashFunctions) and
# split/extract-all/arrays_zip (GpuOverrides.scala:2385 StringSplit)
from ..expr import hashing_ext as EHX  # noqa: E402
from ..expr import splits as ESP  # noqa: E402

_long_sig = TypeSig((T.LongType,))

for cls in (EHX.Md5, EHX.Sha1):
    expr_rule(cls, _str)
# every Spark sha2 bit width (0/224/256/384/512) runs on device
expr_rule(EHX.Sha2, _str)
expr_rule(EHX.Crc32, _long_sig)
expr_rule(EHX.XxHash64, _long_sig)
expr_rule(EHX.HiveHash, _int)


def _tag_string_split(meta: ExprMeta) -> None:
    p = meta.expr.pattern
    if not (ESP.is_literal_pattern(p) and len(p) == 1 and ord(p) < 128):
        meta.will_not_work(
            "split requires a literal single-byte ASCII delimiter on TPU "
            "(the reference rejects unsupported regex the same way)")


expr_rule(ESP.StringSplit, _nested, tag_fn=_tag_string_split)
expr_rule(ESP.RegExpExtractAll, _nested,
          tag_fn=lambda m: m.will_not_work(
              "regexp_extract_all runs on CPU (regex extraction)"))
expr_rule(ESP.ArraysZip, _nested)

# higher-order functions (higherOrderFunctions.scala,
# GpuOverrides.scala:2629-2810): lambdas evaluate over the flattened
# [n*K] element space of the fixed-fanout layout
from ..expr import higher_order as EHO  # noqa: E402

for cls in (EHO.NamedLambdaVariable, EHO.ArrayTransform, EHO.ArrayFilter,
            EHO.ArrayExists, EHO.ArrayForAll, EHO.ArrayAggregate,
            EHO.ZipWith, EHO.TransformKeys, EHO.TransformValues,
            EHO.MapFilter):
    expr_rule(cls, _nested38)

# extended string surface (stringFunctions.scala breadth push)
from ..expr import strings_ext as ESX  # noqa: E402


def _lit_tag(attr, what):
    def tag(meta: ExprMeta) -> None:
        if getattr(meta.expr, attr, None) is None:
            meta.will_not_work(
                f"{meta.expr.name} requires a literal {what} on TPU "
                "(static output width)")
    return tag


def _tag_pad(meta: ExprMeta) -> None:
    if meta.expr.target is None:
        meta.will_not_work("lpad/rpad requires a literal length on TPU")
        return
    if meta.expr.pad is None:
        meta.will_not_work("lpad/rpad requires a literal pad string on TPU")
        return
    if any(ord(ch) > 127 for ch in meta.expr.pad):
        meta.will_not_work("non-ASCII pad strings are not supported on TPU")


def _tag_translate(meta: ExprMeta) -> None:
    if meta.expr.matching is None or meta.expr.replace is None:
        meta.will_not_work("translate requires literal from/to strings on TPU")
        return
    if any(ord(ch) > 127 for ch in meta.expr.matching + meta.expr.replace):
        meta.will_not_work("non-ASCII translate arguments are not supported "
                           "on TPU")


def _tag_replace(meta: ExprMeta) -> None:
    if meta.expr.search is None or meta.expr.replacement is None:
        meta.will_not_work("replace requires literal search/replacement "
                           "strings on TPU")


def _tag_substring_index(meta: ExprMeta) -> None:
    if meta.expr.delim is None or meta.expr.count is None:
        meta.will_not_work("substring_index requires literal delimiter/count "
                           "on TPU")


expr_rule(ESX.StringRepeat, _str, tag_fn=_lit_tag("times", "repeat count"))
expr_rule(ESX.StringLPad, _str, tag_fn=_tag_pad)
expr_rule(ESX.StringRPad, _str, tag_fn=_tag_pad)
expr_rule(ESX.StringLocate, _int)
expr_rule(ESX.StringInstr, _int)
expr_rule(ESX.StringReplace, _str, tag_fn=_tag_replace)
expr_rule(ESX.StringTranslate, _str, tag_fn=_tag_translate)
expr_rule(ESX.StringReverse, _str)
expr_rule(ESX.ConcatWs, _str, tag_fn=_lit_tag("sep", "separator"))
expr_rule(ESX.SubstringIndex, _str, tag_fn=_tag_substring_index)
expr_rule(ESX.InitCap, _str, incompat=True,
          doc="ASCII-only case mapping on device, like Upper/Lower.")
expr_rule(ESX.Ascii, _int)
expr_rule(ESX.Chr, _str)
expr_rule(ESX.Left, _str)
expr_rule(ESX.Right, _str)
expr_rule(ESX.StringSpace, _str, tag_fn=_lit_tag("count", "count"))
expr_rule(ESX.BitLength, _int)
expr_rule(ESX.OctetLength, _int)
expr_rule(ESX.FindInSet, _int)

# extended math (mathExpressions.scala breadth)
for cls in (EM.Atan2, EM.Hypot, EM.Logarithm, EM.Expm1, EM.Log1p, EM.Rint,
            EM.Cot):
    expr_rule(cls, _dbl, incompat=True,
              doc="Transcendental results may differ from the JVM in ULPs.")
expr_rule(EM.BRound, _num)

# extended datetime (datetimeExpressions.scala breadth)
expr_rule(ED.LastDay, TypeSig((T.DateType,)))
expr_rule(ED.AddMonths, TypeSig((T.DateType,)))
expr_rule(ED.MonthsBetween, _dbl)
expr_rule(ED.TruncDate, TypeSig((T.DateType,)))
expr_rule(ED.NextDay, TypeSig((T.DateType,)))
for cls in (Sum, Count, Min, Max, Average, First, Last):
    expr_rule(cls, _basic38)

from ..expr.aggregates import (ApproximatePercentile, CollectList,  # noqa: E402
                               CollectSet, StddevPop, StddevSamp, VariancePop,
                               VarianceSamp)

for cls in (VariancePop, VarianceSamp, StddevPop, StddevSamp):
    expr_rule(cls, _dbl, incompat=True,
              doc="Moment-form variance (sum/sumsq/count partials) can differ "
                  "from the JVM's Welford updates in low ULPs.")


def _tag_collect(meta: ExprMeta) -> None:
    try:
        ct = meta.expr.child.data_type
    except Exception:
        return
    if ct.is_nested:
        meta.will_not_work("collect of nested values is not supported on TPU")


def _tag_percentile(meta: ExprMeta) -> None:
    try:
        ct = meta.expr.child.data_type
    except Exception:
        return
    if not (T.is_integral(ct) or T.is_floating(ct)):
        meta.will_not_work("approx_percentile needs a numeric input on TPU")


for cls in (CollectList, CollectSet):
    expr_rule(cls, TypeSig.all_with_nested(), tag_fn=_tag_collect)
expr_rule(ApproximatePercentile, TypeSig.all_with_nested(),
          tag_fn=_tag_percentile)

# --------------------------------------------------------------------------
# breadth push: misc / datetime tail / more strings / array set ops / new
# aggregates (GpuOverrides.scala rule families)
# --------------------------------------------------------------------------
from ..expr import collections_ext as ECE  # noqa: E402
from ..expr import misc as EMI  # noqa: E402
from ..expr import strings_more as ESM  # noqa: E402
from ..expr.aggregates import (BitAndAgg, BitOrAgg, BitXorAgg, BoolAnd,  # noqa: E402
                               BoolOr, CountIf, Kurtosis, Skewness)
from ..expr.base import Literal as _Lit  # noqa: E402


def _tag_primitive_elems(meta: ExprMeta) -> None:
    for c in meta.expr.children:
        try:
            dt = c.data_type
        except ValueError:
            continue
        if isinstance(dt, T.ArrayType):
            et = dt.element_type
            if et.is_nested or isinstance(et, T.StringType):
                meta.will_not_work(
                    f"{meta.expr.name} over {et.simple_string()} elements "
                    "is not supported on TPU")
                return


def _tag_string_elems(meta: ExprMeta) -> None:
    try:
        et = meta.expr.children[0].data_type.element_type
    except Exception:
        return
    if not isinstance(et, T.StringType):
        meta.will_not_work("array_join needs array<string>")


# misc
expr_rule(EMI.SparkPartitionID, _int)
expr_rule(EMI.MonotonicallyIncreasingID, TypeSig((T.LongType,)))
expr_rule(EMI.InputFileName, _str)
expr_rule(EMI.RaiseError, TypeSig.all_basic())
expr_rule(EMI.AssertTrue, TypeSig.all_basic())
expr_rule(EMI.Pi, _dbl)
expr_rule(EMI.Euler, _dbl)
expr_rule(EMI.WidthBucket, _num)
expr_rule(EMI.Sequence, TypeSig.all_with_nested())

# datetime tail
expr_rule(ED.WeekOfYear, _int)
expr_rule(ED.DayName, _str)
expr_rule(ED.MonthName, _str)
expr_rule(ED.TimestampSeconds, TypeSig((T.TimestampType,)))
expr_rule(ED.TimestampMillis, TypeSig((T.TimestampType,)))
expr_rule(ED.TimestampMicros, TypeSig((T.TimestampType,)))
expr_rule(ED.DateFromUnixDate, TypeSig((T.DateType,)))
expr_rule(ED.UnixDate, _int)
expr_rule(ED.MakeDate, TypeSig((T.DateType,)))
expr_rule(ED.TruncTimestamp, TypeSig((T.TimestampType,)))
expr_rule(ED.DateFormat, _str,
          doc="Enable date_format (fixed-width yyyy/MM/dd/HH/mm/ss "
              "patterns; UTC).")
expr_rule(ED.FromUnixTime, _str)
expr_rule(ED.ToUnixTimestamp, TypeSig((T.LongType,)))
expr_rule(ED.UnixTimestamp, TypeSig((T.LongType,)))

# more strings
expr_rule(ESM.Overlay, _str)
expr_rule(ESM.Levenshtein, _int)
expr_rule(ESM.SoundEx, _str)
expr_rule(ESM.Empty2Null, _str)
expr_rule(ESM.FormatNumber, _str,
          doc="Enable format_number; |values| at int64 scale or beyond "
              "return null (19+ digit JVM DecimalFormat not reproduced).")
expr_rule(ESM.Conv, _str)

# array breadth
expr_rule(ECE.ArrayPosition, TypeSig.all_with_nested(),
          tag_fn=_tag_primitive_elems)
expr_rule(ECE.ArrayRemove, TypeSig.all_with_nested(),
          tag_fn=_tag_primitive_elems)
expr_rule(ECE.ArrayDistinct, TypeSig.all_with_nested(),
          tag_fn=_tag_primitive_elems)
expr_rule(ECE.ArraysOverlap, TypeSig.all_with_nested(),
          tag_fn=_tag_primitive_elems)
expr_rule(ECE.ArrayUnion, TypeSig.all_with_nested(),
          tag_fn=_tag_primitive_elems)
expr_rule(ECE.ArrayIntersect, TypeSig.all_with_nested(),
          tag_fn=_tag_primitive_elems)
expr_rule(ECE.ArrayExcept, TypeSig.all_with_nested(),
          tag_fn=_tag_primitive_elems)
expr_rule(ECE.Slice, TypeSig.all_with_nested())
expr_rule(ECE.Reverse, TypeSig.all_with_nested())
expr_rule(ECE.Flatten, TypeSig.all_with_nested())


expr_rule(ECE.ArrayRepeat, TypeSig.all_with_nested())
expr_rule(ECE.ArrayJoin, TypeSig.all_with_nested(),
          tag_fn=_tag_string_elems)

# JSON (GpuGetJsonObject.scala, GpuJsonToStructs.scala)
from ..expr import json_ as EJ  # noqa: E402


def _tag_from_json(meta: ExprMeta) -> None:
    from ..expr.cast import device_supported
    for f in meta.expr.schema.fields:
        if not isinstance(f.data_type, T.StringType) and \
                not device_supported(T.STRING, f.data_type):
            meta.will_not_work(
                f"from_json field {f.name}: string -> "
                f"{f.data_type.simple_string()} parse runs on CPU")
            return


expr_rule(EJ.GetJsonObject, _str,
          doc="Enable get_json_object (literal paths; escape sequences in "
              "string results are returned raw, not decoded).")
expr_rule(EJ.JsonTuple, _str)
expr_rule(EJ.JsonToStructs, TypeSig.all_with_nested(),
          tag_fn=_tag_from_json)

# new aggregates
expr_rule(CountIf, TypeSig((T.LongType,)))
expr_rule(BoolAnd, _bool)
expr_rule(BoolOr, _bool)
for cls in (BitAndAgg, BitOrAgg, BitXorAgg):
    expr_rule(cls, TypeSig((T.ByteType, T.ShortType, T.IntegerType,
                            T.LongType)))
for cls in (Skewness, Kurtosis):
    expr_rule(cls, _dbl, incompat=True,
              doc="Moment-form (power sums) can differ from the JVM's "
                  "streaming updates in low ULPs.")


def _tag_window_agg(meta: ExprMeta) -> None:
    from ..expr import windowexprs as WX
    e: WX.WindowAggregate = meta.expr
    name = type(e.func).__name__
    if name not in ("Sum", "Count", "Min", "Max", "Average", "First", "Last"):
        meta.will_not_work(f"{name} is not supported over a window on TPU")
        return
    frame = e.frame
    bounded = WX.is_value_range_frame(frame) or (
        isinstance(frame, WX.RowFrame) and not (
            frame.lower is None and frame.upper in (0, None)))
    child = e.func.child
    if child is not None and name in ("Min", "Max") and bounded:
        # running/unbounded string min/max rides the segmented lex scan;
        # arbitrary index windows would need a sparse table of byte
        # matrices — stays on CPU
        try:
            if isinstance(child.data_type, T.StringType):
                meta.will_not_work(
                    f"bounded-frame window {name} over STRING runs on CPU")
        except ValueError:
            pass


def _tag_regex(meta: ExprMeta) -> None:
    e = meta.expr
    if not meta.conf.get("spark.rapids.sql.regexp.enabled"):
        meta.will_not_work("regular expressions are disabled via "
                           "spark.rapids.sql.regexp.enabled")
        return
    if e.device_reason is not None:
        meta.will_not_work(
            f"{e.name} pattern is not supported on TPU: {e.device_reason}")


def _tag_regex_cpu_only(meta: ExprMeta) -> None:
    meta.will_not_work(
        f"{meta.expr.name} runs on CPU (device byte-rewrite kernel pending)")


def _register_regex_exprs():
    from ..expr import regex as RX
    for cls in (RX.RLike, RX.Like):
        expr_rule(cls, _bool, incompat=True, tag_fn=_tag_regex,
                  doc="Byte-level regex machine: exact for ASCII subjects; "
                      "counted quantifiers over multi-byte UTF-8 characters "
                      "can differ from the JVM (reference marks regexp "
                      "incompat similarly).")
    for cls in (RX.RegExpReplace, RX.RegExpExtract):
        expr_rule(cls, _str, tag_fn=_tag_regex_cpu_only)


_register_regex_exprs()


def _register_udf_exprs():
    from ..udf.pandas_udf import PandasUDF
    from ..udf.spi import ColumnarUDFExpr
    expr_rule(ColumnarUDFExpr, _basic,
              doc="User columnar UDF (TpuUDF SPI, RapidsUDF.java analog): "
                  "runs inside device kernels.")
    expr_rule(PandasUDF, _basic, incompat=True,
              doc="Arrow/pandas UDF: host round trip around the python "
                  "function (GpuArrowEvalPythonExec analog); the projection "
                  "containing it runs eagerly, not fused.")


_register_udf_exprs()


def _register_window_exprs():
    from ..expr import windowexprs as WX
    for cls in (WX.RowNumber, WX.Rank, WX.DenseRank, WX.PercentRank,
                WX.CumeDist, WX.NTile, WX.Lead, WX.Lag):
        expr_rule(cls, _basic)
    # decimal sums and averages over a whole partition are exact in limbs
    # (`_tag_window` holds every other decimal frame to what is exact)
    expr_rule(WX.WindowAggregate, _basic38, tag_fn=_tag_window_agg)
    expr_rule(WX.NthValue, _basic)


_register_window_exprs()


def lookup_expr_rule(expr: EB.Expression, conf: TpuConf) -> ExprMeta:
    rule = _EXPR_RULES.get(type(expr))
    return ExprMeta(expr, conf, rule)


# ----------------------------------------------------------------------------
# Exec rules
# ----------------------------------------------------------------------------


@dataclasses.dataclass
class ExecRule:
    cls: Type
    sig: TypeSig
    conf_key: str
    incompat: bool = False
    disabled: bool = False
    tag_fn: Optional[Callable[[PlanMeta], None]] = None
    expr_fn: Optional[Callable[[PlanMeta], None]] = None
    convert_fn: Optional[Callable] = None


_EXEC_RULES: Dict[Type, ExecRule] = {}


def exec_rule(cls: Type, sig: TypeSig, convert_fn, tag_fn=None, expr_fn=None,
              incompat: bool = False, disabled: bool = False,
              doc: str = "") -> None:
    key = f"spark.rapids.sql.exec.{cls.__name__.replace('Cpu', 'Tpu')}"
    C.register(key, "bool", not disabled,
               doc or f"Enable TPU execution of {cls.__name__}.")
    _EXEC_RULES[cls] = ExecRule(cls, sig, key, incompat, disabled, tag_fn,
                                expr_fn, convert_fn)


# NOTE: metas tag the BOUND expression copies (the nodes bind in __init__) so
# data_type is resolvable during tagging.

def _exprs_project(m: PlanMeta):
    for e in m.plan._bound:
        m.add_expr(e)


def _exprs_filter(m: PlanMeta):
    m.add_expr(m.plan._bound)


def _exprs_agg(m: PlanMeta):
    for e in m.plan._bound_groups:
        m.add_expr(e)
    for a in m.plan._bound_aggs:
        m.add_expr(a.func)


def _exprs_join(m: PlanMeta):
    for e in m.plan._bl + m.plan._br:
        m.add_expr(e)
    if m.plan._bcond is not None:
        m.add_expr(m.plan._bcond)


def _exprs_sort(m: PlanMeta):
    for e, _, _ in m.plan._bound:
        m.add_expr(e)


def _exprs_expand(m: PlanMeta):
    for p in m.plan._bound:
        for e in p:
            m.add_expr(e)


def _tag_join(m: PlanMeta):
    from ..expr.base import AttributeReference
    for e in m.plan.left_keys + m.plan.right_keys:
        if not isinstance(e, AttributeReference):
            m.will_not_work("join keys must be column references "
                            "(project them first)")
    if m.plan.join_type not in ("inner", "left", "right", "full", "semi",
                                "anti", "existence"):
        m.will_not_work(f"join type {m.plan.join_type} not supported on TPU")
    for e in m.plan._bl + m.plan._br:
        try:
            if e.data_type.is_nested:
                m.will_not_work("nested types cannot be join keys on TPU")
        except Exception:
            pass


def _c_scan(plan, children, conf):
    from ..exec.basic import TpuScanExec
    return TpuScanExec(plan.table, conf)


def _c_project(plan, children, conf):
    from ..exec.basic import TpuProjectExec
    return TpuProjectExec(plan.exprs, children[0], conf)


def _c_filter(plan, children, conf):
    from ..exec.basic import TpuFilterExec
    return TpuFilterExec(plan.condition, children[0], conf)


def _c_agg(plan, children, conf):
    from ..exec.aggregate import TpuHashAggregateExec
    return TpuHashAggregateExec(plan.group_exprs, plan.aggs, children[0], conf)


def _estimated_bytes(plan, conf=None) -> float:
    """Heuristic output size in bytes: CBO cardinality x schema row width
    (history-corrected cardinality when stats feedback is enabled — a
    build side that turned out broadcast-sized flips to broadcast on the
    next run)."""
    from .cbo import row_estimate
    width = 0
    for dt in plan.output.types:
        npdt = getattr(dt, "np_dtype", None)
        width += 20 if npdt is None else npdt.itemsize + 1  # +validity
    return row_estimate(plan, conf) * max(width, 1)


# join types whose BUILD (right) side may be replicated: every probe shard
# sees the full build table and no output depends on build-side match
# bookkeeping being global (right/full outer would emit unmatched build rows
# once PER SHARD if the build side were replicated — Spark broadcasts the
# other side for those, which this engine's fixed build-right layout doesn't
# support, so they stay shuffled)
_BROADCASTABLE = ("inner", "cross", "left", "semi", "anti", "existence")


def _c_join(plan, children, conf):
    from ..exec.broadcast import TpuBroadcastExchangeExec
    from ..exec.joins import (TpuBroadcastHashJoinExec, TpuNestedLoopJoinExec,
                              TpuShuffledHashJoinExec)
    threshold = conf.get("spark.rapids.sql.autoBroadcastJoinThreshold")
    no_nested = not any(getattr(dt, "is_nested", False)
                        for dt in plan.children[1].output.types)
    small_build = (threshold >= 0 and no_nested and
                   _estimated_bytes(plan.children[1], conf) <= threshold and
                   plan.join_type in _BROADCASTABLE)
    if not plan.left_keys:
        # keyless: cartesian product / pure-condition nested loop join; a
        # small build side rides the broadcast exchange (the reference's
        # GpuBroadcastNestedLoopJoinExec vs GpuCartesianProductExec split)
        build = TpuBroadcastExchangeExec(children[1], conf) if small_build \
            else children[1]
        return TpuNestedLoopJoinExec(children[0], build, plan.condition,
                                     plan.join_type, conf)
    if small_build:
        join = TpuBroadcastHashJoinExec(
            children[0], TpuBroadcastExchangeExec(children[1], conf),
            plan.left_keys, plan.right_keys, plan.join_type, conf,
            condition=plan.condition)
        _wire_dynamic_pruning(join, plan, conf)
        return join
    return TpuShuffledHashJoinExec(children[0], children[1], plan.left_keys,
                                   plan.right_keys, plan.join_type, conf,
                                   condition=plan.condition)


# join types where a probe row WITHOUT a build match never reaches the
# output, so pruning probe files by build keys cannot change results
# (left/anti/existence emit unmatched probe rows — never prune those)
_DPP_SAFE = ("inner", "semi")


def _dpp_scan_for_column(node, colname):
    """Descend column-preserving execs from the probe root to a parquet
    scan that provides `colname` unchanged (the conservative leg of the
    reference's DynamicPruningExpression plumbing)."""
    from ..exec.basic import TpuFilterExec, TpuProjectExec
    from ..exec.coalesce import TpuCoalesceBatchesExec
    from ..io.scanbase import TpuFileScanExec
    if isinstance(node, TpuFileScanExec):
        return (node, colname) if (node.cpu_scan.format_name == "parquet"
                                   and colname in node.output.names) \
            else None
    if isinstance(node, (TpuFilterExec, TpuCoalesceBatchesExec)):
        return _dpp_scan_for_column(node.children[0], colname)
    if isinstance(node, TpuProjectExec):
        from ..expr.base import Alias, AttributeReference
        for e in node.exprs:
            src = e.children[0] if isinstance(e, Alias) else e
            name = e.alias if isinstance(e, Alias) else \
                getattr(e, "col_name", None)
            if name == colname and isinstance(src, AttributeReference):
                return _dpp_scan_for_column(node.children[0], src.col_name)
        return None
    return None


def _wire_dynamic_pruning(join, plan, conf) -> None:
    """Attach DynamicKeyFilters between a broadcast hash join and probe
    parquet scans its keys are direct columns of."""
    if not conf.get("spark.rapids.sql.dynamicFilePruning.enabled"):
        return
    if plan.join_type not in _DPP_SAFE:
        return
    from ..expr.base import AttributeReference
    from ..io.dynamic_pruning import DynamicKeyFilter
    from .. import types as T
    for i, lk in enumerate(plan.left_keys):
        if not isinstance(lk, AttributeReference):
            continue
        res = _dpp_scan_for_column(join.children[0], lk.col_name)
        if res is None:
            continue
        scan, scan_col = res
        # Only key types whose parquet footer min/max compare reliably in
        # the value domain: int/float/string. Decimal (limb pairs),
        # timestamp/date (logical-type units), and anything nested would
        # need domain-aware stat decoding — wrong pruning DROPS ROWS, so
        # the gate is an allowlist, not try/except on the cast path.
        ci = scan.output.names.index(scan_col)
        dt = scan.output.types[ci]
        if not (T.is_integral(dt) or T.is_floating(dt) or dt == T.STRING):
            continue
        filt = DynamicKeyFilter(scan_col)
        scan.dynamic_filters.append(filt)
        join.dpp_filters.append((join._rk_ix[i], filt))


def _c_generate(plan, children, conf):
    from ..exec.generate import TpuGenerateExec
    return TpuGenerateExec(plan.generator, children[0], conf)


def _exprs_generate(m: PlanMeta):
    m.add_expr(m.plan._bound)


def _c_sort(plan, children, conf):
    from ..exec.sort import TpuSortExec
    return TpuSortExec(plan.orders, children[0], conf)


def _c_limit(plan, children, conf):
    from ..exec.basic import TpuLimitExec
    from ..exec.sort import TpuSortExec, TpuTopKExec
    child = children[0]
    # LIMIT over ORDER BY -> top-k (TakeOrderedAndProjectExec analog,
    # GpuOverrides.scala:3705): per-batch k-select + running merge
    # replaces the full out-of-core sort
    if conf.get("spark.rapids.sql.topK.enabled") and \
            isinstance(child, TpuSortExec) and not child.each_batch and \
            plan.limit + plan.offset <= \
            conf.get("spark.rapids.sql.topK.threshold"):
        return TpuTopKExec(child.orders, plan.limit, child.child, conf,
                           plan.offset)
    return TpuLimitExec(plan.limit, children[0], plan.offset, conf)


def _c_sample(plan, children, conf):
    from ..exec.basic import TpuSampleExec
    return TpuSampleExec(plan.fraction, plan.seed, children[0], conf)


def _c_union(plan, children, conf):
    from ..exec.basic import TpuUnionExec
    return TpuUnionExec(children, conf)


def _c_range(plan, children, conf):
    from ..exec.basic import TpuRangeExec
    return TpuRangeExec(plan.start, plan.end, plan.step, conf)


def _c_expand(plan, children, conf):
    from ..exec.basic import TpuExpandExec
    return TpuExpandExec(plan.projections, plan.output.names, children[0], conf)


def _exprs_window(m: PlanMeta):
    for e in m.plan._bound_part:
        m.add_expr(e)
    for e, _, _ in m.plan._bound_order:
        m.add_expr(e)
    for f, _ in m.plan._bound_fns:
        m.add_expr(f)


def _tag_window(m: PlanMeta):
    from ..expr import windowexprs as WX
    has_order = bool(m.plan.order_spec)
    for f, name in m.plan._bound_fns:
        if f.requires_order and not has_order:
            m.will_not_work(f"window function {name} requires an ORDER BY")
        if isinstance(f, WX.WindowAggregate):
            reason = _decimal_window_agg_reason(
                f, f.frame or WX.default_frame(has_order))
            if reason:
                m.will_not_work(f"window column {name}: {reason}")
        if isinstance(f, (WX.WindowAggregate, WX.NthValue)) and \
                WX.is_value_range_frame(f.frame):
            # value-offset RANGE frames: Spark restricts these to a single
            # orderable numeric order column; the device binary search
            # additionally needs a sortable numeric axis
            if len(m.plan.order_spec) != 1:
                m.will_not_work("value-offset RANGE frames require exactly "
                                "one order column")
                continue
            try:
                key_t = m.plan._bound_order[0][0].data_type
            except ValueError:
                m.will_not_work("value-offset RANGE frame order key could "
                                "not be resolved")
                continue
            if not (T.is_numeric(key_t) or
                    isinstance(key_t, (T.DateType, T.TimestampType))):
                m.will_not_work("value-offset RANGE frames need a numeric "
                                "order column")


def _decimal_window_agg_reason(f, frame):
    """Why an aggregate of a decimal over `frame` cannot run on the device,
    or None: `sum` and `avg` over a whole partition are exact in limbs
    (exec/window.py), a `sum` that stays within 18 digits is exact int64
    arithmetic under every frame, count / first / last move no value; what
    is left (a running or bounded 128-bit sum, a running or bounded average,
    a 128-bit min / max) has no exact kernel and is answered, exactly, by
    the CPU engine."""
    from ..expr.decimal128 import is_dec128
    child = f.func.child
    try:
        ct = None if child is None else child.data_type
    except ValueError:
        return None
    name = type(f.func).__name__
    if not isinstance(ct, T.DecimalType):
        return None
    whole = frame.lower is None and frame.upper is None
    if name == "Sum" and (whole or not is_dec128(f.func.data_type)):
        return None
    if name == "Average" and whole:
        return None
    if name in ("Sum", "Average"):
        return (f"{name.lower()} of {ct.simple_string()} over the frame "
                f"{frame!r} has no exact device kernel (only a whole "
                "partition, or a sum within 18 digits)")
    if name in ("Min", "Max") and is_dec128(ct):
        return (f"{name.lower()} of {ct.simple_string()} over a window has "
                "no device kernel for 128-bit decimals")
    return None


def _c_window(plan, children, conf):
    from ..exec.window import TpuWindowExec
    return TpuWindowExec(plan.window_exprs, plan.partition_spec,
                         plan.order_spec, children[0], conf)


def _tag_exchange(m: PlanMeta):
    from .. import types as T
    from ..expr.base import AttributeReference
    spec = m.plan.partitioning
    if spec is None:
        return
    if isinstance(spec, N.RangePartitionSpec):
        if not isinstance(spec.key, AttributeReference):
            m.will_not_work("range partition key must be a column reference")
            return
        schema = m.plan.children[0].output
        if isinstance(schema.types[schema.index_of(spec.key.col_name)],
                      T.StringType):
            m.will_not_work("range partitioning on STRING not supported on "
                            "device")
    elif isinstance(spec, N.HashPartitionSpec):
        for k in spec.keys:
            if not isinstance(k, AttributeReference):
                m.will_not_work("hash partition keys must be column "
                                "references (project them first)")


def _c_exchange(plan, children, conf):
    from ..exec.coalesce import TpuCoalesceBatchesExec
    from ..exec.exchange import TpuShuffleExchangeExec
    if plan.partitioning is None:
        # bare exchange boundary: becomes a coalesce locally
        return TpuCoalesceBatchesExec(children[0], conf=conf)
    return TpuShuffleExchangeExec(plan.partitioning, children[0], conf=conf)


def _c_file_scan(plan, children, conf):
    from ..io.scanbase import make_tpu_file_scan
    return make_tpu_file_scan(plan, conf)


def _lazy_rule_group(sentinel_module: str, sentinel_class: str, register_fn):
    """Idempotent registration of exec rules for PhysicalPlan subclasses that
    live OUTSIDE plan/ (io formats, datasources). Those modules import
    plan.nodes, so importing one of them directly re-enters this module
    mid-cycle, before the subclass exists — detected via the sentinel
    (module in sys.modules but class not yet defined) and retried at first
    rule lookup (Overrides.apply). A genuine ImportError in the target
    module must NOT be swallowed: it would silently degrade those plan nodes
    to the CPU path, so outside the mid-cycle window imports fail loudly."""
    state = {"done": False}

    def ensure():
        if state["done"]:
            return
        import sys
        mod = sys.modules.get(sentinel_module)
        if mod is not None and not hasattr(mod, sentinel_class):
            return  # mid-import cycle; retried at first rule lookup
        register_fn()
        state["done"] = True
    return ensure


def _do_register_file_scans():
    from ..io.parquet import CpuParquetScanExec
    from ..io.csv import CpuCsvScanExec
    from ..io.json_ import CpuJsonScanExec
    from ..io.orc import CpuOrcScanExec
    from ..io.avro import CpuAvroScanExec
    from ..io.hive_text import CpuHiveTextScanExec
    for cls in (CpuParquetScanExec, CpuCsvScanExec, CpuJsonScanExec,
                CpuOrcScanExec, CpuAvroScanExec, CpuHiveTextScanExec):
        exec_rule(cls, TypeSig.all_basic(), _c_file_scan)


_register_file_scan_rules = _lazy_rule_group(
    "spark_rapids_tpu.io.scanbase", "CpuFileScanExec",
    _do_register_file_scans)


exec_rule(N.CpuScanExec, _nested38, _c_scan)
exec_rule(N.CpuProjectExec, _nested38, _c_project,
          expr_fn=_exprs_project)
exec_rule(N.CpuFilterExec, _nested38, _c_filter,
          expr_fn=_exprs_filter)
def _tag_agg(m: PlanMeta) -> None:
    # nested types may only appear as collect_* OUTPUTS; nested group keys
    # and nested aggregate inputs stay on CPU
    for e in m.plan._bound_groups:
        try:
            if e.data_type.is_nested:
                m.will_not_work("nested group-by keys are not supported "
                                "on TPU")
        except Exception:
            pass
    for a in m.plan._bound_aggs:
        try:
            if a.func.child is not None and a.func.child.data_type.is_nested:
                m.will_not_work("nested aggregate inputs are not supported "
                                "on TPU")
        except Exception:
            pass


exec_rule(N.CpuHashAggregateExec, _nested38, _c_agg,
          expr_fn=_exprs_agg, tag_fn=_tag_agg)
exec_rule(N.CpuHashJoinExec, TypeSig.all_with_nested(), _c_join,
          tag_fn=_tag_join, expr_fn=_exprs_join)
exec_rule(N.CpuSortExec, TypeSig.orderable(decimal_max=38), _c_sort,
          expr_fn=_exprs_sort)
exec_rule(N.CpuLimitExec, _nested38, _c_limit)
exec_rule(N.CpuSampleExec, _nested38, _c_sample)
exec_rule(N.CpuUnionExec, _nested38, _c_union)
exec_rule(N.CpuGenerateExec, TypeSig.all_with_nested(), _c_generate,
          expr_fn=_exprs_generate)
exec_rule(N.CpuRangeExec, TypeSig.all_basic(), _c_range)
exec_rule(N.CpuExpandExec, TypeSig.all_basic(), _c_expand,
          expr_fn=_exprs_expand)
exec_rule(N.CpuShuffleExchangeExec, TypeSig.all_basic(), _c_exchange,
          tag_fn=_tag_exchange)
exec_rule(N.CpuWindowExec, TypeSig.all_basic(decimal_max=38), _c_window,
          tag_fn=_tag_window, expr_fn=_exprs_window)


def _c_cached(plan, children, conf):
    from ..datasources.cache import TpuInMemoryTableScanExec
    return TpuInMemoryTableScanExec(plan, children[0], conf)


def _do_register_cache():
    from ..datasources.cache import CpuCachedExec
    exec_rule(CpuCachedExec, TypeSig.all_with_nested(), _c_cached)


_register_cache_rule = _lazy_rule_group(
    "spark_rapids_tpu.datasources.cache", "CpuCachedExec", _do_register_cache)


def _c_map_in_pandas(plan, children, conf):
    from ..udf.pandas_execs import TpuMapInPandasExec
    return TpuMapInPandasExec(plan, children[0], conf)


def _c_flat_map_groups(plan, children, conf):
    from ..udf.pandas_execs import TpuFlatMapGroupsInPandasExec
    return TpuFlatMapGroupsInPandasExec(plan, children[0], conf)


def _c_agg_in_pandas(plan, children, conf):
    from ..udf.pandas_execs import TpuAggregateInPandasExec
    return TpuAggregateInPandasExec(plan, children[0], conf)


def _c_window_in_pandas(plan, children, conf):
    from ..udf.pandas_execs import TpuWindowInPandasExec
    return TpuWindowInPandasExec(plan, children[0], conf)


def _c_cogroups_in_pandas(plan, children, conf):
    from ..udf.pandas_execs import TpuCoGroupsInPandasExec
    return TpuCoGroupsInPandasExec(plan, children[0], children[1], conf)


def _do_register_pandas_execs():
    from ..udf.pandas_execs import (CpuAggregateInPandasExec,
                                    CpuCoGroupsInPandasExec,
                                    CpuFlatMapGroupsInPandasExec,
                                    CpuMapInPandasExec,
                                    CpuWindowInPandasExec)
    sig = TypeSig.all_basic()
    exec_rule(CpuMapInPandasExec, sig, _c_map_in_pandas,
              doc="Enable TPU execution of mapInPandas "
                  "(GpuMapInPandasExec analog).")
    exec_rule(CpuFlatMapGroupsInPandasExec, sig, _c_flat_map_groups,
              doc="Enable TPU execution of grouped applyInPandas "
                  "(GpuFlatMapGroupsInPandasExec analog).")
    exec_rule(CpuAggregateInPandasExec, sig, _c_agg_in_pandas,
              doc="Enable TPU execution of grouped pandas-UDF aggregation "
                  "(GpuAggregateInPandasExec analog).")
    exec_rule(CpuWindowInPandasExec, sig, _c_window_in_pandas,
              doc="Enable TPU execution of windowInPandas "
                  "(GpuWindowInPandasExecBase analog).")
    exec_rule(CpuCoGroupsInPandasExec, sig, _c_cogroups_in_pandas,
              doc="Enable TPU execution of cogrouped applyInPandas "
                  "(GpuFlatMapCoGroupsInPandasExec analog).")


_register_pandas_exec_rules = _lazy_rule_group(
    "spark_rapids_tpu.udf.pandas_execs", "CpuMapInPandasExec",
    _do_register_pandas_execs)


def _c_write_files(plan, children, conf):
    from ..io.writer import make_tpu_write_files
    return make_tpu_write_files(plan, children[0], conf)


def _do_register_write_files():
    from ..io.writer import CpuWriteFilesExec
    exec_rule(CpuWriteFilesExec, TypeSig.all_basic(), _c_write_files,
              doc="Enable TPU execution of file write commands "
                  "(GpuDataWritingCommandExec analog; parquet takes the "
                  "device encoder, other formats write at the host "
                  "boundary).")


_register_write_files_rule = _lazy_rule_group(
    "spark_rapids_tpu.io.writer", "CpuWriteFilesExec",
    _do_register_write_files)

_register_cache_rule()
_register_file_scan_rules()
_register_pandas_exec_rules()
_register_write_files_rule()


# ----------------------------------------------------------------------------
# apply
# ----------------------------------------------------------------------------


class Overrides:
    """Entry point (reference GpuOverrides.apply / applyOverrides)."""

    def __init__(self, conf: TpuConf):
        self.conf = conf
        self.explain_log: List[str] = []

    def apply(self, plan: N.PhysicalPlan):
        """Returns either a TpuExec (fully/partially converted, device root) or a
        CPU PhysicalPlan with converted subtrees bridged back to host."""
        if not self.conf.is_sql_enabled:
            return plan
        meta = self._tag_tree(plan)
        # one estimate/fingerprint memo spans the CBO pass and the convert
        # walk: per-node annotate + per-join _estimated_bytes collapse to
        # one _estimate_from frame (and one history probe) per node
        from .cbo import estimate_pass
        with estimate_pass():
            if self.conf.get("spark.rapids.sql.optimizer.enabled"):
                from .cbo import optimize
                optimize(meta, self.conf)
            result = self._convert_tagged(plan, meta)
        explain = self.conf.explain
        if explain != "NONE":
            lines = meta.explain_lines()
            if explain == "ALL" or any(l.lstrip().startswith("!")
                                       for l in lines):
                self.explain_log.extend(lines)
        if self.conf.get("spark.rapids.sql.mode") == "explainOnly":
            return plan
        # scan pushdown (plan/scan_pushdown.py): fold supported
        # filter/project/aggregate chains into the file scans they sit on.
        # Off (default) this is one conf read returning the tree untouched.
        from .scan_pushdown import apply_scan_pushdown
        result = apply_scan_pushdown(result, self.conf)
        from ..exec.base import TpuExec
        if isinstance(result, TpuExec):
            from ..exec.requirements import ensure_distribution
            result = ensure_distribution(result, self.conf)
            # sharded mesh execution (mesh/plan.py): shard scans across
            # mesh positions, resize safe hash-exchange boundaries to the
            # mesh, mark device-resident exchange->consumer seams. Off
            # (default) this is one conf read — zero mesh imports,
            # byte-identical plans.
            if self.conf.get("spark.rapids.tpu.mesh.enabled"):
                from ..mesh import mesh_enabled
                if mesh_enabled(self.conf):
                    from ..mesh.plan import apply_mesh_plan
                    result = apply_mesh_plan(result, self.conf,
                                             self.explain_log)
            # whole-stage fusion (plan/fusion.py): replace maximal
            # project/filter/broadcast-probe/partial-agg chains with
            # single-program fused stages. Runs after the mesh pass so
            # mesh-resident seams are visible as chain breaks. Off
            # (default) this is one conf read — zero fusion imports,
            # byte-identical plans.
            if self.conf.get("spark.rapids.tpu.fusion.enabled"):
                from .fusion import apply_fusion
                result = apply_fusion(result, self.conf)
        return result

    def _tag_tree(self, plan: N.PhysicalPlan) -> PlanMeta:
        """Phase 1 (wrapAndTagPlan analog): build the meta mirror tree and tag
        every node, WITHOUT converting — so cross-tree passes (CBO) can see
        the full tagging picture first."""
        _register_file_scan_rules()  # lazy retry if module import was cyclic
        _register_cache_rule()
        _register_pandas_exec_rules()
        _register_write_files_rule()
        rule = _EXEC_RULES.get(type(plan))
        meta = PlanMeta(plan, self.conf, rule)
        for c in plan.children:
            meta.child_metas.append(self._tag_tree(c))
        if rule is not None and rule.expr_fn is not None:
            rule.expr_fn(meta)
        if rule is not None and not isinstance(
                plan, (N.CpuProjectExec, N.CpuFilterExec,
                       N.CpuHashAggregateExec)):
            # a pandas UDF is a host black box, and needs_eager exprs
            # (data-dependent output fanout, e.g. str_to_map) cannot be
            # traced: the Project/Filter/HashAggregate execs run their
            # kernels eagerly when one is present (GpuArrowEvalPythonExec
            # analog); any other exec would trace them inside jit and crash
            from ..exec.basic import has_host_black_box
            for em in meta.expr_metas:
                if has_host_black_box([em.expr]):
                    meta.will_not_work(
                        "host-eager expressions (pandas UDFs, str_to_map) "
                        "are only supported in projections, filters, and "
                        "aggregations on TPU (project into a column first)")
                    break
        if rule is not None and not isinstance(
                plan, (N.CpuProjectExec, N.CpuFilterExec)):
            # side-effect expressions (raise_error/assert_true) append traced
            # error flags only Project/Filter kernels plumb back to the host
            for em in meta.expr_metas:
                if em.expr.collect(lambda x: x.has_side_effects):
                    meta.will_not_work(
                        "side-effect expressions are only supported in "
                        "projections and filters on TPU")
                    break
        if rule is not None and not isinstance(plan, N.CpuProjectExec):
            # monotonically_increasing_id needs the cumulative row offset
            # only the Project execs thread across their batch stream
            from ..expr.misc import MonotonicallyIncreasingID as _MIID
            for em in meta.expr_metas:
                if em.expr.collect(lambda x: isinstance(x, _MIID)):
                    meta.will_not_work(
                        "monotonically_increasing_id is only supported in "
                        "projections")
                    break
        meta.tag_for_device()
        if self.conf.is_test_enabled and not meta.can_run_on_device:
            raise AssertionError(
                "spark.rapids.sql.test.enabled: plan node fell back to CPU: "
                + "; ".join(meta.reasons))
        return meta

    def _convert_tagged(self, plan: N.PhysicalPlan, meta: PlanMeta):
        """Phase 2 (convertIfNeeded analog): convert per the (possibly
        CBO-adjusted) tags, bridging CPU<->TPU boundaries."""
        from ..exec.transitions import CpuFromTpuExec, TpuFromCpuExec
        from ..exec.base import TpuExec

        converted_children = [self._convert_tagged(c, cm) for c, cm in
                              zip(plan.children, meta.child_metas)]
        if meta.can_run_on_device:
            device_children = [
                c if isinstance(c, TpuExec) else TpuFromCpuExec(c, self.conf)
                for c in converted_children]
            result = meta.rule.convert_fn(plan, device_children, self.conf)
            # runtime statistics: pair the converted exec with its plan-
            # time identity (CBO estimate + stats fingerprint) so the
            # per-query observer can compute estimate-vs-actual q-error
            # and key actuals for the history store. One bool when off.
            from .. import stats
            stats.annotate(plan, result, self.conf)
            return result
        # stay on CPU; bridge any device children back to host
        host_children = [
            c if not isinstance(c, TpuExec) else CpuFromTpuExec(c)
            for c in converted_children]
        plan.children = host_children
        return plan

    def explain_string(self) -> str:
        return "\n".join(self.explain_log)
