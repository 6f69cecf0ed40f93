"""Expression IR core.

TPU counterpart of the reference's expression layer (`GpuExpression.columnarEval`;
expression classes across `org/apache/spark/sql/rapids/*.scala`, ~203 ops registered at
`GpuOverrides.scala:866-3475`). Design difference from the reference: every expression's
semantics are implemented ONCE as an array-namespace-generic kernel (`xp` = numpy on the
CPU engine, jax.numpy under jit on the TPU engine). The CPU engine is the differential
peer (the role CPU Spark plays in the reference's test harness) and shares no *backend*
with the TPU path — only the semantic spec — so the harness validates padding/validity/
XLA-lowering behavior.

Evaluation operates on `Vec` (dtype + data/validity[/lengths] arrays of either backend);
the exec layer converts `Column` <-> `Vec` zero-copy.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import decimal as _decimal
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .. import types as T
from ..columnar.column import Column

__all__ = ["Vec", "EvalContext", "Expression", "LeafExpression", "Literal",
           "AttributeReference", "BoundReference", "Alias", "bind_references",
           "all_valid", "and_validity", "require_flat_strings"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Vec:
    """Backend-generic column value: arrays are np.ndarray or jnp tracers.
    Registered as a pytree so jitted kernels can take/return Vecs directly.

    Nested layout (one design shared with Column — see columnar/column.py):
      * array<elem>:  data = int32 per-row element count, lengths = None,
        children = (elem Vec,) whose arrays have leading dims [cap, K]
        (K = fanout bucket) — the fixed-fanout analog of the string
        byte-matrix;
      * struct<...>:  data = bool placeholder (mirror of validity),
        children = one Vec per field with leading dim [cap].
    Every child array's leading dim equals the parent capacity, so row-wise
    gather/slice/compact apply uniformly down the tree."""
    dtype: T.DataType
    data: Any
    validity: Any
    lengths: Any = None
    children: Any = None  # tuple of child Vecs for nested types
    # long-string layout (columnar/strings.py): (blob, tail_start). The
    # blob is row-UNALIGNED: row-wise structural ops gather tail_start and
    # pass the blob through; byte-inspecting kernels must go through
    # require_flat_strings (per-op fallback).
    overflow: Any = None

    def tree_flatten(self):
        leaves = [self.data, self.validity]
        has_len = self.lengths is not None
        if has_len:
            leaves.append(self.lengths)
        kids = tuple(self.children) if self.children else ()
        leaves.extend(kids)
        has_ovf = self.overflow is not None
        if has_ovf:
            leaves.extend(self.overflow)
        return tuple(leaves), (self.dtype, has_len, len(kids), has_ovf)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        dtype, has_len, nk, has_ovf = aux
        i = 3 if has_len else 2
        lengths = leaves[2] if has_len else None
        kids = tuple(leaves[i:i + nk]) if nk else None
        ovf = (leaves[i + nk], leaves[i + nk + 1]) if has_ovf else None
        return cls(dtype, leaves[0], leaves[1], lengths, kids, ovf)

    @property
    def is_string(self) -> bool:
        return isinstance(self.dtype, T.StringType)

    @property
    def is_nested(self) -> bool:
        return self.children is not None

    @staticmethod
    def from_column(col: Column) -> "Vec":
        kids = None if col.children is None else tuple(
            Vec.from_column(c) for c in col.children)
        return Vec(col.dtype, col.data, col.validity, col.lengths, kids,
                   col.overflow)

    def to_column(self) -> Column:
        import jax.numpy as jnp
        kids = None if self.children is None else tuple(
            c.to_column() for c in self.children)
        return Column(self.dtype, jnp.asarray(self.data),
                      jnp.asarray(self.validity),
                      None if self.lengths is None else jnp.asarray(self.lengths),
                      kids,
                      None if self.overflow is None else
                      (jnp.asarray(self.overflow[0]),
                       jnp.asarray(self.overflow[1])))

    # -- uniform row-wise structural ops (recurse through children) ----------
    def gather(self, xp, idx) -> "Vec":
        """Gather rows by index along axis 0, down the tree. A long-string
        blob is shared/row-unaligned: the row move gathers only the
        tail_start pointers — O(1) per row regardless of string size."""
        return Vec(self.dtype, self.data[idx], self.validity[idx],
                   None if self.lengths is None else self.lengths[idx],
                   None if self.children is None else tuple(
                       c.gather(xp, idx) for c in self.children),
                   None if self.overflow is None else
                   (self.overflow[0], self.overflow[1][idx]))

    def slice_rows(self, lo, hi) -> "Vec":
        """Slice rows [lo, hi) along axis 0, down the tree."""
        return Vec(self.dtype, self.data[lo:hi], self.validity[lo:hi],
                   None if self.lengths is None else self.lengths[lo:hi],
                   None if self.children is None else tuple(
                       c.slice_rows(lo, hi) for c in self.children),
                   None if self.overflow is None else
                   (self.overflow[0], self.overflow[1][lo:hi]))


def vec_map_arrays(v: Vec, fn, blob_fn=None) -> Vec:
    """Apply fn to every ROW-ALIGNED array buffer of a Vec, recursing through
    children. fn must preserve the invariant that those buffers share the
    leading dim. A long-string overflow blob is NOT row-aligned: it gets
    blob_fn (default: passed through untouched); callers doing
    backend/device conversion must supply blob_fn explicitly."""
    return Vec(v.dtype, fn(v.data), fn(v.validity),
               None if v.lengths is None else fn(v.lengths),
               None if v.children is None else tuple(
                   vec_map_arrays(c, fn, blob_fn) for c in v.children),
               None if v.overflow is None else
               ((blob_fn or (lambda a: a))(v.overflow[0]),
                fn(v.overflow[1])))


def require_flat_strings(v: Vec, op: str) -> Vec:
    """Per-op gate for kernels that must see ALL string bytes: a long-string
    column (overflow layout) cannot feed a byte-matrix kernel. Device
    engines raise CpuFallbackRequired (the stage re-runs on the host, where
    exact-length matrices exist) — the reference's per-op fallback
    discipline applied to the strings layout."""
    if v.overflow is None:
        return v
    from ..errors import CpuFallbackRequired
    raise CpuFallbackRequired(
        f"{op} needs full string bytes; column uses the long-string "
        "overflow layout")


def zero_vec(xp, dt: T.DataType, shape: tuple) -> Vec:
    """All-null Vec of any (possibly nested) dtype with the given leading
    shape — (cap,) at top level, (cap, K) inside an array, etc. The ONE
    definition of the empty/null column layout (minimal string width 8,
    minimal array fanout 8)."""
    validity = xp.zeros(shape, dtype=bool)
    if isinstance(dt, T.StringType):
        return Vec(dt, xp.zeros(shape + (8,), dtype=xp.uint8), validity,
                   xp.zeros(shape, dtype=xp.int32))
    if isinstance(dt, T.ArrayType):
        return Vec(dt, xp.zeros(shape, dtype=xp.int32), validity, None,
                   (zero_vec(xp, dt.element_type, shape + (8,)),))
    if isinstance(dt, T.MapType):
        # map<k,v> rides the array layout: per-row entry count + parallel
        # key/value children at [*, K] (structurally array<struct<k,v>>,
        # the same shape Arrow and Spark give maps)
        return Vec(dt, xp.zeros(shape, dtype=xp.int32), validity, None,
                   (zero_vec(xp, dt.key_type, shape + (8,)),
                    zero_vec(xp, dt.value_type, shape + (8,))))
    if isinstance(dt, T.StructType):
        return Vec(dt, xp.zeros(shape, dtype=bool), validity, None,
                   tuple(zero_vec(xp, f.data_type, shape) for f in dt.fields))
    if isinstance(dt, T.DecimalType) and \
            dt.precision > T.DecimalType.MAX_LONG_DIGITS:
        return Vec(dt, xp.zeros(shape + (2,), dtype=np.int64), validity)
    return Vec(dt, xp.zeros(shape, dtype=dt.np_dtype or np.int32), validity)


@dataclasses.dataclass
class EvalContext:
    """xp: the array namespace (numpy | jax.numpy). ansi: ANSI SQL mode.
    row_mask: bool[n] live-row mask (None on the CPU engine where arrays are exact
    length). Expressions needing whole-column reasoning (aggs) use row_mask.
    errors: under ANSI on device, a list of (traced bool, message) pairs the
    enclosing kernel returns so the exec can raise host-side (XLA can't raise
    mid-kernel; the CPU engine raises eagerly instead)."""
    xp: Any
    ansi: bool = False
    row_mask: Any = None
    conf: Any = None
    errors: Any = None
    # per-partition identity for SparkPartitionID / MonotonicallyIncreasingID:
    # the executing exec sets these (Project threads a cumulative live-row
    # offset, possibly a traced scalar, across its batch stream)
    partition_id: Any = 0
    partition_row_offset: Any = 0
    # exact decimal divisions lowered while this context's kernel was traced
    # (the projection's `numDecimalDivides`)
    decimal_divides: int = 0

    @property
    def is_device(self) -> bool:
        return self.xp is not np


def ansi_raise(ctx: EvalContext, flag, message: str) -> None:
    """Report an ANSI runtime error condition for the rows where `flag` is
    true. Device: append a reduced traced flag to ctx.errors (the exec raises
    after the kernel). Host (CPU oracle): raise immediately, like Spark."""
    if ctx.row_mask is not None:
        flag = flag & ctx.row_mask
    if ctx.is_device:
        if ctx.errors is not None:
            ctx.errors.append((ctx.xp.any(flag), message))
    elif np.any(flag):
        from ..errors import AnsiViolation
        raise AnsiViolation(message)


def all_valid(xp, n_like) -> Any:
    return xp.ones(n_like.shape[0], dtype=bool)


def and_validity(xp, *vs) -> Any:
    out = None
    for v in vs:
        if v is None:
            continue
        out = v if out is None else (out & v)
    return out


class Expression:
    """Base expression node. Subclasses define `children`, `data_type`, and
    `_compute(ctx, *child_vecs) -> Vec`."""

    def __init__(self, children: Sequence["Expression"] = ()):
        self.children: List[Expression] = list(children)

    # --- static properties ----------------------------------------------------
    @property
    def data_type(self) -> T.DataType:
        raise NotImplementedError

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children)

    @property
    def name(self) -> str:
        return type(self).__name__

    # is this expression deterministic (affects planning, like the reference)
    deterministic = True
    # does this expression have side effects under ANSI (div-by-zero raise etc.)
    has_side_effects = False
    # can this expression's kernel consume the long-string overflow layout
    # (head+blob, columnar/strings.py)? Default False: byte-matrix kernels
    # would silently truncate at the head width, so eval() gates them into
    # the per-op fallback. Whitelist kernels that only read lengths/validity.
    accepts_long_strings = False

    # --- evaluation -----------------------------------------------------------
    def eval(self, ctx: EvalContext, batch_vecs: Sequence[Vec]) -> Vec:
        child_results = [c.eval(ctx, batch_vecs) for c in self.children]
        if not self.accepts_long_strings:
            for v in child_results:
                if isinstance(v, Vec) and v.overflow is not None:
                    require_flat_strings(v, self.name)
        return self._compute(ctx, *child_results)

    def _compute(self, ctx: EvalContext, *children: Vec) -> Vec:
        raise NotImplementedError(type(self).__name__)

    # --- tree utilities -------------------------------------------------------
    def transform_up(self, fn) -> "Expression":
        new_children = [c.transform_up(fn) for c in self.children]
        unchanged = len(new_children) == len(self.children) and \
            all(a is b for a, b in zip(new_children, self.children))
        node = self if unchanged else self.with_children(new_children)
        return fn(node)

    def with_children(self, children: Sequence["Expression"]) -> "Expression":
        import copy
        node = copy.copy(self)
        node.children = list(children)
        return node

    def collect(self, pred) -> List["Expression"]:
        out = [self] if pred(self) else []
        for c in self.children:
            out.extend(c.collect(pred))
        return out

    def __repr__(self):
        if not self.children:
            return self.name
        return f"{self.name}({', '.join(map(repr, self.children))})"

    # --- operator sugar for the DataFrame frontend ---------------------------
    @staticmethod
    def _wrap(v) -> "Expression":
        return v if isinstance(v, Expression) else Literal(v)

    def __add__(self, o):
        from .arithmetic import Add
        return Add(self, self._wrap(o))

    def __sub__(self, o):
        from .arithmetic import Subtract
        return Subtract(self, self._wrap(o))

    def __mul__(self, o):
        from .arithmetic import Multiply
        return Multiply(self, self._wrap(o))

    def __truediv__(self, o):
        from .arithmetic import Divide
        return Divide(self, self._wrap(o))

    def __mod__(self, o):
        from .arithmetic import Remainder
        return Remainder(self, self._wrap(o))

    def __neg__(self):
        from .arithmetic import UnaryMinus
        return UnaryMinus(self)

    def __eq__(self, o):  # type: ignore[override]
        from .predicates import EqualTo
        return EqualTo(self, self._wrap(o))

    def __ne__(self, o):  # type: ignore[override]
        from .predicates import EqualTo, Not
        return Not(EqualTo(self, self._wrap(o)))

    def __lt__(self, o):
        from .predicates import LessThan
        return LessThan(self, self._wrap(o))

    def __le__(self, o):
        from .predicates import LessThanOrEqual
        return LessThanOrEqual(self, self._wrap(o))

    def __gt__(self, o):
        from .predicates import GreaterThan
        return GreaterThan(self, self._wrap(o))

    def __ge__(self, o):
        from .predicates import GreaterThanOrEqual
        return GreaterThanOrEqual(self, self._wrap(o))

    def __and__(self, o):
        from .predicates import And
        return And(self, self._wrap(o))

    def __or__(self, o):
        from .predicates import Or
        return Or(self, self._wrap(o))

    def __invert__(self):
        from .predicates import Not
        return Not(self)

    # literal-on-the-left forms (1 - col, 2 * col, ...)
    def __radd__(self, o):
        return self._wrap(o).__add__(self)

    def __rsub__(self, o):
        return self._wrap(o).__sub__(self)

    def __rmul__(self, o):
        return self._wrap(o).__mul__(self)

    def __rtruediv__(self, o):
        return self._wrap(o).__truediv__(self)

    def __rmod__(self, o):
        return self._wrap(o).__mod__(self)

    def __rand__(self, o):
        return self._wrap(o).__and__(self)

    def __ror__(self, o):
        return self._wrap(o).__or__(self)

    def __bool__(self):
        # `==` returns an Expression, so `and`/`or`/`in`/`if` over expressions
        # would silently drop conditions; fail loudly (PySpark Column behavior)
        raise ValueError(
            "Cannot convert an Expression to a bool. Use '&' for AND, '|' for "
            "OR, '~' for NOT when building conditions.")

    def __hash__(self):
        return id(self)

    def alias(self, name: str) -> "Expression":
        return Alias(self, name)

    def cast(self, dt) -> "Expression":
        from .cast import Cast
        return Cast(self, dt)

    def is_null(self):
        from .nullexprs import IsNull
        return IsNull(self)

    def is_not_null(self):
        from .nullexprs import IsNotNull
        return IsNotNull(self)


class LeafExpression(Expression):
    def __init__(self):
        super().__init__(())


class Literal(LeafExpression):
    def __init__(self, value, dtype: Optional[T.DataType] = None):
        super().__init__()
        self.value = value
        if dtype is None:
            dtype = _infer_literal_type(value)
        self._dtype = dtype

    @property
    def data_type(self) -> T.DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self.value is None

    def _compute(self, ctx: EvalContext, *children: Vec) -> Vec:
        xp = ctx.xp
        n = ctx.row_mask.shape[0] if ctx.row_mask is not None else 1
        dt = self._dtype
        if self.value is None:
            if isinstance(dt, T.StringType):
                return Vec(dt, xp.zeros((n, 8), dtype=xp.uint8),
                           xp.zeros(n, dtype=bool), xp.zeros(n, dtype=xp.int32))
            if isinstance(dt, T.DecimalType) and \
                    dt.precision > T.DecimalType.MAX_LONG_DIGITS:
                return Vec(dt, xp.zeros((n, 2), dtype=np.int64),
                           xp.zeros(n, dtype=bool))
            npdt = dt.np_dtype or np.dtype(np.int32)
            return Vec(dt, xp.zeros(n, dtype=npdt), xp.zeros(n, dtype=bool))
        if isinstance(dt, T.StringType):
            b = self.value.encode("utf-8")
            from ..columnar.padding import width_bucket
            w = width_bucket(max(len(b), 1))
            row = np.zeros(w, dtype=np.uint8)
            row[:len(b)] = np.frombuffer(b, dtype=np.uint8)
            data = xp.broadcast_to(xp.asarray(row), (n, w))
            return Vec(dt, data, xp.ones(n, dtype=bool),
                       xp.full((n,), len(b), dtype=xp.int32))
        v = self.value
        if isinstance(dt, T.TimestampType) and isinstance(v, _dt.datetime):
            v = _epoch_micros(v)
        elif isinstance(dt, T.DateType) and isinstance(v, _dt.date):
            v = v.toordinal() - _EPOCH_ORDINAL
        if isinstance(dt, T.DecimalType):
            import decimal as _d
            if isinstance(v, _d.Decimal):
                from .decimal128 import unscaled_int
                v = unscaled_int(v, dt.scale)
            if dt.precision > T.DecimalType.MAX_LONG_DIGITS:
                from .decimal128 import split_int
                hi, lo = split_int(int(v))
                row = np.array([hi, lo], dtype=np.int64)
                data = xp.broadcast_to(xp.asarray(row), (n, 2))
                return Vec(dt, data, xp.ones(n, dtype=bool))
        data = xp.full((n,), v, dtype=dt.np_dtype)
        return Vec(dt, data, xp.ones(n, dtype=bool))

    def __repr__(self):
        # an explicit dtype beyond what the value infers is part of the
        # literal's identity: lit(1) as INT and as LONG trace different
        # programs, so repr-derived cache keys must not alias them
        try:
            inferred = self._dtype == _infer_literal_type(self.value)
        except Exception:
            inferred = False
        if inferred:
            return f"lit({self.value!r})"
        return f"lit({self.value!r}:{self._dtype.simple_string()})"


def _infer_literal_type(v) -> T.DataType:
    if v is None:
        return T.NULL
    if isinstance(v, bool):
        return T.BOOLEAN
    if isinstance(v, int):
        return T.INT if -2**31 <= v < 2**31 else T.LONG
    if isinstance(v, float):
        return T.DOUBLE
    if isinstance(v, str):
        return T.STRING
    if isinstance(v, np.generic):
        return T.from_arrow(__import__("pyarrow").array([v]).type)
    if isinstance(v, _dt.datetime):  # before date: a datetime is a date too
        return T.TIMESTAMP
    if isinstance(v, _dt.date):
        return T.DATE
    if isinstance(v, _decimal.Decimal) and v.is_finite():
        # Spark's Decimal(BigDecimal) + DecimalType.fromDecimal: a negative
        # scale becomes 0, and the precision is never under the scale
        _, digits, exp = v.as_tuple()
        scale = max(-exp, 0)
        return T.DecimalType(max(len(digits) + max(exp, 0), scale), scale)
    raise TypeError(f"cannot infer literal type for {v!r}")


_EPOCH_ORDINAL = _dt.date(1970, 1, 1).toordinal()


def _epoch_micros(v: "_dt.datetime") -> int:
    """Microseconds since the epoch; a naive datetime is UTC, the engine's
    session time zone."""
    if v.tzinfo is None:
        v = v.replace(tzinfo=_dt.timezone.utc)
    delta = v - _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
    return (delta.days * 86_400 + delta.seconds) * 1_000_000 \
        + delta.microseconds


class AttributeReference(LeafExpression):
    """Named column reference (unresolved; bind_references resolves to ordinal)."""

    def __init__(self, name: str, dtype: Optional[T.DataType] = None,
                 nullable: bool = True):
        super().__init__()
        self._name = name
        self._dtype = dtype
        self._nullable = nullable

    @property
    def data_type(self) -> T.DataType:
        if self._dtype is None:
            raise ValueError(f"unresolved attribute {self._name}")
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def col_name(self) -> str:
        return self._name

    def _compute(self, ctx, *children):
        raise RuntimeError(f"unbound attribute {self._name}; call bind_references")

    def __repr__(self):
        return f"col({self._name})"


class BoundReference(LeafExpression):
    def __init__(self, ordinal: int, dtype: T.DataType, nullable: bool = True):
        super().__init__()
        self.ordinal = ordinal
        self._dtype = dtype
        self._nullable = nullable

    @property
    def data_type(self) -> T.DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    def eval(self, ctx: EvalContext, batch_vecs: Sequence[Vec]) -> Vec:
        return batch_vecs[self.ordinal]

    def __repr__(self):
        return f"input[{self.ordinal}]"


class Alias(Expression):
    def __init__(self, child: Expression, alias: str):
        super().__init__([child])
        self.alias = alias

    @property
    def data_type(self):
        return self.children[0].data_type

    @property
    def nullable(self):
        return self.children[0].nullable

    def eval(self, ctx, batch_vecs):
        return self.children[0].eval(ctx, batch_vecs)

    def __repr__(self):
        return f"{self.children[0]!r} AS {self.alias}"


def bind_references(expr: Expression, schema) -> Expression:
    """Resolve AttributeReference -> BoundReference against a Schema."""

    def fn(node):
        if isinstance(node, AttributeReference):
            i = schema.index_of(node.col_name)
            return BoundReference(i, schema.types[i], node._nullable)
        return node

    return expr.transform_up(fn)


def output_name(expr: Expression, default: str) -> str:
    if isinstance(expr, Alias):
        return expr.alias
    if isinstance(expr, AttributeReference):
        return expr.col_name
    return default
