"""Arithmetic expressions with Spark/Java semantics.

Reference: `org/apache/spark/sql/rapids/arithmetic.scala` (GpuAdd/GpuSubtract/GpuMultiply/
GpuDivide/GpuIntegralDivide/GpuRemainder/GpuPmod/GpuUnaryMinus/GpuAbs). Semantics notes:
  * integral +,-,* wrap (Java two's complement) in non-ANSI mode;
  * Divide yields DOUBLE (inputs implicitly cast) unless it is decimal arithmetic,
    which is exact in Spark's decimal result type; x/0 -> null (non-ANSI);
  * IntegralDivide / Remainder / Pmod truncate toward zero (Java), unlike numpy's
    floor semantics — implemented explicitly;
  * ANSI overflow/zero-division raising is implemented on the CPU engine and marked
    has_side_effects for planning; the TPU engine tags ANSI arithmetic unsupported in
    this round (planner falls back), matching the reference's per-op tagging approach.
"""

from __future__ import annotations

import numpy as np

from .. import types as T
from .base import Expression, EvalContext, Vec, and_validity, ansi_raise


def _overflow_msg(dt: T.DataType) -> str:
    if isinstance(dt, T.DecimalType):
        return f"[ARITHMETIC_OVERFLOW] {dt.simple_string()} overflow"
    name = {8: "tinyint", 16: "smallint"}.get(
        (dt.np_dtype.itemsize * 8) if dt.np_dtype else 64)
    if isinstance(dt, T.LongType):
        return "[ARITHMETIC_OVERFLOW] long overflow"
    if isinstance(dt, T.IntegerType):
        return "[ARITHMETIC_OVERFLOW] integer overflow"
    return f"[ARITHMETIC_OVERFLOW] {name or dt.simple_string()} overflow"


_DIV_ZERO = "[DIVIDE_BY_ZERO] Division by zero"

__all__ = ["Add", "Subtract", "Multiply", "Divide", "IntegralDivide", "Remainder",
           "Pmod", "UnaryMinus", "Abs", "cast_data", "promote_args"]


def cast_data(xp, vec: Vec, dt: T.DataType) -> Vec:
    """Backend-generic numeric dtype change (no semantic checks — used for implicit
    widening only; the full checked matrix lives in cast.py)."""
    if vec.dtype == dt:
        return vec
    return Vec(dt, vec.data.astype(dt.np_dtype), vec.validity)


def promote_args(xp, left: Vec, right: Vec):
    dt = T.numeric_promote(left.dtype, right.dtype)
    return cast_data(xp, left, dt), cast_data(xp, right, dt), dt


class BinaryExpression(Expression):
    def __init__(self, left: Expression, right: Expression):
        super().__init__([left, right])

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]


_INTEGRAL_AS_DECIMAL = {T.ByteType: 3, T.ShortType: 5, T.IntegerType: 10,
                        T.LongType: 20}


def _as_decimal_type(e: Expression):
    """The decimal type Spark's DecimalPrecision gives an operand of decimal
    arithmetic: a decimal its own, an integral column decimal(3|5|10|20, 0),
    an integral literal just its digits; None for anything else."""
    dt = e.data_type
    if isinstance(dt, T.DecimalType):
        return dt
    if not T.is_integral(dt):
        return None
    from .base import Literal
    if isinstance(e, Literal) and e.value is not None:
        return T.DecimalType(len(str(abs(int(e.value)))), 0)
    return T.DecimalType(_INTEGRAL_AS_DECIMAL[type(dt)], 0)


def _decimal_operands(left: Expression, right: Expression):
    """(left, right) as decimal types where `left op right` is decimal
    arithmetic (a decimal on either side and a decimal or an integral on
    the other), else None."""
    if not (isinstance(left.data_type, T.DecimalType) or
            isinstance(right.data_type, T.DecimalType)):
        return None
    pair = _as_decimal_type(left), _as_decimal_type(right)
    return None if None in pair else pair


class BinaryArithmetic(BinaryExpression):
    def _decimal_types(self):
        """(left, right) as decimal types when this is decimal arithmetic
        with an exact kernel (+, -, * with a decimal on either side and a
        decimal or an integral on the other), else None."""
        if type(self) not in (Add, Subtract, Multiply):
            return None
        return _decimal_operands(self.left, self.right)

    @property
    def data_type(self) -> T.DataType:
        pair = self._decimal_types()
        if pair is None:
            return T.numeric_promote(self.left.data_type,
                                     self.right.data_type)
        from .decimal128 import add_result_type, adjust_precision_scale
        if isinstance(self, Multiply):
            # Spark: precision p1 + p2 + 1, scale s1 + s2, then bounded
            return adjust_precision_scale(
                pair[0].precision + pair[1].precision + 1,
                pair[0].scale + pair[1].scale)
        return add_result_type(*pair)

    def _decimal_addsub(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        """Decimal +/- computed EXACTLY in 256-bit limbs (the JVM uses
        unbounded BigDecimal intermediates): rescale both operands to the
        max input scale, add (negating the rhs for subtract), HALF_UP
        round down to the adjusted result scale, then overflow -> null
        (non-ANSI) or raise (ANSI). The wide intermediate is what makes
        the rescale exact — a 128-bit rescale can wrap back into bounds
        and return silently wrong values."""
        from .decimal128 import (add128, in_bounds, is_dec128, neg128,
                                 pack_limbs, rescale_up, wide_add,
                                 wide_div_pow10_half_up, wide_from128,
                                 wide_mul_pow10, wide_neg, wide_to128,
                                 widen_operand)
        xp = ctx.xp
        out_t = self.data_type
        s_max = max(l.dtype.scale, r.dtype.scale)
        k_l = s_max - l.dtype.scale
        k_r = s_max - r.dtype.scale
        lhi, llo = widen_operand(xp, l)
        rhi, rlo = widen_operand(xp, r)
        if out_t.scale == s_max and l.dtype.precision + k_l <= 38 \
                and r.dtype.precision + k_r <= 38:
            # 128-bit fast path (the common case): rescaled operands stay
            # < 10^38 so the pow10 multiply cannot wrap, and a SUM that
            # wraps 2^127 lands at magnitude >= 2^128 - 2*10^38 > 10^38,
            # which in_bounds rejects — exact without the 8-limb chain
            lhi, llo = rescale_up(xp, lhi, llo, k_l)
            rhi, rlo = rescale_up(xp, rhi, rlo, k_r)
            if isinstance(self, Subtract):
                rhi, rlo = neg128(xp, rhi, rlo)
            hi, lo = add128(xp, lhi, llo, rhi, rlo)
            ok = in_bounds(xp, hi, lo, out_t.precision)
        else:
            wl = wide_mul_pow10(xp, wide_from128(xp, lhi, llo), k_l)
            wr = wide_mul_pow10(xp, wide_from128(xp, rhi, rlo), k_r)
            if isinstance(self, Subtract):
                wr = wide_neg(xp, wr)
            ws = wide_add(xp, wl, wr)
            ws = wide_div_pow10_half_up(xp, ws, s_max - out_t.scale)
            hi, lo, fits = wide_to128(xp, ws)
            ok = fits & in_bounds(xp, hi, lo, out_t.precision)
        validity = and_validity(xp, l.validity, r.validity)
        if ctx.ansi:
            ansi_raise(ctx, ~ok & validity, _overflow_msg(out_t))
        if is_dec128(out_t):
            return Vec(out_t, pack_limbs(xp, hi, lo), validity & ok)
        return Vec(out_t, lo.astype(np.int64), validity & ok)

    def _decimal_mul(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        """Decimal x decimal, EXACT: the product of the magnitudes in 32-bit
        limbs (`wide_mul`), HALF_UP rounded where Spark's bound lowered the
        scale, out of range -> null (non-ANSI) or raise (ANSI). How wide the
        arithmetic is follows from the operand TYPES at trace time: a result
        of <= 18 digits is one int64 multiply; an ideal precision <= 38
        cannot overflow and keeps 128 bits of the limbs; past that the full
        256-bit product is rounded and bounds-checked."""
        from .decimal128 import (_join32, _split32, abs128, in_bounds,
                                 limbs_for, neg128, pack_limbs,
                                 wide_div_pow10_half_up, wide_mul,
                                 wide_to128, widen_operand)
        xp = ctx.xp
        out_t = self.data_type
        lt, rt = l.dtype, r.dtype
        validity = and_validity(xp, l.validity, r.validity)
        ideal_p = lt.precision + rt.precision + 1
        if ideal_p <= T.DecimalType.MAX_LONG_DIGITS:
            return Vec(out_t, l.data.astype(np.int64)
                       * r.data.astype(np.int64), validity)
        lhi, llo, lneg = abs128(xp, *widen_operand(xp, l))
        rhi, rlo, rneg = abs128(xp, *widen_operand(xp, r))
        prod = wide_mul(xp, _split32(xp, lhi, llo)[:limbs_for(lt.precision)],
                        _split32(xp, rhi, rlo)[:limbs_for(rt.precision)])
        prod += [xp.zeros_like(prod[0])] * (8 - len(prod))
        if ideal_p <= T.DecimalType.MAX_PRECISION:
            hi, lo = _join32(xp, *prod[:4])
        else:
            # a non-negative 256-bit value: the signed helpers round and
            # narrow the magnitude itself
            prod = wide_div_pow10_half_up(
                xp, prod, lt.scale + rt.scale - out_t.scale)
            hi, lo, fits = wide_to128(xp, prod)
            ok = fits & in_bounds(xp, hi, lo, out_t.precision)
            if ctx.ansi:
                ansi_raise(ctx, ~ok & validity, _overflow_msg(out_t))
            validity = validity & ok
        nhi, nlo = neg128(xp, hi, lo)
        neg = lneg != rneg
        return Vec(out_t, pack_limbs(xp, xp.where(neg, nhi, hi),
                                     xp.where(neg, nlo, lo)), validity)

    def _compute(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        pair = self._decimal_types()
        if pair is not None:
            # an integral operand IS its decimal(p, 0): same unscaled value
            l = Vec(pair[0], l.data, l.validity)
            r = Vec(pair[1], r.data, r.validity)
            if isinstance(self, Multiply):
                return self._decimal_mul(ctx, l, r)
            return self._decimal_addsub(ctx, l, r)
        l, r, dt = promote_args(ctx.xp, l, r)
        validity = and_validity(ctx.xp, l.validity, r.validity)
        data = self._op(ctx.xp, l.data, r.data)
        data = data.astype(dt.np_dtype, copy=False)
        if ctx.ansi and T.is_integral(dt):
            bad = self._overflowed(ctx.xp, l.data, r.data, data) & validity
            ansi_raise(ctx, bad, _overflow_msg(dt))
        return Vec(dt, data, validity)

    def _op(self, xp, a, b):
        raise NotImplementedError

    def _overflowed(self, xp, a, b, res):
        raise NotImplementedError


class Add(BinaryArithmetic):
    def _op(self, xp, a, b):
        return a + b

    def _overflowed(self, xp, a, b, res):
        # sign trick: overflow iff operands share a sign the result lost
        return ((a ^ res) & (b ^ res)) < 0


class Subtract(BinaryArithmetic):
    def _op(self, xp, a, b):
        return a - b

    def _overflowed(self, xp, a, b, res):
        return ((a ^ b) & (a ^ res)) < 0


class Multiply(BinaryArithmetic):
    def _op(self, xp, a, b):
        return a * b

    def _overflowed(self, xp, a, b, res):
        # recover a from the wrapped product by truncating division; any
        # mismatch means the true product left the type's range
        mn = np.iinfo(res.dtype).min
        q = _trunc_div(xp, res, xp.where(b == 0, 1, b))
        return ((b != 0) & (q != a)) | ((a == mn) & (b == -1)) | \
            ((b == mn) & (a == -1))


class Divide(BinaryExpression):
    """Spark Divide. Decimal / decimal (an integral beside a decimal is its
    decimal(p, 0)) is exact, in Spark's result type (`DecimalPrecision`:
    decimal(21,2) / decimal(27,2) is decimal(38,17)) and with Spark's two
    roundings; x/0 -> null (non-ANSI) or DIVIDE_BY_ZERO (ANSI), a quotient
    out of the result type -> null or ARITHMETIC_OVERFLOW. Every other pair
    of types yields DOUBLE (inputs implicitly cast), as before."""

    @property
    def data_type(self):
        pair = _decimal_operands(self.left, self.right)
        if pair is None:
            return T.DOUBLE
        from .decimal128 import divide_result_type
        return divide_result_type(*pair)

    @property
    def nullable(self):
        return True

    def _compute(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        pair = _decimal_operands(self.left, self.right)
        if pair is not None:
            # an integral operand IS its decimal(p, 0): same unscaled value
            l = Vec(pair[0], l.data, l.validity)
            r = Vec(pair[1], r.data, r.validity)
            if ctx.xp is np:
                return self._decimal_div_host(ctx, l, r)
            return self._decimal_div(ctx, l, r)
        xp = ctx.xp
        a, b = (self._as_double(xp, v) for v in (l, r))
        zero = b == 0.0
        both = and_validity(xp, l.validity, r.validity)
        if ctx.ansi:
            ansi_raise(ctx, zero & both, _DIV_ZERO)
        validity = both & ~zero
        if ctx.xp is np:
            with np.errstate(divide="ignore", invalid="ignore"):
                data = np.where(zero, 0.0, a / b)
        else:
            data = xp.where(zero, 0.0, a / xp.where(zero, 1.0, b))
        return Vec(T.DOUBLE, data, validity)

    @staticmethod
    def _as_double(xp, v: Vec):
        """The operand as float64: a decimal beside a float is cast by
        value, as Spark casts it (its unscaled integer is not its value)."""
        if isinstance(v.dtype, T.DecimalType):
            from .cast import _decimal_cast
            return _decimal_cast(xp, v, T.DOUBLE).data
        return v.data.astype(np.float64)

    def _finish_decimal(self, ctx: EvalContext, out_t, hi, lo, ok, zero,
                        both) -> Vec:
        from .decimal128 import is_dec128, pack_limbs
        if ctx.ansi:
            ansi_raise(ctx, zero & both, _DIV_ZERO)
            ansi_raise(ctx, ~ok & ~zero & both, _overflow_msg(out_t))
        validity = both & ~zero & ok
        if is_dec128(out_t):
            return Vec(out_t, pack_limbs(ctx.xp, hi, lo), validity)
        return Vec(out_t, lo.astype(np.int64), validity)

    def _decimal_div(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        """The device kernel: `decimal128.div_half_up` on the limbs, the
        dividend scaled by 10^(s - s1 + s2) so that the quotient's unit is
        the result scale's (the exponent is never negative: Spark's bounded
        scale keeps at least 38 - p1 >= 0 of it)."""
        from .decimal128 import div_half_up, in_bounds, widen_operand
        xp = ctx.xp
        out_t = self.data_type
        k = out_t.scale - l.dtype.scale + r.dtype.scale
        rhi, rlo = widen_operand(xp, r)
        zero = (rhi == 0) & (rlo == 0)
        hi, lo, fits = div_half_up(
            xp, *widen_operand(xp, l), l.dtype.precision, k,
            rhi, xp.where(zero, np.int64(1), rlo))
        ok = fits & in_bounds(xp, hi, lo, out_t.precision)
        ctx.decimal_divides += 1
        return self._finish_decimal(
            ctx, out_t, hi, lo, ok, zero,
            and_validity(xp, l.validity, r.validity))

    def _decimal_div_host(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        """The CPU engine's division, on Python's `decimal` and integers,
        row by row: it shares nothing with the limb kernel it is the oracle
        of."""
        import decimal as _d
        from .decimal128 import split_int, unscaled_ints
        out_t = self.data_type
        at38 = _d.Context(prec=38, rounding=_d.ROUND_HALF_UP)
        exact = _d.Context(prec=160)
        unit = _d.Decimal(1).scaleb(-out_t.scale)
        bound = 10 ** out_t.precision
        n = l.validity.shape[0]
        hi, lo = np.zeros(n, np.int64), np.zeros(n, np.int64)
        ok, zero = np.ones(n, bool), np.zeros(n, bool)
        both = and_validity(np, l.validity, r.validity)
        for i, (a, b) in enumerate(zip(unscaled_ints(l.data),
                                       unscaled_ints(r.data))):
            if not both[i]:
                continue
            if b == 0:
                zero[i] = True
                continue
            q = at38.divide(exact.scaleb(_d.Decimal(a), -l.dtype.scale),
                            exact.scaleb(_d.Decimal(b), -r.dtype.scale))
            u = int(exact.scaleb(q.quantize(
                unit, rounding=_d.ROUND_HALF_UP, context=exact),
                out_t.scale))
            if abs(u) >= bound:
                ok[i] = False
            else:
                hi[i], lo[i] = split_int(u)
        return self._finish_decimal(ctx, out_t, hi, lo, ok, zero, both)


def _trunc_div(xp, a, b):
    """Java integer division: truncates toward zero; INT_MIN / -1 wraps to INT_MIN.
    No abs() — abs(INT_MIN) overflows; derive from floor division + remainder."""
    safe_b = xp.where(b == -1, 1, b)  # avoid INT_MIN // -1 overflow inside //
    q = a // safe_b
    r = a - q * safe_b
    q = q + ((r != 0) & ((a < 0) != (b < 0)))
    return xp.where(b == -1, -a, q)  # -INT_MIN wraps to INT_MIN, matching Java


class IntegralDivide(BinaryExpression):
    """`div` operator: LONG result, truncation toward zero, /0 -> null."""

    @property
    def data_type(self):
        return T.LONG

    @property
    def nullable(self):
        return True

    def _compute(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        xp = ctx.xp
        a = l.data.astype(np.int64)
        b = r.data.astype(np.int64)
        zero = b == 0
        both = and_validity(xp, l.validity, r.validity)
        if ctx.ansi:
            ansi_raise(ctx, zero & both, _DIV_ZERO)
            mn = np.int64(-2**63)
            ansi_raise(ctx, (a == mn) & (b == -1) & both,
                       "[ARITHMETIC_OVERFLOW] long overflow")
        validity = both & ~zero
        safe_b = xp.where(zero, 1, b)
        data = _trunc_div(xp, a, safe_b)
        return Vec(T.LONG, xp.where(zero, 0, data), validity)


class Remainder(BinaryArithmetic):
    """Java %: sign follows dividend; x%0 -> null."""

    @property
    def nullable(self):
        return True

    def _compute(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        xp = ctx.xp
        l, r, dt = promote_args(xp, l, r)
        zero = r.data == 0 if not T.is_floating(dt) else r.data == 0.0
        both = and_validity(xp, l.validity, r.validity)
        if ctx.ansi:
            ansi_raise(ctx, zero & both, _DIV_ZERO)
        validity = both & ~zero
        if T.is_floating(dt):
            data = xp.where(zero, 0.0, xp.fmod(l.data, xp.where(zero, 1.0, r.data)))
        else:
            b = xp.where(zero, 1, r.data)
            data = l.data - b * _trunc_div(xp, l.data, b)
        return Vec(dt, data.astype(dt.np_dtype, copy=False), validity)


class Pmod(BinaryArithmetic):
    """Positive modulus."""

    @property
    def nullable(self):
        return True

    def _compute(self, ctx: EvalContext, l: Vec, r: Vec) -> Vec:
        xp = ctx.xp
        l, r, dt = promote_args(xp, l, r)
        zero = r.data == 0 if not T.is_floating(dt) else r.data == 0.0
        both = and_validity(xp, l.validity, r.validity)
        if ctx.ansi:
            ansi_raise(ctx, zero & both, _DIV_ZERO)
        validity = both & ~zero
        if T.is_floating(dt):
            b = xp.where(zero, 1.0, r.data)
            m = xp.fmod(l.data, b)
            data = xp.where(m < 0, xp.fmod(m + b, b), m)
            data = xp.where(zero, 0.0, data)
        else:
            b = xp.where(zero, 1, r.data)
            m = l.data - b * _trunc_div(xp, l.data, b)
            data = xp.where(m < 0, m + xp.abs(b), m)
        return Vec(dt, data.astype(dt.np_dtype, copy=False), validity)


class UnaryMinus(Expression):
    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def data_type(self):
        return self.children[0].data_type

    def _compute(self, ctx, c: Vec) -> Vec:
        from .decimal128 import is_dec128, neg128, pack_limbs
        if is_dec128(c.dtype):
            hi, lo = neg128(ctx.xp, c.data[:, 0], c.data[:, 1])
            return Vec(c.dtype, pack_limbs(ctx.xp, hi, lo), c.validity)
        if ctx.ansi and T.is_integral(c.dtype):
            mn = np.iinfo(c.dtype.np_dtype).min
            ansi_raise(ctx, (c.data == mn) & c.validity, _overflow_msg(c.dtype))
        return Vec(c.dtype, (-c.data).astype(c.dtype.np_dtype, copy=False),
                   c.validity)


class Abs(Expression):
    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def data_type(self):
        return self.children[0].data_type

    def _compute(self, ctx, c: Vec) -> Vec:
        from .decimal128 import is_dec128, neg128, pack_limbs
        if is_dec128(c.dtype):
            xp = ctx.xp
            hi, lo = c.data[:, 0], c.data[:, 1]
            nhi, nlo = neg128(xp, hi, lo)
            neg = hi < 0
            out = pack_limbs(xp, xp.where(neg, nhi, hi),
                             xp.where(neg, nlo, lo))
            return Vec(c.dtype, out, c.validity)
        if ctx.ansi and T.is_integral(c.dtype):
            mn = np.iinfo(c.dtype.np_dtype).min
            ansi_raise(ctx, (c.data == mn) & c.validity, _overflow_msg(c.dtype))
        return Vec(c.dtype, ctx.xp.abs(c.data), c.validity)
