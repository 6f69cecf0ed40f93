"""Decimal128 limb arithmetic (reference: `decimalExpressions.scala` +
spark-rapids-jni's decimal128 kernels — SURVEY lists Spark-exact decimal128
as the first 'hard part').

Representation: a decimal column with precision > 18 carries its unscaled
128-bit integer as TWO int64 limbs in `data[n, 2]` — column 0 the signed
high limb (bits 64..127), column 1 the low limb's BIT PATTERN (bits 0..63,
interpreted unsigned). This is the same rank-2 shape strings use, so the
generic row machinery (gather, compaction, selection, spill, key packing)
moves decimal128 columns without modification; only VALUE semantics (adds,
compares, rescales, reductions) live here.

All helpers are xp-generic (numpy | jax.numpy) and run under jit with x64
enabled. Sum aggregation avoids carry chains entirely: each value splits
into three <=2^43 signed chunks, segment-summed independently (no overflow
for < 2^20 rows), then recombined in limb arithmetic — parallel-friendly,
unlike a sequential carry propagation."""

from __future__ import annotations

from typing import Tuple

import numpy as np

from .. import types as T

__all__ = ["is_dec128", "split_int", "join_int", "add128", "neg128",
           "cmp_keys", "mul_pow10", "div_pow10_half_up", "in_bounds",
           "SUM_CHUNK_BITS"]

_U64 = np.uint64
_MASK32 = np.uint64(0xFFFFFFFF)
SUM_CHUNK_BITS = 43


def is_dec128(dt) -> bool:
    return isinstance(dt, T.DecimalType) and \
        dt.precision > T.DecimalType.MAX_LONG_DIGITS


def split_int(v: int) -> Tuple[int, int]:
    """python int -> (hi signed, lo bit-pattern as signed int64)."""
    u = v & ((1 << 128) - 1)
    lo = u & ((1 << 64) - 1)
    hi = (u >> 64) & ((1 << 64) - 1)
    def s64(x):
        return x - (1 << 64) if x >= (1 << 63) else x
    return s64(hi), s64(lo)


def join_int(hi: int, lo: int) -> int:
    """(hi signed, lo bit-pattern) -> python int."""
    u = ((hi & ((1 << 64) - 1)) << 64) | (lo & ((1 << 64) - 1))
    return u - (1 << 128) if u >= (1 << 127) else u


# Exact context for host-boundary Decimal<->unscaled-int conversion.
# `Decimal.scaleb` (like all Decimal ARITHMETIC) rounds to the ambient
# thread-local context precision — default 28, silently corrupting >28-digit
# decimal(38) values on any engine worker thread (shuffle writers, pipeline
# prefetch); the main thread only looked safe because the test harness set
# its context wide. 80 digits covers any decimal(38) at any engine scale
# shift, so these helpers are exact everywhere, on every thread.
import decimal as _decimal

_EXACT_CTX = _decimal.Context(prec=80)


def unscaled_int(d: "_decimal.Decimal", scale: int) -> int:
    """Decimal value -> exact unscaled int at `scale`, independent of the
    caller's thread-local decimal context."""
    return int(_EXACT_CTX.scaleb(d, scale))


def to_decimal(unscaled: int, scale: int) -> "_decimal.Decimal":
    """Exact unscaled int at `scale` -> Decimal, context-independent."""
    return _EXACT_CTX.scaleb(_decimal.Decimal(unscaled), -scale)


def unscaled_ints(data) -> list:
    """A decimal column's host array ((n, 2) limb pairs or (n,) int64) as
    Python ints: what the CPU engine's exact paths compute on."""
    if data.ndim == 2:
        return [join_int(int(h), int(lw)) for h, lw in data]
    return [int(x) for x in data]


def _u(xp, x):
    return x.astype(np.uint64)


def _s(xp, x):
    return x.astype(np.int64)


def add128(xp, ahi, alo, bhi, blo):
    """(ahi, alo) + (bhi, blo) -> (hi, lo), wrapping at 128 bits."""
    lo = _u(xp, alo) + _u(xp, blo)
    carry = (lo < _u(xp, alo)).astype(np.uint64)
    hi = _u(xp, ahi) + _u(xp, bhi) + carry
    return _s(xp, hi), _s(xp, lo)


def neg128(xp, hi, lo):
    nlo = _u(xp, ~lo) + _U64(1)
    carry = (nlo == 0).astype(np.uint64)
    nhi = _u(xp, ~hi) + carry
    return _s(xp, nhi), _s(xp, nlo)


def cmp_keys(xp, hi, lo):
    """Sort keys: (hi, lo-as-unsigned-order-in-signed-space). Two-key
    lexicographic ascending sort == signed 128-bit ascending order."""
    lo_key = _s(xp, _u(xp, lo) ^ _U64(1 << 63))
    return hi, lo_key


def lt128(xp, ahi, alo, bhi, blo):
    """signed (ahi,alo) < (bhi,blo)."""
    alo_k = _u(xp, alo)
    blo_k = _u(xp, blo)
    return (ahi < bhi) | ((ahi == bhi) & (alo_k < blo_k))


def eq128(xp, ahi, alo, bhi, blo):
    return (ahi == bhi) & (alo == blo)


def _split32(xp, hi, lo):
    """128-bit -> 4 unsigned 32-bit limbs (as uint64 arrays), LSB first."""
    lo_u = _u(xp, lo)
    hi_u = _u(xp, hi)
    return (lo_u & _MASK32, lo_u >> np.uint64(32),
            hi_u & _MASK32, hi_u >> np.uint64(32))


def _join32(xp, l0, l1, l2, l3):
    lo = (l0 & _MASK32) | ((l1 & _MASK32) << np.uint64(32))
    hi = (l2 & _MASK32) | ((l3 & _MASK32) << np.uint64(32))
    return _s(xp, hi), _s(xp, lo)


def _mul_u64(xp, hi, lo, m: int):
    """(hi, lo) * unsigned 64-bit constant m, wrapping at 128 bits."""
    m0 = np.uint64(m & 0xFFFFFFFF)
    m1 = np.uint64((m >> 32) & 0xFFFFFFFF)
    l0, l1, l2, l3 = _split32(xp, hi, lo)
    # schoolbook partial products; each limb < 2^32 so products fit u64.
    # NOTE p[k] can reach ~2^65 conceptually only past column 3, which we
    # discard (wrap at 128 bits); within kept columns every sum fits u64
    p0 = l0 * m0
    p1 = l0 * m1 + l1 * m0
    p2 = l1 * m1 + l2 * m0
    p3 = l2 * m1 + l3 * m0
    cols = [p0 & _MASK32,
            (p0 >> np.uint64(32)) + (p1 & _MASK32),
            (p1 >> np.uint64(32)) + (p2 & _MASK32),
            (p2 >> np.uint64(32)) + (p3 & _MASK32)]
    res = []
    carry = np.uint64(0) * l0
    for k in range(4):
        acc = cols[k] + carry
        res.append(acc & _MASK32)
        carry = acc >> np.uint64(32)
    return _join32(xp, *res)


def mul_pow10(xp, hi, lo, k: int):
    """(hi, lo) * 10^k, wrapping (caller bounds-checks)."""
    while k > 0:
        step = min(k, 19)
        hi, lo = _mul_u64(xp, hi, lo, 10 ** step)
        k -= step
    return hi, lo


def _divmod_u32(xp, limbs, d: int):
    """Unsigned 128-bit (4x32 limbs, LSB first) // uint32 d -> (limbs, rem).
    Long division, MSB first; remainders stay < 2^32 so each step fits u64."""
    du = np.uint64(d)
    q = [None] * 4
    rem = np.uint64(0) * limbs[0]
    for k in (3, 2, 1, 0):
        acc = (rem << np.uint64(32)) | limbs[k]
        q[k] = acc // du
        rem = acc % du
    return q, rem


def div_pow10_half_up(xp, hi, lo, k: int):
    """(hi, lo) / 10^k with HALF_UP rounding on the magnitude (Spark
    decimal rescale semantics)."""
    neg = hi < 0
    mhi, mlo = neg128(xp, hi, lo)
    mhi = xp.where(neg, mhi, hi)
    mlo = xp.where(neg, mlo, lo)
    # HALF_UP on base 10 is decided solely by the MOST significant dropped
    # digit: drop k-1 digits, then one more capturing that digit
    limbs = list(_split32(xp, mhi, mlo))
    if k > 0:
        # drop k-1 digits, then one more capturing that digit
        for _ in range(k - 1):
            limbs, _ = _divmod_u32(xp, limbs, 10)
        limbs, first_dropped = _divmod_u32(xp, limbs, 10)
        round_up = first_dropped >= np.uint64(5)
        qhi, qlo = _join32(xp, *limbs)
        inc_hi, inc_lo = add128(xp, qhi, qlo,
                                xp.zeros_like(qhi),
                                xp.ones_like(qlo))
        qhi = xp.where(round_up, inc_hi, qhi)
        qlo = xp.where(round_up, inc_lo, qlo)
    else:
        qhi, qlo = _join32(xp, *limbs)
    nhi, nlo = neg128(xp, qhi, qlo)
    out_hi = xp.where(neg, nhi, qhi)
    out_lo = xp.where(neg, nlo, qlo)
    return out_hi, out_lo


def div_pow10_trunc(xp, hi, lo, k: int):
    """(hi, lo) / 10^k truncated toward zero (Spark Decimal.toLong
    semantics for decimal -> integral casts)."""
    neg = hi < 0
    mhi, mlo = neg128(xp, hi, lo)
    mhi = xp.where(neg, mhi, hi)
    mlo = xp.where(neg, mlo, lo)
    limbs = list(_split32(xp, mhi, mlo))
    for _ in range(k):
        limbs, _ = _divmod_u32(xp, limbs, 10)
    qhi, qlo = _join32(xp, *limbs)
    nhi, nlo = neg128(xp, qhi, qlo)
    return xp.where(neg, nhi, qhi), xp.where(neg, nlo, qlo)


def in_bounds(xp, hi, lo, precision: int):
    """|value| <= 10^precision - 1 (Spark overflow check)."""
    bound = 10 ** precision - 1
    bhi, blo = split_int(bound)
    bhi_a = xp.full(hi.shape, bhi, dtype=np.int64)
    blo_a = xp.full(hi.shape, blo, dtype=np.int64)
    neg = hi < 0
    mhi, mlo = neg128(xp, hi, lo)
    mhi = xp.where(neg, mhi, hi)
    mlo = xp.where(neg, mlo, lo)
    # -2^127 is its own negation: magnitude stays negative -> out of bounds
    gt = lt128(xp, bhi_a, blo_a, mhi, mlo) | (mhi < 0)
    return ~gt


def widen_operand(xp, v):
    """A decimal Vec's (hi, lo) limbs: dec128 data is [n,2]; dec64 int64
    data sign-extends into a high limb."""
    if v.data.ndim == 2:
        return v.data[:, 0], v.data[:, 1]
    lo = v.data.astype(np.int64)
    hi = xp.where(lo < 0, np.int64(-1), np.int64(0))
    return hi, lo


def pack_limbs(xp, hi, lo):
    return xp.stack([hi, lo], axis=1)


def adjust_precision_scale(p: int, s: int) -> "T.DecimalType":
    """Spark DecimalType.adjustPrecisionScale (allowPrecisionLoss=true,
    `DecimalType.scala`): when the ideal precision exceeds 38, keep the
    integral digits and give fractional digits whatever is left, but never
    fewer than min(s, 6)."""
    if p <= T.DecimalType.MAX_PRECISION:
        return T.DecimalType(p, s)
    int_digits = p - s
    min_scale = min(s, 6)
    adjusted = max(T.DecimalType.MAX_PRECISION - int_digits, min_scale)
    return T.DecimalType(T.DecimalType.MAX_PRECISION, adjusted)


def add_result_type(a, b) -> "T.DecimalType":
    """Spark decimal +/- result: ideal scale max(s1,s2), ideal precision
    max(p1-s1, p2-s2) + scale + 1, then adjustPrecisionScale."""
    s = max(a.scale, b.scale)
    p = max(a.precision - a.scale, b.precision - b.scale) + s + 1
    return adjust_precision_scale(p, s)


def rescale_up(xp, hi, lo, k: int):
    """Multiply by 10^k (k >= 0), WRAPPING at 128 bits. Callers must prove
    no wrap (operand precision + k <= 38) or use the wide_* 256-bit path —
    an unguarded call can alias out-of-range values back into bounds."""
    if k == 0:
        return hi, lo
    return mul_pow10(xp, hi, lo, k)


# ---------------------------------------------------------------------------
# 256-bit "wide" arithmetic: 8 x 32-bit limbs (LSB first, each held in a
# uint64 array so every partial product / carry fits the lane). The JVM
# computes decimal intermediates in unbounded BigDecimal; rescaling a
# 38-digit value by up to 38 more digits needs up to ~10^76 < 2^253, so a
# 256-bit two's-complement intermediate makes add/sub/cast/compare EXACT,
# with overflow detected on the narrowing back to 128 bits instead of
# silently wrapping (round-2 advisor finding).
# ---------------------------------------------------------------------------

_WIDE_N = 8


def wide_from128(xp, hi, lo):
    """Sign-extend a 128-bit (hi, lo) value into 8 u32 limbs."""
    l0, l1, l2, l3 = _split32(xp, hi, lo)
    ext = xp.where(hi < 0, _MASK32, np.uint64(0))
    return [l0, l1, l2, l3, ext, ext, ext, ext]


def wide_add(xp, a, b):
    out = []
    carry = xp.zeros_like(a[0])
    for k in range(_WIDE_N):
        acc = a[k] + b[k] + carry
        out.append(acc & _MASK32)
        carry = acc >> np.uint64(32)
    return out


def wide_neg(xp, a):
    out = []
    carry = xp.ones_like(a[0])
    for k in range(_WIDE_N):
        acc = (~a[k] & _MASK32) + carry
        out.append(acc & _MASK32)
        carry = acc >> np.uint64(32)
    return out


def wide_is_neg(xp, a):
    return (a[_WIDE_N - 1] >> np.uint64(31)) != 0


def _wide_mul_small(xp, a, m: int):
    """a * m for m < 2^32, wrapping at 256 bits."""
    mu = np.uint64(m)
    out = []
    carry = xp.zeros_like(a[0])
    for k in range(_WIDE_N):
        acc = a[k] * mu + carry
        out.append(acc & _MASK32)
        carry = acc >> np.uint64(32)
    return out


def wide_mul_pow10(xp, a, k: int):
    """a * 10^k in steps of 10^9 (each step's multiplier fits u32)."""
    while k > 0:
        step = min(k, 9)
        a = _wide_mul_small(xp, a, 10 ** step)
        k -= step
    return a


def _wide_divmod_small(xp, a, d: int):
    """Unsigned a // d (d < 2^32) via MSB-first long division."""
    du = np.uint64(d)
    q = [None] * _WIDE_N
    rem = xp.zeros_like(a[0])
    for k in range(_WIDE_N - 1, -1, -1):
        acc = (rem << np.uint64(32)) | a[k]
        q[k] = acc // du
        rem = acc % du
    return q, rem


def wide_div_pow10_half_up(xp, a, k: int):
    """a / 10^k with HALF_UP rounding on the magnitude (Spark rescale)."""
    if k <= 0:
        return a
    neg = wide_is_neg(xp, a)
    mag = wide_neg(xp, a)
    mag = [xp.where(neg, m, v) for m, v in zip(mag, a)]
    drop = k - 1
    while drop > 0:  # drop all but the most significant discarded digit
        step = min(drop, 9)
        mag, _ = _wide_divmod_small(xp, mag, 10 ** step)
        drop -= step
    mag, first_dropped = _wide_divmod_small(xp, mag, 10)
    round_up = first_dropped >= np.uint64(5)
    one = [xp.where(round_up, np.uint64(1), np.uint64(0))] + \
        [xp.zeros_like(mag[0])] * (_WIDE_N - 1)
    mag = wide_add(xp, mag, one)
    nmag = wide_neg(xp, mag)
    return [xp.where(neg, n, m) for n, m in zip(nmag, mag)]


def limbs_for(precision: int) -> int:
    """32-bit limbs that hold every magnitude of a decimal of `precision`."""
    return -(-(10 ** precision - 1).bit_length() // 32)


def abs128(xp, hi, lo):
    """(|value| as (hi, lo), value < 0)."""
    neg = hi < 0
    nhi, nlo = neg128(xp, hi, lo)
    return xp.where(neg, nhi, hi), xp.where(neg, nlo, lo), neg


def wide_mul(xp, a, b):
    """Exact unsigned product of two limb lists (u32 limbs in uint64 lanes,
    LSB first, any lengths): len(a) + len(b) limbs. Schoolbook, row by row:
    limb x limb + limb + carry <= (2^32-1)^2 + 2(2^32-1) = 2^64-1, so every
    step fits its lane. 4 x 4 limbs is the 128 x 128 -> 256-bit multiply;
    callers pass fewer limbs where the TYPES bound the operands."""
    out = [xp.zeros_like(a[0])] * (len(a) + len(b))
    for i, ai in enumerate(a):
        carry = xp.zeros_like(ai)
        for j, bj in enumerate(b):
            t = ai * bj + out[i + j] + carry
            out[i + j] = t & _MASK32
            carry = t >> np.uint64(32)
        out[i + len(b)] = carry
    return out


def _repeat(xp, n: int, step, state):
    """`state = step(state)` n times: a Python loop under numpy, ONE loop
    operation under jax.numpy. The v5e compiler took 269 s over 87 such
    steps unrolled and 0.4 s over the loop (sandbox v5e compiler, PR 29);
    a 64-bit `//` costs it 22 s apiece, which rules out Knuth's division."""
    if xp is np:
        for _ in range(n):
            state = step(state)
        return state
    import jax
    return jax.lax.fori_loop(0, n, lambda _, st: step(st), state)


def _left_aligned_words(xp, limbs, nbits: int):
    """u32 limbs (LSB first) of a value under 2^nbits as 64-bit words, least
    significant first, shifted so that bit nbits - 1 is the top bit of the
    last word: a restoring division's dividend register."""
    nwords = -(-nbits // 64)
    limbs = list(limbs)
    limbs += [xp.zeros_like(limbs[0])] * (2 * nwords - len(limbs))
    words = [limbs[2 * i] | (limbs[2 * i + 1] << np.uint64(32))
             for i in range(nwords)]
    shift = 64 * nwords - nbits
    if shift:
        words = [(w << np.uint64(shift)) |
                 (words[i - 1] >> np.uint64(64 - shift) if i else
                  np.uint64(0)) for i, w in enumerate(words)]
    return words


def div_count_half_up(xp, hi, lo, precision: int, k: int, count):
    """(hi, lo) * 10^k / count rounded HALF_UP on the magnitude, for a
    value of at most `precision` digits and an int64 `count` >= 1 (rows
    with count < 1 divide by 1): (hi, lo, fits), `fits` False where the
    quotient leaves 128 bits. The exact step of a decimal average: sum /
    count at the result scale. Restoring long division, one bit a step:
    the scaled magnitude sits left-aligned in 64-bit words, its top bit
    moves into the remainder and the quotient's bit into the place it
    vacated, for as many steps as the TYPES' digits need bits (87 for a
    decimal(22, s) sum), no more."""
    mhi, mlo, neg = abs128(xp, hi, lo)
    one, top_bit = np.uint64(1), np.uint64(63)
    n = wide_mul(xp, list(_split32(xp, mhi, mlo)),
                 [xp.full(mlo.shape, np.uint64(10 ** k & 0xFFFFFFFF)),
                  xp.full(mlo.shape, np.uint64(10 ** k >> 32))])
    nbits = (10 ** (precision + k) - 1).bit_length()
    words = _left_aligned_words(xp, n, nbits)
    nwords = len(words)
    d = _u(xp, xp.where(count < 1, np.int64(1), count))

    def step(state):
        *ws, rem = state
        rem = (rem << one) | (ws[-1] >> top_bit)  # rem < d < 2^63: it fits
        ge = rem >= d
        rem = xp.where(ge, rem - d, rem)
        carry, out = ge.astype(np.uint64), []
        for w in ws:
            out.append((w << one) | carry)
            carry = w >> top_bit
        return (*out, rem)

    *q, rem = _repeat(xp, nbits, step, (*words, xp.zeros_like(d)))
    qlo = _s(xp, q[0])
    qhi = _s(xp, q[1]) if nwords > 1 else xp.zeros_like(qlo)
    up = rem >= d - rem  # 2 * rem >= d without the overflow
    ihi, ilo = add128(xp, qhi, qlo, xp.zeros_like(qhi), xp.ones_like(qlo))
    qhi, qlo = xp.where(up, ihi, qhi), xp.where(up, ilo, qlo)
    fits = qhi >= 0
    if nwords > 2:
        fits = fits & (q[2] == 0)
    nhi, nlo = neg128(xp, qhi, qlo)
    return xp.where(neg, nhi, qhi), xp.where(neg, nlo, qlo), fits


def divide_result_type(a, b) -> "T.DecimalType":
    """Spark's decimal / decimal result (`DecimalPrecision`): ideal scale
    max(6, s1 + p2 + 1), ideal precision p1 - s1 + s2 + scale, then
    adjustPrecisionScale."""
    s = max(6, a.scale + b.precision + 1)
    return adjust_precision_scale(a.precision - a.scale + b.scale + s, s)


def _const_limbs(xp, like, value: int):
    """A non-negative Python int as u32 limbs (uint64 lanes, LSB first)
    shaped like `like`: as many as it has bits, at least one."""
    n = max(1, -(-value.bit_length() // 32))
    return [xp.full(like.shape, np.uint64((value >> (32 * i)) & 0xFFFFFFFF))
            for i in range(n)]


# 10^0 .. 10^38 as four u32 limbs each, for a per-row power (div_half_up)
_POW10_LIMBS = np.array([[(10 ** m >> (32 * i)) & 0xFFFFFFFF
                          for i in range(4)] for m in range(39)],
                        dtype=np.uint64)


def div_half_up(xp, ahi, alo, precision: int, k: int, bhi, blo):
    """(ahi, alo) * 10^k / (bhi, blo) as Spark's `Decimal./` followed by
    `toPrecision` computes it, for a dividend of at most `precision` digits
    and a non-zero divisor (rows with a zero divisor divide by 1): the
    quotient at 38 SIGNIFICANT digits HALF_UP (`BigDecimal.divide` under
    `MathContext(38, HALF_UP)`), then HALF_UP at the result scale, which is
    the unit here because the caller chose k = result scale - s1 + s2.
    Returns (hi, lo, fits), `fits` False where the quotient leaves 128 bits.

    Exact, in integers: N = |a| * 10^k in as many 64-bit words as the TYPES'
    digits need, Q = N div |b| and R = N mod |b| by restoring long division,
    one bit a step in ONE loop (a 64-bit `//` costs the v5e compiler 22 s
    apiece, 87 unrolled steps 269 s; PERF.md, PR 29), with a 128-bit
    remainder (R < |b| < 2^127, so 2R + 1 fits). The two roundings fold into
    one decision. With D the digits of Q, the first rounding keeps m = 38 - D
    digits of the fraction F = R / |b|. For m <= 0 it is a rounding at the
    unit or above: up iff 2R >= |b| (a Q of more than 38 digits is out of
    every result type anyway). For m >= 1 the fraction rounded to m digits
    reaches one half iff F >= 1/2 - 1/2 * 10^-m, i.e.
    (|b| - 2R) * 10^m <= |b|: the single rounding's condition and, beside
    it, the fractions 0.4999..95.. that the first rounding lifts to 0.5.
    The second can only happen when 10^m <= |b|, so it is left out where the
    types rule it out (precision + k < 38: then m > digits of |b|)."""
    mahi, malo, aneg = abs128(xp, ahi, alo)
    bhi_m, blo_m, bneg = abs128(xp, bhi, blo)
    bh, bl = _u(xp, bhi_m), _u(xp, blo_m)
    one, top_bit = np.uint64(1), np.uint64(63)
    n = list(_split32(xp, mahi, malo))[:limbs_for(precision)]
    if k:
        n = wide_mul(xp, n, _const_limbs(xp, malo, 10 ** k))
    nbits = (10 ** (precision + k) - 1).bit_length()
    words = _left_aligned_words(xp, n, nbits)
    nwords = len(words)

    def step(state):
        *ws, rh, rl = state
        rh = (rh << one) | (rl >> top_bit)
        rl = (rl << one) | (ws[-1] >> top_bit)
        ge = (rh > bh) | ((rh == bh) & (rl >= bl))
        dl = rl - bl
        dh = rh - bh - (rl < bl).astype(np.uint64)
        rh, rl = xp.where(ge, dh, rh), xp.where(ge, dl, rl)
        carry, out = ge.astype(np.uint64), []
        for w in ws:
            out.append((w << one) | carry)
            carry = w >> top_bit
        return (*out, rh, rl)

    zero = xp.zeros_like(bl)
    *q, rh, rl = _repeat(xp, nbits, step, (*words, zero, zero))
    qlo = _s(xp, q[0])
    qhi = _s(xp, q[1]) if nwords > 1 else xp.zeros_like(qlo)
    fits = qhi >= 0
    for w in q[2:]:
        fits = fits & (w == 0)
    # t = |b| - 2R as a signed 129-bit quantity: up at once where t <= 0
    r2h = (rh << one) | (rl >> top_bit)
    r2l = rl << one
    r2_over = (rh >> top_bit) != 0          # 2R >= 2^128 > |b|
    tl = bl - r2l
    th = bh - r2h - (bl < r2l).astype(np.uint64)
    t_pos = ~r2_over & ((bh > r2h) | ((bh == r2h) & (bl > r2l)))
    up = ~t_pos
    if precision + k >= 38:
        digits = xp.zeros(qlo.shape, dtype=np.int32)
        for j in range(38):
            phi, plo = split_int(10 ** j)
            digits = digits + (~lt128(xp, qhi, qlo, np.int64(phi),
                                      np.int64(plo))).astype(np.int32)
        m = xp.clip(38 - digits, 0, 38)
        pow_m = xp.asarray(_POW10_LIMBS)[m]           # (n, 4)
        prod = wide_mul(xp, list(_split32(xp, _s(xp, th), _s(xp, tl))),
                        [pow_m[:, i] for i in range(4)])
        # both under 2^255, so the signed 256-bit compare is theirs
        lt, eq = wide_cmp(xp, prod, list(_split32(xp, bhi_m, blo_m))
                          + [zero] * 4)
        up = up | (t_pos & (m >= 1) & (lt | eq))
    ihi, ilo = add128(xp, qhi, qlo, xp.zeros_like(qhi), xp.ones_like(qlo))
    # Q = 2^127 - 1 rounded up wraps negative: it did not fit
    fits = fits & ~(up & (ihi < 0))
    qhi, qlo = xp.where(up, ihi, qhi), xp.where(up, ilo, qlo)
    nhi, nlo = neg128(xp, qhi, qlo)
    neg = aneg != bneg
    return xp.where(neg, nhi, qhi), xp.where(neg, nlo, qlo), fits


def wide_to128(xp, a):
    """Narrow to 128 bits: (hi, lo, fits) where fits is False on rows whose
    value does not fit a signed 128-bit integer."""
    hi, lo = _join32(xp, a[0], a[1], a[2], a[3])
    ext = xp.where(hi < 0, _MASK32, np.uint64(0))
    fits = (a[4] == ext) & (a[5] == ext) & (a[6] == ext) & (a[7] == ext)
    return hi, lo, fits


def wide_cmp(xp, a, b):
    """(lt, eq) for signed 256-bit operands."""
    diff = wide_add(xp, a, wide_neg(xp, b))
    eq = (a[0] == b[0])
    for k in range(1, _WIDE_N):
        eq = eq & (a[k] == b[k])
    return wide_is_neg(xp, diff), eq


def sum_chunks(xp, hi, lo):
    """128-bit -> three int64 chunks (bits 0:43, 43:86, 86:128-signed) whose
    independent sums reconstruct the total without carry chains."""
    lo_u = _u(xp, lo)
    hi_u = _u(xp, hi)
    mask43 = np.uint64((1 << 43) - 1)
    c0 = _s(xp, lo_u & mask43)
    c1 = _s(xp, ((lo_u >> np.uint64(43)) |
                 ((hi_u & np.uint64((1 << 22) - 1)) << np.uint64(21)))
            & mask43)
    c2 = hi >> np.int64(22)  # arithmetic shift: signed top 42 bits
    return c0, c1, c2


def sum_recombine(xp, s0, s1, s2):
    """Inverse of sum_chunks after summation: s0 + (s1 << 43) + (s2 << 86)
    in 128-bit limbs (each s fits int64)."""
    zero = xp.zeros_like(s0)
    h0 = xp.where(s0 < 0, np.int64(-1), np.int64(0))
    acc_hi, acc_lo = h0, s0
    # s1 << 43 spans bits 43..106
    s1u = _u(xp, s1)
    part_lo = _s(xp, s1u << np.uint64(43))
    part_hi = _s(xp, s1u >> np.uint64(21))
    # sign-extend the shifted value's high limb for negative s1
    part_hi = xp.where(s1 < 0, _s(xp, _u(xp, part_hi)
                                  | (~np.uint64(0) << np.uint64(43))),
                       part_hi)
    acc_hi, acc_lo = add128(xp, acc_hi, acc_lo, part_hi, part_lo)
    # s2 << 86: entirely within the high limb (shift 22)
    part2_hi = _s(xp, _u(xp, s2) << np.uint64(22))
    acc_hi, acc_lo = add128(xp, acc_hi, acc_lo, part2_hi, zero)
    return acc_hi, acc_lo
