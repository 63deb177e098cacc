"""Exact arithmetic over wide decimals (19-38 digits) in the stage program.

A value is the 128-bit two's-complement unscaled integer in two 64-bit
words, `hi` int64 and `lo` uint64 (columnar/batch.py
`DeviceDecimal128Column` states the layout).  Everything here is
elementwise jnp over those words, exact for every value the type holds:
no float, no shortcut for values that happen to fit one word.  Semantics
are Spark 3's non-ANSI ones: results at Spark's result type, rounded half
up (away from zero), a value past the result's precision null
(`CheckOverflow` with nullOnOverflow).

Arithmetic runs on magnitudes (unsigned words, most significant first)
with the sign apart, as java.math.BigDecimal does, so that rounding half
up is rounding the magnitude.  The only division is by a divisor under
2**63 (a row count, or a power of ten of at most 18 digits): a restoring
bit loop, one quotient bit a step, which needs nothing of the chip but
64-bit shifts, compares and subtractions.

Each kernel family traces under its own named scope (`dec128/sum`,
`dec128/div`, `dec128/mul`, `dec128/cmp`, `dec128/cast`) beneath the
operator's label, so `python -m auron_tpu.trace device` files its time.
"""

from __future__ import annotations

import decimal
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from auron_tpu.columnar.batch import DeviceColumn, DeviceDecimal128Column
from auron_tpu.ir.schema import DataType
from auron_tpu.ops import segments

Array = Any
_U64 = jnp.uint64
_M32 = 0xFFFFFFFF
# the widest power of ten the divider takes (its divisor stays under 2**63)
MAX_DOWNSCALE_DIGITS = 18
_CTX = decimal.Context(prec=80)


def _u(x: Array) -> Array:
    return lax.bitcast_convert_type(x, jnp.uint64)


def _s(x: Array) -> Array:
    return lax.bitcast_convert_type(x, jnp.int64)


def _const(value: int) -> Tuple[Array, Array]:
    """A non-negative python integer under 2**128 as (hi, lo) u64 scalars."""
    assert 0 <= value < 1 << 128
    return _U64(value >> 64), _U64(value & ((1 << 64) - 1))


# ---------------------------------------------------------------------------
# words
# ---------------------------------------------------------------------------

def words_of(col) -> Tuple[Array, Array]:
    """(hi int64, lo uint64) of a decimal or integer column: a narrow
    column's one word, sign-extended."""
    if isinstance(col, DeviceDecimal128Column):
        return col.hi, col.lo
    x = col.data.astype(jnp.int64)
    return x >> 63, _u(x)


def _negate(hi: Array, lo: Array) -> Tuple[Array, Array]:
    nlo = ~lo + _U64(1)
    return ~hi + (nlo == 0).astype(jnp.int64), nlo


def magnitude(hi: Array, lo: Array) -> Tuple[Array, Array, Array]:
    """(mhi, mlo, negative): |value| as unsigned words, and its sign."""
    negative = hi < 0
    nhi, nlo = _negate(hi, lo)
    return (_u(jnp.where(negative, nhi, hi)), jnp.where(negative, nlo, lo),
            negative)


def signed(mhi: Array, mlo: Array, negative: Array) -> Tuple[Array, Array]:
    """The words of a magnitude under 2**127 with its sign restored."""
    nhi, nlo = _negate(_s(mhi), mlo)
    return jnp.where(negative, nhi, _s(mhi)), jnp.where(negative, nlo, mlo)


def _ult(ahi, alo, bhi, blo) -> Array:
    """a < b over unsigned (hi, lo) pairs."""
    return jnp.logical_or(ahi < bhi,
                          jnp.logical_and(ahi == bhi, alo < blo))


def _mul_u64(a: Array, b: Array) -> Tuple[Array, Array]:
    """a * b of two u64 as (hi, lo): four 32-by-32-bit products."""
    a0, a1 = a & _U64(_M32), a >> _U64(32)
    b0, b1 = b & _U64(_M32), b >> _U64(32)
    p00, p01, p10, p11 = a0 * b0, a0 * b1, a1 * b0, a1 * b1
    mid = (p00 >> _U64(32)) + (p01 & _U64(_M32)) + (p10 & _U64(_M32))
    lo = (p00 & _U64(_M32)) | (mid << _U64(32))
    hi = p11 + (p01 >> _U64(32)) + (p10 >> _U64(32)) + (mid >> _U64(32))
    return hi, lo


def _mul_words(words: Sequence[Array], m: Array) -> List[Array]:
    """A magnitude (u64 words, most significant first) times a u64: one
    word longer."""
    out: List[Array] = []
    carry = jnp.zeros_like(words[-1])
    for w in reversed(words):
        hi, lo = _mul_u64(w, m)
        low = lo + carry
        carry = hi + (low < lo).astype(jnp.uint64)   # hi <= 2**64 - 2
        out.append(low)
    out.append(carry)
    return out[::-1]


def _divmod_words(words: Sequence[Array], d: Array
                  ) -> Tuple[List[Array], Array]:
    """(quotient words, remainder) of a magnitude by a u64 divisor with
    0 < d < 2**63: restoring division, one bit a step.  The remainder
    stays under d, so doubling it never leaves the word."""
    n = len(words)

    def step(_i, carry):
        r, num, quo = carry
        bit = num[0] >> _U64(63)
        num = tuple((num[k] << _U64(1)) |
                    (num[k + 1] >> _U64(63) if k + 1 < n else _U64(0))
                    for k in range(n))
        r = (r << _U64(1)) | bit
        ge = r >= d
        r = jnp.where(ge, r - d, r)
        quo = tuple((quo[k] << _U64(1)) |
                    (quo[k + 1] >> _U64(63) if k + 1 < n
                     else ge.astype(jnp.uint64))
                    for k in range(n))
        return r, num, quo

    zero = jnp.zeros_like(words[0])
    r, _num, quo = lax.fori_loop(
        0, 64 * n, step, (zero, tuple(words), tuple(zero for _ in words)))
    return list(quo), r


def _div_half_up(words: Sequence[Array], d: Array) -> List[Array]:
    """A magnitude over d, rounded half up: quotient, plus one where twice
    the remainder reaches d."""
    quo, r = _divmod_words(words, d)
    carry = ((r << _U64(1)) >= d).astype(jnp.uint64)
    out = []
    for w in reversed(quo):
        s = w + carry
        carry = (s < w).astype(jnp.uint64)
        out.append(s)
    return out[::-1]


def _fits(words: Sequence[Array], precision: int) -> Array:
    """Is the magnitude under 10**precision (precision <= 38)?  Words
    above the low two have to be zero."""
    bhi, blo = _const(10 ** precision)
    ok = _ult(words[-2], words[-1], bhi, blo)
    for w in words[:-2]:
        ok = jnp.logical_and(ok, w == 0)
    return ok


def _scale_up(words: List[Array], digits: int) -> List[Array]:
    """The magnitude times 10**digits, a word longer for every 19 digits."""
    while digits > 0:
        step = min(digits, 19)
        words = _mul_words(words, _U64(10 ** step))
        digits -= step
    return words


def _rescale(words: List[Array], shift: int) -> List[Array]:
    """The magnitude at `shift` more fractional digits (fewer, rounded
    half up, where negative)."""
    if shift >= 0:
        return _scale_up(words, shift)
    assert -shift <= MAX_DOWNSCALE_DIGITS
    return _div_half_up(words, _U64(10 ** -shift))


def _column(dst: DataType, words: List[Array], negative: Array,
            valid: Array):
    """The column of type `dst` (wide or narrow) from a magnitude and its
    sign; a magnitude of `dst.precision` digits or more is null."""
    valid = jnp.logical_and(valid, _fits(words, dst.precision))
    hi, lo = signed(words[-2], words[-1], negative)
    if dst.is_wide_decimal:
        return DeviceDecimal128Column(dst, hi, lo, valid).masked(valid)
    return DeviceColumn(dst, jnp.where(valid, _s(lo), 0), valid)


# ---------------------------------------------------------------------------
# expressions
# ---------------------------------------------------------------------------

def unscaled_literal(value, dtype: DataType) -> int:
    """The unscaled integer of a decimal literal (an int is unscaled
    already, as everywhere in the program)."""
    if isinstance(value, int):
        return value
    return int(decimal.Decimal(str(value)).scaleb(dtype.scale, _CTX)
               .to_integral_value(decimal.ROUND_HALF_UP, _CTX))


def literal_column(value, dtype: DataType, capacity: int
                   ) -> DeviceDecimal128Column:
    u = unscaled_literal(value, dtype) & ((1 << 128) - 1)
    return DeviceDecimal128Column(
        dtype,
        jnp.full(capacity, _s(_U64(u >> 64)), jnp.int64),
        jnp.full(capacity, _U64(u & ((1 << 64) - 1)), jnp.uint64),
        jnp.ones(capacity, bool))


def cast_ok(src: DataType, dst: DataType) -> bool:
    """The casts `cast` takes: between decimals (an integer is a decimal
    of scale 0), wider or narrower, losing at most 18 digits of scale."""
    if not dst.is_decimal or not (src.is_decimal or src.is_integral):
        return False
    return src.scale - dst.scale <= MAX_DOWNSCALE_DIGITS


def cast(col, dst: DataType):
    """`Cast` between decimals where either side is wide: rescale by the
    power of ten, half up where digits go, null past `dst`'s precision."""
    with jax.named_scope("dec128/cast"):
        mhi, mlo, negative = magnitude(*words_of(col))
        words = _rescale([mhi, mlo], dst.scale - col.dtype.scale)
        return _column(dst, words, negative, col.validity)


def multiply_ok(lt: DataType, rt: DataType, l_expr, r_expr,
                dst: DataType) -> bool:
    """`multiply` takes a decimal times a decimal literal or a narrow
    decimal, at most 18 digits of scale lost to the result type."""
    if not (lt.is_decimal and rt.is_decimal and dst.is_decimal):
        return False
    if lt.scale + rt.scale - dst.scale > MAX_DOWNSCALE_DIGITS:
        return False
    return any(_one_word(t, x) for t, x in ((lt, l_expr), (rt, r_expr)))


def _one_word(dtype: DataType, expr) -> bool:
    """Does every value of the operand fit one word: a narrow decimal, or
    a literal whose value does?"""
    if not dtype.is_wide_decimal:
        return True
    return getattr(expr, "kind", None) == "literal" and \
        expr.value is not None and \
        abs(unscaled_literal(expr.value, dtype)) < 1 << 63


def multiply(l, r, l_expr, r_expr, dst: DataType):
    """`CheckOverflow(Multiply(l, r), dst)`: the exact product at scale
    s1 + s2, rounded half up to `dst`'s scale, null past its precision."""
    if not _one_word(r.dtype, r_expr):
        l, r = r, l
    with jax.named_scope("dec128/mul"):
        mhi, mlo, lneg = magnitude(*words_of(l))
        _rhi, rlo, rneg = magnitude(*words_of(r))
        words = _mul_words([mhi, mlo], rlo)
        words = _rescale(words, dst.scale - l.dtype.scale - r.dtype.scale)
        return _column(dst, words, jnp.logical_xor(lneg, rneg),
                       jnp.logical_and(l.validity, r.validity))


def compare_ok(lt: DataType, rt: DataType) -> bool:
    return lt.is_decimal and rt.is_decimal and lt.scale == rt.scale


def compare(op: str, l, r) -> Tuple[Array, Array]:
    """(l op r, both valid) for decimals of one scale: the signed 128-bit
    order."""
    with jax.named_scope("dec128/cmp"):
        ahi, alo = words_of(l)
        bhi, blo = words_of(r)
        eq = jnp.logical_and(ahi == bhi, alo == blo)
        lt = jnp.logical_or(ahi < bhi,
                            jnp.logical_and(ahi == bhi, alo < blo))
        if op in ("==", "=", "<=>"):
            out = eq
        elif op == "!=":
            out = jnp.logical_not(eq)
        elif op == "<":
            out = lt
        elif op == "<=":
            out = jnp.logical_or(lt, eq)
        elif op == ">":
            out = jnp.logical_not(jnp.logical_or(lt, eq))
        elif op == ">=":
            out = jnp.logical_not(lt)
        else:
            raise NotImplementedError(op)
        return out, jnp.logical_and(l.validity, r.validity)


# ---------------------------------------------------------------------------
# aggregates
# ---------------------------------------------------------------------------

def segment_sum(col, keep: Array, seg: Array, n: int
                ) -> Tuple[List[Array], Array]:
    """The sum of `col`'s rows under `keep` per sorted segment, as a
    magnitude of three words and its sign.  Each value is cut into 32-bit
    limbs (two for a narrow column, four for a wide one, the top one
    signed); a limb's segment sum is an exact int64 for any segment of
    under 2**31 rows (`sorted_segment_sum`: a modular running sum,
    differenced); the limbs' sums are then added where they belong, with
    the carries, in 192 bits."""
    with jax.named_scope("dec128/sum"):
        hi, lo = words_of(col)
        limbs = [_s(lo & _U64(_M32)), _s(lo >> _U64(32))]
        if isinstance(col, DeviceDecimal128Column):
            limbs += [_s(_u(hi) & _U64(_M32)), hi >> 32]
        else:
            # one word: its top limb is the signed one
            limbs[1] = _s(lo) >> 32
        sums = [segments.sorted_segment_sum(jnp.where(keep, x, 0), seg, n)
                for x in limbs]
        # total = sum_k sums[k] * 2**(32 k), every sums[k] a signed 64-bit
        # integer: three signed words, least significant first
        w = [jnp.zeros(n, jnp.uint64) for _ in range(3)]
        for k, s in enumerate(sums):
            part = _shift_into(s, 32 * k)
            carry = jnp.zeros(n, jnp.uint64)
            for j in range(3):
                t = w[j] + part[j]
                c1 = (t < w[j]).astype(jnp.uint64)
                t2 = t + carry
                c2 = (t2 < t).astype(jnp.uint64)
                w[j], carry = t2, c1 + c2
        negative = _s(w[2]) < 0
        # negate the three words where negative
        nw, carry = [], jnp.ones(n, jnp.uint64)
        for j in range(3):
            t = ~w[j] + carry
            carry = jnp.logical_and(carry == 1, t == 0).astype(jnp.uint64)
            nw.append(t)
        mag = [jnp.where(negative, nw[j], w[j]) for j in (2, 1, 0)]
        return mag, negative


def _shift_into(s: Array, bits: int) -> List[Array]:
    """A signed 64-bit integer times 2**bits (bits in 0, 32, 64, 96) as
    three words, least significant first, sign-extended."""
    ext = _u(s >> 63)                  # all ones where negative
    us = _u(s)
    if bits % 64:
        low, high = us << _U64(32), (us >> _U64(32)) | (ext << _U64(32))
    else:
        low, high = us, ext
    if bits < 64:
        return [low, high, ext]
    return [jnp.zeros_like(us), low, high]


def sum_state(dst: DataType, col, keep: Array, poisoned: Array,
              seg: Array, n: int) -> DeviceDecimal128Column:
    """The wide sum state of type `dst` per segment: the exact sum of the
    kept rows, null where it passes `dst`'s precision or where a kept row
    was `poisoned` (a partial sum that had overflowed)."""
    mag, negative = segment_sum(col, keep, seg, n)
    bad = segments.sorted_segment_sum(
        jnp.logical_and(keep, poisoned).astype(jnp.int32), seg, n) > 0
    return _column(dst, mag, negative, jnp.logical_not(bad))


def avg_divide_type(sum_dt: DataType) -> DataType:
    """Spark's type of `Divide(sum, cast(count as decimal(20,0)))`
    (DecimalPrecision's division rule with its precision-loss
    adjustment)."""
    p1, s1, p2, s2 = sum_dt.precision, sum_dt.scale, 20, 0
    scale = max(6, s1 + p2 + 1)
    precision = p1 - s1 + s2 + scale
    if precision > 38:
        scale = max(38 - (precision - scale), min(scale, 6))
        precision = 38
    return DataType.decimal(precision, scale)


def average(sum_col, count: Array, dst: DataType):
    """A decimal `Average`'s result: `Divide(sum, count)` at
    `avg_divide_type`, rounded half up, null past it or over no rows;
    then cast to `dst` — both roundings, in that order."""
    mid = avg_divide_type(sum_col.dtype)
    with jax.named_scope("dec128/div"):
        mhi, mlo, negative = magnitude(*words_of(sum_col))
        words = _scale_up([mhi, mlo], mid.scale - sum_col.dtype.scale)
        some = count > 0
        words = _div_half_up(words, _u(jnp.where(some, count, 1)))
        q = _column(mid, words, negative,
                    jnp.logical_and(sum_col.validity, some))
    return q if mid == dst else cast(q, dst)
