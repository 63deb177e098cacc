"""Device scalar-function kernels (the ScalarFunction enum +
Spark_* extension families of the reference, TPU-shaped).

Math runs in float64 (Spark double semantics); date functions use the civil
calendar kernels; string functions use the padded-matrix kernels.  Functions
not listed here are compiled as host islands.
"""

from __future__ import annotations

from typing import Any, List

import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import DeviceColumn, DeviceStringColumn
from auron_tpu.exprs import datetime as D
from auron_tpu.exprs import hashing as H
from auron_tpu.exprs import strings_device as S
from auron_tpu.exprs.cast import data_round_half_up
from auron_tpu.exprs.values import flat, literal_column, promote, string_col
from auron_tpu.ir.schema import DataType, TypeId


def eval_scalar_function(e, ctx):
    from auron_tpu.exprs.compiler import _typed_multiply, evaluate
    name = e.name
    product = _typed_multiply(e)
    if product is not None and e.return_type.is_decimal:
        # Spark 3 types a decimal product by the CheckOverflow above it;
        # with a wide decimal in it (the stage program alone holds one)
        # the product is exact (exprs/decimal128.py)
        from auron_tpu.exprs import decimal128 as dec128
        from auron_tpu.exprs.typing import infer_type
        types = [infer_type(x, ctx.schema)
                 for x in (product.left, product.right)]
        if all(t.is_decimal for t in types) and any(
                t.is_wide_decimal for t in types + [e.return_type]):
            return dec128.multiply(
                evaluate(product.left, ctx), evaluate(product.right, ctx),
                product.left, product.right, e.return_type)
    args = [evaluate(a, ctx) for a in e.args]
    raw = [a.value if hasattr(a, "value") else None for a in e.args]
    fn = _FUNCS.get(name)
    if fn is None:
        raise NotImplementedError(f"device function {name!r}")
    return fn(args, raw, e, ctx)


def _all_valid(args: List[Any]):
    v = args[0].validity
    for a in args[1:]:
        v = jnp.logical_and(v, a.validity)
    return v


def _f64(col):
    if col.dtype.id == TypeId.DECIMAL:
        return col.data.astype(jnp.float64) / (10.0 ** col.dtype.scale)
    return col.data.astype(jnp.float64)


def _spark_log(args, raw, e, ctx):
    """Spark log: unary = ln(x); binary = log_base(x) with (base, x) arg
    order (Logarithm.nullSafeEval): NULL for x<=0 or base<=0; base==1 is
    allowed and yields ln(x)/0 = ±Inf/NaN per IEEE double division."""
    if len(args) == 1:
        return _unary_f64(jnp.log, domain=lambda x: ~(x <= 0),
                          domain_null=True)(args, raw, e, ctx)
    b, x = _f64(args[0]), _f64(args[1])
    valid = jnp.logical_and(args[0].validity, args[1].validity)
    # NaN inputs stay in-domain (Java `NaN <= 0` is false -> NaN result)
    ok = jnp.logical_not((x <= 0) | (b <= 0))
    valid = jnp.logical_and(valid, ok)
    out = jnp.log(jnp.where(ok, x, 1.0)) / jnp.log(jnp.where(ok, b, 2.0))
    return flat(DataType.float64(), out, valid)


def _unary_f64(jfn, domain=None, domain_null=False):
    """domain_null=True: out-of-domain rows become NULL (the Spark
    UnaryLogExpression contract); False: NaN with validity kept (the
    UnaryMathExpression contract, e.g. acos/sqrt)."""
    def impl(args, raw, e, ctx):
        x = _f64(args[0])
        valid = args[0].validity
        if domain is not None:
            ok = domain(x)
            x = jnp.where(ok, x, 1.0)
            out = jnp.where(ok, jfn(x), jnp.nan)
            if domain_null:
                valid = jnp.logical_and(valid, ok)
        else:
            out = jfn(x)
        return flat(DataType.float64(), out, valid)
    return impl


def _math_binary(jfn):
    def impl(args, raw, e, ctx):
        return flat(DataType.float64(), jfn(_f64(args[0]), _f64(args[1])),
                    _all_valid(args))
    return impl


# -- rounding ---------------------------------------------------------------

def _round(args, raw, e, ctx):
    c = args[0]
    scale = int(raw[1]) if len(raw) > 1 and raw[1] is not None else 0
    if c.dtype.id == TypeId.DECIMAL:
        # returns same decimal type rounded at `scale`
        shift = c.dtype.scale - scale
        if shift <= 0:
            return c
        div = 10 ** shift
        from auron_tpu.exprs.cast import rescale_half_up
        return flat(c.dtype, rescale_half_up(c.data, div) * div, c.validity)
    if c.dtype.is_integral:
        if scale >= 0:
            return c
        m = 10 ** (-scale)
        half = m // 2
        q = _signed_div_round(c.data, m, half)
        return flat(c.dtype, q * m, c.validity)
    m = 10.0 ** scale
    return flat(c.dtype, (data_round_half_up(_f64(c) * m) / m).astype(
        c.data.dtype), c.validity)


def _signed_div_round(x, m: int, half: int):
    q = jnp.abs(x) // m
    rem = jnp.abs(x) - q * m
    q = q + (rem >= half).astype(q.dtype)
    return jnp.sign(x) * q


def _bround(args, raw, e, ctx):
    """round-half-even at scale."""
    c = args[0]
    scale = int(raw[1]) if len(raw) > 1 and raw[1] is not None else 0
    x = _f64(c)
    m = 10.0 ** scale
    scaled = x * m
    fl = jnp.floor(scaled)
    diff = scaled - fl
    even_up = jnp.logical_and(diff == 0.5, (fl % 2) != 0)
    rounded = jnp.where(diff > 0.5, fl + 1,
                        jnp.where(diff < 0.5, fl, fl + even_up))
    out = rounded / m
    return flat(c.dtype if c.dtype.is_floating else DataType.float64(),
                out.astype(c.data.dtype if c.dtype.is_floating
                           else jnp.float64), c.validity)


# -- conditional ------------------------------------------------------------

def _coalesce(args, raw, e, ctx):
    out = args[0]
    if isinstance(out, DeviceStringColumn):
        w = max(a.width for a in args)
        data = S._pad_width(out.data, w)
        lens, valid = out.lengths, out.validity
        for a in args[1:]:
            use = jnp.logical_and(jnp.logical_not(valid), a.validity)
            data = jnp.where(use[:, None], S._pad_width(a.data, w), data)
            lens = jnp.where(use, a.lengths, lens)
            valid = jnp.logical_or(valid, a.validity)
        return string_col(out.dtype, data, lens, valid)
    data, valid = out.data, out.validity
    for a in args[1:]:
        use = jnp.logical_and(jnp.logical_not(valid), a.validity)
        data = jnp.where(use, a.data.astype(data.dtype), data)
        valid = jnp.logical_or(valid, a.validity)
    return flat(out.dtype, data, valid)


def _nvl2(args, raw, e, ctx):
    cond_valid = args[0].validity
    b, c = args[1], args[2]
    if isinstance(b, DeviceStringColumn):
        w = max(b.width, c.width)
        return string_col(
            b.dtype,
            jnp.where(cond_valid[:, None], S._pad_width(b.data, w),
                      S._pad_width(c.data, w)),
            jnp.where(cond_valid, b.lengths, c.lengths),
            jnp.where(cond_valid, b.validity, c.validity))
    return flat(b.dtype, jnp.where(cond_valid, b.data, c.data.astype(b.data.dtype)),
                jnp.where(cond_valid, b.validity, c.validity))


def _null_if(args, raw, e, ctx):
    from auron_tpu.exprs.compiler import _compare, _to_numeric
    a, b = args[0], args[1]
    if isinstance(a, DeviceStringColumn):
        eq = S.string_eq(a, b)
    else:
        t = promote(a.dtype, b.dtype)
        eq = _compare("==", _to_numeric(a, t), _to_numeric(b, t), t)
    kill = jnp.logical_and(eq, b.validity)
    if isinstance(a, DeviceStringColumn):
        return string_col(a.dtype, a.data, a.lengths,
                          jnp.logical_and(a.validity, jnp.logical_not(kill)))
    return flat(a.dtype, a.data,
                jnp.logical_and(a.validity, jnp.logical_not(kill)))


def _null_if_zero(args, raw, e, ctx):
    a = args[0]
    return flat(a.dtype, a.data,
                jnp.logical_and(a.validity, a.data != 0))


def _least_greatest(is_least: bool):
    def impl(args, raw, e, ctx):
        # skips nulls (Spark least/greatest ignore nulls); compares in the
        # promoted common type so mixed-width args don't truncate
        t = args[0].dtype
        for a in args[1:]:
            t = promote(t, a.dtype)
        from auron_tpu.exprs.compiler import _to_numeric
        data = _to_numeric(args[0], t)
        valid = args[0].validity
        for a in args[1:]:
            ad = _to_numeric(a, t)
            pick_other = jnp.logical_and(
                a.validity, jnp.logical_or(
                    jnp.logical_not(valid),
                    (ad < data) if is_least else (ad > data)))
            data = jnp.where(pick_other, ad, data)
            valid = jnp.logical_or(valid, a.validity)
        return flat(t, data, valid)
    return impl


# -- dates ------------------------------------------------------------------

def _date_fn(kernel, from_ts=False):
    def impl(args, raw, e, ctx):
        c = args[0]
        if c.dtype.id == TypeId.TIMESTAMP_US:
            days = D.ts_days(c.data)
        else:
            days = c.data.astype(jnp.int32)
        return flat(DataType.int32(), kernel(days), c.validity)
    return impl


def _ts_fn(kernel):
    def impl(args, raw, e, ctx):
        c = args[0]
        us = c.data if c.dtype.id == TypeId.TIMESTAMP_US else \
            c.data.astype(jnp.int64) * D.US_PER_DAY
        return flat(DataType.int32(), kernel(us), c.validity)
    return impl


def _make_date(args, raw, e, ctx):
    y, m, d = (a.data.astype(jnp.int32) for a in args[:3])
    days = D.make_date(y, m, d)
    ok = D.make_date_valid(y, m, d)
    return flat(DataType.date32(), days, jnp.logical_and(_all_valid(args), ok))


def _date_add(sign: int):
    def impl(args, raw, e, ctx):
        days = args[0].data.astype(jnp.int32)
        delta = args[1].data.astype(jnp.int32)
        return flat(DataType.date32(), days + sign * delta, _all_valid(args))
    return impl


def _datediff(args, raw, e, ctx):
    a = args[0].data.astype(jnp.int32)
    b = args[1].data.astype(jnp.int32)
    return flat(DataType.int32(), a - b, _all_valid(args))


def _last_day(args, raw, e, ctx):
    return flat(DataType.date32(), D.last_day(args[0].data.astype(jnp.int32)),
                args[0].validity)


def _date_trunc(args, raw, e, ctx):
    unit = str(raw[0])
    c = args[1]
    us = c.data if c.dtype.id == TypeId.TIMESTAMP_US else \
        c.data.astype(jnp.int64) * D.US_PER_DAY
    out = D.date_trunc_us(us, unit)
    return flat(DataType.timestamp_us(), out, c.validity)


def _months_between(args, raw, e, ctx):
    def to_days(c):
        return D.ts_days(c.data) if c.dtype.id == TypeId.TIMESTAMP_US \
            else c.data.astype(jnp.int32)
    out = D.months_between(to_days(args[0]), to_days(args[1]))
    return flat(DataType.float64(), out, _all_valid(args))


def _to_timestamp(mult: int):
    def impl(args, raw, e, ctx):
        c = args[0]
        return flat(DataType.timestamp_us(),
                    c.data.astype(jnp.int64) * mult, c.validity)
    return impl


def _unix_timestamp(args, raw, e, ctx):
    c = args[0]
    us = c.data if c.dtype.id == TypeId.TIMESTAMP_US else \
        c.data.astype(jnp.int64) * D.US_PER_DAY
    return flat(DataType.int64(), jnp.floor_divide(us, D.US_PER_SECOND),
                c.validity)


# -- hashes -----------------------------------------------------------------

def _murmur3(args, raw, e, ctx):
    h = H.hash_columns(args, seed=42, capacity=ctx.capacity)
    return DeviceColumn(DataType.int32(), h,
                        jnp.ones(ctx.capacity, bool))


def _xxhash64(args, raw, e, ctx):
    h = jnp.full(ctx.capacity, np.uint64(42), jnp.uint64)
    for c in args:
        if isinstance(c, DeviceStringColumn):
            raise NotImplementedError("xxhash64 over strings runs on host")
        hh = H.xxh64_int64(c.data.astype(jnp.int64), h)
        h = jnp.where(c.validity, hh, h)
    return DeviceColumn(DataType.int64(), h.astype(jnp.int64),
                        jnp.ones(ctx.capacity, bool))


# -- strings ----------------------------------------------------------------

def _str_unary(kernel):
    def impl(args, raw, e, ctx):
        return kernel(args[0])
    return impl


def _str_pred(kernel):
    def impl(args, raw, e, ctx):
        needle = (raw[1] or "").encode("utf-8")
        return flat(DataType.bool_(), kernel(args[0], needle),
                    args[0].validity)
    return impl


def _substr(args, raw, e, ctx):
    c = args[0]
    start = args[1].data.astype(jnp.int32)
    if len(args) > 2:
        length = args[2].data.astype(jnp.int32)
    else:
        length = jnp.full(ctx.capacity, 2**30, jnp.int32)
    out = S.substr(c, start, length)
    return string_col(out.dtype, out.data, out.lengths, _all_valid(args))


def _concat(args, raw, e, ctx):
    return S.concat(args, DataType.string())


def _trim_fn(left: bool, right: bool):
    def impl(args, raw, e, ctx):
        return S.trim(args[0], left_side=left, right_side=right)
    return impl


def _lpad(args, raw, e, ctx):
    pad = (raw[2] if len(raw) > 2 and raw[2] is not None else " ").encode()
    return S.lpad(args[0], int(raw[1]), pad)


def _rpad(args, raw, e, ctx):
    pad = (raw[2] if len(raw) > 2 and raw[2] is not None else " ").encode()
    return S.rpad(args[0], int(raw[1]), pad)


def _repeat(args, raw, e, ctx):
    return S.repeat(args[0], int(raw[1]))


def _strpos(args, raw, e, ctx):
    needle = (raw[1] or "").encode()
    return flat(DataType.int32(), S.strpos(args[0], needle), args[0].validity)


def _left_right(is_left: bool):
    def impl(args, raw, e, ctx):
        k = args[1].data.astype(jnp.int32)
        out = S.left(args[0], k) if is_left else S.right(args[0], k)
        return string_col(out.dtype, out.data, out.lengths, _all_valid(args))
    return impl


# -- decimals ---------------------------------------------------------------

def _check_overflow(args, raw, e, ctx):
    c = args[0]
    dst = e.return_type if e.return_type.id == TypeId.DECIMAL else c.dtype
    from auron_tpu.exprs.cast import cast_column
    return cast_column(c, dst)


def _make_decimal(args, raw, e, ctx):
    c = args[0]  # int64 unscaled
    dst = e.return_type if e.return_type.id == TypeId.DECIMAL \
        else DataType.decimal(18, 0)
    bound = 10 ** dst.precision
    ok = jnp.logical_and(c.data > -bound, c.data < bound)
    return flat(dst, c.data.astype(jnp.int64),
                jnp.logical_and(c.validity, ok))


def _unscaled_value(args, raw, e, ctx):
    return flat(DataType.int64(), args[0].data.astype(jnp.int64),
                args[0].validity)


def _normalize_nan_and_zero(args, raw, e, ctx):
    c = args[0]
    x = c.data
    x = jnp.where(x == 0.0, jnp.zeros((), x.dtype), x)       # -0.0 -> +0.0
    x = jnp.where(jnp.isnan(x), jnp.full((), jnp.nan, x.dtype), x)
    return flat(c.dtype, x, c.validity)


def _is_nan(args, raw, e, ctx):
    c = args[0]
    data = jnp.isnan(c.data) if c.dtype.is_floating \
        else jnp.zeros(ctx.capacity, bool)
    return flat(DataType.bool_(), jnp.where(c.validity, data, False),
                jnp.ones(ctx.capacity, bool))


def _abs(args, raw, e, ctx):
    c = args[0]
    return flat(c.dtype, jnp.abs(c.data), c.validity)


def _signum(args, raw, e, ctx):
    c = args[0]
    return flat(DataType.float64(), jnp.sign(_f64(c)), c.validity)


def _ceil_floor(is_ceil: bool):
    def impl(args, raw, e, ctx):
        c = args[0]
        if c.dtype.is_integral:
            return c
        x = jnp.ceil(_f64(c)) if is_ceil else jnp.floor(_f64(c))
        # Java .toLong semantics: NaN -> 0, +/-inf clamps (astype on NaN is
        # platform-undefined, make it explicit)
        nan = jnp.isnan(x)
        clamped = jnp.clip(jnp.where(nan, 0.0, x), -(2.0**63), 2.0**63 - 1)
        out = jnp.where(nan, 0, clamped.astype(jnp.int64))
        return flat(DataType.int64(), out, c.validity)
    return impl


def _factorial(args, raw, e, ctx):
    c = args[0]
    n = c.data.astype(jnp.int64)
    table = np.ones(21, dtype=np.int64)
    for i in range(2, 21):
        table[i] = table[i - 1] * i
    t = jnp.asarray(table)
    ok = jnp.logical_and(n >= 0, n <= 20)
    out = t[jnp.clip(n, 0, 20)]
    return flat(DataType.int64(), out, jnp.logical_and(c.validity, ok))


_FUNCS = {
    # math
    "abs": _abs,
    "acos": _unary_f64(jnp.arccos, domain=lambda x: jnp.abs(x) <= 1),
    "acosh": _unary_f64(jnp.arccosh, domain=lambda x: x >= 1),
    "asin": _unary_f64(jnp.arcsin, domain=lambda x: jnp.abs(x) <= 1),
    "atan": _unary_f64(jnp.arctan),
    "atan2": _math_binary(jnp.arctan2),
    "ceil": _ceil_floor(True),
    "floor": _ceil_floor(False),
    "cos": _unary_f64(jnp.cos),
    "cosh": _unary_f64(jnp.cosh),
    "exp": _unary_f64(jnp.exp),
    "expm1": _unary_f64(jnp.expm1),
    # log family: Spark UnaryLogExpression -> NULL outside the domain
    "ln": _unary_f64(jnp.log, domain=lambda x: ~(x <= 0),
                     domain_null=True),
    "log": _spark_log,
    "log10": _unary_f64(jnp.log10, domain=lambda x: ~(x <= 0),
                        domain_null=True),
    "log2": _unary_f64(jnp.log2, domain=lambda x: ~(x <= 0),
                       domain_null=True),
    "power": _math_binary(jnp.power),
    "round": _round,
    "bround": _bround,
    "signum": _signum,
    "sin": _unary_f64(jnp.sin),
    "sinh": _unary_f64(jnp.sinh),
    "sqrt": _unary_f64(jnp.sqrt, domain=lambda x: x >= 0),
    "tan": _unary_f64(jnp.tan),
    "tanh": _unary_f64(jnp.tanh),
    "trunc": _unary_f64(jnp.trunc),
    "factorial": _factorial,
    "is_nan": _is_nan,
    # conditional
    "coalesce": _coalesce,
    "nvl": _coalesce,
    "nvl2": _nvl2,
    "null_if": _null_if,
    "null_if_zero": _null_if_zero,
    "least": _least_greatest(True),
    "greatest": _least_greatest(False),
    # dates
    "year": _date_fn(D.year),
    "quarter": _date_fn(D.quarter),
    "month": _date_fn(D.month),
    "day": _date_fn(D.day),
    "day_of_week": _date_fn(D.day_of_week),
    "week_of_year": _date_fn(D.week_of_year),
    "hour": _ts_fn(D.hour),
    "minute": _ts_fn(D.minute),
    "second": _ts_fn(D.second),
    "make_date": _make_date,
    "date_add": _date_add(1),
    "date_sub": _date_add(-1),
    "datediff": _datediff,
    "last_day": _last_day,
    "date_trunc": _date_trunc,
    "months_between": _months_between,
    "to_timestamp_seconds": _to_timestamp(1_000_000),
    "to_timestamp_millis": _to_timestamp(1_000),
    "to_timestamp_micros": _to_timestamp(1),
    "unix_timestamp": _unix_timestamp,
    # hashes
    "murmur3_hash": _murmur3,
    "xxhash64": _xxhash64,
    # strings
    "upper": _str_unary(S.upper),
    "lower": _str_unary(S.lower),
    "reverse": _str_unary(S.reverse),
    "character_length": lambda a, r, e, c: flat(
        DataType.int32(), S.char_length(a[0]), a[0].validity),
    "octet_length": lambda a, r, e, c: flat(
        DataType.int32(), a[0].lengths, a[0].validity),
    "bit_length": lambda a, r, e, c: flat(
        DataType.int32(), a[0].lengths * 8, a[0].validity),
    "ascii": lambda a, r, e, c: flat(
        DataType.int32(), S.ascii_code(a[0]), a[0].validity),
    "substr": _substr,
    "left": _left_right(True),
    "right": _left_right(False),
    "trim": _trim_fn(True, True),
    "btrim": _trim_fn(True, True),
    "ltrim": _trim_fn(True, False),
    "rtrim": _trim_fn(False, True),
    "concat": _concat,
    "lpad": _lpad,
    "rpad": _rpad,
    "repeat": _repeat,
    "strpos": _strpos,
    "starts_with": _str_pred(S.starts_with),
    "ends_with": _str_pred(S.ends_with),
    "contains": _str_pred(S.contains),
    # decimal/spark-specific
    "check_overflow": _check_overflow,
    "make_decimal": _make_decimal,
    "unscaled_value": _unscaled_value,
    "normalize_nan_and_zero": _normalize_nan_and_zero,
}
