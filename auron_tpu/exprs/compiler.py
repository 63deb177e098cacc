"""Device expression compiler.

`build_evaluator(exprs, schema)` returns a `CompiledExprs` that evaluates an
expression list over a Batch: device-capable subtrees become one jitted jnp
program (with common-subexpression caching — the CachedExprsEvaluator
analogue); host-only subtrees ("islands": regex, json, nested types, UDFs,
host-resident columns) are evaluated by exprs.host_eval over the Arrow view
and spliced in as extra device inputs before the jitted program runs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field as dfield
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import (
    Batch, DeviceColumn, DeviceDecimal128Column, DeviceStringColumn,
    HostColumn, is_device_type, stage_holds,
)
from auron_tpu.columnar.arrow_interop import arrow_array_to_column
from auron_tpu.exprs import datetime as dt_kernels
from auron_tpu.exprs import decimal128 as dec128
from auron_tpu.exprs import hashing
from auron_tpu.exprs import strings_device as S
from auron_tpu.exprs.cast import cast_column
from auron_tpu.exprs.typing import infer_type
from auron_tpu.exprs.values import (
    flat, literal_column, promote, string_col,
)
from auron_tpu.ir import expr as E
from auron_tpu.ir.node import Node
from auron_tpu.ir.schema import DataType, Schema, TypeId

Col = Any

# Expr kinds that always require host evaluation
_HOST_KINDS = {"py_udf_wrapper", "get_indexed_field", "get_map_value",
               "named_struct"}
# functions with device kernels (everything else goes to host islands)
_DEVICE_FUNCS = {
    "abs", "acos", "asin", "atan", "atan2", "ceil", "cos", "cosh", "exp",
    "expm1", "floor", "ln", "log", "log10", "log2", "power", "round",
    "bround", "signum", "sin", "sinh", "sqrt", "tan", "tanh", "trunc",
    "is_nan", "null_if", "null_if_zero", "nvl", "nvl2", "coalesce", "least",
    "greatest", "year", "quarter", "month", "day", "day_of_week",
    "week_of_year", "hour", "minute", "second", "last_day", "make_date",
    "date_add", "date_sub", "datediff", "date_trunc", "months_between",
    "to_timestamp_seconds", "to_timestamp_millis", "to_timestamp_micros",
    "unix_timestamp", "murmur3_hash", "xxhash64", "upper", "lower",
    "character_length", "bit_length", "octet_length", "ascii", "substr",
    "left", "right", "trim", "ltrim", "rtrim", "btrim", "starts_with",
    "ends_with", "contains", "strpos", "reverse", "concat", "lpad", "rpad",
    "repeat", "check_overflow", "make_decimal", "unscaled_value",
    "normalize_nan_and_zero", "acosh",
}


_ROW_BASE_KINDS = {"row_num", "monotonically_increasing_id"}


def _tree_has_row_base(e: Node) -> bool:
    """Does this expr (sub)tree read the running row offset?  Operators
    only track row_base (a per-batch host count, i.e. a sync on lazy
    batches) when an expression actually needs it."""
    from auron_tpu.ir.node import tree_has_kind
    return tree_has_kind(e, _ROW_BASE_KINDS)


def _is_literal(e: E.Expr) -> bool:
    return e.kind in ("literal", "scalar_subquery")


def _lit_value(e: E.Expr):
    return e.value


# ---------------------------------------------------------------------------
# device capability analysis
# ---------------------------------------------------------------------------

def device_capable(expr: E.Expr, schema: Schema,
                   host_cols: frozenset, wide: bool = False) -> bool:
    """Can this whole subtree run on device?  `wide`: the caller is the
    stage program, which also holds decimals of 19-38 digits
    (`stage_holds`) and evaluates the expressions `_wide_verdict` names
    over them."""
    k = expr.kind
    if k in _HOST_KINDS:
        return False
    holds = stage_holds if wide else is_device_type
    if k == "column":
        try:
            i = schema.index_of(expr.name)
        except KeyError:
            return False
        return expr.name not in host_cols and holds(schema[i].dtype)
    if k == "bound_reference":
        return holds(schema[expr.index].dtype)
    if k == "literal" or k == "scalar_subquery":
        dt = expr.dtype
        return holds(dt) or dt.id == TypeId.NULL
    if wide:
        verdict = _wide_verdict(expr, schema, host_cols)
        if verdict is not None:
            return verdict
    if k == "scalar_function":
        if expr.name not in _DEVICE_FUNCS:
            return False
        if expr.name in ("upper", "lower", "lpad", "rpad"):
            # byte-level kernels: exact only for ASCII (case mapping; pad
            # target counts).  Opt-in via config, else exact host path.
            from auron_tpu.config import conf
            if not conf.get("auron.string.ascii.case.enable"):
                return False
        # substr/lpad/... with non-literal control args fall back to host
        if expr.name in ("lpad", "rpad", "repeat") and \
                any(not _is_literal(a) for a in expr.args[1:]):
            return False
        if expr.name in ("starts_with", "ends_with", "contains", "strpos") \
                and len(expr.args) > 1 and not _is_literal(expr.args[1]):
            return False
        if expr.name in ("trim", "btrim", "ltrim", "rtrim") \
                and len(expr.args) > 1:
            # trim(str, trimChars) form: device kernel only strips spaces
            return False
        if expr.name == "date_trunc" and not _is_literal(expr.args[0]):
            return False
    if k == "like":
        # device path only for patterns reducible to prefix/suffix/infix/eq
        if not _is_literal(expr.pattern) or expr.case_insensitive:
            return False
        if _translate_like(_lit_value(expr.pattern)) is None:
            return False
    if k == "cast" or k == "try_cast":
        src = infer_type(expr.child, schema)
        if not _device_cast_ok(src, expr.dtype):
            return False
    if k == "wire_udf":
        # args evaluate in the ENCLOSING schema, the body under the
        # param schema — the generic children walk below would wrongly
        # resolve the body's param references against the outer schema
        from auron_tpu.exprs.typing import wire_udf_param_schema
        try:
            pschema = wire_udf_param_schema(expr, schema)  # validates
        except (TypeError, KeyError):
            return False
        return (all(device_capable(a, schema, host_cols, wide)
                    for a in expr.args) and
                device_capable(expr.body, pschema, frozenset(), wide))
    try:
        dt = infer_type(expr, schema)
        if not (is_device_type(dt) or dt.id == TypeId.NULL):
            return False
    except (TypeError, KeyError):
        return False
    return all(device_capable(c, schema, host_cols, wide)
               for c in _expr_children(expr))


def _typed_multiply(expr: E.Expr) -> Optional[E.BinaryExpr]:
    """The product under `CheckOverflow(Multiply(l, r), type)`, the form in
    which Spark 3 types a decimal product; None for anything else."""
    if expr.kind == "scalar_function" and expr.name == "check_overflow" \
            and expr.args and expr.args[0].kind == "binary" \
            and expr.args[0].op == "*":
        return expr.args[0]
    return None


def _wide_verdict(expr: E.Expr, schema: Schema,
                  host_cols: frozenset) -> Optional[bool]:
    """Whether the stage program evaluates `expr`, where a wide decimal is
    its result or one of its operands; None where none is, and the rules
    for everything else decide.  Over wide decimals the program has casts
    between decimals, a typed product with a one-word factor, comparisons
    at one scale, and the null tests (exprs/decimal128.py)."""
    product = _typed_multiply(expr)
    operands = [product.left, product.right] if product is not None \
        else _expr_children(expr)
    try:
        out = infer_type(expr, schema)
        types = [infer_type(c, schema) for c in operands]
    except (TypeError, KeyError):
        return False
    if not (out.is_wide_decimal or any(t.is_wide_decimal for t in types)):
        return None
    k = expr.kind
    if product is not None:
        ok = dec128.multiply_ok(types[0], types[1], product.left,
                                product.right, out)
    elif k in ("is_null", "is_not_null"):
        ok = True
    elif k in ("cast", "try_cast") or (
            k == "scalar_function" and expr.name == "check_overflow"):
        ok = dec128.cast_ok(types[0], out)
    elif k == "binary" and expr.op in ("==", "=", "!=", "<", "<=", ">",
                                       ">=", "<=>"):
        ok = dec128.compare_ok(types[0], types[1])
    else:
        ok = False
    return ok and all(device_capable(c, schema, host_cols, True)
                      for c in operands)


def _expr_children(expr: Node) -> List[E.Expr]:
    out = []
    for c in expr.children_nodes():
        if isinstance(c, E.Expr):
            out.append(c)
        elif isinstance(c, Node):
            out.extend(_expr_children(c))
    return out


def _device_cast_ok(src: DataType, dst: DataType) -> bool:
    # string parsing casts run on host (full spark semantics incl. trim,
    # scientific notation); everything numeric/temporal is device
    if src.is_stringlike and not dst.is_stringlike:
        return False
    if dst.is_stringlike and not src.is_stringlike:
        # int -> string formatting is device-capable (digits kernel);
        # float/decimal formatting goes host for exact Spark text
        return src.is_integral or src.id in (TypeId.BOOL,)
    if src.is_nested or dst.is_nested:
        return False
    return True


def _translate_like(pattern: str) -> Optional[Tuple[str, str]]:
    """Translate a LIKE pattern into (mode, needle) where mode in
    {eq, prefix, suffix, infix}; None if it needs the host regex path."""
    if pattern is None:
        return None
    if "_" in pattern:
        return None
    body = pattern.strip("%")
    if "%" in body or "\\" in body:
        return None
    starts = pattern.startswith("%")
    ends_p = pattern.endswith("%")
    if not starts and not ends_p:
        return ("eq", pattern)
    if starts and ends_p:
        return ("infix", body)
    if ends_p:
        return ("prefix", body)
    return ("suffix", body)


# ---------------------------------------------------------------------------
# evaluation context
# ---------------------------------------------------------------------------

@dataclass
class EvalCtx:
    cols: List[Col]                  # device columns (schema order + islands)
    schema: Schema                   # logical schema incl. island columns
    num_rows: Any                    # traced int32 scalar
    capacity: int
    partition_id: Any = 0            # traced or python int
    row_base: Any = 0                # rows emitted before this batch
    cse: Dict[str, Col] = dfield(default_factory=dict)

    def col_by_name(self, name: str) -> Col:
        return self.cols[self.schema.index_of(name)]


# ---------------------------------------------------------------------------
# the dispatcher
# ---------------------------------------------------------------------------

def evaluate(expr: E.Expr, ctx: EvalCtx) -> Col:
    key = None
    if expr.kind not in ("column", "bound_reference", "literal"):
        import json as _json
        key = _json.dumps(expr.to_dict(), sort_keys=True, default=str)
        hit = ctx.cse.get(key)
        if hit is not None:
            return hit
    out = _evaluate(expr, ctx)
    if key is not None:
        ctx.cse[key] = out
    return out


def _evaluate(expr: E.Expr, ctx: EvalCtx) -> Col:
    k = expr.kind
    fn = _DISPATCH.get(k)
    if fn is None:
        raise NotImplementedError(f"device eval for expr kind {k!r}")
    return fn(expr, ctx)


def _eval_column(e: E.Column, ctx: EvalCtx) -> Col:
    return ctx.col_by_name(e.name)


def _eval_bound(e: E.BoundReference, ctx: EvalCtx) -> Col:
    return ctx.cols[e.index]


def _eval_literal(e, ctx: EvalCtx) -> Col:
    dt = e.dtype
    if dt.is_wide_decimal and e.value is not None:
        return dec128.literal_column(e.value, dt, ctx.capacity)
    return literal_column(e.value, dt, ctx.capacity)


def _eval_wire_udf(e: "E.WireUdf", ctx: EvalCtx) -> Col:
    from auron_tpu.exprs.typing import wire_udf_param_schema
    pschema = wire_udf_param_schema(e, ctx.schema)
    arg_cols = [evaluate(a, ctx) for a in e.args]
    # fresh cse: the body's param names would collide across call sites
    sub = EvalCtx(cols=arg_cols, schema=pschema, num_rows=ctx.num_rows,
                  capacity=ctx.capacity, partition_id=ctx.partition_id,
                  row_base=ctx.row_base)
    return evaluate(e.body, sub)


def _eval_is_null(e: E.IsNull, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return DeviceColumn(DataType.bool_(), jnp.logical_not(c.validity),
                        jnp.ones(ctx.capacity, bool))


def _eval_is_not_null(e: E.IsNotNull, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return DeviceColumn(DataType.bool_(), c.validity,
                        jnp.ones(ctx.capacity, bool))


def _eval_not(e: E.Not, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return flat(DataType.bool_(), jnp.logical_not(c.data.astype(bool)),
                c.validity)


def _eval_negative(e: E.Negative, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return flat(c.dtype, -c.data, c.validity)


def _to_numeric(col: Col, target: DataType) -> Any:
    """Raw data as the target numeric dtype (decimal => float via scale,
    unless target is the same decimal)."""
    if col.dtype.id == TypeId.DECIMAL and target.id != TypeId.DECIMAL:
        return col.data.astype(jnp.float64) / (10.0 ** col.dtype.scale)
    if target.id == TypeId.DECIMAL:
        return col.data  # unscaled passthrough (same-scale ops only)
    return col.data.astype(target.numpy_dtype())


def _eval_binary(e: E.BinaryExpr, ctx: EvalCtx) -> Col:
    op = e.op
    if op in ("and", "or"):
        return _kleene(op, evaluate(e.left, ctx), evaluate(e.right, ctx))
    l = evaluate(e.left, ctx)
    r = evaluate(e.right, ctx)
    if isinstance(l, DeviceStringColumn) or isinstance(r, DeviceStringColumn):
        return _string_binary(op, l, r, ctx)
    if isinstance(l, DeviceDecimal128Column) or \
            isinstance(r, DeviceDecimal128Column):
        # comparisons alone (`_wide_verdict`); a product is typed by the
        # CheckOverflow above it (functions_device.eval_scalar_function)
        data, both = dec128.compare(op, l, r)
        if op == "<=>":
            eq_nulls = jnp.logical_and(jnp.logical_not(l.validity),
                                       jnp.logical_not(r.validity))
            return flat(DataType.bool_(), jnp.where(both, data, eq_nulls),
                        jnp.ones(ctx.capacity, bool))
        return flat(DataType.bool_(), data, both)
    both = jnp.logical_and(l.validity, r.validity)
    if op in ("==", "=", "!=", "<", "<=", ">", ">=", "<=>"):
        t = promote(l.dtype, r.dtype)
        a, b = _to_numeric(l, t), _to_numeric(r, t)
        data = _compare(op, a, b, t)
        if op == "<=>":  # null-safe equal
            eq_nulls = jnp.logical_and(jnp.logical_not(l.validity),
                                       jnp.logical_not(r.validity))
            data = jnp.where(both, data, eq_nulls)
            return flat(DataType.bool_(), data, jnp.ones(ctx.capacity, bool))
        return flat(DataType.bool_(), data, both)
    # date arithmetic
    if l.dtype.id == TypeId.DATE32 and op in ("+", "-"):
        if r.dtype.id == TypeId.DATE32 and op == "-":
            return flat(DataType.int32(),
                        l.data.astype(jnp.int32) - r.data.astype(jnp.int32),
                        both)
        delta = r.data.astype(jnp.int32)
        data = l.data + (delta if op == "+" else -delta)
        return flat(DataType.date32(), data.astype(jnp.int32), both)
    t = _binary_result_type(op, l.dtype, r.dtype)
    a, b = _to_numeric(l, t), _to_numeric(r, t)
    if op == "+":
        data = a + b
    elif op == "-":
        data = a - b
    elif op == "*":
        data = a * b
    elif op == "/":
        if t.is_floating:
            zero = b == 0
            data = a / jnp.where(zero, 1, b)
            both = jnp.logical_and(both, jnp.logical_not(zero))  # spark: null
        else:
            zero = b == 0
            data = _int_div(a, jnp.where(zero, 1, b))
            both = jnp.logical_and(both, jnp.logical_not(zero))
    elif op in ("%", "mod"):
        zero = b == 0
        bb = jnp.where(zero, 1, b)
        data = a - _trunc_div(a, bb) * bb if t.is_floating else \
            jnp.sign(a) * (jnp.abs(a) % jnp.abs(bb))
        both = jnp.logical_and(both, jnp.logical_not(zero))
    elif op == "&":
        data = a & b
    elif op == "|":
        data = a | b
    elif op == "^":
        data = a ^ b
    elif op == "<<":
        data = a << (b.astype(a.dtype) % (a.dtype.itemsize * 8))
    elif op == ">>":
        data = a >> (b.astype(a.dtype) % (a.dtype.itemsize * 8))
    else:
        raise NotImplementedError(f"binary op {op!r}")
    if t.id == TypeId.DECIMAL and data.dtype != jnp.int64:
        data = data.astype(jnp.int64)
    return flat(t, data, both)


def _binary_result_type(op: str, lt: DataType, rt: DataType) -> DataType:
    if op == "/":
        if lt.is_decimal or rt.is_decimal:
            return DataType.float64()
        if lt.is_integral and rt.is_integral:
            return DataType.float64()
    if lt.id == TypeId.DECIMAL and rt.id == TypeId.DECIMAL \
            and lt.scale == rt.scale and op in ("+", "-"):
        return DataType.decimal(min(max(lt.precision, rt.precision) + 1, 18),
                                lt.scale)
    return promote(lt, rt)


def _int_div(a, b):
    """Truncated (toward zero) integer division, Java/Spark semantics."""
    q = jnp.abs(a) // jnp.abs(b)
    return jnp.sign(a) * jnp.sign(b) * q


def _trunc_div(a, b):
    return jnp.trunc(a / b)


def _compare(op: str, a, b, t: DataType):
    if t.is_floating:
        an, bn = jnp.isnan(a), jnp.isnan(b)
        eq = jnp.logical_or(jnp.logical_and(an, bn),
                            jnp.logical_and(jnp.logical_and(~an, ~bn), a == b))
        lt = jnp.logical_or(jnp.logical_and(~an, bn),
                            jnp.logical_and(jnp.logical_and(~an, ~bn), a < b))
    else:
        eq = a == b
        lt = a < b
    if op in ("==", "=", "<=>"):
        return eq
    if op == "!=":
        return jnp.logical_not(eq)
    if op == "<":
        return lt
    if op == "<=":
        return jnp.logical_or(lt, eq)
    if op == ">":
        return jnp.logical_not(jnp.logical_or(lt, eq))
    if op == ">=":
        return jnp.logical_not(lt)
    raise NotImplementedError(op)


def _string_binary(op: str, l: Col, r: Col, ctx: EvalCtx) -> Col:
    if not isinstance(l, DeviceStringColumn) or \
            not isinstance(r, DeviceStringColumn):
        raise TypeError("string binary op requires two string columns")
    both = jnp.logical_and(l.validity, r.validity)
    if op in ("==", "=", "<=>"):
        data = S.string_eq(l, r)
    elif op == "!=":
        data = jnp.logical_not(S.string_eq(l, r))
    else:
        c = S.string_cmp(l, r)
        data = {"<": c < 0, "<=": c <= 0, ">": c > 0, ">=": c >= 0}[op]
    if op == "<=>":
        eq_nulls = jnp.logical_and(jnp.logical_not(l.validity),
                                   jnp.logical_not(r.validity))
        return flat(DataType.bool_(), jnp.where(both, data, eq_nulls),
                    jnp.ones(ctx.capacity, bool))
    return flat(DataType.bool_(), data, both)


def _kleene(op: str, l: Col, r: Col) -> Col:
    a, av = l.data.astype(bool), l.validity
    b, bv = r.data.astype(bool), r.validity
    if op == "and":
        data = jnp.logical_and(jnp.where(av, a, True), jnp.where(bv, b, True))
        valid = jnp.logical_or(
            jnp.logical_and(av, bv),
            jnp.logical_or(jnp.logical_and(av, jnp.logical_not(a)),
                           jnp.logical_and(bv, jnp.logical_not(b))))
    else:
        data = jnp.logical_or(jnp.where(av, a, False), jnp.where(bv, b, False))
        valid = jnp.logical_or(
            jnp.logical_and(av, bv),
            jnp.logical_or(jnp.logical_and(av, a), jnp.logical_and(bv, b)))
    return flat(DataType.bool_(), data, valid)


def _eval_sc_and(e: E.ScAnd, ctx: EvalCtx) -> Col:
    # vectorized execution evaluates both sides; short-circuit is a
    # sequential-engine optimization, semantics equal Kleene AND
    return _kleene("and", evaluate(e.left, ctx), evaluate(e.right, ctx))


def _eval_sc_or(e: E.ScOr, ctx: EvalCtx) -> Col:
    return _kleene("or", evaluate(e.left, ctx), evaluate(e.right, ctx))


def _eval_case(e: E.Case, ctx: EvalCtx) -> Col:
    branches = [(evaluate(b.when, ctx), evaluate(b.then, ctx))
                for b in e.branches]
    else_col = evaluate(e.else_expr, ctx) if e.else_expr is not None else None
    # result type: the engine's own inference over ALL branch/else
    # values (the host evaluator's policy).  Taking any single value's
    # dtype is wrong twice over: a null-literal first branch poisons
    # the accumulator to its bool placeholder, and an int THEN beside
    # a float ELSE truncates the float (q39's `CASE mean WHEN 0 THEN 0
    # ELSE stdev/mean END > 1` dropped every row).
    values = [t for _, t in branches] + \
        ([else_col] if else_col is not None else [])
    value_exprs = [b.then for b in e.branches] + \
        ([e.else_expr] if e.else_expr is not None else [])
    pick = values[0]
    for xe, xc in zip(value_exprs, values):
        if not (getattr(xe, "kind", None) == "literal" and
                xe.value is None):
            pick = xc
            break
    out_dtype = pick.dtype
    try:
        from auron_tpu.exprs.typing import infer_type
        inferred = infer_type(e, ctx.schema)
        if inferred is not None and inferred.id.name != "NULL":
            out_dtype = inferred
    except Exception:  # noqa: BLE001 - fall back to the value pick
        pass
    if isinstance(pick, DeviceStringColumn) or out_dtype.is_stringlike:
        return _case_strings(branches, else_col, ctx)
    # accumulator device dtype: jnp promotion across the non-null
    # values (logical types like date32 have no jnp equivalent; their
    # device data is already integral)
    real = [c for xe, c in zip(value_exprs, values)
            if not (getattr(xe, "kind", None) == "literal" and
                    xe.value is None) and
            not isinstance(c, DeviceStringColumn)]
    acc_dt = jnp.result_type(*[c.data.dtype for c in real]) \
        if real else pick.data.dtype
    data = jnp.zeros(ctx.capacity, dtype=acc_dt)
    valid = jnp.zeros(ctx.capacity, bool)
    decided = jnp.zeros(ctx.capacity, bool)
    for w, t in branches:
        fire = jnp.logical_and(jnp.logical_not(decided),
                               jnp.logical_and(w.validity, w.data.astype(bool)))
        data = jnp.where(fire, t.data.astype(data.dtype), data)
        valid = jnp.where(fire, t.validity, valid)
        decided = jnp.logical_or(decided, fire)
    if else_col is not None:
        rest = jnp.logical_not(decided)
        data = jnp.where(rest, else_col.data.astype(data.dtype), data)
        valid = jnp.where(rest, else_col.validity, valid)
    return flat(out_dtype, data, valid)


def _case_strings(branches, else_col, ctx: EvalCtx) -> Col:
    # null-literal branches carry a flat placeholder, not a string
    # column: they contribute no bytes, only a decided+invalid slot
    strs = [t for _, t in branches
            if isinstance(t, DeviceStringColumn)]
    if else_col is not None and isinstance(else_col, DeviceStringColumn):
        strs.append(else_col)
    if not strs:
        # every branch/else is a typed null literal (flat placeholder):
        # the result is an all-null string column — max() over the empty
        # width list used to ValueError at trace time (ADVICE r5)
        return string_col(DataType.string(),
                          jnp.zeros((ctx.capacity, 1), jnp.uint8),
                          jnp.zeros(ctx.capacity, jnp.int32),
                          jnp.zeros(ctx.capacity, bool))
    w_max = max(t.width for t in strs)
    dt = strs[0].dtype
    data = jnp.zeros((ctx.capacity, w_max), jnp.uint8)
    lens = jnp.zeros(ctx.capacity, jnp.int32)
    valid = jnp.zeros(ctx.capacity, bool)
    decided = jnp.zeros(ctx.capacity, bool)
    for w, t in branches:
        fire = jnp.logical_and(jnp.logical_not(decided),
                               jnp.logical_and(w.validity, w.data.astype(bool)))
        if isinstance(t, DeviceStringColumn):
            td = S._pad_width(t.data, w_max)
            data = jnp.where(fire[:, None], td, data)
            lens = jnp.where(fire, t.lengths, lens)
            valid = jnp.where(fire, t.validity, valid)
        decided = jnp.logical_or(decided, fire)
    if else_col is not None and isinstance(else_col, DeviceStringColumn):
        rest = jnp.logical_not(decided)
        ed = S._pad_width(else_col.data, w_max)
        data = jnp.where(rest[:, None], ed, data)
        lens = jnp.where(rest, else_col.lengths, lens)
        valid = jnp.where(rest, else_col.validity, valid)
    return string_col(dt, data, lens, valid)


def _eval_in_list(e: E.InList, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    hit = jnp.zeros(ctx.capacity, bool)
    any_null_lit = False
    for v in e.values:
        lv = evaluate(v, ctx)
        if isinstance(c, DeviceStringColumn):
            m = S.string_eq(c, lv)
        else:
            t = promote(c.dtype, lv.dtype)
            m = _compare("==", _to_numeric(c, t), _to_numeric(lv, t), t)
        m = jnp.logical_and(m, lv.validity)
        hit = jnp.logical_or(hit, m)
    data = jnp.logical_not(hit) if e.negated else hit
    # SQL semantics: x IN (..) is null when x is null, or when no match and
    # the list contains null; we approximate with child validity (front-ends
    # do not emit null literals in IN lists after optimization)
    return flat(DataType.bool_(), data, c.validity)


def _eval_cast(e, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return cast_column(c, e.dtype, try_=e.kind == "try_cast")


def _eval_like(e: E.Like, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    mode, needle = _translate_like(_lit_value(e.pattern))
    nb = needle.encode("utf-8")
    if mode == "eq":
        lv = literal_column(needle, DataType.string(), ctx.capacity)
        m = S.string_eq(c, lv)
    elif mode == "prefix":
        m = S.starts_with(c, nb)
    elif mode == "suffix":
        m = S.ends_with(c, nb)
    else:
        m = S.contains(c, nb)
    if e.negated:
        m = jnp.logical_not(m)
    return flat(DataType.bool_(), m, c.validity)


def _eval_string_starts_with(e, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return flat(DataType.bool_(), S.starts_with(c, e.prefix.encode()), c.validity)


def _eval_string_ends_with(e, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return flat(DataType.bool_(), S.ends_with(c, e.suffix.encode()), c.validity)


def _eval_string_contains(e, ctx: EvalCtx) -> Col:
    c = evaluate(e.child, ctx)
    return flat(DataType.bool_(), S.contains(c, e.infix.encode()), c.validity)


def _eval_row_num(e, ctx: EvalCtx) -> Col:
    rn = jnp.arange(ctx.capacity, dtype=jnp.int64) + \
        jnp.asarray(ctx.row_base, jnp.int64) + 1
    return DeviceColumn(DataType.int64(), rn, jnp.ones(ctx.capacity, bool))


def _eval_partition_id(e, ctx: EvalCtx) -> Col:
    pid = jnp.full(ctx.capacity, jnp.asarray(ctx.partition_id, jnp.int32))
    return DeviceColumn(DataType.int32(), pid, jnp.ones(ctx.capacity, bool))


def _eval_monotonic_id(e, ctx: EvalCtx) -> Col:
    base = jnp.asarray(ctx.partition_id, jnp.int64) << 33
    rn = jnp.arange(ctx.capacity, dtype=jnp.int64) + \
        jnp.asarray(ctx.row_base, jnp.int64)
    return DeviceColumn(DataType.int64(), base + rn,
                        jnp.ones(ctx.capacity, bool))


def _eval_scalar_subquery(e, ctx: EvalCtx) -> Col:
    return _eval_literal(e, ctx)


def _eval_bloom_might_contain(e, ctx: EvalCtx) -> Col:
    from auron_tpu.ops.agg.bloom import bloom_might_contain_expr
    return bloom_might_contain_expr(e, ctx)


_DISPATCH = {
    "column": _eval_column,
    "bound_reference": _eval_bound,
    "literal": _eval_literal,
    "binary": _eval_binary,
    "is_null": _eval_is_null,
    "is_not_null": _eval_is_not_null,
    "not": _eval_not,
    "negative": _eval_negative,
    "case": _eval_case,
    "in_list": _eval_in_list,
    "cast": _eval_cast,
    "try_cast": _eval_cast,
    "like": _eval_like,
    "sc_and": _eval_sc_and,
    "sc_or": _eval_sc_or,
    "string_starts_with": _eval_string_starts_with,
    "string_ends_with": _eval_string_ends_with,
    "string_contains": _eval_string_contains,
    "row_num": _eval_row_num,
    "partition_id": _eval_partition_id,
    "monotonically_increasing_id": _eval_monotonic_id,
    "scalar_subquery": _eval_scalar_subquery,
    "bloom_filter_might_contain": _eval_bloom_might_contain,
    "wire_udf": _eval_wire_udf,
}

# function dispatch lives in functions_device.py (registered lazily to keep
# import order simple)
from auron_tpu.exprs import functions_device  # noqa: E402

_DISPATCH["scalar_function"] = functions_device.eval_scalar_function


# ---------------------------------------------------------------------------
# compiled wrapper: island extraction + jit cache
# ---------------------------------------------------------------------------

class CompiledExprs:
    """Evaluates a fixed expr list over batches of a fixed input schema."""

    def __init__(self, exprs: Tuple[E.Expr, ...], schema: Schema):
        self.exprs = tuple(exprs)
        self.schema = schema
        self.uses_row_base = any(_tree_has_row_base(x) for x in self.exprs)
        self.out_types: List[DataType] = []
        # placeholder; resolved per batch because host-column placement can
        # depend on runtime column representation (oversize strings)
        for x in self.exprs:
            self.out_types.append(infer_type(x, schema))
        # per-call overhead caches: the island split walks device_capable
        # over every subtree and the kernel-cache key used to hash the
        # whole frozen-dataclass expr forest — ~40% of warm per-batch
        # host time in the q01 profile.  The split memoizes per
        # host-column set, and the structural key is serialized ONCE (a
        # flat string hashes in nanoseconds).
        self._split_cache: Dict[frozenset, Tuple] = {}
        self._struct_key: Optional[str] = None

    # -- island splitting ---------------------------------------------------

    def _split(self, host_cols: frozenset):
        """Returns (device_exprs, islands) where islands are (expr, name).

        Maximal-island strategy: any subtree that cannot run fully on device
        is host-evaluated whole and re-enters as a virtual input column —
        the analogue of Auron wrapping unconvertible exprs in a JVM-UDF call
        (NativeConverters.scala:277-324)."""
        islands: List[Tuple[E.Expr, str]] = []

        def rewrite(x: E.Expr) -> E.Expr:
            if device_capable(x, self.schema, host_cols):
                return x
            for prev, name in islands:
                if prev == x:
                    return E.Column(name=name)
            name = f"__island_{len(islands)}"
            islands.append((x, name))
            return E.Column(name=name)

        device_exprs = tuple(rewrite(x) for x in self.exprs)
        return device_exprs, islands

    def _split_cached(self, host_cols: frozenset):
        hit = self._split_cache.get(host_cols)
        if hit is None:
            hit = self._split(host_cols)
            self._split_cache[host_cols] = hit
        return hit

    def _structural_key(self) -> str:
        if self._struct_key is None:
            import json as _json
            self._struct_key = _json.dumps(
                [x.to_dict() for x in self.exprs]
                + [self.schema.to_dict()
                   if hasattr(self.schema, "to_dict")
                   else repr(self.schema)],
                sort_keys=True, separators=(",", ":"), default=str)
        return self._struct_key

    # -- main entry ---------------------------------------------------------

    def __call__(self, batch: Batch, partition_id: int = 0,
                 row_base: int = 0) -> List[Col]:
        host_cols = frozenset(
            f.name for f, c in zip(batch.schema, batch.columns)
            if isinstance(c, HostColumn))
        device_exprs, islands = self._split_cached(host_cols)
        work_schema = self.schema
        work_cols = list(batch.columns)
        if islands:
            from auron_tpu.exprs import host_eval
            from auron_tpu.ir.schema import Field
            rb = batch.to_arrow()
            extra_fields = []
            for ix, (iexpr, iname) in enumerate(islands):
                arr = host_eval.evaluate_arrow(iexpr, rb, self.schema,
                                               partition_id=partition_id,
                                               row_base=row_base)
                idt = infer_type(iexpr, self.schema)
                col = arrow_array_to_column(idt, arr, batch.capacity)
                extra_fields.append(Field(iname, idt))
                work_cols.append(col)
            work_schema = Schema(self.schema.fields + tuple(extra_fields))
        # outputs that are plain references to host-resident columns (nested
        # types, oversize strings) bypass the device program entirely
        name_to_col = {f.name: c for f, c in zip(work_schema, work_cols)}
        passthrough: Dict[int, Col] = {}
        run_exprs: List[E.Expr] = []
        for i, dx in enumerate(device_exprs):
            if dx.kind == "column" and isinstance(
                    name_to_col.get(dx.name), HostColumn):
                passthrough[i] = name_to_col[dx.name]
            else:
                run_exprs.append(dx)
        dev_in = [c for c in work_cols if not isinstance(c, HostColumn)]
        dev_schema = Schema(tuple(
            f for f, c in zip(work_schema, work_cols)
            if not isinstance(c, HostColumn)))
        outs: List[Col] = []
        if run_exprs:
            fn = self._get_jit(tuple(run_exprs), dev_schema, batch.capacity,
                               tuple(self._shape_sig(c) for c in dev_in),
                               host_cols)
            outs = list(fn(dev_in, batch.num_rows_dev(),
                           np.int32(partition_id),
                           np.int64(row_base)))
        result: List[Col] = []
        it = iter(outs)
        for i in range(len(device_exprs)):
            result.append(passthrough[i] if i in passthrough else next(it))
        return result

    def _shape_sig(self, c) -> Tuple:
        if isinstance(c, DeviceStringColumn):
            return ("s", c.capacity, c.width)
        return ("f", c.capacity, str(c.data.dtype))

    def _get_jit(self, device_exprs, dev_schema: Schema, capacity: int,
                 sig: Tuple, host_cols: frozenset = frozenset()):
        # module-global cache: operator instances are rebuilt per task, so a
        # per-instance cache would re-trace every execute_plan call.
        # cached_jit routes the `exprs` family through the jit-site
        # registry (runtime/jitcheck.py): a key regression that re-traces
        # per execute shows up as compile-manifest drift by site name
        from auron_tpu.ops.kernel_cache import cached_jit
        from auron_tpu.config import conf as _conf
        # case.sensitive is read at trace time (wire_udf param-dup
        # validation + column resolution) — cache-key rule: every
        # trace-time config read must appear in the kernel cache key.
        # The expr forest enters as ONE precomputed string (plus the
        # host-column set that determined the island split): hashing the
        # nested frozen dataclasses per batch was ~17ms/call in the warm
        # q01 profile; (struct_key, host_cols) determines device_exprs.
        key = ("exprs", self._structural_key(),
               tuple(sorted(host_cols)), dev_schema, capacity, sig,
               bool(_conf.get("auron.case.sensitive")),
               str(_conf.get("auron.sort.f64.exactbits")))

        def build():
            def run(cols, num_rows, partition_id, row_base):
                ctx = EvalCtx(cols=list(cols), schema=dev_schema,
                              num_rows=num_rows, capacity=capacity,
                              partition_id=partition_id, row_base=row_base)
                return [evaluate(x, ctx) for x in device_exprs]
            return run
        return cached_jit(key, build)


def build_evaluator(exprs, schema: Schema) -> CompiledExprs:
    return CompiledExprs(tuple(exprs), schema)


def build_predicate(predicates, schema: Schema) -> CompiledExprs:
    """Conjunction of predicates -> single boolean output."""
    if len(predicates) == 1:
        pred = predicates[0]
    else:
        pred = predicates[0]
        for p in predicates[1:]:
            pred = E.ScAnd(left=pred, right=p)
    return CompiledExprs((pred,), schema)
