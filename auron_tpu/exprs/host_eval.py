"""Host expression evaluator (numpy/pyarrow), full Spark semantics.

Serves two roles:
1. the host-island fallback of the device compiler (regex, json, UDFs,
   nested types, string-parsing casts) — analogue of the reference's
   JVM-callback expressions (SparkUDFWrapperExpr, spark_get_json_object's
   JVM fallback);
2. the reference implementation the differential test harness compares the
   device engine against (SURVEY.md §4's checkSparkAnswer analogue).

Values are (numpy-or-list values, bool validity mask, DataType) triples;
strings are numpy object arrays; nested types are python lists.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np
import pyarrow as pa

from auron_tpu.ir import expr as E
from auron_tpu.ir.schema import DataType, Schema, TypeId, to_arrow_type
from auron_tpu.exprs.typing import infer_type
from auron_tpu.exprs.values import promote


@dataclass
class HV:
    """Host value: vals is np.ndarray (object dtype for strings/nested)."""
    vals: np.ndarray
    mask: np.ndarray  # True = valid
    dtype: DataType

    def __len__(self):
        return len(self.vals)


def evaluate_arrow(expr: E.Expr, rb: pa.RecordBatch, schema: Schema,
                   partition_id: int = 0, row_base: int = 0) -> pa.Array:
    hv = evaluate(expr, rb, schema, partition_id, row_base)
    return hv_to_arrow(hv)


def hv_to_arrow(hv: HV) -> pa.Array:
    at = to_arrow_type(hv.dtype if hv.dtype.id != TypeId.NULL
                       else DataType.bool_())
    vals = hv.vals
    out = []
    for i in range(len(vals)):
        if not hv.mask[i]:
            out.append(None)
        else:
            v = vals[i]
            if isinstance(v, (np.generic,)):
                v = v.item()
            if hv.dtype.id == TypeId.DECIMAL and isinstance(v, int):
                from decimal import Decimal
                v = Decimal(v).scaleb(-hv.dtype.scale)
            out.append(v)
    return pa.array(out, type=at)


def arrow_to_hv(arr: pa.Array, dtype: DataType) -> HV:
    n = len(arr)
    mask = np.ones(n, bool) if arr.null_count == 0 else np.asarray(arr.is_valid())
    if dtype.id == TypeId.DECIMAL:
        vals = np.array([None if v is None
                         else decimal_unscaled(v, dtype.scale)
                         for v in arr.to_pylist()], dtype=object)
        vals = np.where(mask, vals, 0)
        return HV(vals if dtype.is_wide_decimal else vals.astype(np.int64),
                  mask, dtype)
    if dtype.is_stringlike or dtype.is_nested:
        vals = np.array(arr.to_pylist(), dtype=object)
        return HV(vals, mask, dtype)
    if dtype.id == TypeId.DATE32:
        vals = np.array([0 if v is None else (v - _EPOCH_DATE).days
                         for v in arr.to_pylist()], dtype=np.int64)
        return HV(vals.astype(np.int32), mask, dtype)
    if dtype.id == TypeId.TIMESTAMP_US:
        a2 = arr.cast(pa.timestamp("us"))
        vals = np.array([0 if v is None else v
                         for v in a2.cast(pa.int64()).to_pylist()],
                        dtype=np.int64)
        return HV(vals, mask, dtype)
    filled = arr.fill_null(False if dtype.id == TypeId.BOOL else 0) \
        if arr.null_count else arr
    vals = np.asarray(filled.to_numpy(zero_copy_only=False))
    return HV(vals.astype(dtype.numpy_dtype(), copy=False), mask, dtype)


import datetime as _dt
_EPOCH_DATE = _dt.date(1970, 1, 1)


_WIDE_DECIMAL_CTX = None


def decimal_unscaled(v, scale: int) -> int:
    """Exact unscaled integer of a Decimal at `scale` — the default
    28-digit decimal context silently ROUNDS 38-digit values, so scaleb
    runs under a reusable wide context."""
    import decimal
    global _WIDE_DECIMAL_CTX
    if _WIDE_DECIMAL_CTX is None:
        _WIDE_DECIMAL_CTX = decimal.Context(prec=80)
    return int(decimal.Decimal(v).scaleb(scale, _WIDE_DECIMAL_CTX))


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def evaluate(expr: E.Expr, rb: pa.RecordBatch, schema: Schema,
             partition_id: int = 0, row_base: int = 0,
             bindings: Optional[Dict[str, HV]] = None) -> HV:
    """`bindings` pre-binds column names to already-evaluated HVs — the
    wire_udf body scope (params resolve to argument values, NOT to the
    batch), avoiding any synthetic RecordBatch (which cannot hold
    NULL-typed columns and collapses to 0 rows with no arrays)."""
    n = rb.num_rows
    k = expr.kind

    def rec(x):
        return evaluate(x, rb, schema, partition_id, row_base, bindings)

    if k == "column":
        if bindings is not None:
            # body scope: NEVER fall through to the enclosing batch — a
            # case-folded miss would silently read an unrelated column.
            # Case folding honors auron.case.sensitive, matching
            # Schema.index_of (the resolution every other column takes).
            from auron_tpu.config import conf as _conf
            hit = bindings.get(expr.name)
            if hit is None and not _conf.get("auron.case.sensitive"):
                for bn, bv in bindings.items():
                    if bn.lower() == expr.name.lower():
                        hit = bv
                        break
            if hit is None:
                raise KeyError(f"unbound wire_udf param {expr.name!r}")
            return hit
        i = schema.index_of(expr.name)
        return arrow_to_hv(rb.column(i), schema[i].dtype)
    if k == "bound_reference":
        if bindings is not None:
            # body scope: positional param binding, mirroring the device
            # compiler's sub-EvalCtx (cols=arg_cols) — falling through to
            # the ENCLOSING batch here would silently read an unrelated
            # column and diverge from the device path (ADVICE r4).
            vals = list(bindings.values())
            if not 0 <= expr.index < len(vals):
                raise IndexError(
                    f"wire_udf body bound_reference #{expr.index} out of "
                    f"range for {len(vals)} params")
            return vals[expr.index]
        return arrow_to_hv(rb.column(expr.index), schema[expr.index].dtype)
    if k in ("literal", "scalar_subquery"):
        dt = expr.dtype
        v = expr.value
        if v is None or dt.id == TypeId.NULL:
            t = dt if dt.id != TypeId.NULL else DataType.bool_()
            return HV(np.zeros(n, object if (t.is_stringlike or t.is_nested)
                               else t.numpy_dtype()), np.zeros(n, bool), t)
        if dt.id == TypeId.DECIMAL:
            if not isinstance(v, int):
                # exact unscaling (a float round-trip or narrow decimal
                # context would corrupt high-precision literals)
                v = decimal_unscaled(str(v), dt.scale)
            if dt.is_wide_decimal:   # beyond int64: object-int column
                return HV(np.full(n, v, dtype=object), np.ones(n, bool),
                          dt)
        if dt.is_stringlike or dt.is_nested:
            return HV(np.array([v] * n, dtype=object), np.ones(n, bool), dt)
        return HV(np.full(n, v, dtype=dt.numpy_dtype()), np.ones(n, bool), dt)
    if k == "binary":
        return _binary(expr, rec(expr.left), rec(expr.right))
    if k in ("sc_and", "sc_or"):
        return _kleene(k == "sc_and", rec(expr.left), rec(expr.right))
    if k == "is_null":
        c = rec(expr.child)
        return HV(~c.mask, np.ones(n, bool), DataType.bool_())
    if k == "is_not_null":
        c = rec(expr.child)
        return HV(c.mask.copy(), np.ones(n, bool), DataType.bool_())
    if k == "not":
        c = rec(expr.child)
        return HV(~c.vals.astype(bool), c.mask, DataType.bool_())
    if k == "negative":
        c = rec(expr.child)
        return HV(-c.vals, c.mask, c.dtype)
    if k == "case":
        return _case(expr, rec, n, schema)
    if k == "in_list":
        return _in_list(expr, rec)
    if k in ("cast", "try_cast"):
        return _cast(rec(expr.child), expr.dtype)
    if k == "like":
        return _like(expr, rec)
    if k == "scalar_function":
        from auron_tpu.exprs import functions_host
        return functions_host.eval_function(expr, rec, n, schema)
    if k == "py_udf_wrapper":
        return _py_udf(expr, rec, n)
    if k == "wire_udf":
        # args evaluate HERE (enclosing schema + bindings = lexical
        # scoping for nested calls); the body evaluates under the param
        # schema with params pre-bound — mirror of the device compiler's
        # _eval_wire_udf.  rb still rides along only for num_rows.
        from auron_tpu.exprs.typing import wire_udf_param_schema
        pschema = wire_udf_param_schema(expr, schema)   # validates
        binds = {p: rec(a) for p, a in zip(expr.params, expr.args)}
        return evaluate(expr.body, rb, pschema, partition_id, row_base,
                        binds)
    if k == "string_starts_with":
        c = rec(expr.child)
        return _str_pred(c, lambda s: s.startswith(expr.prefix))
    if k == "string_ends_with":
        c = rec(expr.child)
        return _str_pred(c, lambda s: s.endswith(expr.suffix))
    if k == "string_contains":
        c = rec(expr.child)
        return _str_pred(c, lambda s: expr.infix in s)
    if k == "row_num":
        return HV(np.arange(n, dtype=np.int64) + row_base + 1,
                  np.ones(n, bool), DataType.int64())
    if k == "partition_id":
        return HV(np.full(n, partition_id, np.int32), np.ones(n, bool),
                  DataType.int32())
    if k == "monotonically_increasing_id":
        return HV((np.int64(partition_id) << 33)
                  + np.arange(n, dtype=np.int64) + row_base,
                  np.ones(n, bool), DataType.int64())
    if k == "get_indexed_field":
        return _get_indexed_field(expr, rec, schema)
    if k == "get_map_value":
        return _get_map_value(expr, rec, schema)
    if k == "named_struct":
        return _named_struct(expr, rec, n, schema)
    if k == "bloom_filter_might_contain":
        from auron_tpu.ops.agg.bloom import host_might_contain
        return host_might_contain(rec(expr.bloom_filter), rec(expr.value))
    raise NotImplementedError(f"host eval for {k!r}")


# ---------------------------------------------------------------------------
# binary / comparison with Spark NaN + null-safe semantics
# ---------------------------------------------------------------------------

def _num(hv: HV, t: DataType) -> np.ndarray:
    if hv.dtype.id == TypeId.DECIMAL and t.id != TypeId.DECIMAL:
        return hv.vals.astype(np.float64) / (10.0 ** hv.dtype.scale)
    if t.id == TypeId.DECIMAL:
        return hv.vals
    if hv.dtype.is_stringlike:
        return hv.vals
    return hv.vals.astype(t.numpy_dtype(), copy=False)


def _binary(expr: E.BinaryExpr, l: HV, r: HV) -> HV:
    op = expr.op
    n = len(l)
    if op in ("and", "or"):
        return _kleene(op == "and", l, r)
    both = l.mask & r.mask
    if l.dtype.is_stringlike or r.dtype.is_stringlike:
        return _string_binary(op, l, r)
    if op in ("==", "=", "!=", "<", "<=", ">", ">=", "<=>"):
        t = promote(l.dtype, r.dtype)
        a, b = _num(l, t), _num(r, t)
        data = _np_compare(op, a, b, t)
        if op == "<=>":
            data = np.where(both, data, ~l.mask & ~r.mask)
            return HV(data, np.ones(n, bool), DataType.bool_())
        return HV(data, both, DataType.bool_())
    if l.dtype.id == TypeId.DATE32 and op in ("+", "-"):
        if r.dtype.id == TypeId.DATE32 and op == "-":
            return HV(l.vals.astype(np.int32) - r.vals.astype(np.int32),
                      both, DataType.int32())
        d = r.vals.astype(np.int32)
        return HV((l.vals + (d if op == "+" else -d)).astype(np.int32),
                  both, DataType.date32())
    from auron_tpu.exprs.compiler import _binary_result_type
    t = _binary_result_type(op, l.dtype, r.dtype)
    a, b = _num(l, t), _num(r, t)
    with np.errstate(all="ignore"):
        if op == "+":
            data = a + b
        elif op == "-":
            data = a - b
        elif op == "*":
            data = a * b
        elif op == "/":
            zero = b == 0
            data = a / np.where(zero, 1, b)
            both = both & ~zero
            if not t.is_floating:
                data = data.astype(t.numpy_dtype())
        elif op in ("%", "mod"):
            zero = b == 0
            bb = np.where(zero, 1, b)
            if t.is_floating:
                data = np.fmod(a, bb)
            else:
                data = np.sign(a) * (np.abs(a) % np.abs(bb))
            both = both & ~zero
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
            raise NotImplementedError(op)
    if t.id == TypeId.DECIMAL:
        data = data.astype(np.int64)
    return HV(data, both, t)


def _np_compare(op, a, b, t: DataType):
    if t.is_floating:
        an, bn = np.isnan(a), np.isnan(b)
        eq = (an & bn) | (~an & ~bn & (a == b))
        lt = (~an & bn) | (~an & ~bn & (a < b))
    else:
        eq = a == b
        lt = a < b
    return {"==": eq, "=": eq, "<=>": eq, "!=": ~eq, "<": lt,
            "<=": lt | eq, ">": ~(lt | eq), ">=": ~lt}[op]


def _string_binary(op, l: HV, r: HV) -> HV:
    both = l.mask & r.mask
    n = len(l)
    lv = np.where(l.mask, l.vals, "")
    rv = np.where(r.mask, r.vals, "")
    cmp = np.array([(x > y) - (x < y) for x, y in zip(lv, rv)], dtype=np.int32)
    data = {"==": cmp == 0, "=": cmp == 0, "<=>": cmp == 0, "!=": cmp != 0,
            "<": cmp < 0, "<=": cmp <= 0, ">": cmp > 0, ">=": cmp >= 0}[op]
    if op == "<=>":
        return HV(np.where(both, data, ~l.mask & ~r.mask),
                  np.ones(n, bool), DataType.bool_())
    return HV(data, both, DataType.bool_())


def _kleene(is_and: bool, l: HV, r: HV) -> HV:
    a, av = l.vals.astype(bool), l.mask
    b, bv = r.vals.astype(bool), r.mask
    if is_and:
        data = np.where(av, a, True) & np.where(bv, b, True)
        valid = (av & bv) | (av & ~a) | (bv & ~b)
    else:
        data = np.where(av, a, False) | np.where(bv, b, False)
        valid = (av & bv) | (av & a) | (bv & b)
    return HV(data, valid, DataType.bool_())


def _case(expr: E.Case, rec, n, schema: Schema) -> HV:
    out_dtype = infer_type(expr, schema)
    is_obj = out_dtype.is_stringlike or out_dtype.is_nested
    vals = np.zeros(n, dtype=object if is_obj else out_dtype.numpy_dtype())
    mask = np.zeros(n, bool)
    decided = np.zeros(n, bool)
    for b in expr.branches:
        w = rec(b.when)
        t = rec(b.then)
        fire = ~decided & w.mask & w.vals.astype(bool)
        vals = np.where(fire, t.vals, vals)
        mask = np.where(fire, t.mask, mask)
        decided |= fire
    if expr.else_expr is not None:
        e = rec(expr.else_expr)
        vals = np.where(~decided, e.vals, vals)
        mask = np.where(~decided, e.mask, mask)
    return HV(vals, mask, out_dtype)


def _in_list(expr: E.InList, rec) -> HV:
    c = rec(expr.child)
    hit = np.zeros(len(c), bool)
    for v in expr.values:
        lv = rec(v)
        if c.dtype.is_stringlike:
            m = np.array([a == b for a, b in zip(c.vals, lv.vals)])
        else:
            t = promote(c.dtype, lv.dtype)
            m = _np_compare("==", _num(c, t), _num(lv, t), t)
        hit |= m & lv.mask
    return HV(~hit if expr.negated else hit, c.mask.copy(), DataType.bool_())


def _like(expr: E.Like, rec) -> HV:
    c = rec(expr.child)
    p = rec(expr.pattern)
    out = np.zeros(len(c), bool)
    flags = re.DOTALL | (re.IGNORECASE if expr.case_insensitive else 0)
    cache = {}
    for i in range(len(c)):
        if not (c.mask[i] and p.mask[i]):
            continue
        pat = p.vals[i]
        rx = cache.get(pat)
        if rx is None:
            rx = re.compile(_like_to_regex(pat), flags)
            cache[pat] = rx
        out[i] = rx.fullmatch(str(c.vals[i])) is not None
    if expr.negated:
        out = ~out
    return HV(out, c.mask & p.mask, DataType.bool_())


def _like_to_regex(pattern: str) -> str:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == "\\" and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "".join(out)


def _str_pred(c: HV, fn) -> HV:
    out = np.array([bool(fn(str(v))) if m else False
                    for v, m in zip(c.vals, c.mask)])
    return HV(out, c.mask.copy(), DataType.bool_())


def _py_udf(expr: E.PyUdfWrapper, rec, n) -> HV:
    import pickle
    fn = pickle.loads(expr.serialized)
    args = [rec(a) for a in expr.args]
    out_vals = []
    out_mask = np.ones(n, bool)
    for i in range(n):
        row = [a.vals[i] if a.mask[i] else None for a in args]
        v = fn(*row)
        if v is None:
            out_mask[i] = False
            out_vals.append(None)
        else:
            out_vals.append(v)
    dt = expr.return_type
    if dt.is_stringlike or dt.is_nested:
        return HV(np.array(out_vals, dtype=object), out_mask, dt)
    vals = np.array([0 if v is None else v for v in out_vals],
                    dtype=dt.numpy_dtype())
    return HV(vals, out_mask, dt)


def _get_indexed_field(expr, rec, schema: Schema) -> HV:
    c = rec(expr.child)
    out_dt = infer_type(expr, schema)
    n = len(c)
    vals, mask = [], np.zeros(n, bool)
    for i in range(n):
        v = None
        if c.mask[i] and c.vals[i] is not None:
            x = c.vals[i]
            if isinstance(x, dict):
                v = x.get(expr.ordinal)
            elif isinstance(x, (list, tuple)):
                j = int(expr.ordinal)
                v = x[j] if 0 <= j < len(x) else None
        mask[i] = v is not None
        vals.append(v)
    return _from_pylist(vals, mask, out_dt)


def _get_map_value(expr, rec, schema: Schema) -> HV:
    c = rec(expr.child)
    out_dt = infer_type(expr, schema)
    n = len(c)
    vals, mask = [], np.zeros(n, bool)
    for i in range(n):
        v = None
        if c.mask[i] and c.vals[i] is not None:
            x = c.vals[i]
            if isinstance(x, list):      # arrow map -> list of (k, v)
                for kk, vv in x:
                    if kk == expr.key:
                        v = vv
                        break
            elif isinstance(x, dict):
                v = x.get(expr.key)
        mask[i] = v is not None
        vals.append(v)
    return _from_pylist(vals, mask, out_dt)


def _named_struct(expr, rec, n, schema: Schema) -> HV:
    args = [rec(v) for v in expr.values]
    out_dt = infer_type(expr, schema)
    vals = []
    for i in range(n):
        vals.append({name: (a.vals[i].item() if isinstance(a.vals[i], np.generic)
                            else a.vals[i]) if a.mask[i] else None
                     for name, a in zip(expr.names, args)})
    return HV(np.array(vals, dtype=object), np.ones(n, bool), out_dt)


def _from_pylist(vals, mask, dt: DataType) -> HV:
    if dt.is_stringlike or dt.is_nested:
        return HV(np.array(vals, dtype=object), mask, dt)
    arr = np.array([0 if v is None else v for v in vals],
                   dtype=dt.numpy_dtype())
    return HV(arr, mask, dt)


# ---------------------------------------------------------------------------
# casts with string parsing (Spark non-ANSI: invalid -> null)
# ---------------------------------------------------------------------------

def _cast(c: HV, dst: DataType) -> HV:
    src = c.dtype
    n = len(c)
    if src.id == dst.id and src.precision == dst.precision \
            and src.scale == dst.scale:
        return c
    if src.is_stringlike and not dst.is_stringlike:
        return _cast_from_string(c, dst)
    if dst.is_stringlike:
        return _cast_to_string(c, dst)
    if dst.id == TypeId.BOOL:
        return HV(c.vals.astype(bool) if not src.is_floating
                  else (c.vals != 0), c.mask, dst)
    if dst.id == TypeId.DECIMAL:
        return _cast_to_decimal(c, dst)
    if src.id == TypeId.DECIMAL:
        real = c.vals.astype(np.float64) / 10.0 ** src.scale
        return _cast(HV(real, c.mask, DataType.float64()), dst)
    if dst.is_floating:
        return HV(c.vals.astype(dst.numpy_dtype()), c.mask, dst)
    if dst.id == TypeId.DATE32:
        if src.id == TypeId.TIMESTAMP_US:
            days = np.floor_divide(c.vals, 86_400_000_000)
            return HV(days.astype(np.int32), c.mask, dst)
        return HV(c.vals.astype(np.int32), c.mask, dst)
    if dst.id == TypeId.TIMESTAMP_US:
        if src.id == TypeId.DATE32:
            return HV(c.vals.astype(np.int64) * 86_400_000_000, c.mask, dst)
        return HV(c.vals.astype(np.int64), c.mask, dst)
    # -> integral
    from auron_tpu.exprs.cast import _INT_BOUNDS
    lo, hi = _INT_BOUNDS[dst.id]
    if src.is_floating:
        nan = np.isnan(c.vals)
        clamped = np.clip(np.where(nan, 0.0, c.vals), lo, hi)
        out = np.trunc(clamped).astype(dst.numpy_dtype())
        return HV(np.where(nan, 0, out), c.mask, dst)
    return HV(c.vals.astype(dst.numpy_dtype()), c.mask, dst)


def _cast_from_string(c: HV, dst: DataType) -> HV:
    n = len(c)
    mask = c.mask.copy()
    out = []
    for i in range(n):
        v = None
        if mask[i]:
            s = str(c.vals[i]).strip()
            try:
                if dst.is_integral:
                    # spark accepts "12", "-3", "1.0" is invalid for int...
                    # actually spark casts "1.5" -> 1 (truncates); accept float form
                    f = float(s)
                    if math.isnan(f):
                        v = None
                    else:
                        v = int(f)
                        from auron_tpu.exprs.cast import _INT_BOUNDS
                        lo, hi = _INT_BOUNDS[dst.id]
                        if v < lo or v > hi:
                            v = None
                elif dst.is_floating:
                    v = float(s)
                elif dst.id == TypeId.BOOL:
                    ls = s.lower()
                    if ls in ("t", "true", "y", "yes", "1"):
                        v = True
                    elif ls in ("f", "false", "n", "no", "0"):
                        v = False
                elif dst.id == TypeId.DECIMAL:
                    from decimal import Decimal, InvalidOperation
                    d = Decimal(s).scaleb(dst.scale).to_integral_value(
                        rounding="ROUND_HALF_UP")
                    v = int(d)
                    if abs(v) >= 10 ** dst.precision:
                        v = None
                elif dst.id == TypeId.DATE32:
                    v = (_dt.date.fromisoformat(s[:10]) - _EPOCH_DATE).days
                elif dst.id == TypeId.TIMESTAMP_US:
                    ts = _dt.datetime.fromisoformat(s)
                    if ts.tzinfo is None:
                        ts = ts.replace(tzinfo=_dt.timezone.utc)
                    v = int(ts.timestamp() * 1_000_000)
            except (ValueError, ArithmeticError, Exception):
                v = None
        mask[i] = v is not None
        out.append(v)
    return _from_pylist(out, mask, dst)


def _cast_to_string(c: HV, dst: DataType) -> HV:
    src = c.dtype
    out = []
    for i in range(len(c)):
        if not c.mask[i]:
            out.append(None)
            continue
        v = c.vals[i]
        if src.id == TypeId.BOOL:
            out.append("true" if v else "false")
        elif src.id == TypeId.DECIMAL:
            from decimal import Decimal
            out.append(str(Decimal(int(v)).scaleb(-src.scale)))
        elif src.id == TypeId.DATE32:
            out.append(str(_EPOCH_DATE + _dt.timedelta(days=int(v))))
        elif src.id == TypeId.TIMESTAMP_US:
            ts = _dt.datetime.fromtimestamp(int(v) / 1e6, tz=_dt.timezone.utc)
            out.append(ts.strftime("%Y-%m-%d %H:%M:%S") +
                       (f".{int(v) % 1_000_000:06d}".rstrip("0").rstrip(".")
                        if int(v) % 1_000_000 else ""))
        elif src.is_floating:
            out.append(_spark_float_str(float(v)))
        else:
            out.append(str(int(v)))
    mask = np.array([o is not None for o in out])
    return HV(np.array(out, dtype=object), mask, dst)


def _spark_float_str(f: float) -> str:
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "Infinity" if f > 0 else "-Infinity"
    if f == int(f) and abs(f) < 1e16:
        return f"{int(f)}.0"
    return repr(f)


def np_rescale_half_up(x: np.ndarray, div: int) -> np.ndarray:
    mag = np.abs(x)
    q = mag // div
    rem = mag - q * div
    q = q + (2 * rem >= div).astype(q.dtype)
    return np.sign(x) * q


def _cast_to_decimal(c: HV, dst: DataType) -> HV:
    if c.dtype.id == TypeId.DECIMAL:
        shift = dst.scale - c.dtype.scale
        if shift >= 0:
            unscaled = c.vals * (10 ** shift)
        else:
            unscaled = np_rescale_half_up(c.vals, 10 ** (-shift))
    elif c.dtype.is_floating:
        scaled = c.vals.astype(np.float64) * 10 ** dst.scale
        unscaled = np.where(scaled >= 0, np.floor(scaled + 0.5),
                            np.ceil(scaled - 0.5)).astype(np.int64)
    else:
        unscaled = c.vals.astype(np.int64) * 10 ** dst.scale
    bound = 10 ** dst.precision
    ok = (unscaled > -bound) & (unscaled < bound)
    return HV(unscaled.astype(np.int64), c.mask & ok, dst)
