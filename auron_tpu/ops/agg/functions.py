"""Aggregate function state machines (accumulator specs).

Analogue of the reference's agg function zoo (agg/sum.rs, avg.rs, count.rs,
min.rs, max.rs, first.rs, first_ignores_null.rs, collect.rs, bloom_filter
agg, spark_udaf_wrapper.rs) over a different substrate: states are columns,
updates are segment reductions after sort-based grouping (TPU-shaped: the
MXU-friendly alternative to the SIMD hash map of agg_hash_map.rs).

Each AggSpec defines:
- state_fields: the partial-state schema (what a `partial` agg emits)
- update_segments(vals, seg_ids, num_segments): input values -> states
- merge_segments(states, seg_ids, num_segments): partial states -> states
- eval_final(states): states -> result column
Device specs reduce with ops/segments.py sorted-segment kernels — seg ids
MUST be ascending (AggExec lexsorts before reducing); host specs
(collect/udaf/bloom) run in python over arrow values.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import DeviceColumn, DeviceStringColumn
from auron_tpu.exprs.values import flat
from auron_tpu.ir.schema import DataType, Field, Schema, TypeId
from auron_tpu.ops import segments


def _seg_sum(x, seg, n):
    # seg ids arrive sorted (AggExec lexsorts before reducing) — use the
    # gather-shaped kernels instead of scatter-add (ops/segments.py)
    return segments.sorted_segment_sum(x, seg, n)


def _seg_min(x, seg, n):
    return segments.sorted_segment_min(x, seg, n)


def _seg_max(x, seg, n):
    return segments.sorted_segment_max(x, seg, n)


class AggSpec:
    """Device agg spec over flat numeric columns."""
    n_states = 1

    def __init__(self, fn: str, in_dtype: DataType, out_dtype: DataType,
                 name: str):
        self.fn = fn
        self.in_dtype = in_dtype
        self.out_dtype = out_dtype
        self.name = name

    def state_fields(self) -> List[Field]:
        raise NotImplementedError

    def update_segments(self, cols: List[Any], seg, n: int) -> List[Any]:
        """cols: evaluated input columns; -> state (data, validity) columns
        of length n."""
        raise NotImplementedError

    def merge_segments(self, states: List[Any], seg, n: int) -> List[Any]:
        raise NotImplementedError

    def eval_final(self, states: List[Any]):
        raise NotImplementedError


class SumSpec(AggSpec):
    def state_fields(self):
        return [Field(f"{self.name}#sum", self.out_dtype)]

    def _acc_dtype(self):
        dt = self.out_dtype
        return dt.numpy_dtype()

    def update_segments(self, cols, seg, n):
        c = cols[0]
        x = _sum_input(c, self.out_dtype)
        contrib = jnp.where(c.validity, x, 0)
        s = _seg_sum(contrib, seg, n)
        has = _seg_sum(c.validity.astype(jnp.int32), seg, n) > 0
        return [DeviceColumn(self.out_dtype, s, has)]

    def merge_segments(self, states, seg, n):
        c = states[0]
        s = _seg_sum(jnp.where(c.validity, c.data, 0), seg, n)
        has = _seg_sum(c.validity.astype(jnp.int32), seg, n) > 0
        return [DeviceColumn(self.out_dtype, s, has)]

    def eval_final(self, states):
        return flat(self.out_dtype, states[0].data, states[0].validity)


def _sum_input(c, out_dtype: DataType):
    if out_dtype.id == TypeId.DECIMAL:
        return c.data.astype(jnp.int64)
    return c.data.astype(out_dtype.numpy_dtype())


class CountSpec(AggSpec):
    """count(expr): counts non-null; count(*) (no children) counts rows."""

    def state_fields(self):
        return [Field(f"{self.name}#count", DataType.int64(), nullable=False)]

    def update_segments(self, cols, seg, n):
        if cols:
            ones = cols[0].validity.astype(jnp.int64)
        else:
            ones = jnp.ones(seg.shape[0], jnp.int64)
        s = _seg_sum(ones, seg, n)
        return [DeviceColumn(DataType.int64(), s,
                             jnp.ones(n, bool))]

    def merge_segments(self, states, seg, n):
        s = _seg_sum(jnp.where(states[0].validity, states[0].data, 0), seg, n)
        return [DeviceColumn(DataType.int64(), s, jnp.ones(n, bool))]

    def eval_final(self, states):
        return flat(DataType.int64(), states[0].data, jnp.ones(
            states[0].data.shape[0], bool))


class MinMaxSpec(AggSpec):
    def __init__(self, fn, in_dtype, out_dtype, name):
        super().__init__(fn, in_dtype, out_dtype, name)
        self.is_min = fn == "min"

    def state_fields(self):
        return [Field(f"{self.name}#{self.fn}", self.out_dtype)]

    def _neutral(self, dtype):
        np_dt = dtype.numpy_dtype()
        if np_dt.kind == "f":
            return jnp.asarray(np.inf if self.is_min else -np.inf, np_dt)
        info = np.iinfo(np_dt)
        return jnp.asarray(info.max if self.is_min else info.min, np_dt)

    def _reduce(self, c, seg, n):
        neutral = self._neutral(self.out_dtype)
        x = jnp.where(c.validity, c.data.astype(neutral.dtype), neutral)
        red = _seg_min(x, seg, n) if self.is_min else _seg_max(x, seg, n)
        has = _seg_sum(c.validity.astype(jnp.int32), seg, n) > 0
        return [DeviceColumn(self.out_dtype, jnp.where(has, red, 0), has)]

    def update_segments(self, cols, seg, n):
        return self._reduce(cols[0], seg, n)

    def merge_segments(self, states, seg, n):
        return self._reduce(states[0], seg, n)

    def eval_final(self, states):
        return flat(self.out_dtype, states[0].data, states[0].validity)


class AvgSpec(AggSpec):
    n_states = 2

    def __init__(self, fn, in_dtype, out_dtype, name):
        super().__init__(fn, in_dtype, out_dtype, name)
        # sum state: decimal keeps unscaled i64; else f64
        self.sum_dtype = in_dtype if in_dtype.id == TypeId.DECIMAL \
            else DataType.float64()

    def state_fields(self):
        return [Field(f"{self.name}#sum", self.sum_dtype),
                Field(f"{self.name}#count", DataType.int64(), nullable=False)]

    def update_segments(self, cols, seg, n):
        c = cols[0]
        x = _sum_input(c, self.sum_dtype)
        s = _seg_sum(jnp.where(c.validity, x, 0), seg, n)
        cnt = _seg_sum(c.validity.astype(jnp.int64), seg, n)
        return [DeviceColumn(self.sum_dtype, s, cnt > 0),
                DeviceColumn(DataType.int64(), cnt, jnp.ones(n, bool))]

    def merge_segments(self, states, seg, n):
        s = _seg_sum(jnp.where(states[0].validity, states[0].data, 0), seg, n)
        cnt = _seg_sum(jnp.where(states[1].validity, states[1].data, 0),
                       seg, n)
        return [DeviceColumn(self.sum_dtype, s, cnt > 0),
                DeviceColumn(DataType.int64(), cnt, jnp.ones(n, bool))]

    def eval_final(self, states):
        s, cnt = states[0], states[1]
        safe = jnp.maximum(cnt.data, 1)
        if self.out_dtype.id == TypeId.DECIMAL:
            # decimal avg: result scale = out_dtype.scale; sum is at input
            # scale; out = sum * 10^(out_scale - in_scale) / count, half-up
            shift = self.out_dtype.scale - self.sum_dtype.scale
            num = s.data * (10 ** max(shift, 0))
            div = safe * (10 ** max(-shift, 0))
            mag = jnp.abs(num)
            q = mag // div
            rem = mag - q * div
            q = q + (2 * rem >= div).astype(q.dtype)
            q = jnp.sign(num) * q
            return flat(self.out_dtype, q, cnt.data > 0)
        avg = s.data.astype(jnp.float64) / safe
        return flat(DataType.float64(), avg, cnt.data > 0)


class WideSumSpec(AggSpec):
    """`Sum` whose result is a decimal of 19-38 digits, in the stage
    program (exprs/decimal128.py): Spark's buffer (sum, isEmpty).  The sum
    is exact, with the carries between the words; one that passes the
    result's precision is null and stays null through every merge
    (non-ANSI overflow), which is what isEmpty is for: a null sum beside
    isEmpty = false is an overflow, not a group without rows."""
    n_states = 2

    def state_fields(self):
        return [Field(f"{self.name}#sum", self.out_dtype),
                Field(f"{self.name}#isEmpty", DataType.bool_(),
                      nullable=False)]

    def _pack(self, total, any_rows, n):
        return [total, DeviceColumn(DataType.bool_(),
                                    jnp.logical_not(any_rows),
                                    jnp.ones(n, bool))]

    def update_segments(self, cols, seg, n):
        from auron_tpu.exprs import decimal128 as dec128
        c = cols[0]
        if c.dtype.scale != self.out_dtype.scale:
            c = dec128.cast(c, self.out_dtype)
        total = dec128.sum_state(self.out_dtype, c, c.validity,
                                 jnp.zeros(c.validity.shape, bool), seg, n)
        return self._pack(
            total, _seg_sum(c.validity.astype(jnp.int32), seg, n) > 0, n)

    def merge_segments(self, states, seg, n):
        from auron_tpu.exprs import decimal128 as dec128
        s, empty = states
        some = jnp.logical_and(empty.validity,
                               jnp.logical_not(empty.data.astype(bool)))
        total = dec128.sum_state(self.out_dtype, s, some,
                                 jnp.logical_not(s.validity), seg, n)
        return self._pack(total,
                          _seg_sum(some.astype(jnp.int32), seg, n) > 0, n)

    def eval_final(self, states):
        s, empty = states
        return s.masked(jnp.logical_not(empty.data.astype(bool)))


class WideAvgSpec(AggSpec):
    """`Average` over a decimal that Spark leaves a decimal (result
    precision over 15 digits, so no rewrite to doubles), in the stage
    program: the buffer (sum decimal(p + 10, s), count) and the result
    `Divide(sum, count)` at Spark's adjusted type cast to the result
    type, two roundings (exprs/decimal128.py `average`).  A sum past its
    precision is null and poisons every merge it enters."""
    n_states = 2

    def _sum_dtype(self) -> DataType:
        if self.in_dtype.is_decimal:
            p, s = self.in_dtype.precision, self.in_dtype.scale
        else:
            # final mode: the input's type is not in the state schema;
            # the result's is decimal(p + 4, s + 4)
            p, s = self.out_dtype.precision - 4, self.out_dtype.scale - 4
        return DataType.decimal(min(38, p + 10), s)

    def state_fields(self):
        return [Field(f"{self.name}#sum", self._sum_dtype()),
                Field(f"{self.name}#count", DataType.int64(), nullable=False)]

    def update_segments(self, cols, seg, n):
        from auron_tpu.exprs import decimal128 as dec128
        c = cols[0]
        total = dec128.sum_state(self._sum_dtype(), c, c.validity,
                                 jnp.zeros(c.validity.shape, bool), seg, n)
        cnt = _seg_sum(c.validity.astype(jnp.int64), seg, n)
        return [total, DeviceColumn(DataType.int64(), cnt, jnp.ones(n, bool))]

    def merge_segments(self, states, seg, n):
        from auron_tpu.exprs import decimal128 as dec128
        s, cnt = states
        rows = jnp.where(cnt.validity, cnt.data, 0)
        total = dec128.sum_state(s.dtype, s, rows > 0,
                                 jnp.logical_not(s.validity), seg, n)
        return [total, DeviceColumn(DataType.int64(), _seg_sum(rows, seg, n),
                                    jnp.ones(n, bool))]

    def eval_final(self, states):
        from auron_tpu.exprs import decimal128 as dec128
        s, cnt = states
        return dec128.average(s, cnt.data, self.out_dtype)


class StddevSpec(AggSpec):
    """stddev_samp / var_samp over (sum, sum-of-squares, count) power-sum
    state.  The reference's central-moment accumulators (Spark's
    StddevSamp lowered through agg.rs) update (n, mean, m2) row-at-a-time;
    power sums carry the same information, are merge-associative, and
    reduce in one segmented pass — the device-friendly formulation."""
    n_states = 3

    def __init__(self, fn, in_dtype, out_dtype, name):
        super().__init__(fn, in_dtype, out_dtype, name)
        self.is_std = fn == "stddev_samp"

    def state_fields(self):
        return [Field(f"{self.name}#sum", DataType.float64()),
                Field(f"{self.name}#sumsq", DataType.float64()),
                Field(f"{self.name}#count", DataType.int64(),
                      nullable=False)]

    def _pack(self, s, s2, cnt, n):
        return [DeviceColumn(DataType.float64(), s, cnt > 0),
                DeviceColumn(DataType.float64(), s2, cnt > 0),
                DeviceColumn(DataType.int64(), cnt, jnp.ones(n, bool))]

    def update_segments(self, cols, seg, n):
        c = cols[0]
        x = c.data.astype(jnp.float64)
        s = _seg_sum(jnp.where(c.validity, x, 0.0), seg, n)
        s2 = _seg_sum(jnp.where(c.validity, x * x, 0.0), seg, n)
        cnt = _seg_sum(c.validity.astype(jnp.int64), seg, n)
        return self._pack(s, s2, cnt, n)

    def merge_segments(self, states, seg, n):
        s = _seg_sum(jnp.where(states[0].validity, states[0].data, 0.0),
                     seg, n)
        s2 = _seg_sum(jnp.where(states[1].validity, states[1].data, 0.0),
                      seg, n)
        cnt = _seg_sum(jnp.where(states[2].validity, states[2].data, 0),
                       seg, n)
        return self._pack(s, s2, cnt, n)

    def eval_final(self, states):
        s, s2, cnt = states
        nf = cnt.data.astype(jnp.float64)
        # var_samp = (sum_sq - sum^2/n) / (n-1); clamped at 0 against
        # catastrophic cancellation on near-constant groups
        var = (s2.data - s.data * s.data / jnp.maximum(nf, 1.0)) / \
            jnp.maximum(nf - 1.0, 1.0)
        var = jnp.maximum(var, 0.0)
        out = jnp.sqrt(var) if self.is_std else var
        # Spark: one qualifying row -> NaN, zero -> NULL
        out = jnp.where(cnt.data == 1, jnp.nan, out)
        return flat(DataType.float64(), out, cnt.data > 0)


class FirstSpec(AggSpec):
    """first / first_ignores_null: resolved by taking the value at the
    segment's first (qualifying) row index."""
    n_states = 1

    def __init__(self, fn, in_dtype, out_dtype, name):
        super().__init__(fn, in_dtype, out_dtype, name)
        self.ignores_null = fn == "first_ignores_null"

    def state_fields(self):
        return [Field(f"{self.name}#first", self.out_dtype)]

    def _first_idx(self, valid, seg, n, rows):
        big = jnp.int64(1 << 62)
        idx = jnp.arange(rows, dtype=jnp.int64)
        if self.ignores_null:
            idx = jnp.where(valid, idx, big)
        first = _seg_min(idx, seg, n)
        return first

    def _take(self, c, seg, n):
        rows = c.data.shape[0] if not isinstance(c, DeviceStringColumn) \
            else c.capacity
        first = self._first_idx(c.validity, seg, n, rows)
        has = first < (1 << 62)
        src = jnp.clip(first, 0, rows - 1).astype(jnp.int32)
        if isinstance(c, DeviceStringColumn):
            return [c.gather(src, has)]
        d = jnp.where(has, jnp.take(c.data, src), 0)
        v = jnp.where(has, jnp.take(c.validity, src), False)
        return [DeviceColumn(self.out_dtype, d, v)]

    def update_segments(self, cols, seg, n):
        return self._take(cols[0], seg, n)

    def merge_segments(self, states, seg, n):
        return self._take(states[0], seg, n)

    def eval_final(self, states):
        s = states[0]
        if isinstance(s, DeviceStringColumn):
            return s
        return flat(self.out_dtype, s.data, s.validity)


class HostAggSpec(AggSpec):
    """Host-side accumulation for collect_list/collect_set/bloom_filter/
    brickhouse variants, python UDAFs and string min/max — operates over
    arrow rows (the analogue of JVM-callback UDAF evaluation,
    agg/spark_udaf_wrapper.rs:52)."""
    n_states = 1

    def __init__(self, fn, in_dtype, out_dtype, name, udaf_blob=None):
        super().__init__(fn, in_dtype, out_dtype, name)
        self.udaf_blob = udaf_blob

    def state_fields(self):
        return [Field(f"{self.name}#state", DataType.binary())]


# ---------------------------------------------------------------------------
# host accumulators: EVERY agg fn has one so the host path works for plans
# mixing device aggs with host aggs (and for batches with host-resident
# columns).  Interface: init/update/merge_state/state/eval, where state()
# returns a tuple matching spec.state_fields() (typed partial output).
# ---------------------------------------------------------------------------

class HostAcc:
    def __init__(self, spec: "AggSpec", has_children: bool):
        self.spec = spec
        self.has_children = has_children

    def init(self):
        raise NotImplementedError

    def update(self, acc, v):
        raise NotImplementedError

    def merge_state(self, acc, state: tuple):
        raise NotImplementedError

    def state(self, acc) -> tuple:
        raise NotImplementedError

    def eval(self, acc):
        raise NotImplementedError


class _HSum(HostAcc):
    def init(self): return None
    def update(self, acc, v):
        return acc if v is None else (v if acc is None else acc + v)
    def merge_state(self, acc, st):
        return self.update(acc, st[0])
    def state(self, acc): return (acc,)
    def eval(self, acc): return acc


class _HCount(HostAcc):
    def init(self): return 0
    def update(self, acc, v):
        if not self.has_children:
            return acc + 1
        return acc + (v is not None)
    def merge_state(self, acc, st):
        return acc + (st[0] or 0)
    def state(self, acc): return (acc,)
    def eval(self, acc): return acc


class _HMin(HostAcc):
    larger = False
    def init(self): return None
    def update(self, acc, v):
        if v is None:
            return acc
        if acc is None:
            return v
        return max(acc, v) if self.larger else min(acc, v)
    def merge_state(self, acc, st):
        return self.update(acc, st[0])
    def state(self, acc): return (acc,)
    def eval(self, acc): return acc


class _HMax(_HMin):
    larger = True


class _HAvg(HostAcc):
    def init(self): return [None, 0]
    def update(self, acc, v):
        if v is not None:
            acc[0] = v if acc[0] is None else acc[0] + v
            acc[1] += 1
        return acc
    def merge_state(self, acc, st):
        s, c = st
        if s is not None:
            acc[0] = s if acc[0] is None else acc[0] + s
            acc[1] += c or 0
        return acc
    def state(self, acc): return (acc[0], acc[1])
    def eval(self, acc):
        if acc[1] == 0 or acc[0] is None:
            return None
        from auron_tpu.ir.schema import TypeId as _T
        if self.spec.out_dtype.id == _T.DECIMAL:
            # acc[0] is a Decimal (arrow pylist value); divide at out scale
            from decimal import Decimal, ROUND_HALF_UP
            q = (Decimal(acc[0]) / acc[1]).quantize(
                Decimal(1).scaleb(-self.spec.out_dtype.scale),
                rounding=ROUND_HALF_UP)
            return q
        return float(acc[0]) / acc[1]


class _HStddev(HostAcc):
    """stddev_samp / var_samp over float power sums — the host twin of
    StddevSpec (same (sum, sumsq, count) partial state).  The math lives
    in one place, _StddevInner; this class only adapts it to the
    flat-state HostAcc protocol."""
    def __init__(self, spec, has_children):
        super().__init__(spec, has_children)
        self._inner = _StddevInner(spec.fn)
    def init(self): return self._inner.init()
    def update(self, acc, v): return self._inner.update(acc, v)
    def merge_state(self, acc, st):
        s, s2, c = st
        if c:
            return self._inner.merge(acc, [float(s or 0.0),
                                           float(s2 or 0.0), int(c)])
        return acc
    def state(self, acc): return (acc[0], acc[1], acc[2])
    def eval(self, acc): return self._inner.eval(acc)


class _StddevInner:
    """Power-sum stddev/variance over host-typed values (HostAggSpec
    path, pickled partial state)."""
    def __init__(self, fn: str):
        self.fn = fn
    def init(self): return [0.0, 0.0, 0]
    def update(self, acc, v):
        if v is not None:
            f = float(v)
            acc[0] += f
            acc[1] += f * f
            acc[2] += 1
        return acc
    def merge(self, a, b):
        return [a[0] + b[0], a[1] + b[1], a[2] + b[2]]
    def eval(self, acc):
        s, s2, c = acc
        if c == 0:
            return None
        if c == 1:
            return float("nan")
        var = max((s2 - s * s / c) / (c - 1), 0.0)
        return var ** 0.5 if self.fn == "stddev_samp" else var


class _HFirst(HostAcc):
    def init(self): return [False, None]   # (seen, value)
    def update(self, acc, v):
        ignore_nulls = self.spec.fn == "first_ignores_null"
        if not acc[0] and (v is not None or not ignore_nulls):
            acc[0] = True
            acc[1] = v
        return acc
    def merge_state(self, acc, st):
        return self.update(acc, st[0])
    def state(self, acc): return (acc[1],)
    def eval(self, acc): return acc[1]


class _HPickled(HostAcc):
    """Wraps an init/update/merge/eval object (builtin host agg or user
    UDAF); partial state is a pickle blob."""
    def __init__(self, spec, has_children, inner):
        super().__init__(spec, has_children)
        self.inner = inner
    def init(self): return self.inner.init()
    def update(self, acc, v): return self.inner.update(acc, v)
    def merge_state(self, acc, st):
        import pickle
        if st[0] is None:
            return acc
        other = pickle.loads(st[0]) if isinstance(st[0], (bytes, bytearray)) \
            else st[0]
        return self.inner.merge(acc, other)
    def state(self, acc):
        import pickle
        return (pickle.dumps(acc),)
    def eval(self, acc): return self.inner.eval(acc)


class _SimpleInner:
    """min/max/sum/first over arbitrary python values (host-typed inputs)."""
    def __init__(self, fn: str):
        self.fn = fn
    def init(self):
        return [False, None]
    def update(self, acc, v):
        if v is None:
            if self.fn == "first" and not acc[0]:
                acc[0] = True
            return acc
        if not acc[0] or acc[1] is None:
            acc[0] = True
            acc[1] = v
        elif self.fn == "min":
            acc[1] = min(acc[1], v)
        elif self.fn == "max":
            acc[1] = max(acc[1], v)
        elif self.fn == "sum":
            acc[1] = acc[1] + v
        return acc
    def merge(self, a, b):
        if b[0]:
            self.update(a, b[1])
        return a
    def eval(self, acc):
        return acc[1]


def host_accumulator(spec: "AggSpec", has_children: bool) -> HostAcc:
    if isinstance(spec, HostAggSpec):
        if spec.fn == "udaf":
            import pickle
            inner = pickle.loads(spec.udaf_blob)
        elif spec.fn in _BUILTIN_HOST_AGGS:
            inner = _BUILTIN_HOST_AGGS[spec.fn]()
        elif spec.fn in ("min", "max", "sum", "first", "first_ignores_null"):
            # simple fns whose input type forced the host path (e.g. string
            # min/max, nested first); partial state is pickled
            inner = _SimpleInner(spec.fn)
        elif spec.fn in ("stddev_samp", "var_samp"):
            # non-flat input (e.g. decimal) forced the host path; the
            # accumulator coerces to float like Spark's cast-to-double
            inner = _StddevInner(spec.fn)
        else:
            raise NotImplementedError(f"host agg {spec.fn!r}")
        return _HPickled(spec, has_children, inner)
    return {
        "sum": _HSum, "count": _HCount, "min": _HMin, "max": _HMax,
        "avg": _HAvg, "first": _HFirst, "first_ignores_null": _HFirst,
        "stddev_samp": _HStddev, "var_samp": _HStddev,
    }[spec.fn](spec, has_children)


class _CollectList:
    def init(self): return []
    def update(self, acc, v):
        if v is not None:
            acc.append(v)
        return acc
    def merge(self, a, b):
        a.extend(b)
        return a
    def eval(self, acc): return acc


class _CollectSet(_CollectList):
    def eval(self, acc):
        seen, out = set(), []
        for v in acc:
            k = repr(v)
            if k not in seen:
                seen.add(k)
                out.append(v)
        return out


class _BrickhouseCollect(_CollectList):
    pass


class _BrickhouseCombineUnique(_CollectList):
    def update(self, acc, v):
        if v is not None:
            acc.extend(x for x in v if x is not None)
        return acc
    def eval(self, acc):
        return _CollectSet.eval(self, acc)


class _BloomFilterAgg:
    """Builds the shuffle-safe bloom blob (ops/agg/bloom.py layout)."""
    def __init__(self, expected=100_000, fpp=0.03):
        from auron_tpu.ops.agg.bloom import (BloomFilter, optimal_num_bits,
                                             optimal_num_hashes)
        bits = optimal_num_bits(expected, fpp)
        self._bf = BloomFilter(bits, optimal_num_hashes(bits, expected))

    def init(self):
        return self._bf

    def update(self, acc, v):
        if v is not None:
            import numpy as _np
            from auron_tpu.ir.schema import DataType as _DT
            if isinstance(v, str) or isinstance(v, bytes):
                acc.put_values(_np.array([v], dtype=object), _DT.string(),
                               _np.ones(1, bool))
            else:
                acc.put_values(_np.array([int(v)], dtype=_np.int64),
                               _DT.int64(), _np.ones(1, bool))
        return acc

    def merge(self, a, b):
        a.merge(b)
        return a

    def eval(self, acc):
        return acc.to_bytes()


class WireUdafSpec(AggSpec):
    """Wire-registered algebraic UDAF (ir.expr.WireUdaf): per-slot update
    expressions over the formal params reduced with a primitive
    combinator, finalize expression over the slots.  Fully device-capable
    — updates/finalize compile into the same jitted segment-reduce
    kernels the built-in specs use, so wire UDAFs ride the SPMD stage
    path.  (The expression-tree wire analogue of the reference's
    JVM-callback UDAF, agg/spark_udaf_wrapper.rs:52.)"""

    def __init__(self, wire, in_dtypes: Tuple[DataType, ...],
                 out_dtype: DataType, name: str):
        from auron_tpu.exprs.typing import validate_wire_udaf
        validate_wire_udaf(wire, in_dtypes)
        super().__init__("wire_udaf",
                         in_dtypes[0] if in_dtypes else DataType.int64(),
                         out_dtype, name)
        self.wire = wire
        self.in_dtypes = tuple(in_dtypes)

    def _slot_dtype(self, i: int) -> DataType:
        return DataType.int64() if self.wire.slot_ops[i] == "count" \
            else self.wire.slot_types[i]

    def state_fields(self):
        return [Field(f"{self.name}#{nm}", self._slot_dtype(i))
                for i, nm in enumerate(self.wire.slot_names)]

    def _eval(self, expr, cols, schema, capacity=None):
        from auron_tpu.exprs.compiler import EvalCtx, evaluate
        cap = capacity if capacity is not None else (
            cols[0].capacity if cols else 1)
        ctx = EvalCtx(cols=list(cols), schema=schema,
                      num_rows=jnp.int32(cap), capacity=cap,
                      partition_id=jnp.int32(0), row_base=jnp.int64(0))
        return evaluate(expr, ctx)

    def _reduce_slot(self, i: int, c, seg, n):
        op = self.wire.slot_ops[i]
        dt = self._slot_dtype(i)
        if op == "count":
            s = _seg_sum(c.validity.astype(jnp.int64), seg, n)
            return DeviceColumn(dt, s, jnp.ones(n, bool))
        if op == "sum":
            x = c.data.astype(dt.numpy_dtype())
            s = _seg_sum(jnp.where(c.validity, x, 0), seg, n)
            has = _seg_sum(c.validity.astype(jnp.int32), seg, n) > 0
            return DeviceColumn(dt, s, has)
        # min / max
        np_dt = dt.numpy_dtype()
        if np_dt.kind == "f":
            neutral = jnp.asarray(np.inf if op == "min" else -np.inf, np_dt)
        else:
            info = np.iinfo(np_dt)
            neutral = jnp.asarray(info.max if op == "min" else info.min,
                                  np_dt)
        x = jnp.where(c.validity, c.data.astype(np_dt), neutral)
        red = _seg_min(x, seg, n) if op == "min" else _seg_max(x, seg, n)
        has = _seg_sum(c.validity.astype(jnp.int32), seg, n) > 0
        return DeviceColumn(dt, jnp.where(has, red, 0), has)

    def _merge_slot(self, i: int, c, seg, n):
        op = self.wire.slot_ops[i]
        dt = self._slot_dtype(i)
        if op in ("sum", "count"):
            s = _seg_sum(jnp.where(c.validity, c.data, 0), seg, n)
            if op == "count":
                return DeviceColumn(dt, s, jnp.ones(n, bool))
            has = _seg_sum(c.validity.astype(jnp.int32), seg, n) > 0
            return DeviceColumn(dt, s, has)
        return self._reduce_slot(i, c, seg, n)

    def update_segments(self, cols, seg, n):
        schema = Schema(tuple(
            Field(p, dt) for p, dt in zip(self.wire.params,
                                          self.in_dtypes)))
        cap = int(seg.shape[0])
        return [self._reduce_slot(
                    i, self._eval(upd, cols, schema, capacity=cap), seg, n)
                for i, upd in enumerate(self.wire.updates)]

    def merge_segments(self, states, seg, n):
        return [self._merge_slot(i, c, seg, n)
                for i, c in enumerate(states)]

    def eval_final(self, states):
        schema = Schema(tuple(
            Field(nm, self._slot_dtype(i))
            for i, nm in enumerate(self.wire.slot_names)))
        out = self._eval(self.wire.finalize, list(states), schema)
        if out.dtype != self.out_dtype:
            from auron_tpu.exprs.cast import cast_column
            out = cast_column(out, self.out_dtype)
        return out


_BUILTIN_HOST_AGGS = {
    "collect_list": _CollectList,
    "collect_set": _CollectSet,
    "brickhouse_collect": _BrickhouseCollect,
    "brickhouse_combine_unique": _BrickhouseCombineUnique,
    "bloom_filter": _BloomFilterAgg,
}

_DEVICE_AGG_FNS = {"sum", "count", "min", "max", "avg", "first",
                   "first_ignores_null", "stddev_samp", "var_samp"}


def make_spec(fn: str, in_dtype: DataType, out_dtype: DataType, name: str,
              udaf_blob=None, wire=None,
              in_dtypes: Optional[Tuple[DataType, ...]] = None,
              wide: bool = False) -> AggSpec:
    """`wide`: the caller is the stage program, which holds decimals of
    19-38 digits on the device: there a wide `Sum` and a decimal `Average`
    that Spark leaves a decimal have device specs; in the serial engine
    they are host specs, as they were."""
    from auron_tpu.columnar.batch import is_device_type

    if wide and fn == "sum" and out_dtype.is_wide_decimal:
        return WideSumSpec(fn, in_dtype, out_dtype, name)
    if wide and fn == "avg" and out_dtype.is_decimal \
            and out_dtype.precision > 15:
        return WideAvgSpec(fn, in_dtype, out_dtype, name)

    def flat_numeric(dt: DataType) -> bool:
        return is_device_type(dt) and not dt.is_stringlike

    if fn == "wire_udaf":
        if wire is None:
            raise ValueError("fn='wire_udaf' requires AggExpr.wire")
        return WireUdafSpec(
            wire, in_dtypes if in_dtypes is not None else (in_dtype,),
            out_dtype, name)
    if fn == "sum" and flat_numeric(out_dtype):
        return SumSpec(fn, in_dtype, out_dtype, name)
    if fn == "count":
        return CountSpec(fn, in_dtype, DataType.int64(), name)
    if fn in ("min", "max") and flat_numeric(in_dtype) \
            and flat_numeric(out_dtype):
        return MinMaxSpec(fn, in_dtype, out_dtype, name)
    if fn == "avg" and flat_numeric(in_dtype):
        return AvgSpec(fn, in_dtype, out_dtype, name)
    if fn in ("stddev_samp", "var_samp") and flat_numeric(in_dtype):
        return StddevSpec(fn, in_dtype, out_dtype, name)
    if fn in ("first", "first_ignores_null") and is_device_type(in_dtype):
        return FirstSpec(fn, in_dtype, out_dtype, name)
    return HostAggSpec(fn, in_dtype, out_dtype, name, udaf_blob)


def is_device_agg(fn: str, in_dtype: Optional[DataType],
                  out_dtype: DataType) -> bool:
    from auron_tpu.columnar.batch import is_device_type
    if fn not in _DEVICE_AGG_FNS:
        return False
    if in_dtype is not None and not is_device_type(in_dtype):
        return False
    return is_device_type(out_dtype)
