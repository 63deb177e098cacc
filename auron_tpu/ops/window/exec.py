"""Window operator.

Analogue of window_exec.rs:45 + window/processors/*.rs (row_number, rank,
dense_rank, percent_rank, cume_dist, lead/lag, nth_value/first/last,
agg-over-window, window-group-limit).

TPU shape: sort the partition's rows by (partition_by, order_by) once, then
every processor is a segmented scan/reduce over the sorted batch — no
per-row state machines.  Segmented running aggregates use prefix sums with
segment-start subtraction; rank family uses order-group boundaries.

Frame semantics: Spark's default frame (RANGE BETWEEN UNBOUNDED PRECEDING
AND CURRENT ROW) when order_by is present, whole partition otherwise.
"""

from __future__ import annotations

from typing import Any, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import (
    Batch, DeviceColumn, DeviceStringColumn, bucket_capacity, concat_batches,
)
from auron_tpu.exprs.compiler import build_evaluator
from auron_tpu.exprs.typing import infer_type
from auron_tpu.ir.plan import WindowFuncCall, WindowGroupLimit
from auron_tpu.ir.schema import DataType, Field, Schema
from auron_tpu.memmgr import MemConsumer, SpillManager
from auron_tpu.ops import segments
from auron_tpu.ops.base import Operator, TaskContext, batch_size, compact_indices
from auron_tpu.ops.sort_keys import (
    encode_sort_keys, keys_equal_prev, lexsort_indices,
)


class WindowExec(Operator, MemConsumer):
    def __init__(self, child: Operator, window_funcs: Tuple[WindowFuncCall, ...],
                 partition_by, order_by, group_limit: Optional[WindowGroupLimit]
                 = None, output_window_cols: bool = True):
        in_schema = child.schema
        self.window_funcs = tuple(window_funcs)
        self.partition_by = tuple(partition_by)
        self.order_by = tuple(order_by)
        self.group_limit = group_limit
        self.output_window_cols = output_window_cols
        fields = list(in_schema.fields)
        if output_window_cols:
            for wf in self.window_funcs:
                dt = wf.return_type or _default_window_type(wf)
                fields.append(Field(wf.name or wf.fn, dt))
        super().__init__(Schema(tuple(fields)), [child])
        MemConsumer.__init__(self, "WindowExec")
        self._spills = SpillManager("window")
        self._staged: List[Batch] = []
        self._staged_bytes = 0
        self._part_eval = build_evaluator(self.partition_by, in_schema)
        self._order_eval = build_evaluator(
            tuple(s.child for s in self.order_by), in_schema)
        self._arg_evals = [build_evaluator(
            tuple(wf.args) + ((wf.agg.children if wf.agg else ())), in_schema)
            for wf in self.window_funcs]

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        try:
            with self.mem_scope(ctx):
                yield from self._execute_inner(ctx)
        finally:
            self._staged = []
            self._spills.release_all()

    # -- spillable staging (window_exec.rs buffers per partition; here
    #    staged input spills as (partition, order)-sorted runs and whole
    #    partitions stream out of the run merge) -----------------------

    def _sort_exprs(self):
        from auron_tpu.ir.expr import SortExpr
        return tuple(SortExpr(child=e) for e in self.partition_by) + \
            tuple(self.order_by)

    def spill(self) -> int:
        # hybrid batches are fine: the sorter routes host-resident key
        # columns through its host path, and arrow serde round-trips
        # host columns — refusing them here would strand staged rows
        if not self._staged:
            return 0
        from auron_tpu.ops.sort import SortExec
        sorter = SortExec(self.children[0], self._sort_exprs())
        run = sorter._sort_batch(concat_batches(self.children[0].schema,
                                                self._staged))
        spill = self._spills.new_spill()
        size = spill.write_batches([run.to_arrow()])
        freed = self._staged_bytes
        self._staged = []
        self._staged_bytes = 0
        self.metrics.add("mem_spill_count", 1)
        self.metrics.add("mem_spill_size", size)
        self.update_mem_used(0)
        return freed

    def _execute_inner(self, ctx: TaskContext) -> Iterator[Batch]:
        for b in self.child_stream(ctx):
            if not b.num_rows:
                continue
            self._staged.append(b)
            self._staged_bytes += b.mem_bytes()
            self.update_mem_used(self._staged_bytes)
        if not len(self._spills):
            batches, self._staged = self._staged, []
            self.update_mem_used(0)
            if batches:
                yield from self._process_batches(batches, ctx)
            return
        if self._staged:
            self.spill()
        yield from self._merge_spilled(ctx)

    def _merge_spilled(self, ctx: TaskContext) -> Iterator[Batch]:
        """Stream (partition, order)-sorted runs through the k-way merger
        and process COMPLETE partitions as they close — only the trailing
        open partition stays buffered (the carry), so resident memory is
        one merged batch plus the largest single partition."""
        from auron_tpu.ops.joins.smj import host_keys_of_rows, split_batch
        from auron_tpu.ops.sort import HostKeyMerger
        merger = HostKeyMerger(self.children[0].schema, self._sort_exprs())
        runs = [s.read_batches() for s in self._spills.spills]
        orders = tuple((True, True) for _ in self.partition_by)
        carry: List[Batch] = []
        for mb in merger.merge(runs):
            if mb.num_rows == 0:
                continue
            if not self.partition_by:
                carry.append(mb)      # one global partition: no frontier
                continue
            pcols = self._part_eval(mb, partition_id=ctx.partition_id)
            frontier = host_keys_of_rows(pcols, [mb.num_rows - 1])[0]
            ready, keep = split_batch(mb, pcols, frontier, orders)
            if ready is not None:
                chunk = carry + [ready]
                carry = []
                yield from self._process_batches(chunk, ctx)
            if keep is not None:
                carry.append(keep)
            self.update_mem_used(sum(b.mem_bytes() for b in carry))
        if carry:
            yield from self._process_batches(carry, ctx)

    def _process_batches(self, batches: List[Batch],
                         ctx: TaskContext) -> Iterator[Batch]:
        total = sum(b.num_rows for b in batches)
        cap = bucket_capacity(total)
        merged = concat_batches(self.children[0].schema, batches, cap)
        n = merged.num_rows
        live = merged.row_mask()

        pcols = self._part_eval(merged, partition_id=ctx.partition_id)
        ocols = self._order_eval(merged, partition_id=ctx.partition_id)
        orders = tuple((s.asc, s.nulls_first) for s in self.order_by)
        pwords = encode_sort_keys(pcols, tuple((True, True)
                                               for _ in self.partition_by))
        owords = encode_sort_keys(ocols, orders)
        perm = lexsort_indices(pwords + owords, n, cap)
        sorted_b = merged.gather(perm, n)
        sp = [jnp.take(w, perm) for w in pwords]
        so = [jnp.take(w, perm) for w in owords]
        live = sorted_b.row_mask()

        c = segment_context(sp, so, live, cap)

        out_cols: List[Any] = []
        for wf, arg_eval in zip(self.window_funcs, self._arg_evals):
            args = arg_eval(sorted_b, partition_id=ctx.partition_id)
            out_cols.append(_coerce_to(
                wf, compute_window_fn(wf, args, c, self.order_by)))

        result = sorted_b
        if self.output_window_cols:
            result = Batch(self.schema, list(sorted_b.columns) + out_cols,
                           n, cap)
        if self.group_limit is not None:
            keep = jnp.logical_and(
                group_limit_rank(self.group_limit.rank_fn, c)
                <= self.group_limit.k, live)
            sel, cnt = compact_indices(keep, cap)
            result = result.gather(sel, int(cnt))
        yield from _rechunk_stream(result)


def segment_context(sp, so, live, cap):
    """Segment structure over (partition, order)-sorted key words: the
    shared context dict both the serial operator and the SPMD stage
    tracer (parallel/stage.py:_do_window) compute window functions
    from — single source of truth for boundary/rank semantics."""
    part_bound = _boundaries(sp, live, cap)
    order_bound = jnp.logical_or(part_bound, _boundaries(so, live, cap)) \
        if so else part_bound

    idx = jnp.arange(cap, dtype=jnp.int64)
    NEG = jnp.int64(-1)
    seg_start = jax.lax.cummax(jnp.where(part_bound, idx, NEG))
    og_start = jax.lax.cummax(jnp.where(order_bound, idx, NEG))
    seg_id = jnp.cumsum(part_bound.astype(jnp.int32)) - 1
    # with its bounds, derived here once for every total over partitions
    seg_id = segments.segment_bounds(
        jnp.where(live, seg_id, cap - 1), cap)
    # partition sizes + last index
    ones = jnp.where(live, 1, 0)
    seg_sizes = segments.sorted_segment_sum(ones, seg_id, cap)
    part_n = jnp.take(seg_sizes, seg_id.ids)
    seg_end = seg_start + part_n  # exclusive

    row_number = (idx - seg_start + 1).astype(jnp.int64)
    rank = (og_start - seg_start + 1).astype(jnp.int64)
    return {"row_number": row_number, "rank": rank, "idx": idx,
            "seg_start": seg_start, "seg_end": seg_end, "part_n": part_n,
            "seg_id": seg_id, "og_start": og_start,
            "order_bound": order_bound, "part_bound": part_bound,
            "live": live, "cap": cap}


def group_limit_rank(rank_fn: str, c):
    return {"row_number": c["row_number"], "rank": c["rank"],
            "dense_rank": _dense_rank(c["part_bound"], c["order_bound"])}[
        rank_fn]


def _dense_rank(part_bound, order_bound):
    og = jnp.cumsum(order_bound.astype(jnp.int64))
    og_at_seg_start = jax.lax.cummax(
        jnp.where(part_bound, og, jnp.int64(-1)))
    return og - og_at_seg_start + 1


def compute_window_fn(wf: WindowFuncCall, args, c, order_by) -> Any:
    fn = wf.fn
    cap = c["cap"]
    if fn == "row_number":
        return DeviceColumn(DataType.int64(), c["row_number"],
                            jnp.ones(cap, bool))
    if fn == "rank":
        return DeviceColumn(DataType.int64(), c["rank"],
                            jnp.ones(cap, bool))
    if fn == "dense_rank":
        d = _dense_rank(c["part_bound"], c["order_bound"])
        return DeviceColumn(DataType.int64(), d, jnp.ones(cap, bool))
    if fn == "percent_rank":
        denom = jnp.maximum(c["part_n"] - 1, 1).astype(jnp.float64)
        pr = (c["rank"] - 1).astype(jnp.float64) / denom
        pr = jnp.where(c["part_n"] <= 1, 0.0, pr)
        return DeviceColumn(DataType.float64(), pr, jnp.ones(cap, bool))
    if fn == "cume_dist":
        # rows with order-key <= current = last index of this order group
        og_end = _order_group_end(c)
        cd = (og_end - c["seg_start"]).astype(jnp.float64) / \
            jnp.maximum(c["part_n"], 1).astype(jnp.float64)
        return DeviceColumn(DataType.float64(), cd, jnp.ones(cap, bool))
    if fn in ("lead", "lag"):
        k = int(wf.args[1].value) if len(wf.args) > 1 and \
            hasattr(wf.args[1], "value") else 1
        shift = k if fn == "lead" else -k
        src = c["idx"] + shift
        in_seg = jnp.logical_and(src >= c["seg_start"],
                                 src < c["seg_end"])
        out = _gather_with_default(args[0], src, in_seg, wf, cap)
        default = wf.args[2].value if len(wf.args) > 2 and \
            hasattr(wf.args[2], "value") else None
        if default is not None:
            fill = jnp.asarray(default, out.data.dtype) \
                if not isinstance(out, DeviceStringColumn) else None
            if fill is not None:
                data = jnp.where(in_seg, out.data, fill)
                valid = jnp.logical_or(out.validity,
                                       jnp.logical_not(in_seg))
                out = DeviceColumn(out.dtype, data,
                                   jnp.logical_and(valid, c["live"]))
        return out
    if fn in ("first_value", "nth_value", "nth_value_ignore_nulls",
              "last_value"):
        if fn == "last_value":
            # Spark default RANGE frame: last *peer* row's value
            src = _order_group_end(c) - 1
            ok = c["live"]
        else:
            nth = 1
            if fn.startswith("nth") and len(wf.args) > 1 and \
                    hasattr(wf.args[1], "value"):
                nth = int(wf.args[1].value)
            src = c["seg_start"] + (nth - 1)
            ok = jnp.logical_and(src <= c["idx"], src < c["seg_end"])
        return _gather_with_default(args[0], src, ok, wf, cap)
    if fn == "agg":
        return _agg_over_window(wf, args, c, order_by)
    raise NotImplementedError(f"window function {fn!r}")

def _agg_over_window(wf: WindowFuncCall, args, c, order_by) -> Any:
    agg = wf.agg
    cap = c["cap"]
    val = args[-1] if args else None
    running = bool(order_by)

    def to_range_frame(rowwise):
        """Spark's default frame is RANGE (peers share it): broadcast
        the running value at each order group's LAST row to the whole
        group."""
        last = jnp.clip(_order_group_end(c) - 1, 0, cap - 1) \
            .astype(jnp.int32)
        return jnp.take(rowwise, last)

    if agg.fn == "count":
        x = val.validity.astype(jnp.int64) if agg.children else \
            jnp.where(c["live"], 1, 0).astype(jnp.int64)
        out = to_range_frame(_seg_running_sum(x, c)) if running \
            else _seg_total(x, c)
        return DeviceColumn(DataType.int64(), out, jnp.ones(cap, bool))
    if agg.fn in ("sum", "avg"):
        acc_dt = jnp.float64 if agg.return_type.is_floating or \
            agg.fn == "avg" else jnp.int64
        x = jnp.where(val.validity, val.data.astype(acc_dt), 0)
        hs = val.validity.astype(jnp.int64)
        if running:
            s = to_range_frame(_seg_running_sum(x, c))
            cnt = to_range_frame(_seg_running_sum(hs, c))
        else:
            s = _seg_total(x, c)
            cnt = _seg_total(hs, c)
        if agg.fn == "avg":
            out = s.astype(jnp.float64) / jnp.maximum(cnt, 1)
            return DeviceColumn(DataType.float64(), out, cnt > 0)
        return DeviceColumn(agg.return_type,
                            s.astype(agg.return_type.numpy_dtype()
                                     if not agg.return_type.is_decimal
                                     else jnp.int64), cnt > 0)
    if agg.fn in ("min", "max"):
        np_dt = np.dtype(str(val.data.dtype))
        if np_dt.kind == "f":
            neutral = jnp.asarray(
                np.inf if agg.fn == "min" else -np.inf, np_dt)
        else:
            info = np.iinfo(np_dt)
            neutral = jnp.asarray(info.max if agg.fn == "min"
                                  else info.min, np_dt)
        x = jnp.where(val.validity, val.data, neutral)
        if running:
            scan = to_range_frame(_seg_running_minmax(
                x, c, is_min=agg.fn == "min"))
            has = to_range_frame(
                _seg_running_sum(val.validity.astype(jnp.int64), c)) > 0
        else:
            scan = _seg_total_minmax(x, c, is_min=agg.fn == "min")
            has = _seg_total(val.validity.astype(jnp.int64), c) > 0
        return DeviceColumn(val.dtype, jnp.where(has, scan, 0), has)
    raise NotImplementedError(f"window agg {agg.fn!r}")


def _coerce_to(wf: WindowFuncCall, col):
    """Cast a computed window column to the declared return type (e.g.
    Spark's rank/row_number are IntegerType while the kernel computes in
    int64); the output schema is built from the declaration, and a dtype
    mismatch would reinterpret raw buffers at the Arrow boundary."""
    want = wf.return_type or _default_window_type(wf)
    if isinstance(col, DeviceStringColumn) or want.is_decimal or \
            col.dtype == want:
        return col
    try:
        np_dt = want.numpy_dtype()
    except Exception:
        return col
    return DeviceColumn(want, col.data.astype(np_dt), col.validity)


def _default_window_type(wf: WindowFuncCall) -> DataType:
    if wf.fn in ("row_number", "rank", "dense_rank"):
        return DataType.int64()
    if wf.fn in ("percent_rank", "cume_dist"):
        return DataType.float64()
    return DataType.float64()


def _boundaries(words, live, cap):
    if not words:
        # single partition: row 0 is the only boundary
        return jnp.logical_and(jnp.arange(cap, dtype=jnp.int32) == 0, live)
    eq = keys_equal_prev(words)
    return jnp.logical_and(jnp.logical_not(eq), live)


def _order_group_end(c):
    """Exclusive end index of each row's order group (same order key)."""
    cap = c["cap"]
    idx = c["idx"]
    # next boundary at or after idx+1
    nb = c["order_bound"]
    big = jnp.int64(cap)
    next_bound = jnp.flip(jax.lax.cummin(
        jnp.flip(jnp.where(nb, idx, big))))
    # next_bound[i] = first boundary index >= i; we want > i
    shifted = jnp.concatenate([next_bound[1:], jnp.array([big])])
    end = jnp.minimum(shifted, c["seg_end"])
    return end


def _gather_with_default(val, src, ok, wf: WindowFuncCall, cap):
    srcc = jnp.clip(src, 0, cap - 1).astype(jnp.int32)
    return val.gather(srcc, ok)


def _seg_running_sum(x, c):
    pref = jnp.cumsum(x)
    at_start = jnp.take(pref, jnp.clip(c["seg_start"], 0, None).astype(jnp.int32))
    start_val = jnp.take(x, jnp.clip(c["seg_start"], 0, None).astype(jnp.int32))
    return pref - at_start + start_val


def _seg_total(x, c):
    seg = c["seg_id"]
    cap = c["cap"]
    tot = segments.sorted_segment_sum(x, seg, cap)
    return jnp.take(tot, seg.ids)


def _seg_running_minmax(x, c, is_min: bool):
    # row 0 opens a partition whenever it is live; with no live row at
    # all it has no boundary, and the scan wants one there
    first = c["part_bound"].at[0].set(True)
    return segments.segmented_running(x, first, is_min)


def _seg_total_minmax(x, c, is_min: bool):
    seg = c["seg_id"]
    cap = c["cap"]
    red = segments.sorted_segment_min(x, seg, cap) if is_min else \
        segments.sorted_segment_max(x, seg, cap)
    return jnp.take(red, seg.ids)


def _rechunk_stream(b: Batch) -> Iterator[Batch]:
    bs = batch_size()
    if b.num_rows <= bs:
        yield b
        return
    arrow = b.to_arrow()
    for off in range(0, b.num_rows, bs):
        yield Batch.from_arrow(arrow.slice(off, bs))
