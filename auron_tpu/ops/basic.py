"""Basic operators: Project, Filter, Limit, Union, Expand, CoalesceBatches,
RenameColumns, EmptyPartitions, Debug.

Reference analogues: project_exec.rs:48, filter_exec.rs:44 (fused
filter+project via the shared evaluator), limit_exec.rs:42, union_exec.rs:39,
expand_exec.rs:40, ExecutionContext::coalesce_with_default_batch_size,
rename_columns_exec.rs:41, empty_partitions_exec.rs:36, debug_exec.rs:37.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import Batch, concat_batches
from auron_tpu.exprs.compiler import build_evaluator, build_predicate
from auron_tpu.ir.schema import Field, Schema
from auron_tpu.exprs.typing import infer_type
from auron_tpu.ops.base import (
    Operator, TaskContext, batch_size, compact_indices, cut_batches,
)
from auron_tpu.runtime import jitcheck, tracing

# ONE compact-gather program serves every filter's column structure
# (jax.jit's per-aval cache) — distinct signatures track workload
# diversity, not a retrace bug
jitcheck.waive_retraces(
    "filter.compact_gather", 0,
    "one compact program per column structure by design")


class ProjectExec(Operator):
    def __init__(self, child: Operator, exprs, names):
        in_schema = child.schema
        fields = tuple(Field(n, infer_type(x, in_schema))
                       for n, x in zip(names, exprs))
        super().__init__(Schema(fields), [child])
        self.exprs = tuple(exprs)
        self._eval = build_evaluator(self.exprs, in_schema)
        self._row_base = 0

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        for b in self.child_stream(ctx):
            with tracing.span("project.eval", cat="op",
                              exprs=len(self.exprs)) as sp:
                cols = self._eval(b, partition_id=ctx.partition_id,
                                  row_base=self._row_base)
                out = b.with_columns(self.schema, cols)
                if sp.armed:
                    # a lazy batch's count is on the device: not fetched
                    # for a trace's sake
                    sp.set_args(
                        rows=b.num_rows if b.num_rows_known else -1)
            if self._eval.uses_row_base:
                self._row_base += b.num_rows
            yield out


class FilterExec(Operator):
    """Filter + optional fused projection (reference fuses them too)."""

    def __init__(self, child: Operator, predicates,
                 exprs=None, names=None):
        in_schema = child.schema
        if exprs is None:
            out_schema = in_schema
        else:
            out_schema = Schema(tuple(
                Field(n, infer_type(x, in_schema))
                for n, x in zip(names, exprs)))
        super().__init__(out_schema, [child])
        self.predicates = tuple(predicates)
        self.exprs = tuple(exprs) if exprs is not None else None
        self._pred = build_predicate(self.predicates, in_schema)
        self._proj = build_evaluator(self.exprs, in_schema) \
            if self.exprs is not None else None
        self._row_base = 0

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        from auron_tpu.columnar.batch import HostColumn
        from auron_tpu.ops.kernel_cache import cached_jit, host_sync
        track_base = self._pred.uses_row_base or \
            (self._proj is not None and self._proj.uses_row_base)
        compact = cached_jit("filter.compact_gather",
                             _filter_compact_builder)
        for b in self.child_stream(ctx):
            [m] = self._pred(b, partition_id=ctx.partition_id,
                             row_base=self._row_base)
            src = b
            if self._proj is not None:
                cols = self._proj(b, partition_id=ctx.partition_id,
                                  row_base=self._row_base)
                src = b.with_columns(self.schema, cols)
            if track_base:
                self._row_base += b.num_rows
            host_cols = [i for i, c in enumerate(src.columns)
                         if isinstance(c, HostColumn)]
            dev_cols = [c for i, c in enumerate(src.columns)
                        if i not in host_cols]
            out, idx, count = compact(dev_cols, m.data, m.validity,
                                      b.num_rows_dev())
            if host_cols:
                # hybrid row: host columns gather on host by the same index
                n = int(host_sync(count))
                if n == 0:
                    continue
                hidx = np.asarray(host_sync(idx))[:n]
                merged = []
                it = iter(out)
                for i, c in enumerate(src.columns):
                    merged.append(c.gather_host(hidx) if i in host_cols
                                  else next(it))
                yield Batch(self.schema, merged, n, src.capacity)
            else:
                # lazy emission: the count stays on device; downstream
                # syncs only if it actually needs the host int
                yield Batch(self.schema, list(out), count, src.capacity)


def _filter_compact_builder():
    def run(cols, mask_data, mask_valid, num_rows):
        cap = mask_data.shape[0]
        live = jnp.arange(cap, dtype=jnp.int32) < num_rows
        keep = jnp.logical_and(
            jnp.logical_and(mask_valid, mask_data.astype(bool)), live)
        idx, count = compact_indices(keep, cap)
        valid = jnp.arange(cap, dtype=jnp.int32) < count
        return [c.gather(idx, valid) for c in cols], idx, count
    return run


class LimitExec(Operator):
    def __init__(self, child: Operator, limit: int, offset: int = 0):
        super().__init__(child.schema, [child])
        self.limit = limit
        self.offset = offset

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        return cut_batches(self.child_stream(ctx), self.offset, self.limit)


class UnionExec(Operator):
    """Multi-input union with the proto:542-552 per-input partition
    mapping: this task's output partition streams exactly the child
    partitions assigned to it (so multi-partition children are read once
    across the union's output partitions, never replayed)."""

    def __init__(self, children: List[Operator], schema: Schema,
                 assignments: Optional[List[Tuple[int, int]]] = None):
        super().__init__(schema, children)
        # per-child (out_partition, child_local_partition); None = every
        # partition streams every child at its own partition id (direct
        # construction without a planner-provided mapping)
        self.assignments = assignments

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        import dataclasses
        assignments = self.assignments if self.assignments is not None \
            else [(ctx.partition_id, ctx.partition_id)] * len(self.children)
        # collapsed single-partition execution (exchange-inlined pipeline)
        # must stream EVERY assignment: dropping out_partition != 0 would
        # silently lose those union inputs' rows
        collapsed = ctx.num_partitions == 1
        for i, (out_pid, local_pid) in enumerate(assignments):
            if not collapsed and out_pid != ctx.partition_id:
                continue
            sub = dataclasses.replace(ctx, partition_id=local_pid)
            for b in self.child_stream(sub, i):
                yield b.rename(self.schema.names()) \
                    if b.schema.names() != self.schema.names() else b


class ExpandExec(Operator):
    """Grouping-sets: emits one copy of the input per projection list."""

    def __init__(self, child: Operator, projections, names, types=None):
        in_schema = child.schema
        if types:
            fields = tuple(Field(n, t) for n, t in zip(names, types))
        else:
            fields = tuple(Field(n, infer_type(x, in_schema))
                           for n, x in zip(names, projections[0]))
        super().__init__(Schema(fields), [child])
        self.projections = tuple(tuple(p) for p in projections)
        self._evals = [build_evaluator(p, in_schema) for p in self.projections]

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        for b in self.child_stream(ctx):
            for ev in self._evals:
                cols = ev(b, partition_id=ctx.partition_id)
                yield b.with_columns(self.schema, cols)


class CoalesceBatchesExec(Operator):
    def __init__(self, child: Operator, target: int = 0):
        super().__init__(child.schema, [child])
        self.target = target or batch_size()

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        staged: List[Batch] = []
        staged_rows = 0
        for b in self.child_stream(ctx):
            if b.num_rows == 0:
                continue
            if b.num_rows >= self.target and not staged:
                yield b
                continue
            staged.append(b)
            staged_rows += b.num_rows
            if staged_rows >= self.target:
                yield concat_batches(self.schema, staged)
                staged, staged_rows = [], 0
        if staged:
            yield concat_batches(self.schema, staged)


class RenameColumnsExec(Operator):
    def __init__(self, child: Operator, names):
        super().__init__(child.schema.rename(tuple(names)), [child])
        self.names = tuple(names)

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        for b in self.child_stream(ctx):
            yield b.rename(self.names)


class EmptyPartitionsExec(Operator):
    def __init__(self, schema: Schema, num_partitions: int = 1):
        super().__init__(schema, [])
        self.num_partitions = num_partitions

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        return iter(())


class DebugExec(Operator):
    def __init__(self, child: Operator, debug_id: str = ""):
        super().__init__(child.schema, [child])
        self.debug_id = debug_id

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        import logging
        log = logging.getLogger("auron_tpu.debug")
        for i, b in enumerate(self.child_stream(ctx)):
            log.info("[%s] batch %d: %d rows\n%s", self.debug_id, i,
                     b.num_rows, b.to_arrow().slice(0, 10).to_pydict())
            yield b


class MemoryScanExec(Operator):
    """In-memory table scan (the MemoryExec analogue the reference's operator
    tests build fixtures with, joins/test.rs:57)."""

    def __init__(self, schema: Schema, batches: List[Batch],
                 partitions: Optional[List[List[Batch]]] = None):
        super().__init__(schema, [])
        self._partitions = partitions if partitions is not None else [batches]

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        pid = min(ctx.partition_id, len(self._partitions) - 1)
        yield from iter(self._partitions[pid])
