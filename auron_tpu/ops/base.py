"""Operator protocol + execution context.

Analogue of the reference's ExecutionContext scaffolding
(datafusion-ext-plans/src/common/execution_context.rs:70): operators are
host-driven generators of padded device batches; the hot kernels inside are
jitted jnp programs cached per (fragment, schema, capacity).
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import jax.numpy as jnp

from auron_tpu.columnar.batch import Batch
from auron_tpu.config import conf
from auron_tpu.ir.schema import Schema
from auron_tpu.runtime.metrics import MetricNode
from auron_tpu.runtime.resources import GLOBAL_RESOURCES, ResourceRegistry


@dataclass
class TaskContext:
    """Per-task execution context (stage/partition ids, resources, memory
    manager handle) — analogue of the JVM TaskContext the reference
    propagates to native worker threads (rt.rs:113-139)."""
    stage_id: int = 0
    partition_id: int = 0
    num_partitions: int = 1
    resources: ResourceRegistry = field(default_factory=lambda: GLOBAL_RESOURCES)
    mem_manager: Optional[Any] = None
    is_running: bool = True    # is_task_running analogue (jni lib.rs:35)

    def cancel(self) -> None:
        self.is_running = False


class Operator:
    """Base operator: `execute(ctx)` yields Batches of `self.schema`."""

    def __init__(self, schema: Schema, children: List["Operator"],
                 name: Optional[str] = None):
        self.schema = schema
        self.children = children
        self.name = name or type(self).__name__
        self.metrics = MetricNode(self.name)
        for c in children:
            self.metrics.children.append(c.metrics)

    # -- interface ----------------------------------------------------------

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        raise NotImplementedError

    def execute_arrow(self, ctx: TaskContext) -> Iterator[Any]:
        """execute() for a caller that wants the output on the host: an
        operator that holds its output as Arrow before it makes a device
        batch of it (the file scans) yields the record batches; every
        other yields its batches."""
        return self.execute(ctx)

    # -- helpers ------------------------------------------------------------

    def execute_with_metrics(self, ctx: TaskContext,
                             arrow: bool = False) -> Iterator[Batch]:
        """Wraps execute() (`arrow`: execute_arrow()) with
        output_rows/batches + compute-time metrics and task-cancellation
        checks."""
        import time

        from auron_tpu.faults import fault_point
        from auron_tpu.runtime import tracing
        # one draw per operator instantiation (not per batch): a `device`
        # fault here kills the task, which the executor's degradation
        # tier re-runs (num_retries) — the dynamic proof that operator
        # failure recovery works end to end
        fault_point("op.execute")
        from auron_tpu.runtime import perfscope
        it = self.execute_arrow(ctx) if arrow else self.execute(ctx)
        while True:
            # with perfscope armed, kernels executed during this pull
            # attribute their bytes/seconds to THIS operator's metric
            # node (the EXPLAIN ANALYZE bytes/GB/s columns); the
            # innermost pulling operator wins, matching whose compute
            # slice the kernel wall time already lands in.  Disarmed:
            # one flag read per batch.
            attr = (perfscope.attribution_scope(self.metrics)
                    if perfscope.enabled() else None)
            t0 = time.perf_counter_ns()
            if attr is not None:
                attr.__enter__()
            try:
                batch = next(it)
            except StopIteration:
                self.metrics.add("elapsed_compute_ns",
                                 time.perf_counter_ns() - t0)
                # stream end: one instant event per operator (never one
                # per batch — generator frames interleave, so a span
                # here would mis-nest).  Deferred device counters are
                # NOT settled for this: metrics must not force a sync.
                tracing.event(
                    "op.complete", cat="op", op=self.name,
                    rows=self.metrics.values.get("output_rows", 0),
                    batches=self.metrics.values.get("output_batches", 0))
                return
            finally:
                if attr is not None:
                    attr.__exit__(None, None, None)
            self.metrics.add("elapsed_compute_ns", time.perf_counter_ns() - t0)
            if not ctx.is_running:
                return
            if not isinstance(batch, Batch) or batch.num_rows_known:
                self.metrics.add("output_rows", batch.num_rows)
            else:
                # lazy batch: never force a sync just for a metric
                self.metrics.add_deferred("output_rows",
                                          batch.num_rows_dev())
            self.metrics.add("output_batches", 1)
            yield batch

    @contextmanager
    def mem_scope(self, ctx: TaskContext, consumer=None):
        """Register a MemConsumer (default: the operator itself) with the
        task's memory manager for the duration of the scope, binding this
        operator's MetricNode so the consumer's peak usage lands in the
        metric tree (`mem_peak`) on unregister — the one place memory
        columns enter EXPLAIN ANALYZE and the /queries history."""
        from auron_tpu.memmgr import get_manager
        mgr = ctx.mem_manager or get_manager()
        c = consumer if consumer is not None else self
        c.bind_metrics(self.metrics)
        mgr.register_consumer(c)
        try:
            yield mgr
        finally:
            mgr.unregister_consumer(c)

    def child_stream(self, ctx: TaskContext, i: int = 0) -> Iterator[Batch]:
        stream = self.children[i].execute_with_metrics(ctx)
        if conf.get("auron.input.batch.statistics.enable"):
            return self._counted_input(stream)
        return stream

    def _counted_input(self, stream: Iterator[Batch]) -> Iterator[Batch]:
        for b in stream:
            self.metrics.add("input_batch_count", 1)
            if b.num_rows_known:
                self.metrics.add("input_rows", b.num_rows)
            yield b


def compact_indices(mask, capacity: int):
    """Stable indices of set mask bits, padded with 0; returns (idx, count).
    The core filter/compaction primitive (device-side, static shape)."""
    idx = jnp.nonzero(mask, size=capacity, fill_value=0)[0].astype(jnp.int32)
    count = jnp.sum(mask.astype(jnp.int32))
    return idx, count


def cut_batches(batches: Iterator[Batch], offset: int,
                limit: Optional[int]) -> Iterator[Batch]:
    """`batches` without their first `offset` rows, cut after `limit` more
    (None: no cut): LimitExec's body and a sort's fetch.  Each batch's cut
    is a leaf span, `limit.cut`, closed before the batch is handed on;
    with neither offset nor limit the batches pass untouched and
    uncounted."""
    from auron_tpu.runtime import tracing
    if not offset and limit is None:
        yield from batches
        return
    to_skip = offset
    remaining = limit if limit is not None else 1 << 62
    for b in batches:
        if remaining <= 0:
            return
        with tracing.span("limit.cut", cat="op") as sp:
            rows_in = b.num_rows
            if to_skip >= rows_in:
                to_skip -= rows_in
                b = None
            else:
                if to_skip > 0:
                    idx = jnp.arange(b.capacity, dtype=jnp.int32) + to_skip
                    b = b.gather(idx, rows_in - to_skip)
                    to_skip = 0
                if b.num_rows > remaining:
                    b = b.head(remaining)
                remaining -= b.num_rows
            sp.set_args(rows_in=rows_in,
                        rows_out=0 if b is None else b.num_rows)
        if b is not None:
            yield b


def batch_size() -> int:
    return int(conf.get("auron.batch.size"))


def suggested_output_capacity(n: int) -> int:
    from auron_tpu.columnar.batch import bucket_capacity
    return bucket_capacity(min(n, batch_size()) if n else batch_size())
