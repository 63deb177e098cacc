"""Task execution runtime.

Analogue of NativeExecutionRuntime (native-engine/auron/src/rt.rs:76-308):
decode the TaskDefinition, build the operator tree, pull batches through
it (with cancellation + error ferrying), finalize metrics.  The tokio
mpsc(1) producer/consumer pair becomes a straightforward generator pull —
XLA's async dispatch already overlaps device compute with host work.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Iterator, List, Optional

import pyarrow as pa

from auron_tpu.columnar.batch import Batch
from auron_tpu.ir import plan as P
from auron_tpu.ir import serde as ir_serde
from auron_tpu.memmgr import get_manager
from auron_tpu.ops.base import Operator, TaskContext
from auron_tpu.runtime.metrics import MetricNode
from auron_tpu.runtime.planner import PhysicalPlanner
from auron_tpu.runtime.resources import GLOBAL_RESOURCES, ResourceRegistry

log = logging.getLogger("auron_tpu.runtime")


@dataclass
class ExecutionResult:
    batches: List[pa.RecordBatch]
    metrics: MetricNode
    schema: Optional["pa.Schema"] = None   # plan output (empty results)
    # output batches that came back from the device (`task.to_host`)
    device_batches: int = 0

    def to_table(self) -> pa.Table:
        if not self.batches:
            if self.schema is not None:
                return pa.Table.from_batches([], schema=self.schema)
            return pa.table({})
        return pa.Table.from_batches(self.batches)

    def to_pylist(self) -> List[dict]:
        return self.to_table().to_pylist() if self.batches else []


class NativeExecutionRuntime:
    """One runtime per task (rt.rs:76): start -> iterate batches ->
    finalize."""

    def __init__(self, task: P.TaskDefinition,
                 resources: Optional[ResourceRegistry] = None):
        self.task = task
        self.planner = PhysicalPlanner()
        # verify-before-execute (conf 'auron.plan.verify'): diagnostics
        # log with the task prefix when built inside a task_scope
        self.root: Operator = self.planner.create_verified_plan(task)
        self.ctx = TaskContext(
            stage_id=task.stage_id, partition_id=task.partition_id,
            num_partitions=task.num_partitions,
            resources=resources or GLOBAL_RESOURCES,
            mem_manager=get_manager())
        self.error: Optional[BaseException] = None

    def batches(self, arrow: bool = False) -> Iterator[Batch]:
        """Pull the stream (`arrow`: the root's execute_arrow, record
        batches where it holds them); errors are recorded and re-raised
        (the setError + rethrow-on-next-loadNextBatch contract,
        rt.rs:207-238)."""
        try:
            yield from self.root.execute_with_metrics(self.ctx, arrow)
        except BaseException as e:  # noqa: BLE001 - ferried to caller
            self.error = e
            if self.ctx.is_running:
                log.error("[stage %d part %d] native execution failed: %s",
                          self.task.stage_id, self.task.partition_id, e)
                raise

    def cancel(self) -> None:
        self.ctx.cancel()

    def finalize(self) -> MetricNode:
        return self.root.metrics


def execute_plan(plan: P.PlanNode, partition_id: int = 0,
                 num_partitions: int = 1,
                 resources: Optional[ResourceRegistry] = None,
                 arrow: bool = False) -> ExecutionResult:
    """Convenience driver: run one partition of a plan to completion."""
    td = P.TaskDefinition(plan=plan, partition_id=partition_id,
                          num_partitions=num_partitions)
    return execute_task(td, resources, arrow)


def _count_operators(root: Operator) -> int:
    return 1 + sum(_count_operators(c) for c in root.children)


def task_attempt_counts() -> tuple:
    """(started, completed) task attempts this process — the chaos sweep
    bounds started_with_faults <= factor * started_fault_free.  Counters
    live in runtime/counters.py (the one registry /metrics and /queries
    read too)."""
    from auron_tpu.runtime import counters
    return counters.get("tasks_started"), counters.get("tasks_completed")


def _device_retryable(exc: BaseException) -> bool:
    """The device degradation tier's classifier: injected device faults
    and retryable SPMD guard trips — transient by construction (a
    re-execution re-draws the fault / re-traces with a wider factor);
    everything else ferries to the caller unchanged."""
    from auron_tpu.faults import InjectedDeviceFault
    from auron_tpu.parallel.stage import SpmdGuardTripped
    if isinstance(exc, InjectedDeviceFault):
        return True
    return isinstance(exc, SpmdGuardTripped) and \
        getattr(exc, "retryable", False) and \
        not getattr(exc, "auron_retry_exhausted", False)


def execute_task(task: P.TaskDefinition,
                 resources: Optional[ResourceRegistry] = None,
                 arrow: bool = False) -> ExecutionResult:
    """Run one task and return its output as Arrow.  `arrow` pulls the
    root's execute_arrow: a file scan then hands on the record batches
    it read and nothing of them reaches the device (a stage's ingest,
    parallel/stage.py::_materialize_scans)."""
    from auron_tpu.runtime import (
        counters, jitcheck, profiling, retry, task_logging, tracing,
    )

    profiling.maybe_start_from_conf()   # lazy start (exec.rs:53-59)
    task_logging.install()              # idempotent (init_logging analogue)
    rt_box: List[NativeExecutionRuntime] = []
    retries_box = [0]

    def _attempt():
        counters.bump("tasks_started")
        # per-query attribution: the ambient QueryStats (trace_scope)
        # counts this attempt for the query it belongs to — the global
        # counter above keeps serving process totals
        tracing.stats_bump("attempts")
        with task_logging.task_scope(task.stage_id, task.partition_id):
            # runtime construction sits inside the task scope so
            # plan-verifier diagnostics (create_verified_plan) and
            # planner errors carry the [stage N part M] prefix
            with tracing.span("task.plan", cat="task") as sp:
                rt = NativeExecutionRuntime(task, resources)
                if sp.armed:
                    sp.set_args(operators=_count_operators(rt.root))
            rt_box[:] = [rt]
            # the per-batch pull loop is THE hot path: every implicit
            # device->host transfer in it must route through host_sync
            # (the single-sync policy) — jitcheck audits that here
            with jitcheck.transfer_guard("task.execute"):
                # convert BEFORE the row-count check: to_arrow fetches
                # count + columns in one round trip, while `b.num_rows`
                # alone would pay a separate sync for lazy batches
                out, from_device = [], 0
                for rb in rt.batches(arrow):
                    if isinstance(rb, Batch):
                        from_device += 1
                        with tracing.span("task.to_host", cat="task",
                                          blocked=True) as sp:
                            rb = rb.to_arrow()
                            if sp.armed:
                                sp.set_args(rows=rb.num_rows,
                                            bytes=rb.nbytes)
                    if rb.num_rows > 0:
                        out.append(rb)
                return out, from_device

    def _count_retry(_attempt_no, _exc):
        retries_box[0] += 1
        counters.bump("tasks_retried")

    # device-tier recovery: a task dying with an injected device fault
    # (or a retryable SPMD guard trip that escaped the stage driver) is
    # re-executed on this serial per-partition path with a fresh operator
    # tree, bounded by the shared retry budget; the re-execution count
    # lands in the task's metric tree (num_retries)
    from auron_tpu.ops.kernel_cache import cache_info
    cache0 = cache_info()
    jit0 = sum(jitcheck.compile_counts().values())
    try:
        with tracing.span("task.execute", cat="task",
                          stage=task.stage_id,
                          partition=task.partition_id) as sp:
            stats = tracing.current_stats() if sp.armed else None
            syncs0 = stats.get("host_syncs") if stats is not None else 0
            out, from_device = retry.call_with_retry(
                _attempt, policy=retry.RetryPolicy.from_conf(),
                label=f"task stage={task.stage_id} "
                      f"part={task.partition_id}",
                classify=_device_retryable, on_retry=_count_retry)
            if stats is not None:
                # the query's blocking fetches while this task ran: the
                # task's own where no other task of the query ran beside it
                sp.set_args(syncs=stats.get("host_syncs") - syncs0)
    except BaseException:
        counters.bump("tasks_failed")
        raise
    cache1 = cache_info()
    rt = rt_box[0]
    counters.bump("tasks_completed")
    out_schema = None
    try:
        from auron_tpu.ir.schema import to_arrow_schema
        if rt.root.schema is not None:
            out_schema = to_arrow_schema(rt.root.schema)
    except Exception:  # noqa: BLE001 - schema is advisory (empty case)
        pass
    metrics = rt.finalize()
    if retries_box[0]:
        metrics.add("num_retries", retries_box[0])
    # kernel-cache observability: how many jitted-kernel lookups this
    # task hit vs built (a repeated query shape should be ~all hits —
    # the zero-re-trace contract the fused fragments key on)
    metrics.add("kernel_cache_hits", cache1["hits"] - cache0["hits"])
    metrics.add("kernel_cache_misses",
                cache1["misses"] - cache0["misses"])
    # compilation observability: jitted-program TRACES this task caused
    # (a warm repeat of the same shape must add zero — the jitcheck
    # second-run-compiles-zero contract); per-site totals ride /metrics
    metrics.add("jit_compiles",
                sum(jitcheck.compile_counts().values()) - jit0)
    return ExecutionResult(out, metrics, schema=out_schema,
                           device_batches=from_device)


def execute_task_bytes(task_bytes: bytes,
                       resources: Optional[ResourceRegistry] = None
                       ) -> ExecutionResult:
    """The wire entry point: serialized TaskDefinition in, batches out
    (the callNative/nextBatch/finalizeNative surface, exec.rs:42-144)."""
    td = ir_serde.deserialize(task_bytes)
    assert isinstance(td, P.TaskDefinition)
    return execute_task(td, resources)
