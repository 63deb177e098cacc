"""Front-end session: strategy + conversion + stage-scheduled execution.

The driver-side glue the reference spreads across
AuronSparkSessionExtension.scala (rule injection), NativeRDD.scala /
NativeHelper.scala (per-task native execution), AuronShuffleManager
(exchange materialization) and NativeBroadcastExchangeBase (broadcast
collect): `AuronSession.execute` tags a foreign plan, converts the
convertible sections, then runs the converted tree — native sections
through the task runtime (stage-by-stage across exchange boundaries via
the in-process shuffle service), foreign sections through the pluggable
host engine, with Arrow tables crossing the boundary both ways.
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Protocol

import pyarrow as pa

from auron_tpu import config
from auron_tpu.frontend import converters, strategy
from auron_tpu.frontend.converters import (
    BroadcastJob, ConvertContext, ConvertedT, ForeignSource, ForeignWrap,
    ShuffleJob,
)
from auron_tpu.frontend.foreign import ForeignNode
from auron_tpu.ir import plan as P
from auron_tpu.ir.node import Node
from auron_tpu.ir.schema import to_arrow_schema
from auron_tpu.ops.shuffle.writer import InProcessShuffleService
from auron_tpu.runtime.executor import ExecutionResult, execute_plan
from auron_tpu.runtime.metrics import MetricNode
from auron_tpu.runtime.resources import ResourceRegistry

log = logging.getLogger("auron_tpu.frontend")


def _blocks_nbytes(blocks) -> int:
    """Total serialized bytes of a per-partition block-list fetch result
    (the late-bound `nbytes` span arg on shuffle.fetch)."""
    return sum(len(d) for part in blocks for d in part)


class ForeignEngine(Protocol):
    """The host engine executing non-converted plan sections (the role
    Spark itself plays in the reference).  Native child results arrive as
    Arrow tables."""

    def execute(self, node: ForeignNode, child_tables: List[pa.Table]
                ) -> pa.Table:
        ...


@dataclass
class SessionResult:
    table: pa.Table
    converted: ConvertedT = None  # type: ignore[assignment]
    tags: Optional[strategy.Tags] = None
    metrics: List[MetricNode] = field(default_factory=list)
    ctx: Optional[ConvertContext] = None  # exchange/broadcast subtrees
    spmd: bool = False  # executed as one shard_map program over a mesh
    # why the SPMD stage compiler degraded to the serial path, as a
    # rendered analysis diagnostic (None when spmd ran or no mesh)
    spmd_rejection: Optional[str] = None
    # observability (runtime/tracing.py): the per-execute query id, the
    # driver wall time, and — when `auron.trace.enable` was set — the
    # TraceRecorder whose .to_chrome_trace()/.save() export the query's
    # lifecycle spans
    query_id: Optional[str] = None
    wall_s: float = 0.0
    trace: Optional[object] = None   # runtime.tracing.TraceRecorder
    # adaptive execution (runtime/adaptive.py): structured replan
    # decisions and the observed per-exchange size histograms that
    # drove them — the audit trail /queries/<id> and EXPLAIN ANALYZE
    # surface.  exchange_stats is populated whenever the serial
    # exchange path runs (observation is free); aqe_decisions only
    # when auron.adaptive.enable made replanning act on them.
    aqe_decisions: List[dict] = field(default_factory=list)
    exchange_stats: List[dict] = field(default_factory=list)
    # what the stage program reported of itself (parallel/stage.py::
    # execute_plan_spmd's `stats`): `join_probes`, the probe each K=1
    # join took, `agg_inputs`, the input each aggregate that chose
    # worked on and the width its body ran at, and `join_chains`, the side
    # each join chain took under its first join's label and the width its
    # later joins ran at, by operator label;
    # `segments`, the segment bounds the
    # program's trace derived and the reductions over them; `ingest`, what
    # the scan leaves' tasks read; over more than one device also
    # `exchanges`, `broadcasts` and `sources`, what crossed devices
    stage_stats: Dict[str, object] = field(default_factory=dict)

    def to_pylist(self) -> List[dict]:
        return self.table.to_pylist()

    def explain_analyze(self, normalize: bool = False) -> str:
        """Render the executed plan annotated with the merged per-task
        metric trees (runtime/explain_analyze.py).  `normalize=True`
        yields the run-stable canonical form goldens compare against."""
        from auron_tpu.runtime.explain_analyze import (
            explain_analyze as _ea, metric_totals,
        )
        totals = metric_totals(self.metrics)
        stage_plan = None
        if self.spmd:
            # the stage program's operators under the labels its device
            # time is filed under (python -m auron_tpu.trace device)
            from auron_tpu.parallel.stage import explain_stage
            stage_plan = explain_stage(self.converted, self.ctx,
                                       stats=self.stage_stats)
        return _ea(self.metrics, query_id=self.query_id,
                   wall_s=self.wall_s, rows=self.table.num_rows,
                   spmd=self.spmd,
                   retries=totals.get("num_retries", 0),
                   fallbacks=totals.get("num_fallbacks", 0),
                   aqe=self.aqe_decisions,
                   normalize=normalize, stage_plan=stage_plan)

    def stage_totals(self) -> Dict[str, int]:
        """The stage path's counters as query totals: `scan_rows`,
        `scan_batches` (what the scan leaves' tasks read for this execute;
        0 where every leaf was cached) and `scan_device_batches` (those of
        them that were device batches on the way; 0 expected);
        `scan_cached` and `shards_cached` (scan leaves and sources the
        two source caches served), `shard_put_bytes` (bytes of the sources
        they did not serve, padded on the host and put on the device;
        0 where every source was cached), `source_evictions` (entries this
        execute's stores evicted from them) and `source_over_budget_bytes`
        (bytes they hold past their budgets because this execute reads
        them; 0 where a query fits the budgets);
        `join_probes` (K=1 joins run) and `join_probes_direct` (those
        that probed by direct address on every device); `agg_inputs`
        (aggregates with a width to choose under their input's: the
        capacity their output is cut to, or a rung below it),
        `agg_inputs_compact` (those whose input every device compacted
        to such a width first) and `agg_inputs_below_cap` (those every
        device ran under that capacity, at a rung); `join_chains` (chains
        of inner joins over a scan with a width to choose for their later
        joins) and `join_chains_compact` (those every device ran at a
        rung); `segment_bounds` (segment bounds derived
        while the stage program was traced: one an aggregate body) and
        `segment_reductions` (sorted-segment reductions that took them);
        over more than one device also
        `exchange_rows`, `exchange_rows_moved`, `exchange_buffer_bytes`,
        `exchange_fill_pct_max`, `broadcast_rows`, `broadcast_slots` and
        `broadcast_buffer_bytes` (stage.py::crossing_totals)."""
        if not self.spmd:
            return {}
        from auron_tpu.parallel.stage import stage_totals
        return stage_totals(self.stage_stats)

    def all_native(self) -> bool:
        """True when no foreign section remains (the
        checkSparkAnswerAndOperator plan-walk assertion,
        AuronQueryTest.scala:29-91).  LocalTableScan C2N sources are
        pass-through, matching the reference's allowance for
        ConvertToNative inputs.  A foreign-only run (auron.enable=false)
        has converted=None and is never 'all native'."""
        return self.converted is not None and \
            not isinstance(self.converted, ForeignWrap) and \
            getattr(self, "_foreign_sections", 0) == 0


class AuronSession:
    def __init__(self, foreign_engine: Optional[ForeignEngine] = None,
                 shuffle_service=None):
        # session-level default: arm the persistent XLA compilation
        # cache on device backends (auron.compile.cache.dir) so every
        # front-end entry point — not just the IT CLI — pays device
        # compiles once across processes
        config.apply_compile_cache()
        self.foreign_engine = foreign_engine
        if shuffle_service is None:
            # conf-selected transport: in-process (default) or a remote
            # shuffle service client (Celeborn/Uniffle analogues)
            from auron_tpu.shuffle_rss import service_from_conf
            shuffle_service = service_from_conf() or \
                InProcessShuffleService()
        self.shuffle_service = shuffle_service
        self._metrics: List[MetricNode] = []
        # durable-shuffle bookkeeping (shuffle_rss/durable.py): rid ->
        # side-car shuffle id for exchanges pushed durably, rids that
        # (also) hold executor-local fallback data, and the sticky
        # degrade flag once the side-car proved unreachable
        self._local_shuffle: Optional[InProcessShuffleService] = None
        self._exchange_sids: Dict[str, str] = {}
        self._exchange_local: set = set()
        self._rss_degraded = False
        # sharded side-cars degrade per SHARD ("host:port"), so a dead
        # shard takes down only the shuffle ids it owns
        self._rss_degraded_shards: set = set()
        self._stream_root: Optional[int] = None
        # adaptive execution (runtime/adaptive.py): per-query replan
        # decisions + observed exchange histograms, and the wall-clock
        # start the stage-boundary re-forecast ages against
        self._aqe_decisions: List[dict] = []
        self._exchange_stats: List[dict] = []
        self._plan_signature: str = ""
        self._wall_start: float = 0.0

    # -- public entry (preColumnarTransitions analogue) -------------------

    def execute(self, plan: ForeignNode,
                mesh=None, mesh_axis: str = "parts",
                query_id: Optional[str] = None) -> SessionResult:
        """Run a foreign plan.  With `mesh`, the converted native tree is
        first offered to the SPMD stage compiler (parallel/stage.py): the
        WHOLE pipeline — exchanges included — compiles to one shard_map
        program riding ICI collectives; plans it cannot express fall back
        to the serial per-partition path transparently.

        Every execute runs under a query scope (runtime/tracing.py): a
        query id (minted fresh, or `query_id` — the serving tier passes
        its submission id so `/queries` rows match `/status` ids)
        correlates log prefixes, span attributes and the query-history
        record; with `auron.trace.enable` set the full lifecycle trace
        lands on `SessionResult.trace`.

        Thread-safety: one execute per session instance at a time (the
        serving scheduler creates a session per query); concurrent
        executes MAY share the process (memory pool, task pool, shuffle
        service are lock-protected, and attribution is contextvar-scoped
        per query)."""
        from auron_tpu.runtime import counters, tracing
        from auron_tpu.runtime.explain_analyze import (
            merge_metric_trees, metric_max, metric_totals,
        )

        scope = tracing.trace_scope(query_id=query_id)
        counters.bump("queries_started")
        # a conversion failure must not record THIS run under the
        # previous run's plan signature
        self._plan_signature = ""
        t0 = time.perf_counter()
        wall_start = time.time()
        self._wall_start = wall_start
        res: Optional[SessionResult] = None
        error: Optional[str] = None
        try:
            with scope, tracing.span("query", cat="query",
                                     query_id=scope.query_id):
                res = self._execute_impl(plan, mesh, mesh_axis)
        except BaseException as e:
            counters.bump("queries_failed")
            error = f"{type(e).__name__}: {e}"
            raise
        finally:
            wall_s = time.perf_counter() - t0
            # per-query attribution sink (tracing.QueryStats): recovery
            # and memory sites bumped the scope's own counters, so the
            # record stays correct with other queries interleaving —
            # the old global-counter diffs credited a query with every
            # concurrent neighbor's retries and spills
            st = scope.stats.snapshot()
            trees = res.metrics if res is not None else []
            totals = metric_totals(trees)
            if res is not None:
                totals.update(res.stage_totals())
            # every blocking device->host fetch of the execute: the stage
            # driver's (`spmd.wait`, `spmd.fetch`) and the serial engine's
            totals["host_syncs"] = st.get("host_syncs", 0)
            # the minimal lifecycle timeline of a direct execute (the
            # serving schedulers patch/record the full queued ->
            # admitted -> ... machine over this)
            timeline = [{"state": "running", "t": wall_start},
                        {"state": "failed" if error else "succeeded",
                         "t": wall_start + wall_s}]
            tracing.record_query(tracing.QueryRecord(
                query_id=scope.query_id, wall_s=wall_s,
                signature=self._plan_signature,
                rows=res.table.num_rows if res is not None else 0,
                spmd=res.spmd if res is not None else False,
                attempts=st.get("attempts", 0),
                retries=st.get("retries", 0),
                fallbacks=st.get("fallbacks", 0),
                error=error, started_at=wall_start,
                metric_totals=totals,
                mem_peak=metric_max(trees, "mem_peak"),
                mem_spills=st.get("mem_spills", 0),
                mem_spill_bytes=st.get("mem_spill_bytes", 0),
                metric_trees=[{"tasks": n, "tree": t.to_dict()}
                              for t, n in merge_metric_trees(trees)],
                timeline=timeline,
                aqe_decisions=list(self._aqe_decisions) or None,
                exchange_stats=list(self._exchange_stats) or None,
                trace=scope.recorder.to_chrome_trace()
                if scope.recorder is not None else None))
        counters.bump("queries_completed")
        res.query_id = scope.query_id
        res.wall_s = wall_s
        res.trace = scope.recorder
        return res

    def _execute_impl(self, plan: ForeignNode, mesh,
                      mesh_axis: str) -> SessionResult:
        from auron_tpu.runtime import tracing
        if not config.ENABLE.get():
            return SessionResult(table=self._run_foreign_only(plan))
        if mesh is None and config.SPMD_SINGLE_DEVICE.get():
            from auron_tpu.parallel.mesh import data_mesh
            mesh = data_mesh(1)
        with tracing.span("plan.convert", cat="plan"):
            tags = strategy.apply(plan)
            ctx = ConvertContext()
            converted = converters.convert_recursively(plan, tags, ctx)
        self._metrics = []
        self._spmd_rejection = None
        self._exchange_sids = {}
        self._exchange_local = set()
        self._aqe_decisions = []
        self._exchange_stats = []
        self._plan_signature = ""
        from auron_tpu.runtime import statshist
        if config.ADAPTIVE_ENABLE.get() or statshist.enabled():
            # the unified cost model keys its live exchange history by
            # plan signature (serving/forecast.py) — computed once
            # here; the durable stats store (runtime/statshist.py)
            # keys its terminal fold by the same signature
            from auron_tpu.serving.forecast import plan_signature
            try:
                self._plan_signature = plan_signature(plan)
            except Exception:
                self._plan_signature = ""
        # result streaming (runtime/result_stream.py): only the ROOT
        # native plan's partitions are the query result — exchange map
        # sides and broadcast subtrees run through the same _run_native
        # machinery and must never publish
        self._stream_root = id(converted) \
            if isinstance(converted, P.PlanNode) else None
        if mesh is not None and isinstance(converted, P.PlanNode):
            from auron_tpu.parallel.stage import (
                SpmdUnsupported, execute_plan_spmd, precheck_plan,
            )
            try:
                # cheap kind-level check BEFORE materializing any foreign
                # source (a fallback must not pay for C2N subtrees twice)
                precheck_plan(converted, ctx)
                sources = {rid: self._source_table(src, ctx)
                           for rid, src in ctx.sources.items()}
                stage_stats: Dict[str, object] = {}
                table = execute_plan_spmd(converted, ctx, mesh, sources,
                                          axis=mesh_axis, stats=stage_stats)
                res = SessionResult(table=table, converted=converted,
                                    tags=tags, ctx=ctx, spmd=True,
                                    stage_stats=stage_stats)
                res._foreign_sections = sum(  # type: ignore[attr-defined]
                    1 for s in ctx.sources.values()
                    if s.node.children or
                    s.node.node.op != "LocalTableScanExec")
                return res
            except SpmdUnsupported as e:
                # degradation tier: the serial per-partition path below
                # IS the recovery.  The rejection becomes a structured
                # diagnostic (analysis/spmd.py) — the chaos sweep and
                # refplans report it uniformly — and the fallback is
                # counted (num_fallbacks in the run metrics).
                from auron_tpu.analysis.spmd import rejection_diagnostic
                from auron_tpu.runtime import retry as _retry
                diag = rejection_diagnostic(e, converted)
                log.info("SPMD stage fell back to serial path: %s", diag)
                _retry.add_fallback()
                fb = MetricNode("SpmdFallback")
                fb.add("num_fallbacks", 1)
                self._metrics.append(fb)
                self._spmd_rejection = str(diag)
        try:
            table = self._run_converted(converted, ctx)
        finally:
            # release exchange blocks (local or remote shuffle server —
            # the shuffle-cleanup the reference delegates to Spark's
            # ShuffleManager.unregisterShuffle).  Durable side-car
            # blocks are kept when `auron.rss.defer.cleanup` is set:
            # the fleet deletes them by query tag once the submission
            # is TERMINAL, so a kill -9'd executor's committed map
            # outputs survive for the requeued attempt to resume from.
            for rid in ctx.exchanges:
                self._clear_exchange(rid)
        res = SessionResult(table=table, converted=converted, tags=tags,
                            metrics=self._metrics, ctx=ctx,
                            spmd_rejection=self._spmd_rejection,
                            aqe_decisions=list(self._aqe_decisions),
                            exchange_stats=list(self._exchange_stats))
        # count foreign sections that needed the host engine (local-table
        # sources are data, not computation)
        res._foreign_sections = sum(  # type: ignore[attr-defined]
            1 for s in ctx.sources.values()
            if s.node.children or s.node.node.op != "LocalTableScanExec")
        return res

    # -- foreign-only path (auron.enable=false) ---------------------------

    def _run_foreign_only(self, node: ForeignNode) -> pa.Table:
        engine = self._require_engine()
        child_tables = [self._run_foreign_only(c) for c in node.children]
        return engine.execute(node, child_tables)

    def _require_engine(self) -> ForeignEngine:
        if self.foreign_engine is None:
            raise RuntimeError(
                "plan has non-native sections but no foreign engine is "
                "attached to this AuronSession")
        return self.foreign_engine

    # -- converted-tree execution ----------------------------------------

    def _run_converted(self, c: ConvertedT, ctx: ConvertContext) -> pa.Table:
        if isinstance(c, ForeignWrap):
            engine = self._require_engine()
            child_tables = [self._run_converted(ch, ctx)
                            for ch in c.children]
            return engine.execute(c.node, child_tables)
        return self._run_native(c, ctx)

    def _run_native(self, plan: P.PlanNode, ctx: ConvertContext) -> pa.Table:
        from auron_tpu.runtime import result_stream, tracing
        # stream-root identity is checked BEFORE dependency
        # materialization: with adaptive execution the stage-boundary
        # replan may return a REWRITTEN plan object
        is_stream_root = self._stream_root is not None and \
            id(plan) == self._stream_root
        resources, plan = self._materialize_deps(plan, ctx)
        n_parts = ctx.parts(plan)
        batches: List[pa.RecordBatch] = []
        stream_qid = None
        if is_stream_root:
            qid = tracing.current_query_id()
            if result_stream.active(qid):
                stream_qid = qid

        def run_task(pid: int):
            # the task-retry model above the runtime (the Spark
            # scheduler's role the reference inherits) now lives in
            # run_tasks itself: retryable-classified failures replay
            # with 1 + auron.task.retries attempts against the already-
            # materialized stage inputs (runtime/retry.py)
            res = execute_plan(plan, partition_id=pid,
                               resources=resources,
                               num_partitions=n_parts)
            if stream_qid is not None:
                # the streaming-result drain (?format=arrow&since=N)
                # sees this partition as soon as its task completes —
                # published AFTER the successful return, so a retried
                # task can never double-publish
                result_stream.publish(stream_qid, pid, res.batches)
            return res

        # one runtime per task, tasks in parallel across a thread pool —
        # the analogue of the reference running one native runtime per
        # Spark task across executor cores (rt.rs:76-139).  Each task
        # builds its own operator tree; the shared pieces (resource
        # registry, mem manager) are lock-protected, and jax dispatch is
        # thread-safe.  Results keep partition order.
        from auron_tpu.runtime.task_pool import run_tasks
        results = run_tasks(run_task, range(n_parts))
        for res in results:
            self._metrics.append(res.metrics)
            batches.extend(res.batches)
        if not batches:
            schema = getattr(plan, "schema", None)
            if schema is None:
                # non-leaf IR nodes carry no schema; derive it from the
                # instantiated operator tree
                from auron_tpu.runtime.planner import PhysicalPlanner
                schema = PhysicalPlanner().create_plan(plan).schema
            return pa.Table.from_batches([], schema=to_arrow_schema(schema))
        return pa.Table.from_batches(batches)

    # -- dependency materialization (stage scheduling) --------------------

    def _collect_rids(self, plan: Node, rids: List[str]) -> None:
        if isinstance(plan, (P.IpcReader, P.FFIReader)):
            rids.append(plan.resource_id)
        for c in plan.children_nodes():
            if isinstance(c, Node):
                self._collect_rids(c, rids)

    def _materialize_deps(self, plan: P.PlanNode, ctx: ConvertContext
                          ) -> "tuple[ResourceRegistry, P.PlanNode]":
        """Materialize every dependency of `plan` and return
        (resources, plan).  With `auron.adaptive.enable` off the plan
        comes back unchanged and the materialization order is exactly
        the legacy one (the chaos fault-draw sequences depend on it);
        with it on, every exchange's MAP side completes first, then the
        stage-boundary replanner (runtime/adaptive.py) may rewrite the
        consumer before the reduce-side fetch resources register."""
        from auron_tpu.runtime import adaptive
        resources = ResourceRegistry()
        rids: List[str] = []
        self._collect_rids(plan, rids)
        # a subtree may be referenced from several places (e.g. a union's
        # flattened partition mapping repeats the child) — materialize once
        unique = list(dict.fromkeys(rids))
        if adaptive.enabled() and \
                any(rid in ctx.exchanges for rid in unique):
            return self._materialize_deps_adaptive(plan, ctx, resources,
                                                   unique)
        for rid in unique:
            if rid in ctx.sources:
                self._materialize_source(ctx.sources[rid], ctx, resources)
            elif rid in ctx.broadcasts:
                self._materialize_broadcast(ctx.broadcasts[rid], ctx,
                                            resources)
            elif rid in ctx.exchanges:
                self._materialize_exchange(ctx.exchanges[rid], ctx,
                                           resources)
        return resources, plan

    # -- the adaptive stage boundary (runtime/adaptive.py) ----------------

    def _materialize_deps_adaptive(self, plan: P.PlanNode,
                                   ctx: ConvertContext,
                                   resources: ResourceRegistry,
                                   rids: List[str]
                                   ) -> "tuple[ResourceRegistry, P.PlanNode]":
        """Run every exchange's map side, observe the REAL per-partition
        output sizes, re-plan the consumer, then register reduce-side
        resources per decision (partitioned / broadcast collect /
        coalesced groups / skew fan-out)."""
        import time as _time

        from auron_tpu.runtime import adaptive, tracing
        pending: Dict[str, dict] = {}
        for rid in rids:
            if rid in ctx.sources:
                self._materialize_source(ctx.sources[rid], ctx, resources)
            elif rid in ctx.broadcasts:
                self._materialize_broadcast(ctx.broadcasts[rid], ctx,
                                            resources)
            elif rid in ctx.exchanges:
                pending[rid] = self._adaptive_map_side(
                    ctx.exchanges[rid], ctx)
        stats = {rid: p["stats"] for rid, p in pending.items()
                 if p.get("stats") is not None}
        plan, decisions, actions = adaptive.replan(plan, ctx, stats)
        for d in decisions:
            self._aqe_decisions.append(d.to_dict())
            log.info("aqe: %s %s: %s", d.kind, d.exchange, d.reason)
        for rid, pend in pending.items():
            self._adaptive_fetch(ctx.exchanges[rid], ctx, resources,
                                 pend, actions.get(rid), plan)
        if stats and config.conf.get("auron.adaptive.reforecast.enable"):
            # close the admission loop: re-forecast the running query's
            # reservation from bytes actually observed, so a light
            # query releases early (serving/admission.reforecast via
            # the scheduler-registered hook)
            qid = tracing.current_query_id()
            est = adaptive.stage_mem_estimate(qid, stats.values())
            age = _time.time() - self._wall_start \
                if self._wall_start else 0.0
            adaptive.stage_boundary_reforecast(qid, est, age)
        return resources, plan

    def _adaptive_map_side(self, job: ShuffleJob,
                           ctx: ConvertContext) -> dict:
        """Run ONE exchange's map side (durable commit protocol or
        plain transport) without fetching, returning the observed
        stats and everything the later fetch needs."""
        from auron_tpu.shuffle_rss.durable import (
            DurableShuffleClient, RssUnavailable,
        )
        n_reduce = job.partitioning.num_partitions
        if isinstance(self.shuffle_service, DurableShuffleClient) \
                and not self._rss_degraded_for(job.rid):
            try:
                sid, man, stats = self._durable_map_side(job, ctx)
                self._observe_exchange(job, stats)
                return {"mode": "durable", "sid": sid, "man": man,
                        "stats": stats, "n_reduce": n_reduce}
            except RssUnavailable as e:
                self._note_rss_degrade(job.rid, e)
        service = self._exchange_service(job.rid)
        stats = self._plain_map_side(job, ctx, service)
        self._observe_exchange(job, stats)
        return {"mode": "plain", "service": service, "stats": stats,
                "n_reduce": n_reduce}

    def _adaptive_fetch(self, job: ShuffleJob, ctx: ConvertContext,
                        resources: ResourceRegistry, pend: dict,
                        action, plan: P.PlanNode) -> None:
        """Fetch one exchange's reduce side and register it per the
        replan decision.  The partition count of the (possibly
        rewritten) consumer is refined here when a skew split lands
        fewer parts than planned (block granularity)."""
        from auron_tpu.runtime import adaptive, tracing
        from auron_tpu.shuffle_rss.durable import RssUnavailable
        rid = job.rid
        n_reduce = pend["n_reduce"]
        with tracing.span("shuffle.fetch", cat="shuffle", rid=rid,
                          parts=n_reduce) as sp:
            if pend["mode"] == "durable":
                try:
                    blocks = self._durable_fetch_checked(
                        job, ctx, pend["sid"], pend["man"], n_reduce)
                except RssUnavailable as e:
                    # mirror the legacy degrade tier: the side-car died
                    # between commit and fetch — recompute this
                    # exchange executor-locally (results identical)
                    self._note_rss_degrade(rid, e)
                    service = self._exchange_service(rid)
                    self._plain_map_side(job, ctx, service)
                    blocks = self._plain_fetch(job, service, n_reduce)
            else:
                blocks = self._plain_fetch(job, pend["service"],
                                           n_reduce)
            sp.set_args(nbytes=_blocks_nbytes(blocks))
        if action is None:
            resources.put(rid, PartitionedBlocks(blocks))
            return
        if action.kind == "broadcast":
            # the collected form: ONE chained block stream every probe
            # task shares (the build hash map is built once and cached)
            resources.put(rid, [b for part in blocks for b in part])
        elif action.kind == "coalesce":
            merged = adaptive.merge_partition_groups(blocks,
                                                     action.groups)
            resources.put(rid, PartitionedBlocks(merged))
            ctx.set_parts(plan, len(merged))
        elif action.kind == "skew_split":
            out = adaptive.split_skewed_partition(
                blocks, action.split_pid, action.split_parts)
            resources.put(rid, PartitionedBlocks(out))
            ctx.set_parts(plan, len(out))
            if len(out) == n_reduce:
                log.info("aqe: skew split of %s collapsed (partition "
                         "has a single block run)", rid)

    def _note_rss_degrade(self, rid: str, err: Exception) -> None:
        """Shared degrade bookkeeping (sticky flag + counter + trace
        event + one log line) for the durable->local fallback.  With a
        SHARDED side-car client the stickiness is per shard: only the
        shuffle ids owned by the dead endpoint fall back to local."""
        from auron_tpu.runtime import counters
        from auron_tpu.shuffle_rss.shard_map import (
            ShardedDurableShuffleClient,
        )
        endpoint = getattr(err, "rss_endpoint", None)
        if endpoint and isinstance(self.shuffle_service,
                                   ShardedDurableShuffleClient):
            self._rss_degraded_shards.add(endpoint)
            scope = f"shard {endpoint}"
        else:
            self._rss_degraded = True
            scope = "this query"
        counters.bump("rss_degrades")
        log.warning(
            "durable shuffle degraded to executor-local for %s "
            "(rid %s): %s", scope, rid, err)

    def _rss_degraded_for(self, rid: str) -> bool:
        """Is the durable path out of service for THIS exchange?  The
        global flag covers single side-cars; with a sharded client only
        the owner shard's death counts."""
        if self._rss_degraded:
            return True
        if not self._rss_degraded_shards:
            return False
        from auron_tpu.shuffle_rss.shard_map import (
            ShardedDurableShuffleClient,
        )
        svc = self.shuffle_service
        if not isinstance(svc, ShardedDurableShuffleClient):
            return True
        shard = svc.shard_of(self._durable_sid(rid))
        return f"{shard.host}:{shard.port}" in self._rss_degraded_shards

    def _observe_exchange(self, job: ShuffleJob, stats) -> None:
        """Surface one exchange's observed output: the session list
        (-> SessionResult / QueryRecord / bench JSON), a metric-tree
        marker node (-> EXPLAIN ANALYZE; byte values are canonical-
        volatile), and the unified cost model's live history."""
        self._exchange_stats.append(stats.to_dict())
        mn = MetricNode(f"ExchangeStats[{stats.ordinal()}]")
        mn.add("partitions", stats.num_partitions)
        mn.add("rows_out", stats.total_rows)
        mn.add("bytes_out", stats.total_bytes)
        if stats.partition_bytes:
            mn.add("part_bytes_max", max(stats.partition_bytes))
            mn.add("part_bytes_min", min(stats.partition_bytes))
        self._metrics.append(mn)
        if self._plan_signature:
            from auron_tpu.runtime.adaptive import unified_cost_model
            unified_cost_model().record_exchange(self._plan_signature,
                                                 stats)

    def _source_table(self, src: ForeignSource,
                      ctx: ConvertContext) -> pa.Table:
        is_local_table = (not src.node.children and
                          src.node.node.op == "LocalTableScanExec")
        return self._local_table(src.node.node) if is_local_table \
            else self._run_converted(src.node, ctx)

    def _materialize_source(self, src: ForeignSource, ctx: ConvertContext,
                            resources: ResourceRegistry) -> None:
        """C2N: the foreign engine computes the subtree; its table feeds
        the FFIReader (ConvertToNativeBase.doExecuteNative analogue)."""
        resources.put(src.rid, self._source_table(src, ctx))

    @staticmethod
    def _local_table(node: ForeignNode) -> pa.Table:
        schema = to_arrow_schema(node.output)
        return pa.Table.from_pylist(node.attrs.get("rows", []),
                                    schema=schema)

    def _materialize_broadcast(self, job: BroadcastJob, ctx: ConvertContext,
                               resources: ResourceRegistry) -> None:
        """Broadcast collect: run the build side once (all partitions) and
        serve the IPC bytes to every probe partition
        (NativeBroadcastExchangeBase.collectNative:195-230)."""
        import io

        from auron_tpu.columnar import serde as batch_serde
        table = self._run_converted(job.child, ctx)
        sink = io.BytesIO()
        # broadcast bytes never leave the process: the local
        # exchange codec policy applies (none by default)
        codec = batch_serde.exchange_codec("local")
        for rb in table.to_batches():
            if rb.num_rows:
                batch_serde.write_one_batch(rb, sink, codec=codec)
        resources.put(job.rid, sink.getvalue())

    def _materialize_exchange(self, job: ShuffleJob, ctx: ConvertContext,
                              resources: ResourceRegistry) -> None:
        """Shuffle: run the map side through RssShuffleWriter into the
        shuffle service, then register per-reduce block lists
        (AuronShuffleManager.getWriter/getReader analogue).  A durable
        side-car service takes the commit-protocol path (manifest
        consult, stage/map resume, integrity-checked fetch); when the
        side-car is unreachable the exchange DEGRADES to executor-local
        shuffle with a structured diagnostic instead of hanging."""
        from auron_tpu.shuffle_rss.durable import (
            DurableShuffleClient, RssUnavailable,
        )
        if isinstance(self.shuffle_service, DurableShuffleClient) \
                and not self._rss_degraded_for(job.rid):
            try:
                self._materialize_exchange_durable(job, ctx, resources)
                return
            except RssUnavailable as e:
                # the degrade path back to executor-local shuffle: the
                # side-car is down — upstream stages recompute locally,
                # results stay bit-identical, and the diagnostic is
                # structured (counter + trace event + one log line),
                # never a hang (every RPC rode bounded retries)
                self._note_rss_degrade(job.rid, e)
        self._materialize_exchange_via(job, ctx, resources,
                                       self._exchange_service(job.rid))

    def _exchange_service(self, rid: str):
        """The service an executor-local exchange uses: the session's
        own (in-process/celeborn/uniffle), or a lazily-built in-process
        fallback once the durable side-car degraded."""
        from auron_tpu.shuffle_rss.durable import DurableShuffleClient
        if not isinstance(self.shuffle_service, DurableShuffleClient):
            return self.shuffle_service
        if self._local_shuffle is None:
            self._local_shuffle = InProcessShuffleService()
        self._exchange_local.add(rid)
        return self._local_shuffle

    def _clear_exchange(self, rid: str) -> None:
        try:
            if rid in self._exchange_local and \
                    self._local_shuffle is not None:
                self._local_shuffle.clear(rid)
            sid = self._exchange_sids.get(rid)
            if sid is not None:
                # the fleet owns durable cleanup when deferred (it
                # deletes by query tag at TERMINAL state — resume
                # depends on blocks surviving a killed attempt)
                if not config.conf.get("auron.rss.defer.cleanup"):
                    self.shuffle_service.clear(sid)
            elif rid not in self._exchange_local:
                self.shuffle_service.clear(rid)
        except Exception:
            log.warning("failed to clear shuffle %s", rid)

    def _materialize_exchange_via(self, job: ShuffleJob,
                                  ctx: ConvertContext,
                                  resources: ResourceRegistry,
                                  service) -> None:
        from auron_tpu.runtime import tracing
        stats = self._plain_map_side(job, ctx, service)
        self._observe_exchange(job, stats)
        n_reduce = job.partitioning.num_partitions
        with tracing.span("shuffle.fetch", cat="shuffle", rid=job.rid,
                          parts=n_reduce) as sp:
            blocks = self._plain_fetch(job, service, n_reduce)
            sp.set_args(nbytes=_blocks_nbytes(blocks))
            resources.put(job.rid, PartitionedBlocks(blocks))

    def _plain_map_side(self, job: ShuffleJob, ctx: ConvertContext,
                        service):
        """Run the map side against a plain (in-process/remote)
        transport; returns the observed per-partition ExchangeStats."""
        # job.child is always native: convert_recursively runs every
        # foreign subtree through convert_to_native (FFI source) before a
        # converter sees it
        map_deps, map_plan = self._materialize_deps(job.child, ctx)
        map_parts = ctx.parts(map_plan)

        def map_task(map_pid: int):
            writer_rid = f"{job.rid}:writer:{map_pid}"
            map_deps.put(writer_rid,
                         service.rss_writer(job.rid, map_pid))
            writer = P.RssShuffleWriter(child=map_plan,
                                        partitioning=job.partitioning,
                                        rss_resource_id=writer_rid)
            return execute_plan(writer, partition_id=map_pid,
                                resources=map_deps,
                                num_partitions=map_parts)

        # map tasks in parallel, like the reduce-side task pool in
        # _run_native — but ONLY for the in-process shuffle service,
        # whose reads sort blocks by map id; the remote clients
        # (celeborn aggregate buffers, uniffle arrival-order blocks)
        # record pushes in arrival order, so concurrent maps would make
        # reduce-side streams nondeterministic there
        from auron_tpu.runtime import tracing
        from auron_tpu.runtime.task_pool import run_tasks
        with tracing.span("exchange.map", cat="exchange", rid=job.rid,
                          parts=map_parts):
            if isinstance(service, InProcessShuffleService):
                results = run_tasks(map_task, range(map_parts),
                                    "auron-map")
            else:
                results = [map_task(pid) for pid in range(map_parts)]
        for res in results:
            self._metrics.append(res.metrics)
        from auron_tpu.runtime.adaptive import stats_from_map_results
        return stats_from_map_results(job.rid, results,
                                      job.partitioning.num_partitions)

    def _plain_fetch(self, job: ShuffleJob, service,
                     n_reduce: int) -> List[List[bytes]]:
        """Per-partition block lists from a plain transport.  The fetch
        rides the shared retry policy: it is a pure read (the remote
        clients dedup by id, the in-process store is committed), so
        replays after an injected/transport fault are idempotent.
        Pipelined: up to auron.shuffle.pipeline.depth partition fetches
        in flight, results in partition order, the smallest-pid error
        raised first (the sequential loop's error)."""
        from auron_tpu.runtime.retry import (
            RetryPolicy, call_with_retry, task_classify,
        )
        from auron_tpu.shuffle_rss.pipeline import run_windowed
        policy = RetryPolicy.task_policy()

        def fetch_one(pid: int):
            return call_with_retry(
                lambda: service.reduce_blocks(job.rid, pid),
                policy=policy, classify=task_classify,
                label=f"shuffle fetch {job.rid}:{pid}")

        return run_windowed(fetch_one, range(n_reduce))

    # -- the durable side-car exchange (commit protocol + resume) ---------

    def _durable_sid(self, rid: str) -> str:
        """The side-car shuffle id: a STABLE (query tag, exchange
        ordinal) key.  Conversion rids embed a random per-context uid
        for cross-query isolation on shared servers, so a requeued
        attempt would never match them — the tag (`auron.rss.tag`, set
        by the fleet to the front-door query id; else this execute's
        query id) plus the deterministic conversion ordinal is what
        both attempts agree on."""
        from auron_tpu.runtime import tracing
        tag = str(config.conf.get("auron.rss.tag") or "") or \
            tracing.current_query_id() or "untagged"
        return f"{tag}|x{rid.rsplit(':', 1)[-1]}"

    def _materialize_exchange_durable(self, job: ShuffleJob,
                                      ctx: ConvertContext,
                                      resources: ResourceRegistry
                                      ) -> None:
        """The commit-protocol exchange: consult the manifest, SKIP map
        tasks whose outputs a previous attempt already committed (whole
        stages when sealed), run only the uncommitted remainder, seal,
        then fetch with manifest integrity checks — a damaged block
        regenerates exactly its map output (targeted re-dispatch), not
        a blind replay."""
        from auron_tpu.runtime import tracing
        sid, man, stats = self._durable_map_side(job, ctx)
        self._observe_exchange(job, stats)
        n_reduce = job.partitioning.num_partitions
        with tracing.span("shuffle.fetch", cat="shuffle", rid=job.rid,
                          parts=n_reduce) as sp:
            blocks = self._durable_fetch_checked(job, ctx, sid, man,
                                                 n_reduce)
            sp.set_args(nbytes=_blocks_nbytes(blocks))
        resources.put(job.rid, PartitionedBlocks(blocks))

    def _durable_map_side(self, job: ShuffleJob, ctx: ConvertContext):
        """Map half of the commit protocol: manifest consult, run the
        uncommitted remainder, seal.  Returns (sid, manifest, observed
        ExchangeStats) — for a RESUMED stage the per-partition bytes
        come from the manifest's committed ledger, so the replanner
        sees real sizes without the map side ever re-running."""
        from auron_tpu.runtime import adaptive, counters, tracing
        svc = self.shuffle_service
        sid = self._durable_sid(job.rid)
        self._exchange_sids[job.rid] = sid
        map_parts = ctx.parts(job.child)
        resume = bool(config.conf.get("auron.rss.resume.enable"))
        man = svc.manifest(sid) if resume \
            else {"sealed": None, "maps": {}}
        committed = {int(m) for m in man["maps"]}
        to_run = [p for p in range(map_parts) if p not in committed]
        skipped = map_parts - len(to_run)
        if skipped:
            counters.bump("rss_map_tasks_skipped", skipped)
        resumed = not to_run and man["sealed"] == map_parts
        if resumed:
            # the whole map stage is committed: RESUME — reduce fetches
            # from the side-car, the map subtree (and every exchange
            # under it) is never materialized
            counters.bump("rss_stage_skips")
            tracing.event("rss.resume", cat="shuffle", rid=job.rid,
                          sid=sid, maps=map_parts)
            log.info("durable shuffle %s: stage resumed from side-car "
                     "(%d committed map output(s) reused)", sid,
                     map_parts)
        else:
            self._run_durable_map_stage(job, ctx, sid, to_run)
            svc.seal(sid, map_parts)
            man = svc.manifest(sid)
        n_reduce = job.partitioning.num_partitions
        stats = adaptive.stats_from_manifest(job.rid, man, n_reduce)
        stats.resumed = resumed
        stats.rows_known = False
        return sid, man, stats

    def _durable_fetch_checked(self, job: ShuffleJob,
                               ctx: ConvertContext, sid: str, man: dict,
                               n_reduce: int) -> List[List[bytes]]:
        """Fetch half of the commit protocol: integrity-checked fetch
        with ONE targeted-regeneration round for damaged map outputs."""
        from auron_tpu.runtime import counters
        svc = self.shuffle_service
        map_parts = ctx.parts(job.child)
        blocks, bad = self._durable_fetch(sid, n_reduce, man)
        if bad:
            # missing/corrupt committed block: deterministic, so
            # regenerate those map outputs and fetch once more
            counters.bump("rss_fetch_regens")
            log.warning(
                "durable shuffle %s: fetch failed integrity for "
                "map output(s) %s; regenerating via targeted "
                "re-dispatch", sid, sorted(bad))
            self._run_durable_map_stage(
                job, ctx, sid,
                [m for m in sorted(bad) if m < map_parts])
            svc.seal(sid, map_parts)
            man = svc.manifest(sid)
            blocks, bad = self._durable_fetch(sid, n_reduce, man)
            if bad:
                from auron_tpu.shuffle_rss.durable import (
                    FetchFailedError,
                )
                raise FetchFailedError(
                    sid, sorted(bad),
                    detail="regeneration did not converge")
        return blocks

    def _run_durable_map_stage(self, job: ShuffleJob,
                               ctx: ConvertContext, sid: str,
                               pids: List[int]) -> None:
        """Run the listed map tasks against the side-car.  Frames per
        (map, attempt) are isolated and fetch orders by map id, so
        concurrent map tasks stay deterministic (unlike the aggregate/
        block transports)."""
        from auron_tpu.runtime import counters, tracing
        from auron_tpu.runtime.task_pool import run_tasks
        if not pids:
            return
        map_deps, map_plan = self._materialize_deps(job.child, ctx)
        # the commit protocol's map-id space must be attempt-stable, so
        # task count stays the ORIGINAL conversion-time partition count
        # even when a nested adaptive replan coalesced the map plan's
        # own inputs (the surplus map tasks read empty partitions and
        # commit empty outputs — resume math stays consistent)
        map_parts = ctx.parts(job.child)

        def map_task(map_pid: int):
            writer_rid = f"{job.rid}:writer:{map_pid}"
            map_deps.put(writer_rid,
                         self.shuffle_service.rss_writer(sid, map_pid))
            writer = P.RssShuffleWriter(child=map_plan,
                                        partitioning=job.partitioning,
                                        rss_resource_id=writer_rid)
            return execute_plan(writer, partition_id=map_pid,
                                resources=map_deps,
                                num_partitions=map_parts)

        with tracing.span("exchange.map", cat="exchange", rid=job.rid,
                          parts=len(pids), sid=sid):
            results = run_tasks(map_task, pids, "auron-map")
        counters.bump("rss_map_tasks_run", len(pids))
        for res in results:
            self._metrics.append(res.metrics)

    def _durable_fetch(self, sid: str, n_reduce: int, man: dict):
        """Fetch every reduce partition, validating against the
        manifest; returns (per-partition frame lists, bad map ids) so
        ONE regeneration round covers every damaged map output.
        Partition fetches ride the bounded pipeline window (transport
        errors — RssUnavailable — still raise in partition order)."""
        from auron_tpu.shuffle_rss.durable import FetchFailedError
        from auron_tpu.shuffle_rss.pipeline import run_windowed

        def fetch_one(pid: int):
            try:
                return self.shuffle_service.reduce_blocks(
                    sid, pid, expect=man)
            except FetchFailedError as e:
                return e

        blocks: List[List[bytes]] = []
        bad: set = set()
        for got in run_windowed(fetch_one, range(n_reduce)):
            if isinstance(got, FetchFailedError):
                bad.update(got.map_ids)
                blocks.append([])
            else:
                blocks.append(got)
        return blocks, bad


class PartitionedBlocks:
    """Per-reduce-partition block lists behind one resource id."""

    def __init__(self, per_partition: List[List[bytes]]):
        self.per_partition = per_partition

    def for_partition(self, pid: int) -> List[bytes]:
        if pid >= len(self.per_partition):
            return []
        return self.per_partition[pid]
