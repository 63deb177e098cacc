"""Foreign physical plan -> native IR plan conversion.

Analogue of AuronConverters (spark-extension/.../AuronConverters.scala):
`convert_node` is the per-op dispatch (convertSparkPlan:209-416 + the 24
convert*Exec methods :418-1131); `convert_recursively` mirrors
convertSparkPlanRecursively:186-209, inserting ConvertToNative (FFIReader)
transitions under native parents with foreign children and leaving
foreign sections intact (the N2C direction) for the host engine.

Exchanges do not nest in the converted tree: a converted
ShuffleExchangeExec / BroadcastExchangeExec becomes an `IpcReader` leaf
plus an entry in `ConvertContext.exchanges` / `.broadcasts` that the
driver (frontend.session) materializes — exactly how the reference splits
stages at exchange boundaries via NativeShuffleExchangeExec /
NativeBroadcastExchangeExec and re-enters through ipc_reader_exec.rs.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from auron_tpu import config
from auron_tpu.frontend import expr_convert as EC
from auron_tpu.frontend.expr_convert import NotConvertible
from auron_tpu.frontend.foreign import ForeignExpr, ForeignNode
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir.schema import DataType, Field, Schema


@dataclass
class ShuffleJob:
    """A converted ShuffleExchangeExec: the driver runs `child` as a map
    stage partitioned by `partitioning`, then serves reduce-side blocks
    under resource id `rid`."""
    rid: str
    child: "ConvertedT" = None  # type: ignore[assignment]
    partitioning: P.Partitioning = None  # type: ignore[assignment]
    schema: Schema = None  # type: ignore[assignment]


@dataclass
class BroadcastJob:
    """A converted BroadcastExchangeExec: the driver collects `child` once
    (all partitions) into IPC bytes under resource id `rid`
    (NativeBroadcastExchangeBase.collectNative:195 analogue)."""
    rid: str
    child: "ConvertedT" = None  # type: ignore[assignment]
    schema: Schema = None  # type: ignore[assignment]


@dataclass
class ForeignSource:
    """A C2N transition: the foreign engine executes `node` and feeds its
    Arrow batches into an FFIReader under resource id `rid`
    (ConvertToNativeBase.scala:64-99 analogue)."""
    rid: str
    node: "ForeignWrap" = None  # type: ignore[assignment]


@dataclass
class ForeignWrap:
    """A plan section left to the host engine; children may be native
    sections whose results enter the engine as Arrow tables."""
    node: ForeignNode = None  # type: ignore[assignment]
    children: List["ConvertedT"] = field(default_factory=list)


ConvertedT = Union[P.PlanNode, ForeignWrap]


class ConvertContext:
    def __init__(self) -> None:
        import uuid
        self._ids = itertools.count()
        # resource ids are globally unique so concurrent queries (or
        # sequential queries against a shared remote shuffle server) can
        # never observe each other's blocks
        self._uid = uuid.uuid4().hex[:8]
        self.exchanges: Dict[str, ShuffleJob] = {}
        self.broadcasts: Dict[str, BroadcastJob] = {}
        self.sources: Dict[str, ForeignSource] = {}
        # partition count of each converted native node, keyed by identity
        self.n_parts: Dict[int, int] = {}

    def fresh(self, prefix: str) -> str:
        return f"{prefix}:{self._uid}:{next(self._ids)}"

    def parts(self, plan: P.PlanNode) -> int:
        return self.n_parts.get(id(plan), 1)

    def set_parts(self, plan: P.PlanNode, n: int) -> P.PlanNode:
        self.n_parts[id(plan)] = max(1, n)
        return plan


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _schema(node: ForeignNode) -> Schema:
    if node.output is None:
        raise NotConvertible(f"{node.op} carries no output schema")
    return node.output


def _split_conjunction(fe: ForeignExpr) -> List[ForeignExpr]:
    if fe.name == "And":
        return _split_conjunction(fe.children[0]) + \
            _split_conjunction(fe.children[1])
    return [fe]


def _named_exprs(fexprs) -> Tuple[Tuple[E.Expr, ...], Tuple[str, ...]]:
    """projectList conversion: Alias carries the name; a bare attribute
    keeps its own name."""
    exprs, names = [], []
    for fe in fexprs:
        if fe.name == "Alias":
            names.append(fe.value)
        elif fe.name == "AttributeReference":
            names.append(fe.value)
        else:
            raise NotConvertible(
                f"top-level project expression {fe.name} lacks a name")
        exprs.append(EC.convert_expr_with_fallback(fe))
    return tuple(exprs), tuple(names)


def _native_schema_of(plan: P.PlanNode) -> Optional[Schema]:
    """Exact runtime output schema of a converted subtree (e.g. the state
    layout a partial agg emits), derived by instantiating the operator
    tree — keeps exchange wire schemas honest regardless of what the
    foreign plan declared."""
    try:
        from auron_tpu.runtime.planner import PhysicalPlanner
        return PhysicalPlanner().create_plan(plan).schema
    except Exception:
        return None


def convert_partitioning(spec: Dict[str, Any]) -> P.Partitioning:
    mode = spec.get("mode", "single")
    if mode not in ("hash", "round_robin", "single", "range"):
        raise NotConvertible(f"partitioning mode {mode}")
    exprs = tuple(EC.convert_expr_with_fallback(e)
                  for e in spec.get("expressions", ()))
    orders = tuple(EC.convert_sort_order(s)
                   for s in spec.get("sort_orders", ()))
    return P.Partitioning(
        mode=mode, num_partitions=int(spec.get("num_partitions", 1)),
        expressions=exprs, sort_orders=orders,
        range_bounds=tuple(tuple(b) for b in spec.get("range_bounds", ())))


def _op_enabled(flag: str) -> None:
    if not config.conf.get(f"auron.enable.{flag}"):
        raise NotConvertible(f"native {flag} disabled by conf")


# ---------------------------------------------------------------------------
# per-op converters.  Each takes (node, native_children, ctx) where
# native_children are already-converted native child plans (C2N inserted).
# ---------------------------------------------------------------------------

_PLAN_CONVERTERS: Dict[str, Callable[..., P.PlanNode]] = {}


def _plan(name: str):
    def deco(fn):
        _PLAN_CONVERTERS[name] = fn
        return fn
    return deco


@_plan("FileSourceScanExec")
def _scan(node: ForeignNode, children, ctx: ConvertContext) -> P.PlanNode:
    fmt = node.attrs.get("format", "parquet")
    groups = tuple(
        P.FileGroup(paths=tuple(g)) for g in node.attrs.get("file_groups", ()))
    if not groups:
        raise NotConvertible("scan without file groups")
    schema = _schema(node)
    predicate = None
    pushed = node.attrs.get("pushed_filters", ())
    if pushed:
        conv = [EC.convert_expr(p) for p in pushed]
        predicate = conv[0]
        for p in conv[1:]:
            predicate = E.ScAnd(left=predicate, right=p)
    part_schema = node.attrs.get("partition_schema")
    part_values = tuple(tuple(v) for v in node.attrs.get(
        "partition_values", ()))
    if fmt == "parquet":
        _op_enabled("parquet.scan")
        plan = P.ParquetScan(schema=schema, file_groups=groups,
                             predicate=predicate,
                             partition_schema=part_schema,
                             partition_values=part_values)
    elif fmt == "orc":
        _op_enabled("orc.scan")
        plan = P.OrcScan(schema=schema, file_groups=groups,
                         predicate=predicate)
    else:
        raise NotConvertible(f"scan format {fmt}")
    ctx.set_parts(plan, len(groups))
    if predicate is not None and \
            config.conf.get("auron.adaptive.fuse.adjacency.enable"):
        # the PR 3 follow-up: pushdown hides filter/projection chains
        # from the fuser (the scan predicate swallows the filter).  When
        # the unified cost model says re-evaluating the pushed filter is
        # cheaper than the fusion it unlocks, keep it ALSO as an
        # explicit Filter node above the scan — the scan predicate still
        # prunes IO, the filter re-applies device-side (idempotent, so
        # value-identical), and the fuser sees an adjacent chain.
        # Chosen by cost (SystemML's fusion-plan exemplar), not greedily.
        preds = tuple(EC.convert_expr(p) for p in pushed)
        from auron_tpu.runtime.adaptive import unified_cost_model
        if unified_cost_model().filter_adjacency_pays(preds, schema):
            plan = ctx.set_parts(
                P.Filter(child=plan, predicates=preds), len(groups))
    return plan


@_plan("LocalTableScanExec")
def _local_table_scan(node, children, ctx) -> P.PlanNode:
    rid = ctx.fresh("local_table")
    schema = _schema(node)
    src = ForeignSource(rid=rid, node=ForeignWrap(node=node))
    ctx.sources[rid] = src
    return ctx.set_parts(P.FFIReader(schema=schema, resource_id=rid), 1)


@_plan("ProjectExec")
def _project(node, children, ctx) -> P.PlanNode:
    _op_enabled("project")
    exprs, names = _named_exprs(node.attrs["project_list"])
    return ctx.set_parts(
        P.Projection(child=children[0], exprs=exprs, names=names),
        ctx.parts(children[0]))


@_plan("FilterExec")
def _filter(node, children, ctx) -> P.PlanNode:
    _op_enabled("filter")
    preds = tuple(EC.convert_expr_with_fallback(p)
                  for p in _split_conjunction(node.attrs["condition"]))
    return ctx.set_parts(P.Filter(child=children[0], predicates=preds),
                         ctx.parts(children[0]))


@_plan("SortExec")
def _sort(node, children, ctx) -> P.PlanNode:
    _op_enabled("sort")
    orders = tuple(EC.convert_sort_order(s)
                   for s in node.attrs["sort_order"])
    return ctx.set_parts(P.Sort(child=children[0], sort_exprs=orders),
                         ctx.parts(children[0]))


@_plan("LocalLimitExec")
def _local_limit(node, children, ctx) -> P.PlanNode:
    _op_enabled("limit")
    return ctx.set_parts(
        P.Limit(child=children[0], limit=int(node.attrs["limit"]),
                offset=int(node.attrs.get("offset", 0))),
        ctx.parts(children[0]))


@_plan("GlobalLimitExec")
@_plan("CollectLimitExec")
def _global_limit(node, children, ctx) -> P.PlanNode:
    """Global limit over a multi-partition child: per-partition pre-limit,
    single-partition exchange, then the real limit+offset (CollectLimit's
    gather-to-one shape)."""
    _op_enabled("limit")
    limit = int(node.attrs["limit"])
    offset = int(node.attrs.get("offset", 0))
    child = children[0]
    if ctx.parts(child) > 1:
        local = ctx.set_parts(
            P.Limit(child=child, limit=limit + offset, offset=0),
            ctx.parts(child))
        rid = ctx.fresh("shuffle")
        schema = _native_schema_of(local) or _schema(node)
        ctx.exchanges[rid] = ShuffleJob(
            rid=rid, child=local,
            partitioning=P.Partitioning(mode="single", num_partitions=1),
            schema=schema)
        child = ctx.set_parts(P.IpcReader(schema=schema, resource_id=rid),
                              1)
    return ctx.set_parts(P.Limit(child=child, limit=limit, offset=offset),
                         1)


@_plan("TakeOrderedAndProjectExec")
def _take_ordered(node, children, ctx) -> P.PlanNode:
    """Global top-K: per-partition sort+limit, single-partition exchange,
    final merge sort+limit (NativeTakeOrderedBase's two-stage shape)."""
    _op_enabled("sort")
    orders = tuple(EC.convert_sort_order(s)
                   for s in node.attrs["sort_order"])
    limit = int(node.attrs["limit"])
    offset = int(node.attrs.get("offset", 0))
    merged_child = children[0]
    if ctx.parts(children[0]) > 1:
        local = ctx.set_parts(
            P.Sort(child=children[0], sort_exprs=orders,
                   fetch_limit=limit + offset),
            ctx.parts(children[0]))
        rid = ctx.fresh("shuffle")
        schema = _native_schema_of(local) or _schema(node)
        ctx.exchanges[rid] = ShuffleJob(
            rid=rid, child=local,
            partitioning=P.Partitioning(mode="single", num_partitions=1),
            schema=schema)
        merged_child = ctx.set_parts(
            P.IpcReader(schema=schema, resource_id=rid), 1)
    sort = P.Sort(child=merged_child, sort_exprs=orders,
                  fetch_limit=limit, fetch_offset=offset)
    exprs, names = _named_exprs(node.attrs["project_list"])
    return ctx.set_parts(P.Projection(child=sort, exprs=exprs, names=names),
                         1)


@_plan("HashAggregateExec")
@_plan("ObjectHashAggregateExec")
@_plan("SortAggregateExec")
def _agg(node, children, ctx) -> P.PlanNode:
    _op_enabled("agg")
    grouping, grouping_names = _named_exprs(node.attrs.get("grouping", ()))
    aggs = tuple(EC.convert_agg_expr(a) for a in node.attrs.get("aggs", ()))
    return ctx.set_parts(
        P.Agg(child=children[0],
              exec_mode=node.attrs.get("mode", "single"),
              grouping=grouping, grouping_names=grouping_names,
              aggs=aggs, agg_names=tuple(node.attrs.get("agg_names", ())),
              supports_partial_skipping=bool(
                  node.attrs.get("supports_partial_skipping", False))),
        ctx.parts(children[0]))


@_plan("ExpandExec")
def _expand(node, children, ctx) -> P.PlanNode:
    _op_enabled("expand")
    schema = _schema(node)
    child_schema = _native_schema_of(children[0])

    def conv(e: ForeignExpr, declared: DataType) -> E.Expr:
        x = EC.convert_expr_with_fallback(e)
        # grouping-set projections must hit the declared output types
        # exactly (e.g. int32 literal 0 under a bigint grouping-id column)
        if child_schema is not None:
            from auron_tpu.exprs.typing import infer_type
            try:
                if infer_type(x, child_schema) != declared:
                    return E.Cast(child=x, dtype=declared)
            except Exception:
                pass
        return x

    projections = tuple(
        tuple(conv(e, f.dtype) for e, f in zip(proj, schema.fields))
        for proj in node.attrs["projections"])
    return ctx.set_parts(
        P.Expand(child=children[0], projections=projections,
                 names=schema.names(),
                 types=tuple(f.dtype for f in schema.fields)),
        ctx.parts(children[0]))


@_plan("WindowExec")
def _window(node, children, ctx) -> P.PlanNode:
    _op_enabled("window")
    funcs = []
    for w in node.attrs.get("window_exprs", ()):
        # shape: {"name": out_name, "fn": fn_name, "args": [fexpr...],
        #         "agg": AggregateExpression fexpr (fn == "agg")}
        agg = None
        if w.get("agg") is not None:
            agg = EC.convert_agg_expr(w["agg"])
            rt = agg.return_type
        else:
            # per-function defaults (Spark: rank family is IntegerType,
            # percent_rank/cume_dist are DoubleType); value functions
            # (lead/lag/nth_value/...) have data-dependent types and must
            # declare one
            rt = w.get("dtype")
            if rt is None:
                if w["fn"] in ("percent_rank", "cume_dist"):
                    rt = DataType.float64()
                elif w["fn"] in ("row_number", "rank", "dense_rank"):
                    rt = DataType.int32()
                else:
                    raise NotConvertible(
                        f"window function {w['fn']} requires a dtype")
        funcs.append(P.WindowFuncCall(
            fn=w["fn"],
            args=tuple(EC.convert_expr_with_fallback(a)
                       for a in w.get("args", ())),
            agg=agg, return_type=rt, name=w["name"]))
    part_by = tuple(EC.convert_expr_with_fallback(e)
                    for e in node.attrs.get("partition_spec", ()))
    order_by = tuple(EC.convert_sort_order(s)
                     for s in node.attrs.get("order_spec", ()))
    return ctx.set_parts(
        P.Window(child=children[0], window_funcs=tuple(funcs),
                 partition_by=part_by, order_by=order_by),
        ctx.parts(children[0]))


@_plan("WindowGroupLimitExec")
def _window_group_limit(node, children, ctx) -> P.PlanNode:
    _op_enabled("window")
    part_by = tuple(EC.convert_expr_with_fallback(e)
                    for e in node.attrs.get("partition_spec", ()))
    order_by = tuple(EC.convert_sort_order(s)
                     for s in node.attrs.get("order_spec", ()))
    limit = P.WindowGroupLimit(
        k=int(node.attrs["limit"]),
        rank_fn=node.attrs.get("rank_like_function", "row_number"))
    return ctx.set_parts(
        P.Window(child=children[0], window_funcs=(), partition_by=part_by,
                 order_by=order_by, group_limit=limit,
                 output_window_cols=False),
        ctx.parts(children[0]))


@_plan("GenerateExec")
def _generate(node, children, ctx) -> P.PlanNode:
    _op_enabled("generate")
    gen = node.attrs["generator"]         # ForeignExpr
    gen_map = {"Explode": "explode", "PosExplode": "posexplode",
               "JsonTuple": "json_tuple"}
    udtf = None
    if gen.name in gen_map:
        generator = gen_map[gen.name]
    elif gen.py_fn is not None and config.UDF_FALLBACK_ENABLE.get():
        generator, udtf = "udtf", gen.py_fn
    else:
        raise NotConvertible(f"generator {gen.name} is not supported yet")
    out_names = tuple(node.attrs["generator_output_names"])
    out_types = tuple(node.attrs["generator_output_types"])
    required = tuple(int(i) for i in node.attrs.get(
        "required_child_output", ()))
    return ctx.set_parts(
        P.Generate(child=children[0], generator=generator,
                   args=tuple(EC.convert_expr_with_fallback(a)
                              for a in gen.children),
                   generator_output_names=out_names,
                   generator_output_types=out_types,
                   required_child_output=required,
                   outer=bool(node.attrs.get("outer", False)), udtf=udtf),
        ctx.parts(children[0]))


@_plan("UnionExec")
def _union(node, children, ctx) -> P.PlanNode:
    _op_enabled("union")
    schema = _schema(node)
    # flattened partition mapping (proto:542-552): output partitions are
    # the concatenation of every child's partitions, so each child
    # partition is read exactly once
    inputs = []
    out_pid = 0
    for c in children:
        for q in range(ctx.parts(c)):
            inputs.append(P.UnionInput(child=c, partition=q,
                                       out_partition=out_pid))
            out_pid += 1
    return ctx.set_parts(
        P.Union(inputs=tuple(inputs), schema=schema,
                num_partitions=out_pid, cur_partition=0),
        out_pid)


def _join_on(node) -> P.JoinOn:
    return P.JoinOn(
        left_keys=tuple(EC.convert_expr_with_fallback(k)
                        for k in node.attrs["left_keys"]),
        right_keys=tuple(EC.convert_expr_with_fallback(k)
                         for k in node.attrs["right_keys"]))


def _check_no_condition(node) -> None:
    """A join's residual condition converts for an inner join alone, where
    it is a filter over the joined rows (`_with_condition`)."""
    if node.attrs.get("condition") is not None and \
            node.attrs.get("join_type", "Inner") != "Inner":
        raise NotConvertible(
            f"{node.op} with post-join condition is not supported yet")


def _with_condition(node, join: P.PlanNode, ctx: "ConvertContext"
                    ) -> P.PlanNode:
    """An inner join's residual condition as a filter over its output:
    the pairs that meet the keys and fail the condition are dropped
    after the join instead of inside it, which for an inner join is the
    same rows."""
    cond = node.attrs.get("condition")
    if cond is None:
        return join
    preds = tuple(EC.convert_expr_with_fallback(p)
                  for p in _split_conjunction(cond))
    return ctx.set_parts(P.Filter(child=join, predicates=preds),
                         ctx.parts(join))


@_plan("SortMergeJoinExec")
def _smj(node, children, ctx) -> P.PlanNode:
    if config.FORCE_SHUFFLED_HASH_JOIN.get():
        # rewrite the planned SMJ into a shuffled hash join — what the
        # reference achieves by patching Spark's planner bytecode
        # (ForceApplyShuffledHashJoinInjector.java).  "Prefer when both
        # are legal": if SHJ conversion is not possible (disabled,
        # unsupported shape) fall through to the normal SMJ path.
        try:
            return _shj(node, children, ctx)
        except NotConvertible:
            pass
    _op_enabled("smj")
    _check_no_condition(node)
    jt = EC.convert_join_type(node.attrs.get("join_type", "Inner"))
    nkeys = len(node.attrs["left_keys"])
    on = _join_on(node)

    def ensure_sorted(child: P.PlanNode, keys) -> P.PlanNode:
        # EnsureRequirements analogue: the streaming SMJ consumes
        # key-sorted inputs (childOrderingRequired tag,
        # AuronConvertStrategy.scala:41-47); a real engine plan carries
        # explicit SortExec children, a synthetic plan may not
        want = tuple(E.SortExpr(child=k, asc=True, nulls_first=True)
                     for k in keys)
        if isinstance(child, P.Sort) and child.sort_exprs[:nkeys] == want:
            return child
        return ctx.set_parts(P.Sort(child=child, sort_exprs=want),
                             ctx.parts(child))

    return _with_condition(node, ctx.set_parts(
        P.SortMergeJoin(
            left=ensure_sorted(children[0], on.left_keys),
            right=ensure_sorted(children[1], on.right_keys),
            on=on, join_type=jt,
            sort_options=tuple((True, True) for _ in range(nkeys)),
            existence_output_name=node.attrs.get("existence_name",
                                                 "exists")),
        max(ctx.parts(children[0]), ctx.parts(children[1]))), ctx)


@_plan("ShuffledHashJoinExec")
def _shj(node, children, ctx) -> P.PlanNode:
    _op_enabled("shj")
    _check_no_condition(node)
    jt = EC.convert_join_type(node.attrs.get("join_type", "Inner"))
    return _with_condition(node, ctx.set_parts(
        P.HashJoin(left=children[0], right=children[1], on=_join_on(node),
                   join_type=jt,
                   build_side=node.attrs.get("build_side", "right"),
                   existence_output_name=node.attrs.get("existence_name",
                                                        "exists")),
        max(ctx.parts(children[0]), ctx.parts(children[1]))), ctx)


@_plan("BroadcastHashJoinExec")
def _bhj(node, children, ctx) -> P.PlanNode:
    _op_enabled("bhj")
    _check_no_condition(node)
    jt = EC.convert_join_type(node.attrs.get("join_type", "Inner"))
    side = node.attrs.get("build_side", "right")
    on = _join_on(node)
    build_idx = 1 if side == "right" else 0
    build_keys = on.right_keys if side == "right" else on.left_keys
    cache_id = ctx.fresh("bhm")
    built = P.BroadcastJoinBuildHashMap(
        child=children[build_idx], keys=build_keys, cache_id=cache_id)
    ctx.set_parts(built, ctx.parts(children[build_idx]))
    pair = [children[0], children[1]]
    pair[build_idx] = built
    probe_parts = ctx.parts(children[1 - build_idx])
    return _with_condition(node, ctx.set_parts(
        P.BroadcastJoin(left=pair[0], right=pair[1], on=on, join_type=jt,
                        broadcast_side=side,
                        cached_build_hash_map_id=cache_id,
                        existence_output_name=node.attrs.get(
                            "existence_name", "exists")),
        probe_parts), ctx)


@_plan("ShuffleExchangeExec")
def _shuffle_exchange(node, children, ctx) -> P.PlanNode:
    _op_enabled("shuffle")
    part = convert_partitioning(node.attrs["partitioning"])
    rid = ctx.fresh("shuffle")
    schema = _native_schema_of(children[0]) or _schema(node)
    ctx.exchanges[rid] = ShuffleJob(rid=rid, child=children[0],
                                    partitioning=part, schema=schema)
    return ctx.set_parts(P.IpcReader(schema=schema, resource_id=rid),
                         part.num_partitions)


@_plan("BroadcastExchangeExec")
def _broadcast_exchange(node, children, ctx) -> P.PlanNode:
    rid = ctx.fresh("broadcast")
    schema = _native_schema_of(children[0]) or _schema(node)
    ctx.broadcasts[rid] = BroadcastJob(rid=rid, child=children[0],
                                       schema=schema)
    return ctx.set_parts(P.IpcReader(schema=schema, resource_id=rid), 1)


@_plan("DataWritingCommandExec")
def _data_writing(node, children, ctx) -> P.PlanNode:
    fmt = node.attrs.get("format", "parquet")
    out_dir = node.attrs["output_dir"]
    part_cols = tuple(node.attrs.get("partition_cols", ()))
    if fmt == "parquet":
        _op_enabled("parquet.sink")
        plan = P.ParquetSink(child=children[0], output_dir=out_dir,
                             partition_cols=part_cols,
                             compression=node.attrs.get("compression",
                                                        "zstd"))
    elif fmt == "orc":
        _op_enabled("orc.sink")
        plan = P.OrcSink(child=children[0], output_dir=out_dir,
                         partition_cols=part_cols,
                         compression=node.attrs.get("compression", "zstd"))
    else:
        raise NotConvertible(f"sink format {fmt}")
    return ctx.set_parts(plan, ctx.parts(children[0]))


@_plan("InsertIntoHiveTableExec")
def _insert_into_hive(node, children, ctx) -> P.PlanNode:
    """Hive insert glue (NativeParquetInsertIntoHiveTableBase /
    NativeOrcInsertIntoHiveTableBase analogue): the command carries the
    table's storage descriptor; static partition values extend the
    output path, dynamic partition columns flow to the sink's
    partitioned write."""
    storage = node.attrs.get("storage", {})
    fmt = str(storage.get("format", node.attrs.get("format",
                                                   "parquet"))).lower()
    if "orc" in fmt:
        fmt = "orc"
    elif "parquet" in fmt or fmt in ("hive", ""):
        fmt = "parquet"
    else:
        raise NotConvertible(f"hive serde format {fmt!r}")
    location = storage.get("location") or node.attrs.get("output_dir")
    if not location:
        raise NotConvertible("hive table without a location")
    # static partitions become path segments (k=v), Hive layout
    static_parts = node.attrs.get("static_partitions", {}) or {}
    out_dir = location
    for k, v in static_parts.items():
        out_dir = f"{out_dir}/{k}={v}"
    dyn_cols = tuple(node.attrs.get("dynamic_partition_cols", ()) or ())
    compression = storage.get("compression",
                              node.attrs.get("compression", "zstd"))
    if fmt == "parquet":
        _op_enabled("parquet.sink")
        plan: P.PlanNode = P.ParquetSink(
            child=children[0], output_dir=out_dir,
            partition_cols=dyn_cols, compression=compression)
    else:
        _op_enabled("orc.sink")
        plan = P.OrcSink(child=children[0], output_dir=out_dir,
                         partition_cols=dyn_cols,
                         compression=compression)
    return ctx.set_parts(plan, ctx.parts(children[0]))


# ---------------------------------------------------------------------------
# external convert providers (thirdparty SPI; AuronConvertProvider.scala:27
# + ServiceLoader discovery at AuronConverters.scala:108-112)
# ---------------------------------------------------------------------------

class ConvertProvider:
    """Extension hook: table formats (Iceberg/Paimon/Hudi) register one of
    these to claim foreign scan nodes."""

    def is_supported(self, node: ForeignNode) -> bool:
        raise NotImplementedError

    def convert(self, node: ForeignNode, children, ctx: ConvertContext
                ) -> P.PlanNode:
        raise NotImplementedError


_EXT_PROVIDERS: List[ConvertProvider] = []


def register_provider(p: ConvertProvider) -> None:
    _EXT_PROVIDERS.append(p)


def unregister_provider(p: ConvertProvider) -> None:
    try:
        _EXT_PROVIDERS.remove(p)
    except ValueError:
        pass


def ext_convert_supported(node: ForeignNode) -> bool:
    return any(p.is_supported(node) for p in _EXT_PROVIDERS)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def convert_node(node: ForeignNode, native_children: List[P.PlanNode],
                 ctx: ConvertContext) -> P.PlanNode:
    """Strict single-node conversion given native children."""
    for p in _EXT_PROVIDERS:
        if p.is_supported(node):
            return p.convert(node, native_children, ctx)
    fn = _PLAN_CONVERTERS.get(node.op)
    if fn is None:
        raise NotConvertible(f"{node.op} is not supported yet")
    return fn(node, native_children, ctx)


def dry_run_convertible(node: ForeignNode) -> Optional[str]:
    """Convertibility probe for the strategy pass: children are assumed
    native.  Returns None if convertible, else the reason."""
    ctx = ConvertContext()
    placeholders = []
    for c in node.children:
        schema = c.output if c.output is not None else Schema(())
        ph = P.FFIReader(schema=schema, resource_id="__dryrun__")
        placeholders.append(ctx.set_parts(ph, 1))
    try:
        convert_node(node, placeholders, ctx)
        return None
    except NotConvertible as e:
        return str(e)
    except Exception as e:  # converter bug surfaces as non-convertible
        return f"{type(e).__name__}: {e}"


def convert_to_native(converted: ConvertedT, ctx: ConvertContext
                      ) -> P.PlanNode:
    """C2N insertion (AuronConverters.convertToNative:1132): a foreign
    subtree under a native parent enters through an FFIReader."""
    if not isinstance(converted, ForeignWrap):
        return converted
    node = converted.node
    schema = node.output if node.output is not None else Schema(())
    rid = ctx.fresh("c2n")
    ctx.sources[rid] = ForeignSource(rid=rid, node=converted)
    reader = P.FFIReader(schema=schema, resource_id=rid)
    return ctx.set_parts(reader, 1)


def convert_recursively(node: ForeignNode, tags, ctx: ConvertContext
                        ) -> ConvertedT:
    """convertSparkPlanRecursively:186-209 analogue, driven by the
    strategy's tags (frontend.strategy.Tags)."""
    converted_children = [convert_recursively(c, tags, ctx)
                          for c in node.children]
    if tags.is_always_convert(node):
        native_children = [convert_to_native(c, ctx)
                           for c in converted_children]
        return convert_node(node, native_children, ctx)
    return ForeignWrap(node=node, children=converted_children)
