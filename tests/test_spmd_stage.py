"""SPMD stage compiler: planner-produced IR plans executed as ONE
shard_map program over the virtual 8-device mesh, differentially checked
against the serial per-partition engine (the VERDICT round-1 directive:
the engine itself must ride the mesh, not a hand-built demo kernel)."""

import numpy as np
import pyarrow as pa
import pytest

import jax

from auron_tpu.frontend.converters import BroadcastJob, ShuffleJob
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import AggExpr, SortExpr, col, lit
from auron_tpu.ir.plan import JoinOn
from auron_tpu.ir.schema import DataType, Field, Schema, from_arrow_schema
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.parallel.stage import SpmdUnsupported, execute_plan_spmd
from auron_tpu.runtime.executor import execute_plan
from auron_tpu.runtime.resources import ResourceRegistry

I64 = DataType.int64()
F64 = DataType.float64()


class _Ctx:
    def __init__(self):
        self.exchanges = {}
        self.broadcasts = {}


def _canon(rows):
    def norm(v):
        if v is None:               # None-safe sort (null grouping keys)
            return (0, "")
        if isinstance(v, float):
            return (1, round(v, 6))
        return (1, v)
    return sorted(tuple(sorted((k, norm(v)) for k, v in r.items()))
                  for r in rows)


def _serial_reference(plan, tables):
    """Run the same plan through the serial engine (exchange inlined as a
    single-partition pipeline: FFI sources feed directly)."""
    res = ResourceRegistry()
    for rid, t in tables.items():
        res.put(rid, t.to_batches())
    return execute_plan(plan, resources=res).to_pylist()


def make_fact(n=5000, keys=64, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "key": rng.integers(0, keys, n).astype(np.int64),
        "amount": rng.normal(10, 30, n).astype(np.float64),
    })


def make_dim(keys=64):
    return pa.table({
        "dkey": np.arange(keys, dtype=np.int64),
        "dname": np.array([f"k{i}" for i in range(keys)]),
    })


def test_spmd_filter_project_agg_exchange():
    """scan -> filter -> project -> partial agg -> hash exchange ->
    final agg, all inside one shard_map program."""
    fact = make_fact()
    fact_schema = from_arrow_schema(fact.schema)
    src = P.FFIReader(schema=fact_schema, resource_id="fact")
    partial = P.Agg(
        child=P.Projection(
            child=P.Filter(child=src, predicates=(
                E.BinaryExpr(left=col("amount"), op=">", right=lit(0.0)),)),
            exprs=(col("key"),
                   E.BinaryExpr(left=col("amount"), op="*",
                                right=lit(2.0))),
            names=("key", "net")),
        exec_mode="partial", grouping=(col("key"),), grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("net"),), return_type=F64),
              AggExpr(fn="count", children=(col("net"),),
                      return_type=I64)),
        agg_names=("s", "c"))
    ctx = _Ctx()
    ctx.exchanges["ex0"] = ShuffleJob(
        rid="ex0", child=partial,
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("key"),)),
        schema=None)
    final = P.Agg(
        child=P.IpcReader(schema=None, resource_id="ex0"),
        exec_mode="final", grouping=(col("key"),), grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("net"),), return_type=F64),
              AggExpr(fn="count", children=(col("net"),),
                      return_type=I64)),
        agg_names=("s", "c"))

    mesh = data_mesh(8)
    got = execute_plan_spmd(final, ctx, mesh,
                            {"fact": fact}).to_pylist()

    # serial reference: same pipeline, single partition, no exchange
    serial = P.Agg(
        child=partial, exec_mode="final", grouping=(col("key"),),
        grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("net"),), return_type=F64),
              AggExpr(fn="count", children=(col("net"),),
                      return_type=I64)),
        agg_names=("s", "c"))
    exp = _serial_reference(serial, {"fact": fact})
    assert _canon(got) == _canon(exp)


def test_spmd_broadcast_join_with_sort_root():
    """partial/final agg over an exchange, broadcast dim join on top, and
    a global ORDER BY applied driver-side after the gather."""
    fact = make_fact(n=3000, keys=32)
    dim = make_dim(keys=32)
    fact_schema = from_arrow_schema(fact.schema)
    dim_schema = from_arrow_schema(dim.schema)
    src = P.FFIReader(schema=fact_schema, resource_id="fact")
    agg1 = P.Agg(
        child=src, exec_mode="partial", grouping=(col("key"),),
        grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("amount"),),
                      return_type=F64),),
        agg_names=("s",))
    ctx = _Ctx()
    ctx.exchanges["ex0"] = ShuffleJob(
        rid="ex0", child=agg1,
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("key"),)),
        schema=None)
    ctx.broadcasts["bc0"] = BroadcastJob(
        rid="bc0", child=P.FFIReader(schema=dim_schema, resource_id="dim"),
        schema=None)
    final = P.Agg(
        child=P.IpcReader(schema=None, resource_id="ex0"),
        exec_mode="final", grouping=(col("key"),), grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("amount"),),
                      return_type=F64),),
        agg_names=("s",))
    join = P.BroadcastJoin(
        left=final,
        right=P.IpcReader(schema=None, resource_id="bc0"),
        on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
        join_type="inner", broadcast_side="right")
    root = P.Sort(child=join, sort_exprs=(SortExpr(child=col("key")),))

    mesh = data_mesh(8)
    got = execute_plan_spmd(root, ctx, mesh,
                            {"fact": fact, "dim": dim}).to_pylist()

    serial_join = P.BroadcastJoin(
        left=P.Agg(child=agg1, exec_mode="final", grouping=(col("key"),),
                   grouping_names=("key",),
                   aggs=(AggExpr(fn="sum", children=(col("amount"),),
                                 return_type=F64),),
                   agg_names=("s",)),
        right=P.FFIReader(schema=dim_schema, resource_id="dim"),
        on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
        join_type="inner", broadcast_side="right")
    exp = _serial_reference(P.Sort(child=serial_join, sort_exprs=(
        SortExpr(child=col("key")),)), {"fact": fact, "dim": dim})
    # ordered compare: the root sort is total on unique keys
    assert [r["key"] for r in got] == [r["key"] for r in exp]
    assert _canon(got) == _canon(exp)


def test_spmd_unsupported_falls_out():
    sch = Schema((Field("k", I64),))
    plan = P.Generate(child=P.FFIReader(schema=sch, resource_id="t"),
                      generator="explode", args=(col("k"),),
                      generator_output_names=("x",),
                      generator_output_types=(I64,),
                      required_child_output=(), outer=False)
    mesh = data_mesh(8)
    with pytest.raises(SpmdUnsupported):
        execute_plan_spmd(plan, _Ctx(), mesh,
                          {"t": pa.table({"k": np.arange(4)})})


def test_spmd_round_robin_and_single_exchange():
    fact = make_fact(n=1000, keys=16)
    fact_schema = from_arrow_schema(fact.schema)
    for mode in ("round_robin", "single"):
        ctx = _Ctx()
        ctx.exchanges["ex0"] = ShuffleJob(
            rid="ex0",
            child=P.FFIReader(schema=fact_schema, resource_id="fact"),
            partitioning=P.Partitioning(mode=mode, num_partitions=8),
            schema=None)
        final = P.Agg(
            child=P.IpcReader(schema=None, resource_id="ex0"),
            exec_mode="single", grouping=(), grouping_names=(),
            aggs=(AggExpr(fn="count", children=(col("key"),),
                          return_type=I64),),
            agg_names=("c",))
        mesh = data_mesh(8)
        got = execute_plan_spmd(final, ctx, mesh,
                                {"fact": fact}).to_pylist()
        # a global agg after an exchange produces one row PER DEVICE that
        # holds rows; total count must equal the table size
        assert sum(r["c"] for r in got) == fact.num_rows


def test_spmd_single_agg_guards():
    """Review round-3: (a) an all-empty ungrouped single agg emits the
    one identity row (count=0) like the serial engine; (b) a single-mode
    GROUPED agg after a hash exchange on non-grouping keys is rejected
    (per-device groups would be incomplete)."""
    fact = make_fact(n=800, keys=16)
    fact_schema = from_arrow_schema(fact.schema)
    mesh = data_mesh(8)

    # (a) filter everything out, then global count
    ctx = _Ctx()
    ctx.exchanges["ex0"] = ShuffleJob(
        rid="ex0",
        child=P.Filter(
            child=P.FFIReader(schema=fact_schema, resource_id="fact"),
            predicates=(E.BinaryExpr(left=col("key"), op="<",
                                     right=lit(-1)),)),
        partitioning=P.Partitioning(mode="single", num_partitions=1),
        schema=None)
    plan = P.Agg(
        child=P.IpcReader(schema=None, resource_id="ex0"),
        exec_mode="single", grouping=(), grouping_names=(),
        aggs=(AggExpr(fn="count", children=(col("key"),), return_type=I64),
              AggExpr(fn="sum", children=(col("amount"),),
                      return_type=F64)),
        agg_names=("c", "s"))
    got = execute_plan_spmd(plan, ctx, mesh, {"fact": fact}).to_pylist()
    assert got == [{"c": 0, "s": None}]

    # (b) grouped single agg over a hash exchange on a DIFFERENT column
    ctx2 = _Ctx()
    ctx2.exchanges["ex1"] = ShuffleJob(
        rid="ex1",
        child=P.FFIReader(schema=fact_schema, resource_id="fact"),
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("amount"),)),
        schema=None)
    bad = P.Agg(
        child=P.IpcReader(schema=None, resource_id="ex1"),
        exec_mode="single", grouping=(col("key"),),
        grouping_names=("key",),
        aggs=(AggExpr(fn="count", children=(col("key"),),
                      return_type=I64),),
        agg_names=("c",))
    with pytest.raises(SpmdUnsupported, match="single-mode agg"):
        execute_plan_spmd(bad, ctx2, mesh, {"fact": fact})


@pytest.mark.slow   # PR 18 tier-1 re-split (8.1s; quota accounting
#   units stay fast, the overflow sweep rides nightly)
def test_spmd_exchange_quota_bounded_and_overflow_guard():
    """Round-3 VERDICT #4: hash-exchange receive buffers must be
    O(global/n_dev * margin), not O(global); skew past the margin trips
    the runtime guard instead of silently dropping rows."""
    from auron_tpu.config import conf
    from auron_tpu.parallel.exchange import bounded_quota

    # shape check: the bounded quota is ~capacity/n_dev * margin
    assert bounded_quota(1 << 20, 8, margin=2.0) <= (1 << 18) + 16
    assert bounded_quota(100, 8, margin=2.0) <= 100

    # differential run under a bounded quota (uniform keys: no overflow)
    fact = make_fact(n=4000, keys=64, seed=21)
    fact_schema = from_arrow_schema(fact.schema)
    src = P.FFIReader(schema=fact_schema, resource_id="fact")

    def build(keys_col):
        partial = P.Agg(
            child=src, exec_mode="partial", grouping=(col(keys_col),),
            grouping_names=(keys_col,),
            aggs=(AggExpr(fn="count", children=(col("amount"),),
                          return_type=I64),),
            agg_names=("c",))
        ctx = _Ctx()
        ctx.exchanges["ex"] = ShuffleJob(
            rid="ex", child=P.Projection(
                child=src, exprs=(col("key"), col("amount")),
                names=("key", "amount")),
            partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                        expressions=(col(keys_col),)),
            schema=None)
        final = P.Agg(
            child=P.IpcReader(schema=None, resource_id="ex"),
            exec_mode="single", grouping=(col(keys_col),),
            grouping_names=(keys_col,),
            aggs=(AggExpr(fn="count", children=(col("amount"),),
                          return_type=I64),),
            agg_names=("c",))
        return final, ctx

    mesh = data_mesh(8)
    plan, ctx = build("key")
    got = execute_plan_spmd(plan, ctx, mesh, {"fact": fact}).to_pylist()
    assert sum(r["c"] for r in got) == fact.num_rows

    # skew: every row hashes to ONE destination -> quota overflow must
    # raise (guard), not lose rows
    skew = pa.table({
        "key": np.zeros(4000, dtype=np.int64),
        "amount": np.arange(4000, dtype=np.float64)})
    plan2, ctx2 = build("key")
    with pytest.raises(SpmdUnsupported, match="guard"):
        execute_plan_spmd(plan2, ctx2, mesh, {"fact": skew})

    # 2-D mesh: stage-1 quota must be sized for n_ici destinations — an
    # n_dev-sized quota overflows on UNIFORM data whenever n_dcn > margin
    # (round-3 review finding)
    from auron_tpu.parallel.mesh import hierarchical_mesh
    mesh2d = hierarchical_mesh(n_dcn=4, n_ici=2)
    plan3, ctx3 = build("key")
    got2 = execute_plan_spmd(plan3, ctx3, mesh2d, {"fact": fact},
                             axis=("dcn", "ici")).to_pylist()
    assert sum(r["c"] for r in got2) == fact.num_rows


def test_spmd_join_multi_match_expansion():
    """Round-2 demanded a duplicate-build guard; round-3 goes further:
    the tripped guard RETRIES with K-way pair expansion, so moderate
    multi-match builds still ride the mesh with correct pair output.
    Builds wider than the factor fall back (guard again)."""
    fact = make_fact(n=500, keys=8)

    def bc_join(dim):
        ctx = _Ctx()
        ctx.broadcasts["bc0"] = BroadcastJob(
            rid="bc0",
            child=P.FFIReader(schema=from_arrow_schema(dim.schema),
                              resource_id="dim"),
            schema=None)
        return P.BroadcastJoin(
            left=P.FFIReader(schema=from_arrow_schema(fact.schema),
                             resource_id="fact"),
            right=P.IpcReader(schema=None, resource_id="bc0"),
            on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
            join_type="inner", broadcast_side="right"), ctx

    mesh = data_mesh(8)
    # 2 duplicates per key <= match factor 4: pair expansion kicks in
    dim = pa.table({"dkey": np.array([1, 1, 2], dtype=np.int64),
                    "dval": np.array([10.0, 20.0, 30.0])})
    join, ctx = bc_join(dim)
    got = execute_plan_spmd(join, ctx, mesh,
                            {"fact": fact, "dim": dim}).to_pylist()
    serial = P.BroadcastJoin(
        left=P.FFIReader(schema=from_arrow_schema(fact.schema),
                         resource_id="fact"),
        right=P.FFIReader(schema=from_arrow_schema(dim.schema),
                          resource_id="dim"),
        on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
        join_type="inner", broadcast_side="right")
    exp = _serial_reference(serial, {"fact": fact, "dim": dim})
    assert _canon(got) == _canon(exp)

    # 6 duplicates of one key > factor 4: guard trips on the retry too
    wide = pa.table({"dkey": np.full(6, 1, dtype=np.int64),
                     "dval": np.arange(6, dtype=np.float64)})
    join2, ctx2 = bc_join(wide)
    with pytest.raises(SpmdUnsupported, match="match factor"):
        execute_plan_spmd(join2, ctx2, mesh,
                          {"fact": fact, "dim": wide})


def test_spmd_hierarchical_2d_mesh():
    """The same planner-produced pipeline on a 2-D (dcn x ici) mesh: hash
    exchanges ride the two-stage hierarchical all-to-all, broadcasts
    gather ICI-first — differentially equal to the serial engine."""
    from auron_tpu.parallel.mesh import hierarchical_mesh
    fact = make_fact(n=3000, keys=32, seed=9)
    dim = make_dim(keys=32)
    fact_schema = from_arrow_schema(fact.schema)
    dim_schema = from_arrow_schema(dim.schema)
    src = P.FFIReader(schema=fact_schema, resource_id="fact")
    agg1 = P.Agg(
        child=src, exec_mode="partial", grouping=(col("key"),),
        grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("amount"),),
                      return_type=F64),),
        agg_names=("s",))
    ctx = _Ctx()
    ctx.exchanges["ex0"] = ShuffleJob(
        rid="ex0", child=agg1,
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("key"),)),
        schema=None)
    ctx.broadcasts["bc0"] = BroadcastJob(
        rid="bc0", child=P.FFIReader(schema=dim_schema, resource_id="dim"),
        schema=None)
    final = P.Agg(
        child=P.IpcReader(schema=None, resource_id="ex0"),
        exec_mode="final", grouping=(col("key"),), grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("amount"),),
                      return_type=F64),),
        agg_names=("s",))
    join = P.BroadcastJoin(
        left=final,
        right=P.IpcReader(schema=None, resource_id="bc0"),
        on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
        join_type="inner", broadcast_side="right")

    mesh = hierarchical_mesh(2, 4)
    got = execute_plan_spmd(join, ctx, mesh, {"fact": fact, "dim": dim},
                            axis=("dcn", "ici")).to_pylist()

    serial_join = P.BroadcastJoin(
        left=P.Agg(child=agg1, exec_mode="final", grouping=(col("key"),),
                   grouping_names=("key",),
                   aggs=(AggExpr(fn="sum", children=(col("amount"),),
                                 return_type=F64),),
                   agg_names=("s",)),
        right=P.FFIReader(schema=dim_schema, resource_id="dim"),
        on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
        join_type="inner", broadcast_side="right")
    exp = _serial_reference(serial_join, {"fact": fact, "dim": dim})
    assert _canon(got) == _canon(exp)


@pytest.mark.slow   # PR 18 tier-1 re-split (8.2s; window-on-mesh is
#   pinned fast by test_some_queries_ride_the_mesh's q65w assert)
def test_spmd_window_limit_topk_range():
    """Round-3 VERDICT #5: window / limit / top-k sort / range exchange
    ride the mesh, differentially equal to the serial engine."""
    from auron_tpu.ir.plan import WindowFuncCall, WindowGroupLimit
    fact = make_fact(n=2000, keys=16, seed=17)
    fact_schema = from_arrow_schema(fact.schema)
    src = P.FFIReader(schema=fact_schema, resource_id="fact")
    mesh = data_mesh(8)

    # window (rank + agg-over-window) over a hash exchange on its
    # partition key
    ctx = _Ctx()
    ctx.exchanges["exw"] = ShuffleJob(
        rid="exw", child=src,
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("key"),)),
        schema=None)
    win = P.Window(
        child=P.IpcReader(schema=None, resource_id="exw"),
        window_funcs=(
            WindowFuncCall(fn="row_number", args=(), name="rn",
                           return_type=I64),
            WindowFuncCall(fn="rank", args=(), name="rk",
                           return_type=I64),
        ),
        partition_by=(col("key"),),
        order_by=(SortExpr(child=col("amount")),))
    got = execute_plan_spmd(win, ctx, mesh, {"fact": fact}).to_pylist()
    serial_win = P.Window(
        child=src,
        window_funcs=win.window_funcs,
        partition_by=win.partition_by, order_by=win.order_by)
    exp = _serial_reference(serial_win, {"fact": fact})
    assert _canon(got) == _canon(exp)

    # window group-limit (the window-group-limit proto:590 analogue)
    win_gl = P.Window(
        child=P.IpcReader(schema=None, resource_id="exw"),
        window_funcs=(),
        partition_by=(col("key"),),
        order_by=(SortExpr(child=col("amount")),),
        group_limit=WindowGroupLimit(rank_fn="row_number", k=3),
        output_window_cols=False)
    ctx2 = _Ctx(); ctx2.exchanges = dict(ctx.exchanges)
    got_gl = execute_plan_spmd(win_gl, ctx2, mesh,
                               {"fact": fact}).to_pylist()
    serial_gl = P.Window(
        child=src, window_funcs=(), partition_by=win_gl.partition_by,
        order_by=win_gl.order_by, group_limit=win_gl.group_limit,
        output_window_cols=False)
    exp_gl = _serial_reference(serial_gl, {"fact": fact})
    assert _canon(got_gl) == _canon(exp_gl)

    # top-k sort (unshadowed, mid-plan) + count: per-device top-k
    ctx3 = _Ctx()
    ctx3.exchanges["exs"] = ShuffleJob(
        rid="exs", child=P.Sort(
            child=src,
            sort_exprs=(SortExpr(child=col("amount"), asc=False),),
            fetch_limit=10),
        partitioning=P.Partitioning(mode="single", num_partitions=1),
        schema=None)
    cnt = P.Agg(
        child=P.IpcReader(schema=None, resource_id="exs"),
        exec_mode="single", grouping=(), grouping_names=(),
        aggs=(AggExpr(fn="count", children=(col("key"),),
                      return_type=I64),),
        agg_names=("c",))
    got3 = execute_plan_spmd(cnt, ctx3, mesh, {"fact": fact}).to_pylist()
    # one shard per device, top-10 each -> 8 * 10 rows total
    assert sum(r["c"] for r in got3) == 80

    # mid-plan limit: per-device first-5
    ctx4 = _Ctx()
    ctx4.exchanges["exl"] = ShuffleJob(
        rid="exl", child=P.Limit(child=src, limit=5),
        partitioning=P.Partitioning(mode="single", num_partitions=1),
        schema=None)
    cnt4 = P.Agg(
        child=P.IpcReader(schema=None, resource_id="exl"),
        exec_mode="single", grouping=(), grouping_names=(),
        aggs=(AggExpr(fn="count", children=(col("key"),),
                      return_type=I64),),
        agg_names=("c",))
    got4 = execute_plan_spmd(cnt4, ctx4, mesh, {"fact": fact}).to_pylist()
    assert sum(r["c"] for r in got4) == 40      # 8 devices * 5

    # range exchange: sampled bounds route on device; count preserved
    ctx5 = _Ctx()
    ctx5.exchanges["exr"] = ShuffleJob(
        rid="exr", child=src,
        partitioning=P.Partitioning(
            mode="range", num_partitions=4,
            sort_orders=(SortExpr(child=col("key")),),
            range_bounds=((4,), (8,), (12,))),
        schema=None)
    # range exchange is not colocating-by-grouping in the _single_agg_ok
    # sense, so count through a partial/final pair instead
    partial5 = P.Agg(
        child=P.IpcReader(schema=None, resource_id="exr"),
        exec_mode="partial", grouping=(col("key"),),
        grouping_names=("key",),
        aggs=(AggExpr(fn="count", children=(col("amount"),),
                      return_type=I64),),
        agg_names=("c",))
    ctx5.exchanges["exr2"] = ShuffleJob(
        rid="exr2", child=partial5,
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("key"),)),
        schema=None)
    final5 = P.Agg(
        child=P.IpcReader(schema=None, resource_id="exr2"),
        exec_mode="final", grouping=(col("key"),),
        grouping_names=("key",),
        aggs=(AggExpr(fn="count", children=(col("amount"),),
                      return_type=I64),),
        agg_names=("c",))
    got5 = execute_plan_spmd(final5, ctx5, mesh,
                             {"fact": fact}).to_pylist()
    assert sum(r["c"] for r in got5) == fact.num_rows


@pytest.mark.slow
def test_spmd_sort_merge_join():
    """PR 10 tier-1 re-split: 23.6s measured (heaviest spmd-stage
    test) — nightly slow lane; the TPC-DS multi-device subset keeps
    SPMD SMJ coverage in tier-1.

    Round-3: an SMJ whose sides are hash-colocated on the join keys
    compiles to the per-device sorted-hash probe (single-match build);
    duplicate build keys trip the guard and fall back."""
    rng = np.random.default_rng(41)
    n = 1500
    fact = pa.table({
        "fk": rng.integers(0, 200, n).astype(np.int64),
        "amount": rng.normal(10, 5, n).astype(np.float64)})
    dim = pa.table({"dk": np.arange(200, dtype=np.int64),
                    "w": rng.normal(size=200)})
    mesh = data_mesh(8)

    def smj_plan(dim_table, join_type="inner"):
        ctx = _Ctx()
        ctx.exchanges["exl"] = ShuffleJob(
            rid="exl",
            child=P.FFIReader(schema=from_arrow_schema(fact.schema),
                              resource_id="fact"),
            partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                        expressions=(col("fk"),)),
            schema=None)
        ctx.exchanges["exr"] = ShuffleJob(
            rid="exr",
            child=P.FFIReader(schema=from_arrow_schema(dim_table.schema),
                              resource_id="dim"),
            partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                        expressions=(col("dk"),)),
            schema=None)
        join = P.SortMergeJoin(
            left=P.Sort(child=P.IpcReader(schema=None, resource_id="exl"),
                        sort_exprs=(SortExpr(child=col("fk")),)),
            right=P.Sort(child=P.IpcReader(schema=None,
                                           resource_id="exr"),
                         sort_exprs=(SortExpr(child=col("dk")),)),
            on=JoinOn(left_keys=(col("fk"),), right_keys=(col("dk"),)),
            join_type=join_type)
        return ctx, join

    def serial_smj(dim_table, join_type="inner"):
        return P.SortMergeJoin(
            left=P.Sort(child=P.FFIReader(
                schema=from_arrow_schema(fact.schema),
                resource_id="fact"),
                sort_exprs=(SortExpr(child=col("fk")),)),
            right=P.Sort(child=P.FFIReader(
                schema=from_arrow_schema(dim_table.schema),
                resource_id="dim"),
                sort_exprs=(SortExpr(child=col("dk")),)),
            on=JoinOn(left_keys=(col("fk"),), right_keys=(col("dk"),)),
            join_type=join_type)

    ctx, join = smj_plan(dim)
    got = execute_plan_spmd(join, ctx, mesh,
                            {"fact": fact, "dim": dim}).to_pylist()
    exp = _serial_reference(serial_smj(dim), {"fact": fact, "dim": dim})
    assert _canon(got) == _canon(exp)

    # semi / anti / existence ride the same probe kernel (no pair
    # expansion needed); restrict dim to half the keys so each type has
    # both outcomes
    # full / right emit unmatched build rows locally (colocated sides);
    # a sparse dim (every 3rd key up to 300) gives unmatched rows on
    # both sides
    sparse_dim = pa.table({
        "dk": np.arange(0, 300, 3, dtype=np.int64),
        "w": np.arange(100, dtype=np.float64)})
    for jt in ("full", "right"):
        ctx_f, j_f = smj_plan(sparse_dim, jt)
        got_f = execute_plan_spmd(j_f, ctx_f, mesh,
                                  {"fact": fact,
                                   "dim": sparse_dim}).to_pylist()
        exp_f = _serial_reference(serial_smj(sparse_dim, jt),
                                  {"fact": fact, "dim": sparse_dim})
        assert _canon(got_f) == _canon(exp_f), jt

    half_dim = pa.table({"dk": np.arange(100, dtype=np.int64),
                         "w": np.ones(100)})
    for jt in ("left_semi", "left_anti", "existence"):
        ctx_j, j = smj_plan(half_dim, jt)
        got_j = execute_plan_spmd(j, ctx_j, mesh,
                                  {"fact": fact,
                                   "dim": half_dim}).to_pylist()
        exp_j = _serial_reference(serial_smj(half_dim, jt),
                                  {"fact": fact, "dim": half_dim})
        assert _canon(got_j) == _canon(exp_j), jt

    # shuffled HASH join: same colocation machinery, full-outer output
    sparse2 = pa.table({"dk": np.arange(0, 300, 3, dtype=np.int64),
                        "w": np.arange(100, dtype=np.float64)})
    ctx_h, smj_h = smj_plan(sparse2, "full")
    hj = P.HashJoin(
        left=smj_h.left, right=smj_h.right, on=smj_h.on,
        join_type="full", build_side="right")
    got_h = execute_plan_spmd(hj, ctx_h, mesh,
                              {"fact": fact, "dim": sparse2}).to_pylist()
    exp_h = _serial_reference(serial_smj(sparse2, "full"),
                              {"fact": fact, "dim": sparse2})
    assert _canon(got_h) == _canon(exp_h)

    # NON-colocated shuffled join (round-robin side) must be rejected
    # up front — per-device probing would drop cross-device matches
    ctx_rr, smj_rr = smj_plan(sparse2)
    ctx_rr.exchanges["exl"] = ShuffleJob(
        rid="exl",
        child=P.FFIReader(schema=from_arrow_schema(fact.schema),
                          resource_id="fact"),
        partitioning=P.Partitioning(mode="round_robin",
                                    num_partitions=8),
        schema=None)
    with pytest.raises(SpmdUnsupported, match="colocated"):
        execute_plan_spmd(smj_rr, ctx_rr, mesh,
                          {"fact": fact, "dim": sparse2})

    # duplicate-key build side: the K-way retry makes it ride with
    # correct multi-match pairs across join types (unmatched-emission
    # and outer tails included); wider than K still falls back
    dup_dim = pa.table({"dk": np.array([1, 1, 2, 2, 250], dtype=np.int64),
                        "w": np.array([1.0, 2.0, 3.0, 4.0, 5.0])})
    for jt in ("inner", "left", "full", "right"):
        ctx2, join2 = smj_plan(dup_dim, jt)
        got_d = execute_plan_spmd(
            join2, ctx2, mesh, {"fact": fact, "dim": dup_dim}).to_pylist()
        exp_d = _serial_reference(serial_smj(dup_dim, jt),
                                  {"fact": fact, "dim": dup_dim})
        assert _canon(got_d) == _canon(exp_d), jt
    wide_dim = pa.table({"dk": np.full(6, 1, dtype=np.int64),
                         "w": np.arange(6, dtype=np.float64)})
    ctx3, join3 = smj_plan(wide_dim)
    with pytest.raises(SpmdUnsupported, match="match factor"):
        execute_plan_spmd(join3, ctx3, mesh,
                          {"fact": fact, "dim": wide_dim})


@pytest.mark.slow   # PR 18 tier-1 re-split (10.3s; union/expand SPMD
#   shapes also ride the tier-1 mesh corpus queries)
def test_spmd_union_and_expand():
    """Union (incl. rows-twice duplicate inputs) and Expand compile into
    the shard_map program with serial-engine-equivalent results."""
    from auron_tpu.ir.plan import UnionInput
    fact = make_fact(n=1200, keys=16, seed=11)
    fact_schema = from_arrow_schema(fact.schema)
    src = P.FFIReader(schema=fact_schema, resource_id="fact")
    proj = P.Projection(child=src, exprs=(col("key"), col("amount")),
                        names=("key", "amount"))
    doubled = P.Union(
        inputs=(UnionInput(child=proj, partition=0, out_partition=0),
                UnionInput(child=proj, partition=0, out_partition=1)),
        schema=from_arrow_schema(fact.schema), num_partitions=2)

    def agg_pair(child, fn, rtype, out):
        partial = P.Agg(
            child=child, exec_mode="partial", grouping=(col("key"),),
            grouping_names=("key",),
            aggs=(AggExpr(fn=fn, children=(col("amount"),),
                          return_type=rtype),),
            agg_names=(out,))
        ctx = _Ctx()
        ctx.exchanges["exu"] = ShuffleJob(
            rid="exu", child=partial,
            partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                        expressions=(col("key"),)),
            schema=None)
        final = P.Agg(
            child=P.IpcReader(schema=None, resource_id="exu"),
            exec_mode="final", grouping=(col("key"),),
            grouping_names=("key",),
            aggs=(AggExpr(fn=fn, children=(col("amount"),),
                          return_type=rtype),),
            agg_names=(out,))
        serial = P.Agg(
            child=partial, exec_mode="final", grouping=(col("key"),),
            grouping_names=("key",),
            aggs=(AggExpr(fn=fn, children=(col("amount"),),
                          return_type=rtype),),
            agg_names=(out,))
        return final, ctx, serial

    agg, ctx, serial = agg_pair(doubled, "count", I64, "c")
    mesh = data_mesh(8)
    got = execute_plan_spmd(agg, ctx, mesh, {"fact": fact}).to_pylist()
    exp = _serial_reference(serial, {"fact": fact})
    assert _canon(got) == _canon(exp)
    assert sum(r["c"] for r in got) == 2 * fact.num_rows

    # expand: grouping-sets replication
    exp_node = P.Expand(
        child=proj,
        projections=((col("key"), col("amount")),
                     (lit(None, I64), col("amount"))),
        names=("key", "amount"),
        types=(I64, F64))
    agg2, ctx2, serial2 = agg_pair(exp_node, "sum", F64, "s")
    got2 = execute_plan_spmd(agg2, ctx2, mesh,
                             {"fact": fact}).to_pylist()
    exp2 = _serial_reference(serial2, {"fact": fact})
    assert _canon(got2) == _canon(exp2)


def test_spmd_program_cache_across_conversions():
    """Round-3 regression: two conversions of the same query mint
    different uuid resource ids, but the compiled program must be shared
    (rid canonicalization) — and shared union subtrees must STAY shared
    through the rewrite (an identity-losing rebuild replicated each
    union child's rows)."""
    from auron_tpu.parallel import stage as S

    fact = make_fact(n=2000, keys=16)
    fact_schema = from_arrow_schema(fact.schema)

    def build(uid):
        src = P.FFIReader(schema=fact_schema, resource_id=f"fact:{uid}:0")
        child = P.Projection(
            child=src, exprs=(col("key"), col("amount")),
            names=("key", "amount"))
        # the same child referenced once per partition (3 partitions)
        union = P.Union(
            schema=fact_schema,
            inputs=tuple(P.UnionInput(child=child, partition=p,
                                      out_partition=p)
                         for p in range(3)),
            num_partitions=3)
        partial = P.Agg(
            child=union, exec_mode="partial", grouping=(col("key"),),
            grouping_names=("key",),
            aggs=(AggExpr(fn="sum", children=(col("amount"),),
                          return_type=F64),),
            agg_names=("s",))
        ctx = _Ctx()
        ctx.exchanges[f"ex:{uid}:1"] = ShuffleJob(
            rid=f"ex:{uid}:1", child=partial,
            partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                        expressions=(col("key"),)),
            schema=None)
        final = P.Agg(
            child=P.IpcReader(schema=None, resource_id=f"ex:{uid}:1"),
            exec_mode="final", grouping=(col("key"),),
            grouping_names=("key",),
            aggs=(AggExpr(fn="sum", children=(col("amount"),),
                          return_type=F64),),
            agg_names=("s",))
        return final, ctx, {f"fact:{uid}:0": fact}

    mesh = data_mesh(8)
    n0 = len(S._PROGRAM_CACHE)
    p1, c1, t1 = build("aaaa1111")
    got1 = execute_plan_spmd(p1, c1, mesh, t1).to_pylist()
    n1 = len(S._PROGRAM_CACHE)
    p2, c2, t2 = build("bbbb2222")
    got2 = execute_plan_spmd(p2, c2, mesh, t2).to_pylist()
    n2 = len(S._PROGRAM_CACHE)
    assert n1 == n0 + 1 and n2 == n1, "second conversion missed the cache"
    assert _canon(got1) == _canon(got2)

    # union semantics survived canonicalization: child counted ONCE per
    # distinct object even though three partitions reference it
    k = fact.column("key").to_numpy()
    a = fact.column("amount").to_numpy()
    exp = {int(key): float(a[k == key].sum()) for key in set(k.tolist())}
    got = {int(r["key"]): float(r["s"]) for r in got1}
    assert set(got) == set(exp)
    for key in exp:
        assert abs(got[key] - exp[key]) < 1e-6, (key, got[key], exp[key])


def test_spmd_match_factor_hint_remembered():
    """Repeat executes of a duplicate-key join start at the remembered
    pair-expansion factor instead of paying the factor-1 trip + retry
    double execution every time."""
    from auron_tpu.parallel import stage as S

    fact = make_fact(n=400, keys=8)
    dim = pa.table({"dkey": np.array([1, 1, 2], dtype=np.int64),
                    "dval": np.array([10.0, 20.0, 30.0])})

    def build():
        ctx = _Ctx()
        ctx.broadcasts["bcH"] = BroadcastJob(
            rid="bcH",
            child=P.FFIReader(schema=from_arrow_schema(dim.schema),
                              resource_id="dimH"),
            schema=None)
        return P.BroadcastJoin(
            left=P.FFIReader(schema=from_arrow_schema(fact.schema),
                             resource_id="factH"),
            right=P.IpcReader(schema=None, resource_id="bcH"),
            on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
            join_type="inner", broadcast_side="right"), ctx

    mesh = data_mesh(8)
    tables = {"factH": fact, "dimH": dim}
    join, ctx = build()
    S._MATCH_FACTOR_HINT.clear()     # isolate from other tests' shapes
    first = execute_plan_spmd(join, ctx, mesh, tables).to_pylist()
    assert len(S._MATCH_FACTOR_HINT) == 1   # trip stored the factor
    assert list(S._MATCH_FACTOR_HINT.values()) == [4]
    join2, ctx2 = build()
    second = execute_plan_spmd(join2, ctx2, mesh, tables).to_pylist()
    assert _canon(first) == _canon(second)
    # the hint key is rid-canonical: the second conversion found it
    assert len(S._MATCH_FACTOR_HINT) == 1


def test_spmd_semi_like_joins_with_duplicate_build_keys():
    """Semi/anti/existence are probe-preserving, so TRUE duplicate build
    keys must ride the mesh at K=1 (no guard trip, no fallback) — the
    TPC-DS customer-EXISTS-over-fact shape.  Only hash collisions trip."""
    fact = make_fact(n=600, keys=16)
    # heavily duplicated build side: every key appears ~25 times
    rng = np.random.default_rng(9)
    dup = pa.table({"dkey": np.sort(rng.integers(0, 8, 200)).astype(
        np.int64)})

    mesh = data_mesh(8)
    for jt in ("LeftSemi", "LeftAnti", "ExistenceJoin"):
        jt_ir = {"LeftSemi": "left_semi", "LeftAnti": "left_anti",
                 "ExistenceJoin": "existence"}[jt]
        def bc_join():
            ctx = _Ctx()
            ctx.broadcasts["bcD"] = BroadcastJob(
                rid="bcD",
                child=P.FFIReader(schema=from_arrow_schema(dup.schema),
                                  resource_id="dupD"),
                schema=None)
            return P.BroadcastJoin(
                left=P.FFIReader(schema=from_arrow_schema(fact.schema),
                                 resource_id="factD"),
                right=P.IpcReader(schema=None, resource_id="bcD"),
                on=JoinOn(left_keys=(col("key"),),
                          right_keys=(col("dkey"),)),
                join_type=jt_ir, broadcast_side="right"), ctx
        join, ctx = bc_join()
        got = execute_plan_spmd(join, ctx, mesh,
                                {"factD": fact, "dupD": dup}).to_pylist()
        serial = P.BroadcastJoin(
            left=P.FFIReader(schema=from_arrow_schema(fact.schema),
                             resource_id="factD"),
            right=P.FFIReader(schema=from_arrow_schema(dup.schema),
                              resource_id="dupD"),
            on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
            join_type=jt_ir, broadcast_side="right")
        exp = _serial_reference(serial, {"factD": fact, "dupD": dup})
        assert _canon(got) == _canon(exp), jt


def test_expanded_join_compaction_and_fanout_retry():
    """K-expanded joins compact back to probe capacity (the q85r
    1024x-chain fix); a join that GENUINELY fans out past the target
    trips the join guard and retries with compaction off — correct rows
    either way, and the off-hint is remembered per program."""
    import auron_tpu.parallel.stage as S

    # per-device rows land EXACTLY on a capacity bucket (8192/8 = 1024),
    # so a 2x fan-out overflows the compaction target for sure
    n = 8192
    rng = np.random.default_rng(23)
    # every probe row matches exactly 2 build rows -> live output
    # 2n > probe capacity -> fan-out
    probe = pa.table({"k": rng.integers(0, 64, n).astype(np.int64),
                      "v": rng.normal(0, 1, n).astype(np.float64)})
    bk = np.repeat(np.arange(64, dtype=np.int64), 2)
    build = pa.table({"bk": bk, "w": np.arange(len(bk), dtype=np.float64)})
    mesh = data_mesh(8)
    ctx = _Ctx()
    ctx.exchanges = {}
    from auron_tpu.frontend.converters import BroadcastJob
    ctx.broadcasts = {"b": BroadcastJob(
        rid="b", child=P.FFIReader(schema=from_arrow_schema(build.schema),
                                   resource_id="build"), schema=None)}
    join = P.BroadcastJoin(
        left=P.FFIReader(schema=from_arrow_schema(probe.schema),
                         resource_id="probe"),
        right=P.IpcReader(schema=None, resource_id="b"),
        on=P.JoinOn(left_keys=(col("k"),), right_keys=(col("bk"),)),
        join_type="inner", broadcast_side="right")
    out = execute_plan_spmd(join, ctx, mesh,
                            {"probe": probe, "build": build})
    assert out.num_rows == 2 * n        # every row matches 2 build rows
    got = sorted(zip(out.column("k").to_pylist(),
                     out.column("w").to_pylist()))
    exp = sorted((int(k), float(w)) for k in probe.column("k").to_numpy()
                 for w in (2 * int(k), 2 * int(k) + 1))
    assert got == exp
    # the fan-out tripped the compaction guard and the off-hint stuck
    assert any(S._JOIN_COMPACT_OFF_HINT.values())


def test_source_cache_budget_zero_flushes_and_scan_fp_invalidates(tmp_path):
    """Round-4 cache semantics: lowering auron.spmd.source.cache.mb to 0
    releases retained device shards on the next lookup (memory-pressure
    contract), and a rewritten scan file never serves a stale cached
    table (pre-read fingerprint)."""
    import pyarrow.parquet as pq

    import auron_tpu.parallel.stage as S
    from auron_tpu.config import conf

    S.clear_source_caches()
    t = pa.table({"k": np.arange(100, dtype=np.int64),
                  "v": np.arange(100, dtype=np.float64)})
    mesh = data_mesh(8)
    ctx = _Ctx(); ctx.exchanges = {}; ctx.broadcasts = {}
    proj = P.Projection(
        child=P.FFIReader(schema=from_arrow_schema(t.schema),
                          resource_id="t"),
        exprs=(col("k"),), names=("k",))
    execute_plan_spmd(proj, ctx, mesh, {"t": t})
    assert len(S._DEVICE_SHARDS.values()) == 1
    with conf.scoped({"auron.spmd.source.cache.mb": 0}):
        # a lookup under budget 0 flushes the retained entries
        assert S._DEVICE_SHARDS.get(t, ()) is None
        assert len(S._DEVICE_SHARDS.values()) == 0

    # scan fingerprint: rewrite the file between executes -> re-read
    path = str(tmp_path / "scan.parquet")
    pq.write_table(pa.table({"a": np.arange(5, dtype=np.int64)}), path)
    from auron_tpu.ir.plan import FileGroup
    from auron_tpu.ir.schema import DataType, Field, Schema
    scan = P.ParquetScan(
        schema=Schema((Field("a", DataType.int64()),)),
        file_groups=(FileGroup(paths=(path,)),))
    sctx = _Ctx(); sctx.exchanges = {}; sctx.broadcasts = {}
    out1 = execute_plan_spmd(
        P.Projection(child=scan, exprs=(col("a"),), names=("a",)),
        sctx, mesh, {})
    assert sorted(out1.column("a").to_pylist()) == list(range(5))
    import time as _t
    _t.sleep(0.01)
    pq.write_table(pa.table({"a": np.arange(7, dtype=np.int64)}), path)
    sctx2 = _Ctx(); sctx2.exchanges = {}; sctx2.broadcasts = {}
    out2 = execute_plan_spmd(
        P.Projection(child=scan, exprs=(col("a"),), names=("a",)),
        sctx2, mesh, {})
    assert sorted(out2.column("a").to_pylist()) == list(range(7)), \
        "stale scan table served after the file changed"


@pytest.mark.parametrize("n_dev,rows,keep", [
    (8, 200_000, "all"), (4, 200_000, "all"), (8, 20_000, "none"),
], ids=["eight-devices", "four-devices", "empty-result"])
def test_spmd_gather_fetches_a_compacted_slice(n_dev, rows, keep):
    """The two-phase gather: the serial engine's answer, and a fetched
    footprint of the smallest capacity bucket that holds a shard's live
    rows, not the padded capacity (VERDICT r4 ask #2: gather only final
    aggregated rows, log the bytes)."""
    from auron_tpu.columnar.batch import bucket_capacity
    from auron_tpu.parallel.stage import GATHER_STATS

    # large enough that per-shard capacity (n/8 rows -> 32k bucket) sits
    # far above the 1024-row minimum bucket the compacted slice lands on
    fact = make_fact(n=rows, keys=16)
    src = P.Filter(
        child=P.FFIReader(schema=from_arrow_schema(fact.schema),
                          resource_id="fact"),
        predicates=(E.BinaryExpr(
            left=col("key"), op=">=",
            right=lit(0 if keep == "all" else 99)),))
    agg = dict(grouping=(col("key"),), grouping_names=("key",),
               aggs=(AggExpr(fn="sum", children=(col("amount"),),
                             return_type=F64),),
               agg_names=("s",))
    partial = P.Agg(child=src, exec_mode="partial", **agg)
    ctx = _Ctx()
    ctx.exchanges["ex0"] = ShuffleJob(
        rid="ex0", child=partial,
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("key"),)),
        schema=None)
    final = P.Agg(child=P.IpcReader(schema=None, resource_id="ex0"),
                  exec_mode="final", **agg)
    got = execute_plan_spmd(final, ctx, data_mesh(n_dev),
                            {"fact": fact}).to_pylist()
    want = _serial_reference(
        P.Agg(child=partial, exec_mode="final", **agg), {"fact": fact})
    assert len(got) == (16 if keep == "all" else 0)
    assert _canon(got) == _canon(want)
    assert GATHER_STATS["rows"] == len(got)
    # 16 groups over the shards: the smallest bucket a shard, far below
    # the capacity the program works at
    assert GATHER_STATS["capacity"] == n_dev * bucket_capacity(1) < \
        n_dev * bucket_capacity(rows // n_dev)


def test_spmd_gather_guard_skips_fetch():
    """A guard-tripped run raises (and retries / falls back) from phase
    1, which carries the guard bits: nothing of the result is fetched."""
    from auron_tpu import conf
    from auron_tpu.parallel.stage import GATHER_STATS, SpmdGuardTripped

    fact = make_fact(n=4000, keys=1)   # extreme skew: all rows one key
    fact_schema = from_arrow_schema(fact.schema)
    src = P.FFIReader(schema=fact_schema, resource_id="fact")
    ctx = _Ctx()
    ctx.exchanges["ex0"] = ShuffleJob(
        rid="ex0", child=P.Projection(
            child=src, exprs=(col("key"), col("amount")),
            names=("key", "amount")),
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("key"),)),
        schema=None)
    reread = P.Projection(
        child=P.IpcReader(schema=None, resource_id="ex0"),
        exprs=(col("key"),), names=("key",))
    mesh = data_mesh(8)
    GATHER_STATS.update(bytes=-1)
    with conf.scoped({"auron.spmd.exchange.quota.margin": 1.0}):
        with pytest.raises(SpmdGuardTripped):
            execute_plan_spmd(reread, ctx, mesh, {"fact": fact})
    assert GATHER_STATS["bytes"] == -1


def test_spmd_exchange_quota_skew_sweep():
    """VERDICT r4 weak #9: the quota margin had only ever met one
    synthetic skew.  Sweep realistic key distributions (zipf tails,
    hot-key mixtures, geometric) at capacity and assert the documented
    boundary EXACTLY: per-destination load within the bounded quota
    gives exact results; load past it trips the guard (never silent
    row loss).  Expected load is computed with the engine's own
    murmur3+pmod ids, so the prediction and the device routing agree
    bit-for-bit."""
    from auron_tpu.exprs import hashing as H
    from auron_tpu.parallel.exchange import bounded_quota

    n_dev, n = 8, 20_000
    rng = np.random.default_rng(11)
    dists = {
        "uniform": rng.integers(0, 4096, n),
        "zipf_1.1": rng.zipf(1.1, n) % 100_000,
        "zipf_1.5": rng.zipf(1.5, n) % 100_000,
        "geometric": rng.geometric(0.05, n),
        "hot90_10": np.where(rng.random(n) < 0.9, 7,
                             rng.integers(0, 4096, n)),
        "two_hot": np.where(rng.random(n) < 0.5, 3,
                            np.where(rng.random(n) < 0.5, 11,
                                     rng.integers(0, 4096, n))),
    }
    mesh = data_mesh(n_dev)
    quota = bounded_quota(n, n_dev)
    swept_both = {"overflow": 0, "fits": 0}
    for name, keys in dists.items():
        keys = keys.astype(np.int64)
        fact = pa.table({"key": keys,
                         "amount": rng.normal(0, 1, n)})
        # engine-identical routing prediction (vectorized jnp kernels)
        import jax.numpy as jnp
        uniq = np.unique(keys)
        pids = np.asarray(H.pmod(H.hash_int64(jnp.asarray(uniq), 42),
                                 n_dev))
        by_key = {int(k): int(p) for k, p in zip(uniq, pids)}
        load = np.zeros(n_dev, dtype=np.int64)
        for k in keys:
            load[by_key[int(k)]] += 1
        should_overflow = bool(load.max() > quota)

        src = P.FFIReader(schema=from_arrow_schema(fact.schema),
                          resource_id="fact")
        ctx = _Ctx()
        ctx.exchanges["ex"] = ShuffleJob(
            rid="ex", child=P.Projection(
                child=src, exprs=(col("key"), col("amount")),
                names=("key", "amount")),
            partitioning=P.Partitioning(mode="hash",
                                        num_partitions=n_dev,
                                        expressions=(col("key"),)),
            schema=None)
        final = P.Agg(
            child=P.IpcReader(schema=None, resource_id="ex"),
            exec_mode="single", grouping=(col("key"),),
            grouping_names=("key",),
            aggs=(AggExpr(fn="count", children=(col("amount"),),
                          return_type=I64),),
            agg_names=("c",))
        if should_overflow:
            swept_both["overflow"] += 1
            with pytest.raises(SpmdUnsupported, match="guard"):
                execute_plan_spmd(final, ctx, mesh, {"fact": fact})
        else:
            swept_both["fits"] += 1
            got = execute_plan_spmd(final, ctx, mesh,
                                    {"fact": fact}).to_pylist()
            assert sum(r["c"] for r in got) == n, name
            import collections
            exp = collections.Counter(int(k) for k in keys)
            assert {r["key"]: r["c"] for r in got} == dict(exp), name
    # the sweep must exercise BOTH sides of the boundary to mean
    # anything (hot-key shapes overflow, long tails fit)
    assert swept_both["overflow"] >= 1 and swept_both["fits"] >= 2, \
        swept_both


# ---------------------------------------------------------------------------
# the K=1 join lookup: a direct-address probe where one integer key's range
# fits the build side, chosen inside the program; the sorted-hash search
# everywhere else.  Every case: the serial engine's rows AND the counter.
# ---------------------------------------------------------------------------

I64_MIN, I64_MAX = np.iinfo(np.int64).min, np.iinfo(np.int64).max


def _probe_tables(case):
    """(fact, dim, left keys, right keys) of one case: `fk` probes `dk`."""
    rng = np.random.default_rng(27)
    n = 1200

    def fact_of(keys, mask=None):
        return pa.table({"fk": pa.array(keys, mask=mask),
                         "amount": rng.normal(10, 5, len(keys))})

    def dim_of(keys, mask=None):
        return pa.table({"dk": pa.array(keys, mask=mask),
                         "w": np.arange(len(keys), dtype=np.float64)})

    on = (("fk",), ("dk",))
    if case in ("int64", "filtered-build", "dead-probe-rows"):
        return (fact_of(rng.integers(0, 120, n).astype(np.int64)),
                dim_of(np.arange(100, dtype=np.int64)), *on)
    if case in ("int32", "int16"):
        t = np.dtype(case)
        return (fact_of(rng.integers(0, 120, n).astype(t)),
                dim_of(np.arange(100).astype(t)), *on)
    if case == "date":
        days = pa.array(rng.integers(10957, 11100, n).astype(np.int32),
                        type=pa.int32()).cast(pa.date32())
        dim_days = pa.array(np.arange(10957, 11057, dtype=np.int32),
                            type=pa.int32()).cast(pa.date32())
        return (pa.table({"fk": days, "amount": rng.normal(10, 5, n)}),
                pa.table({"dk": dim_days,
                          "w": np.arange(100, dtype=np.float64)}), *on)
    if case == "negative":
        return (fact_of(rng.integers(-80, 30, n).astype(np.int64)),
                dim_of(np.arange(-60, 14, dtype=np.int64)), *on)
    if case in ("int64-top", "int64-bottom", "int64-both-ends"):
        ends = np.array([I64_MIN, I64_MIN + 1, I64_MIN + 5, -1, 0, 1,
                         I64_MAX - 5, I64_MAX - 1, I64_MAX], dtype=np.int64)
        dim_keys = {"int64-top": np.arange(I64_MAX - 7, I64_MAX,
                                           dtype=np.int64),
                    "int64-bottom": np.arange(I64_MIN, I64_MIN + 7,
                                              dtype=np.int64),
                    "int64-both-ends": np.array([I64_MIN, I64_MAX],
                                                dtype=np.int64)}[case]
        return (fact_of(np.concatenate([rng.choice(ends, n), dim_keys])),
                dim_of(dim_keys), *on)
    if case == "null-keys":
        fk = rng.integers(0, 120, n).astype(np.int64)
        dk = np.arange(100, dtype=np.int64)
        return (fact_of(fk, mask=rng.random(n) < 0.1),
                dim_of(dk, mask=(dk % 7 == 3)), *on)
    if case == "empty-build":
        return (fact_of(rng.integers(0, 120, n).astype(np.int64)),
                dim_of(np.arange(0, dtype=np.int64)), *on)
    if case == "sparse":
        return (fact_of(rng.integers(0, 120, n).astype(np.int64) * 1000),
                dim_of(np.arange(100, dtype=np.int64) * 1000), *on)
    if case == "string":
        return (fact_of([f"k{i}" for i in rng.integers(0, 120, n)]),
                dim_of([f"k{i}" for i in range(100)]), *on)
    if case == "two-keys":
        fk = rng.integers(0, 120, n).astype(np.int64)
        dk = np.arange(100, dtype=np.int64)
        return (pa.table({"fk": fk, "fk2": fk % 5,
                          "amount": rng.normal(10, 5, n)}),
                pa.table({"dk": dk, "dk2": dk % 5,
                          "w": dk.astype(np.float64)}),
                ("fk", "fk2"), ("dk", "dk2"))
    if case == "duplicates":
        return (fact_of(rng.integers(0, 60, n).astype(np.int64)),
                dim_of(np.repeat(np.arange(40, dtype=np.int64), 2)), *on)
    raise AssertionError(case)


def _probe_join(case, join_type, fact, dim, left_keys, right_keys,
                colocated=False):
    """(stage plan, ctx, serial plan): a broadcast join, or a hash join
    of two sides hash-exchanged on the join keys."""
    ctx = _Ctx()
    fsrc = P.FFIReader(schema=from_arrow_schema(fact.schema),
                       resource_id="fact")
    dsrc = P.FFIReader(schema=from_arrow_schema(dim.schema),
                       resource_id="dim")
    probe_side, build_side = fsrc, dsrc
    if case == "filtered-build":
        build_side = P.Filter(child=dsrc, predicates=(E.BinaryExpr(
            left=col("w"), op="<", right=lit(77.0)),
            E.BinaryExpr(left=col("w"), op=">", right=lit(5.0)),))
    if case == "dead-probe-rows":
        probe_side = P.Filter(child=fsrc, predicates=(E.BinaryExpr(
            left=col("amount"), op=">", right=lit(9.0)),))
    on = JoinOn(left_keys=tuple(col(k) for k in left_keys),
                right_keys=tuple(col(k) for k in right_keys))
    if colocated:
        for rid, child, keys in (("exl", probe_side, left_keys),
                                 ("exr", build_side, right_keys)):
            ctx.exchanges[rid] = ShuffleJob(
                rid=rid, child=child, schema=None,
                partitioning=P.Partitioning(
                    mode="hash", num_partitions=8,
                    expressions=tuple(col(k) for k in keys)))
        stage = P.HashJoin(
            left=P.IpcReader(schema=None, resource_id="exl"),
            right=P.IpcReader(schema=None, resource_id="exr"),
            on=on, join_type=join_type, build_side="right")
        serial = P.HashJoin(left=probe_side, right=build_side, on=on,
                            join_type=join_type, build_side="right")
        return stage, ctx, serial
    ctx.broadcasts["bc"] = BroadcastJob(rid="bc", child=build_side,
                                        schema=None)
    stage = P.BroadcastJoin(
        left=probe_side, right=P.IpcReader(schema=None, resource_id="bc"),
        on=on, join_type=join_type, broadcast_side="right")
    serial = P.BroadcastJoin(left=probe_side, right=build_side, on=on,
                             join_type=join_type, broadcast_side="right")
    return stage, ctx, serial


def _run_probe_case(case, join_type, want, retries=0, colocated=False,
                    tables=None):
    from auron_tpu.runtime import retry
    fact, dim, lk, rk = tables or _probe_tables(case)
    stage, ctx, serial = _probe_join(case, join_type, fact, dim, lk, rk,
                                     colocated)
    srcs = {"fact": fact, "dim": dim}
    stats = {}
    before = retry.stats_snapshot()["retries"]
    got = execute_plan_spmd(stage, ctx, data_mesh(8), srcs, stats=stats)
    assert retry.stats_snapshot()["retries"] - before == retries
    assert _canon(got.to_pylist()) == _canon(_serial_reference(serial, srcs))
    # one K=1 join, and the probe it took; none once pair expansion runs
    assert list(stats["join_probes"].values()) == ([want] if want else [])
    return got


@pytest.mark.parametrize("case,join_type,want", [
    ("int64", "inner", "direct"),
    ("int32", "left", "direct"),
    ("int16", "inner", "direct"),
    ("date", "inner", "direct"),
    ("filtered-build", "inner", "direct"),
    ("negative", "left", "direct"),
    ("int64-top", "inner", "direct"),
    ("int64-bottom", "left", "direct"),
    ("int64-both-ends", "inner", "search"),   # a range of 2**64 - 1
    ("null-keys", "left", "direct"),
    ("null-keys", "left_anti", "direct"),
    ("dead-probe-rows", "inner", "direct"),
    ("empty-build", "left", "search"),        # no live key to address by
    ("sparse", "inner", "search"),            # range 99,001 >= capacity
    ("string", "inner", "search"),
    ("two-keys", "inner", "search"),
    ("duplicates", "left_semi", "direct"),
    ("duplicates", "left_anti", "direct"),
    ("duplicates", "existence", "direct"),
])
def test_join_probe_is_chosen_from_the_build_keys(case, join_type, want):
    got = _run_probe_case(case, join_type, want)
    assert got.num_rows > 0


@pytest.mark.parametrize("join_type", ["inner", "left"])
def test_direct_probe_keeps_the_duplicate_key_retry(join_type):
    """A duplicate build key on the direct side raises the same retryable
    guard as on the search side: one retry, then K-way pair expansion
    (which has no K=1 probe to count)."""
    from auron_tpu.parallel import stage as S
    S._MATCH_FACTOR_HINT.clear()
    _run_probe_case("duplicates", join_type, None, retries=1)
    assert list(S._MATCH_FACTOR_HINT.values()) == [4]


@pytest.mark.parametrize("join_type", ["left", "full", "right"])
def test_direct_probe_under_a_colocated_hash_join(join_type):
    """Each device probes its own build shard: 100 dense keys spread by
    hash over 8 devices still span less than a shard's capacity."""
    _run_probe_case("int64", join_type, "direct", colocated=True)


def test_devices_of_a_mesh_choose_for_their_own_shard():
    """One far key makes one device's build shard sparse: that device
    searches, the seven others address directly, the answer is one."""
    from auron_tpu.columnar.batch import DeviceColumn
    from auron_tpu.exprs import hashing as H
    import jax.numpy as jnp
    far = 1 << 40
    keys = np.concatenate([np.arange(100, dtype=np.int64),
                           np.arange(far, far + 64, dtype=np.int64)])
    pid = np.asarray(H.pmod(H.hash_columns(
        [DeviceColumn(I64, jnp.asarray(keys), jnp.ones(len(keys), bool))],
        seed=42), 8))
    lonely = next(k for k, p in zip(keys[100:], pid[100:]) if p == 3)
    dk = np.append(np.arange(100, dtype=np.int64), lonely)
    assert set(pid[:100]) == set(range(8))    # every shard holds dense keys
    rng = np.random.default_rng(5)
    fact = pa.table({"fk": rng.choice(np.append(dk, [100, 101, far]), 1200),
                     "amount": rng.normal(10, 5, 1200)})
    dim = pa.table({"dk": dk, "w": np.arange(len(dk), dtype=np.float64)})
    _run_probe_case("mixed", "full", "direct 7/8", colocated=True,
                    tables=(fact, dim, ("fk",), ("dk",)))


def _lowered_join_text(case, n_dev=8, join_type="inner"):
    """The lowered stage program of one case's broadcast join."""
    from stage_spy import spied_program
    fact, dim, lk, rk = _probe_tables(case)
    stage, ctx, _serial = _probe_join(case, join_type, fact, dim, lk, rk)
    program, inputs = spied_program(stage, ctx, data_mesh(n_dev),
                                    {"fact": fact, "dim": dim})
    return program.lower(inputs).as_text()


@pytest.mark.parametrize("case", ["string", "two-keys"])
def test_a_join_that_does_not_qualify_traces_no_choice(case):
    """A string key, a composite key: no conditional, no flag output
    (test_one_program.py pins the one-device program's text)."""
    text = _lowered_join_text(case)
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    one = _lowered_join_text(case, n_dev=1)
    assert "stablehlo.case" not in one and "stablehlo.if" not in one
    assert "stablehlo.case" in _lowered_join_text("int64")
