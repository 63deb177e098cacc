"""End-to-end differential tests: plan IR -> device engine vs reference
interpreter (the checkSparkAnswerAndOperator analogue, SURVEY §4)."""

import math
import os
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import reference_engine
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir import serde as ir_serde
from auron_tpu.ir.expr import AggExpr, SortExpr, col, lit
from auron_tpu.ir.schema import (DataType, Field, Schema, from_arrow_schema)
from auron_tpu.runtime.executor import execute_plan, execute_task_bytes
from auron_tpu.runtime.resources import ResourceRegistry


def canon(rows):
    def norm(v):
        if isinstance(v, float):
            if v != v:
                return ("nan",)
            return round(v, 9)
        return v
    return sorted([tuple((k, (v is None), str(norm(v)))
                         for k, v in sorted(r.items()))
                   for r in rows])


def check_plan(plan, resources=None, partition_id=0):
    res = resources or ResourceRegistry()
    got = execute_plan(plan, partition_id=partition_id,
                       resources=res).to_pylist()
    exp = reference_engine.run_plan(plan, res, partition_id=partition_id)
    assert canon(got) == canon(exp), \
        f"\nengine={got[:5]}...\noracle={exp[:5]}..."
    return got


def ffi_source(rows, schema=None, name="src", res=None, chunk=100):
    res = res or ResourceRegistry()
    t = pa.Table.from_pylist(rows, schema=schema)
    res.put(name, t.to_batches(max_chunksize=chunk) if rows else [])
    return P.FFIReader(schema=from_arrow_schema(t.schema),
                       resource_id=name), res


def test_scan_filter_project_agg_sort():
    rng = np.random.default_rng(11)
    rows = [{"k": int(rng.integers(0, 20)), "v": float(rng.normal()),
             "s": ["red", "green", "blue"][int(rng.integers(0, 3))]}
            for _ in range(2000)]
    src, res = ffi_source(rows)
    plan = P.Sort(
        child=P.Agg(
            child=P.Filter(child=src, predicates=(
                E.BinaryExpr(left=col("v"), op=">", right=lit(-1.0)),)),
            exec_mode="single",
            grouping=(col("k"), col("s")), grouping_names=("k", "s"),
            aggs=(AggExpr(fn="count", children=(col("v"),),
                          return_type=DataType.int64()),
                  AggExpr(fn="avg", children=(col("v"),),
                          return_type=DataType.float64())),
            agg_names=("c", "av")),
        sort_exprs=(SortExpr(child=col("k")), SortExpr(child=col("s"))))
    check_plan(plan, res)


def test_parquet_scan_pruning(tmp_path):
    rows = [{"id": i, "cat": i % 5, "name": f"item{i}"} for i in range(5000)]
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.Table.from_pylist(rows), path, row_group_size=500)
    schema = from_arrow_schema(pq.read_schema(path))
    plan = P.Filter(
        child=P.ParquetScan(
            schema=schema, file_groups=(P.FileGroup(paths=(path,)),),
            projection=(0, 1, 2),
            predicate=E.BinaryExpr(left=col("id"), op="<", right=lit(750))),
        predicates=(E.BinaryExpr(left=col("id"), op="<", right=lit(750)),))
    got = check_plan(plan)
    assert len(got) == 750
    # pruning metric: only 2 of 10 row groups should be read
    from auron_tpu.runtime.executor import execute_plan as ep
    r = ep(plan)
    scan_metrics = r.metrics.children[0].children[0] \
        if r.metrics.children[0].children else r.metrics.children[0]
    # find the scan node metrics anywhere in the tree
    def find(m):
        if "parquet_row_groups_read" in m.values:
            return m
        for c in m.children:
            f = find(c)
            if f:
                return f
        return None
    m = find(r.metrics)
    assert m is not None and m.get("parquet_row_groups_read") == 2
    assert m.get("parquet_row_groups_pruned") == 8


def test_join_plans():
    rng = np.random.default_rng(12)
    left = [{"lk": int(rng.integers(0, 30)), "lv": i} for i in range(400)]
    right = [{"rk": int(rng.integers(0, 30)), "rv": i} for i in range(300)]
    res = ResourceRegistry()
    lsrc, _ = ffi_source(left, name="L", res=res)
    rsrc, _ = ffi_source(right, name="R", res=res)
    on = P.JoinOn(left_keys=(col("lk"),), right_keys=(col("rk"),))
    for jt in ("inner", "left", "full", "left_semi", "left_anti",
               "existence"):
        plan = P.HashJoin(left=lsrc, right=rsrc, on=on, join_type=jt,
                          build_side="right")
        check_plan(plan, res)
    # the SMJ IR node's contract is key-sorted children (the wire plan
    # carries the SortExecs explicitly, auron.proto SMJ semantics)
    plan = P.SortMergeJoin(
        left=P.Sort(child=lsrc, sort_exprs=(SortExpr(child=col("lk")),)),
        right=P.Sort(child=rsrc, sort_exprs=(SortExpr(child=col("rk")),)),
        on=on, join_type="inner")
    check_plan(plan, res)
    plan = P.BroadcastJoin(left=lsrc, right=rsrc, on=on, join_type="inner",
                           broadcast_side="right")
    check_plan(plan, res)


def test_window_plan():
    rng = np.random.default_rng(13)
    rows = [{"g": int(rng.integers(0, 8)), "o": int(rng.integers(0, 50)),
             "v": float(rng.normal())} for _ in range(600)]
    src, res = ffi_source(rows)
    plan = P.Window(
        child=src,
        window_funcs=(
            P.WindowFuncCall(fn="row_number", return_type=DataType.int64(),
                             name="rn"),
            P.WindowFuncCall(fn="rank", return_type=DataType.int64(),
                             name="rk"),
            P.WindowFuncCall(fn="dense_rank", return_type=DataType.int64(),
                             name="dr"),
            P.WindowFuncCall(fn="lag", args=(col("v"), lit(1)),
                             return_type=DataType.float64(), name="lg"),
            P.WindowFuncCall(fn="agg",
                             agg=AggExpr(fn="sum", children=(col("v"),),
                                         return_type=DataType.float64()),
                             return_type=DataType.float64(), name="rs"),
        ),
        partition_by=(col("g"),),
        order_by=(SortExpr(child=col("o")),))
    got = check_plan(plan, res)
    assert {"rn", "rk", "dr", "lg", "rs"} <= set(got[0].keys())


@pytest.mark.parametrize("fn", ["min", "max"])
@pytest.mark.parametrize("kind", ["int", "float"])
def test_window_running_extremes(fn, kind):
    """Running MIN/MAX over an ordered window — the segmented scan of
    ops/segments.py restarting at every partition — with nulls, against
    the reference engine."""
    rng = np.random.default_rng(31)
    dt = DataType.int64() if kind == "int" else DataType.float64()

    def value():
        if rng.random() < 0.15:
            return None
        return int(rng.integers(-500, 500)) if kind == "int" \
            else float(rng.normal(0, 100))
    rows = [{"g": int(rng.integers(0, 9)), "o": int(rng.integers(0, 40)),
             "v": value()} for _ in range(700)]
    src, res = ffi_source(rows)
    plan = P.Window(
        child=src,
        window_funcs=(P.WindowFuncCall(
            fn="agg", agg=AggExpr(fn=fn, children=(col("v"),),
                                  return_type=dt),
            return_type=dt, name="run"),),
        partition_by=(col("g"),),
        order_by=(SortExpr(child=col("o")),))
    got = check_plan(plan, res)
    assert any(r["run"] is not None for r in got)


def test_window_spill_tiny_budget():
    """Window staging must spill as sorted runs and reassemble whole
    partitions from the run merge (VERDICT r1: window had a non-spillable
    consumer)."""
    from auron_tpu.config import conf
    from auron_tpu.memmgr.manager import reset_manager
    rng = np.random.default_rng(14)
    rows = [{"g": int(rng.integers(0, 12)), "o": int(rng.integers(0, 50)),
             "v": float(rng.normal())} for _ in range(4000)]
    src, res = ffi_source(rows, chunk=256)
    plan = P.Window(
        child=src,
        window_funcs=(
            P.WindowFuncCall(fn="row_number", return_type=DataType.int64(),
                             name="rn"),
            P.WindowFuncCall(fn="agg",
                             agg=AggExpr(fn="sum", children=(col("v"),),
                                         return_type=DataType.float64()),
                             return_type=DataType.float64(), name="rs"),
        ),
        partition_by=(col("g"),),
        order_by=(SortExpr(child=col("o")),))
    mgr = reset_manager(budget_bytes=1)
    try:
        with conf.scoped({"auron.memory.spill.min.trigger.bytes": 1}):
            got = execute_plan(plan, resources=res).to_pylist()
            assert mgr.num_spills > 0
    finally:
        reset_manager()
    exp = reference_engine.run_plan(plan, res)
    assert canon(got) == canon(exp)


def test_window_group_limit():
    rows = [{"g": i % 4, "o": i, "v": i} for i in range(100)]
    src, res = ffi_source(rows)
    plan = P.Window(child=src, window_funcs=(),
                    partition_by=(col("g"),),
                    order_by=(SortExpr(child=col("o")),),
                    group_limit=P.WindowGroupLimit(k=3,
                                                   rank_fn="row_number"))
    got = check_plan(plan, res)
    assert len(got) == 12


def test_generate_plan():
    rows = [{"id": i, "xs": list(range(i % 4))} for i in range(50)]
    t = pa.Table.from_pylist(rows)
    res = ResourceRegistry()
    src, _ = ffi_source(rows, name="g", res=res)
    plan = P.Generate(child=src, generator="explode", args=(col("xs"),),
                      generator_output_names=("x",),
                      generator_output_types=(DataType.int64(),),
                      required_child_output=(0,), outer=False)
    got = check_plan(plan, res)
    assert all("x" in r and "id" in r for r in got)
    plan_outer = P.Generate(child=src, generator="posexplode",
                            args=(col("xs"),),
                            generator_output_names=("pos", "x"),
                            generator_output_types=(DataType.int32(),
                                                    DataType.int64()),
                            required_child_output=(0,), outer=True)
    check_plan(plan_outer, res)


def test_expand_union_limit_plan():
    rows = [{"a": i, "b": i * 2} for i in range(100)]
    res = ResourceRegistry()
    src, _ = ffi_source(rows, name="u", res=res)
    expand = P.Expand(child=src,
                      projections=((col("a"), lit(0)), (col("b"), lit(1))),
                      names=("val", "tag"))
    u = P.Union(inputs=(P.UnionInput(child=expand),
                        P.UnionInput(child=expand)),
                schema=Schema.of(Field("val", DataType.int64()),
                                 Field("tag", DataType.int32())),
                num_partitions=1)
    plan = P.Limit(child=u, limit=250, offset=10)
    got = execute_plan(plan, resources=res).to_pylist()
    assert len(got) == 250


def test_task_bytes_roundtrip_execution():
    rows = [{"x": i} for i in range(10)]
    src, res = ffi_source(rows, name="tb")
    plan = P.Projection(child=src,
                        exprs=(E.BinaryExpr(left=col("x"), op="+",
                                            right=lit(1)),),
                        names=("y",))
    td = P.TaskDefinition(plan=plan, stage_id=1, partition_id=0)
    blob = ir_serde.serialize(td)
    result = execute_task_bytes(blob, resources=res)
    assert [r["y"] for r in result.to_pylist()] == list(range(1, 11))
    assert result.metrics.get("output_rows") == 10


def test_shuffle_write_read_roundtrip(tmp_path):
    """Map side writes data+index; reduce side reads each partition back
    (the AuronShuffleWriterBase.nativeShuffleWrite contract)."""
    import struct
    rows = [{"k": i % 7, "v": i} for i in range(500)]
    src, res = ffi_source(rows, name="sh")
    data_f = str(tmp_path / "shuffle.data")
    index_f = str(tmp_path / "shuffle.index")
    plan = P.ShuffleWriter(
        child=src,
        partitioning=P.Partitioning(mode="hash", num_partitions=4,
                                    expressions=(col("k"),)),
        output_data_file=data_f, output_index_file=index_f)
    stats = execute_plan(plan, resources=res).to_pylist()
    assert sum(r["rows"] for r in stats) == 500
    offsets = struct.unpack("<5q", open(index_f, "rb").read())
    assert offsets[4] == os.path.getsize(data_f)
    # read back every partition via IpcReader
    seen = []
    data = open(data_f, "rb").read()
    for pid in range(4):
        blob = data[offsets[pid]:offsets[pid + 1]]
        res.put(f"part{pid}", blob)
        rd = P.IpcReader(schema=from_arrow_schema(
            pa.Table.from_pylist(rows).schema), resource_id=f"part{pid}")
        part_rows = execute_plan(rd, resources=res).to_pylist()
        # partition assignment must follow spark murmur3(seed 42) pmod
        from auron_tpu.native.bindings import murmur3_32
        for r in part_rows:
            h = murmur3_32(int(r["k"]).to_bytes(8, "little", signed=True), 42)
            assert h % 4 == pid or (h % 4) + 4 == pid
        seen.extend(part_rows)
    assert canon(seen) == canon(rows)


def test_rss_shuffle_and_in_process_service():
    from auron_tpu.ops.shuffle.writer import InProcessShuffleService
    rows = [{"k": i % 5, "v": i} for i in range(300)]
    svc = InProcessShuffleService()
    res = ResourceRegistry()
    src, _ = ffi_source(rows, name="rss_src", res=res)
    res.put("rss0", svc.rss_writer("s1", map_id=0))
    plan = P.RssShuffleWriter(
        child=src,
        partitioning=P.Partitioning(mode="round_robin", num_partitions=3),
        rss_resource_id="rss0")
    stats = execute_plan(plan, resources=res).to_pylist()
    assert sum(r["rows"] for r in stats) == 300
    got = []
    for pid in range(3):
        blocks = svc.reduce_blocks("s1", pid)
        res.put(f"red{pid}", blocks)
        rd = P.IpcReader(schema=from_arrow_schema(
            pa.Table.from_pylist(rows).schema), resource_id=f"red{pid}")
        got.extend(execute_plan(rd, resources=res).to_pylist())
    assert canon(got) == canon(rows)


def test_ipc_writer_broadcast_path():
    rows = [{"x": i} for i in range(20)]
    src, res = ffi_source(rows, name="bsrc")
    w = P.IpcWriter(child=src, resource_id="bcast")
    execute_plan(w, resources=res)
    rd = P.IpcReader(schema=Schema.of(Field("x", DataType.int64())),
                     resource_id="bcast")
    got = execute_plan(rd, resources=res).to_pylist()
    assert [r["x"] for r in got] == list(range(20))


def test_window_range_frame_semantics():
    """Spark default RANGE frame: peer rows (tied order keys) share the
    frame (review regression)."""
    rows = [{"g": 1, "k": 1, "v": 10.0}, {"g": 1, "k": 1, "v": 20.0},
            {"g": 1, "k": 2, "v": 5.0}]
    src, res = ffi_source(rows, name="wrf")
    plan = P.Window(
        child=src,
        window_funcs=(P.WindowFuncCall(
            fn="agg", agg=AggExpr(fn="sum", children=(col("v"),),
                                  return_type=DataType.float64()),
            return_type=DataType.float64(), name="s"),
            P.WindowFuncCall(fn="last_value", args=(col("v"),),
                             return_type=DataType.float64(), name="lv"),
            P.WindowFuncCall(fn="lead", args=(col("v"), lit(1), lit(-99.0)),
                             return_type=DataType.float64(), name="ld")),
        partition_by=(col("g"),), order_by=(SortExpr(child=col("k")),))
    got = check_plan(plan, res)
    by_v = {r["v"]: r for r in got}
    assert by_v[10.0]["s"] == 30.0 and by_v[20.0]["s"] == 30.0
    assert by_v[5.0]["s"] == 35.0
    assert by_v[10.0]["lv"] == 20.0  # last peer, not current row
    assert by_v[5.0]["ld"] == -99.0  # lead default at partition edge


def test_scan_extra_partitions_empty(tmp_path):
    rows = [{"x": i} for i in range(10)]
    path = str(tmp_path / "one.parquet")
    pq.write_table(pa.Table.from_pylist(rows), path)
    schema = from_arrow_schema(pq.read_schema(path))
    plan = P.ParquetScan(schema=schema,
                         file_groups=(P.FileGroup(paths=(path,)),))
    assert len(execute_plan(plan, partition_id=0).to_pylist()) == 10
    # partition 1 has no file group: must be empty, not a duplicate
    assert execute_plan(plan, partition_id=1).to_pylist() == []
