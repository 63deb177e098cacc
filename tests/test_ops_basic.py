"""Operator tests: project/filter/limit/sort/agg incl. tiny-memory spill
fuzzing (SURVEY §4: the reference's fuzztest_external_sorting pattern)."""

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.columnar.batch import Batch
from auron_tpu.ir import expr as E
from auron_tpu.ir.expr import AggExpr, SortExpr, col, lit
from auron_tpu.ir.schema import DataType, Field, Schema, from_arrow_schema
from auron_tpu.memmgr.manager import reset_manager
from auron_tpu.ops.base import TaskContext
from auron_tpu.ops.basic import (
    CoalesceBatchesExec, ExpandExec, FilterExec, LimitExec, MemoryScanExec,
    ProjectExec, RenameColumnsExec, UnionExec,
)
from auron_tpu.ops.sort import SortExec
from auron_tpu.ops.agg.exec import AggExec


def collect(op, ctx=None):
    ctx = ctx or TaskContext()
    out = [b.to_arrow() for b in op.execute_with_metrics(ctx)]
    if not out:
        return []
    return pa.Table.from_batches(out).to_pylist()


def scan_of(rows, schema=None, chunk=50):
    rb = pa.Table.from_pylist(rows, schema=schema)
    batches = [Batch.from_arrow(b)
               for b in rb.to_batches(max_chunksize=chunk)] if rows else []
    s = from_arrow_schema(rb.schema)
    return MemoryScanExec(s, batches)


@pytest.fixture(autouse=True)
def fresh_memmgr():
    from auron_tpu.config import conf
    reset_manager()
    yield
    conf.unset("auron.memory.spill.min.trigger.bytes")
    reset_manager()


def test_project_filter_limit():
    rows = [{"x": i, "y": float(i) / 2} for i in range(200)]
    scan = scan_of(rows)
    filt = FilterExec(scan, [E.BinaryExpr(left=col("x"), op=">=", right=lit(100))])
    proj = ProjectExec(filt, [E.BinaryExpr(left=col("x"), op="*", right=lit(2)),
                              col("y")], ["x2", "y"])
    lim = LimitExec(proj, limit=5, offset=3)
    out = collect(lim)
    assert [r["x2"] for r in out] == [206, 208, 210, 212, 214]


def test_union_rename_expand_coalesce():
    rows = [{"a": i} for i in range(10)]
    u = UnionExec([scan_of(rows), scan_of(rows)], scan_of(rows).schema)
    out = collect(u)
    assert len(out) == 20
    rn = RenameColumnsExec(scan_of(rows), ["zz"])
    assert collect(rn)[0] == {"zz": 0}
    ex = ExpandExec(scan_of(rows),
                    [(col("a"), lit(1)), (col("a"), lit(2))],
                    ["a", "tag"])
    out = collect(ex)
    assert len(out) == 20
    assert sorted({r["tag"] for r in out}) == [1, 2]
    co = CoalesceBatchesExec(scan_of(rows, chunk=3), target=6)
    batches = list(co.execute_with_metrics(TaskContext()))
    assert sum(b.num_rows for b in batches) == 10
    assert batches[0].num_rows >= 6


def test_sort_basic():
    rng = np.random.default_rng(1)
    vals = rng.integers(-1000, 1000, 500)
    rows = [{"k": int(v), "tag": i} for i, v in enumerate(vals)]
    # make some nulls
    for i in range(0, 500, 17):
        rows[i]["k"] = None
    s = SortExec(scan_of(rows),
                 [SortExpr(child=col("k"), asc=True, nulls_first=False)])
    out = collect(s)
    ks = [r["k"] for r in out]
    non_null = [k for k in ks if k is not None]
    assert non_null == sorted(non_null)
    assert ks[len(non_null):] == [None] * (500 - len(non_null))


def test_sort_multi_key_desc_strings():
    rows = [{"s": w, "v": i % 3} for i, w in enumerate(
        ["pear", "apple", "fig", "apple", "banana", "fig", None, "apple"])]
    s = SortExec(scan_of(rows), [
        SortExpr(child=col("s"), asc=True, nulls_first=True),
        SortExpr(child=col("v"), asc=False, nulls_first=True),
    ])
    out = collect(s)
    exp = sorted(rows, key=lambda r: (r["s"] is not None, r["s"] or "",
                                      -(r["v"])))
    assert [(r["s"], r["v"]) for r in out] == [(r["s"], r["v"]) for r in exp]


def test_sort_fetch_limit():
    rows = [{"k": i % 100, "i": i} for i in range(1000)]
    s = SortExec(scan_of(rows), [SortExpr(child=col("k"), asc=True)],
                 fetch_limit=7, fetch_offset=0)
    out = collect(s)
    assert [r["k"] for r in out] == [0] * 7


@pytest.mark.parametrize("limit,offset", [(7, 0), (7, 3), (60, 30),
                                          (10, 120), (500, 140)])
@pytest.mark.parametrize("through", ["limit", "sort-fetch"])
def test_limit_and_sort_fetch_share_one_cut(through, limit, offset):
    """LimitExec and a sort's fetch are one body (`cut_batches`): the
    same rows from either, and one `limit.cut` span a batch handed in,
    closed before the batch is handed on."""
    from auron_tpu.runtime import tracing
    rows = [{"k": i} for i in range(150)]       # three batches of 50
    if through == "limit":
        op = LimitExec(scan_of(rows), limit, offset)
    else:
        op = SortExec(scan_of(rows), [SortExpr(child=col("k"))],
                      fetch_limit=limit, fetch_offset=offset)
    rec = tracing.TraceRecorder("cut", max_events=1000)
    with tracing.trace_scope(recorder=rec):
        out = collect(op)
    assert [r["k"] for r in out] == list(range(150))[offset:offset + limit]
    cuts = [s for s in rec.snapshot() if s.name == "limit.cut"]
    assert cuts and sum(s.args["rows_out"] for s in cuts) == len(out)
    assert all(s.args["rows_out"] <= s.args["rows_in"] for s in cuts)
    for a, b in zip(cuts, cuts[1:]):
        assert a.t0_ns + a.dur_ns <= b.t0_ns


def test_external_sort_spill_fuzz():
    """Tiny memory budget forces spills; result must equal full sort."""
    from auron_tpu.config import conf
    conf.set("auron.memory.spill.min.trigger.bytes", 10_000)
    reset_manager(budget_bytes=60_000)
    rng = np.random.default_rng(7)
    n = 5000
    vals = rng.integers(-10**6, 10**6, n)
    rows = [{"k": int(v), "i": i} for i, v in enumerate(vals)]
    s = SortExec(scan_of(rows, chunk=500),
                 [SortExpr(child=col("k"), asc=True)])
    out = collect(s)
    assert len(out) == n
    assert s.metrics.get("mem_spill_count") > 0, "expected spills"
    ks = [r["k"] for r in out]
    assert ks == sorted(vals.tolist())


def sum_agg(name="s", child="v", dtype=DataType.int64()):
    return AggExpr(fn="sum", children=(col(child),), return_type=dtype)


@pytest.mark.slow
def test_agg_single_mode():
    # PR 10 tier-1 re-split: 12.2s measured — nightly slow lane (the
    # partial/final pipeline test + the TPC-DS subset keep single-agg
    # kernels covered in tier-1)
    rows = [{"k": i % 7, "v": i} for i in range(1000)]
    a = AggExec(scan_of(rows), "single", [col("k")], ["k"],
                [AggExpr(fn="sum", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="count", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="min", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="max", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="avg", children=(col("v"),),
                         return_type=DataType.float64())],
                ["s", "c", "mn", "mx", "av"])
    out = {r["k"]: r for r in collect(a)}
    assert len(out) == 7
    for k in range(7):
        vs = [i for i in range(1000) if i % 7 == k]
        assert out[k]["s"] == sum(vs)
        assert out[k]["c"] == len(vs)
        assert out[k]["mn"] == min(vs)
        assert out[k]["mx"] == max(vs)
        assert out[k]["av"] == pytest.approx(sum(vs) / len(vs))


@pytest.mark.slow   # PR 18 tier-1 re-split (10.3s; partial/final agg
# rides every tier-1 corpus query)
def test_agg_partial_final_pipeline():
    rows = [{"k": i % 5, "v": i} for i in range(500)]
    partial = AggExec(scan_of(rows), "partial", [col("k")], ["k"],
                      [AggExpr(fn="sum", children=(col("v"),),
                               return_type=DataType.int64()),
                       AggExpr(fn="avg", children=(col("v"),),
                               return_type=DataType.float64())],
                      ["s", "av"])
    final = AggExec(partial, "final", [col("k")], ["k"],
                    [AggExpr(fn="sum", children=(col("v"),),
                             return_type=DataType.int64()),
                     AggExpr(fn="avg", children=(col("v"),),
                             return_type=DataType.float64())],
                    ["s", "av"])
    out = {r["k"]: r for r in collect(final)}
    for k in range(5):
        vs = [i for i in range(500) if i % 5 == k]
        assert out[k]["s"] == sum(vs)
        assert out[k]["av"] == pytest.approx(sum(vs) / len(vs))


def test_agg_nulls_and_global():
    rows = [{"k": None if i % 4 == 0 else i % 2, "v": None if i % 3 == 0
             else i} for i in range(100)]
    a = AggExec(scan_of(rows), "single", [col("k")], ["k"],
                [AggExpr(fn="sum", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="count", children=(col("v"),),
                         return_type=DataType.int64())],
                ["s", "c"])
    out = {r["k"]: r for r in collect(a)}
    assert set(out.keys()) == {None, 0, 1}   # null is its own group
    import collections
    exp = collections.defaultdict(list)
    for r in rows:
        if r["v"] is not None:
            exp[r["k"]].append(r["v"])
    for k in out:
        assert out[k]["s"] == sum(exp[k])
        assert out[k]["c"] == len(exp[k])
    # global agg (no grouping)
    g = AggExec(scan_of(rows), "single", [], [],
                [AggExpr(fn="count", children=(), return_type=DataType.int64()),
                 AggExpr(fn="sum", children=(col("v"),),
                         return_type=DataType.int64())],
                ["cnt", "s"])
    [row] = collect(g)
    assert row["cnt"] == 100
    assert row["s"] == sum(v for vs in exp.values() for v in vs)


def test_agg_global_empty_input():
    empty = scan_of([], schema=pa.schema([("v", pa.int64())]))
    g = AggExec(empty, "single", [], [],
                [AggExpr(fn="count", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="sum", children=(col("v"),),
                         return_type=DataType.int64())],
                ["c", "s"])
    [row] = collect(g)
    assert row["c"] == 0
    assert row["s"] is None


def test_agg_string_keys_and_first():
    rows = [{"k": w, "v": i} for i, w in enumerate(
        ["a", "b", "a", None, "c", "b", "a", None])]
    a = AggExec(scan_of(rows), "single", [col("k")], ["k"],
                [AggExpr(fn="first", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="count", children=(col("v"),),
                         return_type=DataType.int64())],
                ["f", "c"])
    out = {r["k"]: r for r in collect(a)}
    assert out["a"]["c"] == 3 and out["a"]["f"] == 0
    assert out[None]["c"] == 2 and out[None]["f"] == 3
    assert out["b"]["f"] == 1


def test_agg_collect_and_mixed_device_host():
    """Mixed device (sum) + host (collect_list) aggs in one plan (review
    regression)."""
    rows = [{"k": i % 3, "v": i} for i in range(30)]
    a = AggExec(scan_of(rows), "single", [col("k")], ["k"],
                [AggExpr(fn="sum", children=(col("v"),),
                         return_type=DataType.int64()),
                 AggExpr(fn="collect_list", children=(col("v"),),
                         return_type=DataType.list_(DataType.int64()))],
                ["s", "lst"])
    out = {r["k"]: r for r in collect(a)}
    for k in range(3):
        vs = [i for i in range(30) if i % 3 == k]
        assert out[k]["s"] == sum(vs)
        assert sorted(out[k]["lst"]) == vs


def test_agg_min_max_strings():
    rows = [{"k": i % 2, "s": w} for i, w in enumerate(
        ["pear", "apple", "fig", None, "banana", "zed"])]
    a = AggExec(scan_of(rows), "single", [col("k")], ["k"],
                [AggExpr(fn="min", children=(col("s"),),
                         return_type=DataType.string()),
                 AggExpr(fn="max", children=(col("s"),),
                         return_type=DataType.string())],
                ["mn", "mx"])
    out = {r["k"]: r for r in collect(a)}
    assert out[0] == {"k": 0, "mn": "banana", "mx": "pear"}
    assert out[1] == {"k": 1, "mn": "apple", "mx": "zed"}


def test_agg_spill_fuzz():
    from auron_tpu.config import conf
    conf.set("auron.memory.spill.min.trigger.bytes", 10_000)
    mgr = reset_manager(budget_bytes=60_000)
    rows = [{"k": i % 1000, "v": i} for i in range(20000)]
    a = AggExec(scan_of(rows, chunk=2000), "single", [col("k")], ["k"],
                [AggExpr(fn="sum", children=(col("v"),),
                         return_type=DataType.int64())], ["s"])
    out = {r["k"]: r["s"] for r in collect(a)}
    assert mgr.num_spills >= 2, "budget must force multiple spilled runs"
    assert len(out) == 1000
    # every group exact: the streaming k-way spill merge must reassemble
    # groups split across runs (incl. the carried boundary group)
    exp = {}
    for i in range(20000):
        exp[i % 1000] = exp.get(i % 1000, 0) + i
    assert out == exp
