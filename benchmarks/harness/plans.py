"""The idioms Spark's planner emits, as foreign plan nodes: what a query's
`build_plan` is written in.  Copied from `auron_tpu/it/queries.py` so that
the plans the benchmark times stay the benchmark's."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from auron_tpu.frontend.foreign import (ForeignExpr, ForeignNode, falias,
                                        fcall, fcol, flit)
from auron_tpu.ir.schema import DataType, Field, Schema

__all__ = ["I32", "I64", "F64", "STR", "DataType", "Field", "Schema",
           "falias", "fcall", "fcol", "flit", "so", "agg", "ffilter",
           "fproject", "bhj", "two_phase_agg", "take_ordered"]

I32 = DataType.int32()
I64 = DataType.int64()
F64 = DataType.float64()
STR = DataType.string()

_BOTH_SIDES = ("Inner", "LeftOuter", "RightOuter", "FullOuter")


def so(e: ForeignExpr, asc: bool = True,
       nulls_first: Optional[bool] = None) -> ForeignExpr:
    return ForeignExpr("SortOrder", children=(e,),
                       attrs={"asc": asc,
                              "nulls_first": asc if nulls_first is None
                              else nulls_first})


def agg(fn: str, child: Optional[ForeignExpr],
        dtype: DataType) -> ForeignExpr:
    children = (child,) if child is not None else ()
    return ForeignExpr("AggregateExpression",
                       children=(fcall(fn, *children, dtype=dtype),),
                       attrs={"distinct": False})


def ffilter(child: ForeignNode, cond: ForeignExpr) -> ForeignNode:
    return ForeignNode("FilterExec", children=(child,), output=child.output,
                       attrs={"condition": cond})


def fproject(child: ForeignNode, exprs: Sequence[ForeignExpr],
             out: Schema) -> ForeignNode:
    return ForeignNode("ProjectExec", children=(child,), output=out,
                       attrs={"project_list": list(exprs)})


def bhj(probe: ForeignNode, build: ForeignNode, left_key: ForeignExpr,
        right_key: ForeignExpr, join_type: str = "Inner") -> ForeignNode:
    bx = ForeignNode("BroadcastExchangeExec", children=(build,),
                     output=build.output)
    out = probe.output.concat(build.output) \
        if join_type in _BOTH_SIDES else probe.output
    return ForeignNode(
        "BroadcastHashJoinExec", children=(probe, bx), output=out,
        attrs={"left_keys": [left_key], "right_keys": [right_key],
               "join_type": join_type, "build_side": "right"})


def two_phase_agg(child: ForeignNode, grouping: Sequence[ForeignExpr],
                  group_fields: Sequence[Field],
                  aggs: Sequence[Tuple[str, ForeignExpr, Field]],
                  n_parts: int = 4) -> ForeignNode:
    """partial HashAggregate -> hash ShuffleExchange -> final HashAggregate
    (the shape of every TPC-DS group-by stage)."""
    agg_exprs = [a for _, a, _ in aggs]
    agg_names = [n for n, _, _ in aggs]
    state_fields = list(group_fields)
    for name, a, out_f in aggs:
        fn = a.children[0].name
        if fn == "Average":
            state_fields += [Field(f"{name}#sum", F64),
                             Field(f"{name}#count", I64)]
        elif fn == "Count":
            state_fields.append(Field(f"{name}#count", I64))
        else:
            state_fields.append(Field(f"{name}#{fn.lower()}", out_f.dtype))
    partial = ForeignNode(
        "HashAggregateExec", children=(child,),
        output=Schema(tuple(state_fields)),
        attrs={"grouping": list(grouping), "aggs": agg_exprs,
               "agg_names": agg_names, "mode": "partial"})
    # the exchange and the final agg see the PARTIAL agg's output, so they
    # name its attributes, not the pre-agg child columns
    out_grouping = [fcol(f.name, f.dtype) for f in group_fields]
    exchange = ForeignNode(
        "ShuffleExchangeExec", children=(partial,), output=partial.output,
        attrs={"partitioning": {"mode": "hash", "num_partitions": n_parts,
                                "expressions": out_grouping}})
    return ForeignNode(
        "HashAggregateExec", children=(exchange,),
        output=Schema(tuple(group_fields) + tuple(f for _, _, f in aggs)),
        attrs={"grouping": out_grouping, "aggs": agg_exprs,
               "agg_names": agg_names, "mode": "final"})


def take_ordered(child: ForeignNode, orders: Sequence[ForeignExpr],
                 limit: int, project: Sequence[ForeignExpr],
                 out: Schema) -> ForeignNode:
    return ForeignNode(
        "TakeOrderedAndProjectExec", children=(child,), output=out,
        attrs={"sort_order": list(orders), "limit": limit,
               "project_list": list(project)})
