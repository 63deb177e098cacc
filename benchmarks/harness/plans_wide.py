"""Plan idioms `plans.py` lacks, for queries whose money outgrows one
64-bit word: the two-phase `Average` over a decimal that Spark leaves a
decimal (buffer: sum decimal(p + 10, s), count), a hash exchange, a
shuffled sort-merge join, and a broadcast join with a residual
condition.  Written from Spark 3's planner, as `plans.py` is."""

from __future__ import annotations

from typing import Sequence

from auron_tpu.frontend.foreign import ForeignExpr, ForeignNode
from benchmarks.harness.plans import (I64, DataType, Field, Schema, agg,
                                      fcol)

__all__ = ["two_phase_decimal_avg", "exchange", "smj", "bhj_where"]


def two_phase_decimal_avg(child: ForeignNode, group: Field, name: str,
                          arg: ForeignExpr, n_parts: int = 4
                          ) -> ForeignNode:
    """partial HashAggregate -> hash ShuffleExchange -> final
    HashAggregate of avg(arg) by one key, `arg` a decimal(p, s) with
    p + 4 > 15 (no DecimalAggregates rewrite to doubles): the buffer is
    (sum decimal(p + 10, s), count bigint), the result decimal(p + 4,
    s + 4)."""
    p, s = arg.dtype.precision, arg.dtype.scale
    result = DataType.decimal(min(38, p + 4), min(38, s + 4))
    avg = agg("Average", arg, result)
    key = fcol(group.name, group.dtype)
    state = Schema((group,
                    Field(f"{name}#sum", DataType.decimal(min(38, p + 10), s)),
                    Field(f"{name}#count", I64)))
    partial = ForeignNode(
        "HashAggregateExec", children=(child,), output=state,
        attrs={"grouping": [key], "aggs": [avg], "agg_names": [name],
               "mode": "partial"})
    return ForeignNode(
        "HashAggregateExec", children=(exchange(partial, [key], n_parts),),
        output=Schema((group, Field(name, result))),
        attrs={"grouping": [key], "aggs": [avg], "agg_names": [name],
               "mode": "final"})


def exchange(child: ForeignNode, keys: Sequence[ForeignExpr],
             n_parts: int = 4) -> ForeignNode:
    return ForeignNode(
        "ShuffleExchangeExec", children=(child,), output=child.output,
        attrs={"partitioning": {"mode": "hash", "num_partitions": n_parts,
                                "expressions": list(keys)}})


def smj(left: ForeignNode, right: ForeignNode, left_key: ForeignExpr,
        right_key: ForeignExpr, n_parts: int = 4) -> ForeignNode:
    """Inner SortMergeJoin, both sides under hash exchanges on their key
    (EnsureRequirements adds the sorts: the converter does)."""
    return ForeignNode(
        "SortMergeJoinExec",
        children=(exchange(left, [left_key], n_parts),
                  exchange(right, [right_key], n_parts)),
        output=left.output.concat(right.output),
        attrs={"left_keys": [left_key], "right_keys": [right_key],
               "join_type": "Inner"})


def bhj_where(probe: ForeignNode, build: ForeignNode, left_key: ForeignExpr,
              right_key: ForeignExpr, condition: ForeignExpr) -> ForeignNode:
    """Inner BroadcastHashJoin, build side right, with a residual
    condition over the joined row."""
    bx = ForeignNode("BroadcastExchangeExec", children=(build,),
                     output=build.output)
    return ForeignNode(
        "BroadcastHashJoinExec", children=(probe, bx),
        output=probe.output.concat(build.output),
        attrs={"left_keys": [left_key], "right_keys": [right_key],
               "join_type": "Inner", "build_side": "right",
               "condition": condition})
